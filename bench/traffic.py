"""The one traffic generator: turns a mix's data file and a seed into the
requests of a closed loop.

A mix (``traffic/<name>.json``) holds:

* ``entry``: the module under ``entries/`` that serves the requests;
* ``request``: fields every request carries unchanged;
* ``draws``: fields drawn per request, each of one of three kinds:

  - ``{"subsets_of": [...], "sizes": [k, ...]}``: a sorted subset of one of
    the given sizes.  Every subset is listed once, in an order drawn from
    the seed, and the list starts again, in a new order, when it runs out;
  - ``{"choice": [...]}``: one of the values, in the same way;
  - ``{"integers": [lo, hi], "strata": s}``: an integer from ``lo`` to
    ``hi``.  The range is cut into ``s`` equal strata, and every ``s``
    successive integers take one from each stratum, in an order drawn from
    the seed, each without replacement inside its stratum.  So every seed
    offers the same spread of sizes, in another order, and any stretch of
    requests has about the mean size of the whole range.

  With ``"count": k`` a draw gives a list of ``k`` successive values.

* ``unique``: when true, a request never repeats within a run; a mix that
  runs out of distinct requests raises instead of repeating one.

The same seed gives the same requests.  Seeds are any non-negative
integer, also beyond 64 bits.
"""
from __future__ import annotations

import itertools
import json
from typing import Dict, Iterator, List

import numpy as np


def _rng(seed: int, field: str) -> np.random.Generator:
    salt = int.from_bytes(field.encode(), "little")
    return np.random.default_rng([int(seed), salt])


def _cycle(values: List, rng: np.random.Generator) -> Iterator:
    while True:
        for i in rng.permutation(len(values)):
            yield values[int(i)]


def _subsets(draw: dict) -> List[tuple]:
    pool = sorted(draw["subsets_of"])
    return [c for k in draw["sizes"] for c in itertools.combinations(pool, k)]


def _integers(draw: dict, rng: np.random.Generator) -> Iterator[int]:
    lo, hi = (int(v) for v in draw["integers"])
    strata = int(draw.get("strata", 1))
    if hi < lo or not 1 <= strata <= hi - lo + 1:
        raise ValueError(f"bad integer draw {draw}")
    edges = [lo + (hi - lo + 1) * k // strata for k in range(strata + 1)]
    pools = [_cycle(list(range(edges[k], edges[k + 1])), rng)
             for k in range(strata)]
    while True:
        for k in rng.permutation(strata):
            yield next(pools[int(k)])


def _field_stream(field: str, draw: dict, seed: int) -> Iterator:
    rng = _rng(seed, field)
    if "subsets_of" in draw:
        values = _cycle(_subsets(draw), rng)
    elif "choice" in draw:
        values = _cycle(list(draw["choice"]), rng)
    elif "integers" in draw:
        values = _integers(draw, rng)
    else:
        raise ValueError(f"traffic field {field!r}: unknown draw {draw}")
    if "count" not in draw:
        return values
    count = int(draw["count"])
    return (tuple(next(values) for _ in range(count))
            for _ in itertools.count())


def requests(mix: dict, seed: int) -> Iterator[Dict]:
    """The mix's requests for `seed`, in order."""
    if int(seed) < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    unique = bool(mix.get("unique", False))
    draws = mix.get("draws", {})
    streams = {f: _field_stream(f, d, seed)
               for f, d in sorted(draws.items())}
    seen = set()
    while True:
        req = dict(mix.get("request", {}))
        for field, stream in streams.items():
            value = next(stream)
            req[field] = list(value) if isinstance(value, tuple) else value
        if unique:
            key = json.dumps(req, sort_keys=True)
            if key in seen:
                raise RuntimeError(
                    "the mix ran out of distinct requests; lengthen its "
                    "draws or shorten the window")
            seen.add(key)
        yield req
