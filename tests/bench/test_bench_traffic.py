"""The traffic generator: seeded, without repeats where a mix asks, and
within the shapes each cell warms up."""
import itertools
import json

import pytest

from bench_cells import CELLS, ROOT, SEED, harness, tiny

from bench import compile_watch, traffic


def _mix(name):
    return harness.Cell.load(name, ROOT).traffic


def _take(mix, seed, k):
    return list(itertools.islice(traffic.requests(mix, seed), k))


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_requests(cell):
    mix = _mix(cell)
    assert _take(mix, SEED, 40) == _take(mix, SEED, 40)
    assert _take(mix, SEED, 40) != _take(mix, SEED + 1, 40)


@pytest.mark.parametrize("cell", ["rst.fig7_read", "grid.xp_default"])
def test_unique_mixes_never_repeat(cell):
    mix = _mix(cell)
    assert mix["unique"]
    k = 246 if cell == "rst.fig7_read" else 600
    reqs = [json.dumps(r, sort_keys=True) for r in _take(mix, SEED, k)]
    assert len(set(reqs)) == len(reqs)


def test_unique_mix_runs_out_rather_than_repeat():
    mix = _mix("rst.fig7_read")
    k = 36 + 84 + 126           # subsets of 2, 3 and 4 of nine strides
    gen = traffic.requests(mix, SEED)
    reqs = list(itertools.islice(gen, k))
    assert len({tuple(r["strides"]) for r in reqs}) == k
    with pytest.raises(RuntimeError, match="ran out"):
        next(gen)


def test_seeds_share_the_set_of_sizes():
    """Every seed draws the same subsets (fig7) and, block by block, one n
    from each stratum (xp_default): only the order changes."""
    mix = _mix("rst.fig7_read")
    k = 246
    a = sorted(tuple(r["strides"]) for r in _take(mix, 1, k))
    b = sorted(tuple(r["strides"]) for r in _take(mix, 2 ** 70, k))
    assert a == b
    mix = _mix("grid.xp_default")
    lo, hi = mix["draws"]["n"]["integers"]
    strata = mix["draws"]["n"]["strata"]
    width = (hi - lo + 1) // strata
    ns = [r["n"] for r in _take(mix, SEED, 4 * strata)]
    assert all(lo <= n <= hi for n in ns)
    for block in range(4):
        got = sorted((n - lo) // width
                     for n in ns[block * strata:(block + 1) * strata])
        assert got == list(range(strata))


def test_draws_stay_in_their_ranges():
    ladder = _take(_mix("grid.ladder"), SEED, 30)
    assert all(len(r["n"]) == 18 for r in ladder)
    assert all(n in {2 ** k for k in range(15, 21)} for r in ladder for n in r["n"])
    pool = set(_mix("rst.fig7_read")["draws"]["strides"]["subsets_of"])
    for r in _take(_mix("rst.fig7_read"), SEED, 50):
        assert 2 <= len(r["strides"]) <= 4 and set(r["strides"]) <= pool


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        next(traffic.requests(_mix("rst.contend4"), -1))


@pytest.mark.parametrize("cell", ["grid.ladder", "grid.xp_default",
                                  "rst.contend4"])
def test_draws_keep_the_warmed_shapes(cell):
    """After the entry's warm-up, drawn requests compile nothing."""
    c = tiny(cell)
    entry = harness.load_module("entries", c.traffic["entry"]).Entry(
        c.config, c.traffic)
    try:
        entry.warm(SEED)
        watch = compile_watch.CompileWatch()
        try:
            for req in _take(c.traffic, SEED, 8):
                assert entry.serve(req)["ok"]
            counts = watch.counts()
        finally:
            watch.close()
    finally:
        entry.close()
    assert counts["compiles"] == 0 and counts["traces"] == 0, counts
