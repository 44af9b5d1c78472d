"""Entry: ``timing_jax.evaluate_grid``, the library call for large grids.

A request is one cross-product: stream parameters (each with its own
transaction count ``n``), address-mapping policies, directions, engine
counts, arbitrations and placements, evaluated in one call.
"""
from __future__ import annotations

import numpy as np

from bench import harness
from bench.references.timing_model import ladder_points


class Entry:
    def __init__(self, config: dict, traffic: dict):
        from repro.core.hwspec import spec_by_name
        self.config, self.traffic = config, traffic
        self.spec = spec_by_name(config["spec"])
        self.b = int(config["memory"]["min_burst"])
        self.capture = harness.load_module(
            "checks", config["check"]).Capture(config)

    def _axes(self, req: dict):
        from repro.core import RSTParams
        from repro.core.timing_jax import GridAxes
        a = req["axes"]
        return GridAxes(
            params=tuple(RSTParams(n=int(n), b=self.b, s=p["s"], w=p["w"])
                         for p, n in zip(a["params"], req["n"])),
            policies=tuple(a["policies"]), ops=tuple(a["ops"]),
            num_engines=tuple(a["engines"]),
            arbitrations=tuple((arb, bb) for arb, bb in a["arbitrations"]),
            placements=tuple(a["placements"]))

    def _evaluate(self, req: dict):
        from repro.core.timing_jax import evaluate_grid
        axes = self._axes(req)
        return axes, evaluate_grid(self.spec, axes)

    def warm(self, seed: int) -> None:
        for extra in self.traffic["warm"]:
            self._evaluate({**self.traffic["request"], **extra})

    def serve(self, req: dict) -> dict:
        axes, grid = self._evaluate(req)
        gbps = np.asarray(grid.gbps, np.float64)
        ok = bool(len(gbps) == axes.size and np.all(np.isfinite(gbps))
                  and np.all(gbps > 0))
        if ok:
            shape, a, ns = axes.shape, req["axes"], list(req["n"])

            def lookup(lane: int) -> dict:
                idx = np.unravel_index(lane, shape)
                point = ladder_points(
                    {"params": [a["params"][idx[0]]],
                     "policies": [a["policies"][idx[1]]],
                     "ops": [a["ops"][idx[2]]],
                     "engines": [a["engines"][idx[3]]],
                     "arbitrations": [a["arbitrations"][idx[4]]],
                     "placements": [a["placements"][idx[5]]]},
                    [ns[idx[0]]], self.b)
                return point[0]
            self.capture.answered(lookup, gbps)
        return {"ok": ok, "points": int(len(gbps))}

    def check(self, records, rng) -> dict:
        return self.capture.check(rng)

    def close(self) -> None:
        self.capture.close()
