"""Entry: ``Sweep.run`` over contention points, one fresh Sweep a request.

A request is one stream (``n``, ``s``, ``w``, at the configuration's burst)
read by ``engines`` engines that share one port, once under each of the
mix's arbitrations.  No registered experiment plans contention at the
RST kernels' burst, so the benchmark plans it on the Sweep directly.
"""
from __future__ import annotations

import math

from bench import harness, reckon


class Entry:
    def __init__(self, config: dict, traffic: dict):
        from repro.core.hwspec import spec_by_name
        self.config, self.traffic = config, traffic
        self.backend = config["backend"]
        self.spec = spec_by_name(config["spec"])
        self.capture = harness.load_module(
            "checks", config["check"]).Capture(config)

    def _points(self, req: dict) -> list:
        return [{"n": int(req["n"]), "b": int(self.config["burst_bytes"]),
                 "s": int(req["s"]), "w": int(req["w"]), "a": 0,
                 "engines": int(req["engines"]), "op": "read",
                 "arbitration": arb, "burst_beats": int(bb)}
                for arb, bb in req["arbitrations"]]

    def _run(self, pts: list) -> list:
        from repro.core import RSTParams, Sweep
        sweep = Sweep(self.spec, self.backend)
        for pt in pts:
            sweep.add_contention(
                RSTParams(n=pt["n"], b=pt["b"], s=pt["s"], w=pt["w"]),
                num_engines=pt["engines"], arbitration=pt["arbitration"],
                burst_beats=pt["burst_beats"])
        return [r.value for r in sweep.run()]

    def warm(self, seed: int) -> None:
        for extra in self.traffic["warm"]:
            self._run(self._points({**self.traffic["request"], **extra}))
        self.capture.clear()

    def serve(self, req: dict) -> dict:
        pts = self._points(req)
        values = self._run(pts)
        ok = len(values) == len(pts) and all(
            v.bound == "measured" and v.num_engines == pt["engines"]
            and v.aggregate_gbps > 0 and math.isfinite(v.aggregate_gbps)
            for v, pt in zip(values, pts))
        self.capture.answered(pts.__getitem__,
                              [v.aggregate_gbps for v in values])
        return {"ok": ok, "points": len(pts),
                "stream_bytes": sum(reckon.stream_bytes(p) for p in pts),
                "reported_gbps": [v.aggregate_gbps for v in values]}

    def check(self, records, rng) -> dict:
        return self.capture.check(rng)

    def close(self) -> None:
        self.capture.close()
