"""Entry: ``CampaignService.submit``, the system's front end.

Each request names an experiment of the registry; the mix's fixed fields
and draws become its overrides.  The service runs with no fallback backend
and no sampled self-validation, so every answer comes from the backend the
configuration names, and the response cache must answer none (the mix
never repeats a request).  The points a request must answer come from the
benchmark's own plan of the experiment (``plans/<experiment>.py``).
"""
from __future__ import annotations

import math

from bench import harness, reckon


class Entry:
    def __init__(self, config: dict, traffic: dict):
        self.config, self.traffic = config, traffic
        self.backend = config["backend"]
        self.plan = harness.load_module("plans",
                                        traffic["request"]["experiment"])
        self.capture = harness.load_module(
            "checks", config["check"]).Capture(config)
        self.service = self._service()

    def _service(self):
        from repro.service import CampaignService
        return CampaignService(self.backend, fallback=None,
                               validate_fraction=0.0)

    @staticmethod
    def _request(req: dict):
        from repro.service import ExperimentRequest
        overrides = {k: v for k, v in req.items()
                     if k not in ("experiment", "spec")}
        return ExperimentRequest.make(req["experiment"], req["spec"],
                                      **overrides)

    def warm(self, seed: int) -> None:
        """Serve the mix's warm-up requests on a service of their own, so
        the window's service starts with an empty response cache."""
        svc = self._service()
        for extra in self.traffic["warm"]:
            resp = svc.submit(self._request({**self.traffic["request"],
                                             **extra}))
            if not resp.ok:
                raise RuntimeError(f"warm-up request failed: {resp.error}")
        self.capture.clear()

    def serve(self, req: dict) -> dict:
        resp = self.service.submit(self._request(req))
        pts = self.plan.points(req, self.config)
        values = self.plan.served(resp.result, pts) if resp.ok else {}
        ok = (resp.ok and not resp.degraded and not resp.coalesced
              and resp.backend == self.backend and len(values) == len(pts)
              and all(v > 0 and math.isfinite(v) for v in values.values()))
        if ok:
            self.capture.answered(pts.__getitem__,
                                  [values[pt["key"]] for pt in pts])
        return {"ok": ok, "points": len(pts),
                "stream_bytes": sum(reckon.stream_bytes(p) for p in pts),
                "reported_gbps": list(values.values())}

    def check(self, records, rng) -> dict:
        checks = self.capture.check(rng)
        checks["deduped_requests"] = {
            "value": float(self.service.stats.deduped), "limit": 0.0}
        return checks

    def close(self) -> None:
        self.capture.close()
