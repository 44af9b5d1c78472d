"""Entry: ``CampaignService.submit`` of ``decode_step`` requests, one decode
step of the configuration's deployment a request.

A run serves one batch: the traffic's sequences, their contexts drawn once
from the seed (log-uniform over the traffic's range, drawn again while the
batch needs more pages than the pool holds), growing by one token a step.
Request ``k`` of the window is step ``k``.  The service runs on the
configuration's backend with no fallback and no sampled self-validation.
Each answer carries the benchmark's own count of the step's bytes
(``plans/decode_step.py``) and the gathers' counters.
"""
from __future__ import annotations

import math

import numpy as np

from bench import harness, traffic as traffic_mod


def draw_contexts(traffic: dict, config: dict, plan, seed: int) -> tuple:
    lo, hi = traffic["context_tokens"]
    rng = traffic_mod._rng(seed, "contexts")
    while True:
        ctx = np.exp(rng.uniform(np.log(lo), np.log(hi),
                                 int(traffic["sequences"])))
        contexts = tuple(int(c) for c in np.clip(np.floor(ctx), lo, hi))
        if plan.reference.pages_needed(config, contexts) <= \
                config["deployment"]["pool_pages"]:
            return contexts


class Entry:
    def __init__(self, config: dict, traffic: dict):
        from repro.core.experiments import get_experiment
        try:
            get_experiment("decode_step")
        except ValueError as e:
            raise harness.CellError(f"the program cannot serve this cell: "
                                    f"{e}") from None
        self.config, self.traffic = config, traffic
        self.plan = harness.load_module("plans", "decode_step")
        self.capture = harness.load_module(
            "checks", config["check"]).Capture(config)
        self.service = self._service()
        self.seed, self.contexts, self.step = 0, (), 0

    def _service(self):
        from repro.service import CampaignService
        return CampaignService(self.config["backend"], fallback=None,
                               validate_fraction=0.0)

    def _request(self, step: int):
        from repro.service import ExperimentRequest
        return ExperimentRequest.make(
            "decode_step", self.config["spec"],
            deployment=self.config["deployment"]["program"],
            seed=self.seed, contexts=list(self.contexts), step=step)

    def warm(self, seed: int) -> None:
        """Draw the batch, then serve the warm-up steps on a service of
        their own: the first builds the arena and compiles every grid the
        batch can reach."""
        self.seed = int(seed)
        self.contexts = draw_contexts(self.traffic, self.config, self.plan,
                                      seed)
        svc = self._service()
        for step in self.traffic["warm_steps"]:
            resp = svc.submit(self._request(step))
            if not resp.ok:
                raise RuntimeError(f"warm-up request failed: {resp.error}")
        self.capture.clear()

    def serve(self, req: dict) -> dict:
        step, self.step = self.step, self.step + 1
        resp = self.service.submit(self._request(step))
        r = resp.result if resp.ok else {}
        gbps = r.get("gbps", 0.0)
        ok = (resp.ok and not resp.degraded and not resp.coalesced
              and resp.backend == self.config["backend"]
              and gbps > 0 and math.isfinite(gbps))
        if ok:
            self.capture.answered([gbps])
        return {"ok": ok, "points": 1,
                "stream_bytes": self.plan.stream_bytes(
                    self.config, self.contexts, step),
                "reported_gbps": [gbps] if ok else [],
                "grid_steps": r.get("grid_steps", 0.0),
                "pad_steps": r.get("pad_steps", 0.0),
                "calls": r.get("calls", 0.0)}

    def check(self, records, rng) -> dict:
        checks = self.capture.check(rng)
        checks["deduped_requests"] = {
            "value": float(self.service.stats.deduped), "limit": 0.0}
        return checks

    def close(self) -> None:
        self.capture.close()
