"""How ``correct`` is decided for the grid cells.

Every answer of the window is kept.  Once the window has closed, a sample
of the answered points, drawn from the seed over all requests of the
window, is evaluated by the plain reference (``references/timing_model.py``)
in float64, and the widest relative gap between a served value and the
reference's is compared with the tolerance the configuration states.
"""
from __future__ import annotations

import numpy as np

from bench.references import timing_model as reference


class Capture:
    def __init__(self, config: dict):
        self.config = config
        self.answers = []        # (lane -> point, served values in lane order)

    def clear(self) -> None:
        self.answers.clear()

    def answered(self, lookup, values) -> None:
        self.answers.append((lookup, np.asarray(values, np.float64)))

    def sample(self, rng) -> list:
        sizes = np.array([len(v) for _, v in self.answers], np.int64)
        total = int(sizes.sum())
        k = min(int(self.config["check_points"]), total)
        picks = np.sort(rng.choice(total, size=k, replace=False))
        ends = np.cumsum(sizes)
        out = []
        for g in picks:
            r = int(np.searchsorted(ends, g, side="right"))
            lane = int(g - (ends[r] - sizes[r]))
            lookup, values = self.answers[r]
            out.append((lookup(lane), float(values[lane])))
        return out

    def check(self, rng, control: bool = False) -> dict:
        sample = self.sample(rng)
        mem = reference.Memory(self.config, np.float64)
        if control:
            low = reference.Memory(self.config, np.float32)
            sample = [(pt, float(reference.point_gbps(low, pt)))
                      for pt, _ in sample]
        worst = float("inf") if not sample else 0.0
        for pt, got in sample:
            want = float(reference.point_gbps(mem, pt))
            worst = max(worst, abs(got - want) / max(abs(want), 1e-300))
        return {"grid_rel_err": {
            "value": worst,
            "limit": float(self.config["limits"]["grid_rel_err"])}}

    def close(self) -> None:
        pass
