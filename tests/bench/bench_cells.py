"""The benchmark's cells, cut to sizes the CPU and the Pallas interpreter
can run in a test: the same entries, mixes and checks, fewer and shorter
streams."""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

from bench import harness  # noqa: E402

CELLS = ("rst.fig7_read", "grid.ladder", "grid.xp_default", "rst.contend4")
SEED = 2 ** 64 + 12345      # seeds may pass 64 bits


def tiny(name: str) -> harness.Cell:
    cell = harness.Cell.load(name, ROOT)
    req, draws = cell.traffic["request"], cell.traffic.get("draws", {})
    if name == "rst.fig7_read":
        req["n"] = 16
    elif name == "rst.contend4":
        req.update(n=16, w=1 << 20)
        draws["s"]["choice"] = [4096, 8192]
    elif name == "grid.ladder":
        axes = req["axes"]
        axes.update(params=axes["params"][::7], engines=[1, 4],
                    arbitrations=axes["arbitrations"][:2])
        draws["n"]["count"] = 3
        cell.traffic["warm"] = [{"n": [131072] * 3}]
    elif name == "grid.xp_default":
        req.update(strides=[64], engines=[1, 4])
    cell.config["check_points"] = 6
    cell.config["check_calls"] = 2
    return cell


def run(cell: harness.Cell, seed: int = SEED, seconds: float = 0.05,
        trace: bool = False) -> dict:
    """A whole run after the device check, on this process's devices."""
    import jax
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            jax.devices(), {"hbm_bytes_per_s": 819e9})
