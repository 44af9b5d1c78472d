"""The points a ``fig7_write_locality`` request must answer: those of
``fig7_locality`` (``plans/fig7_locality.py``), each a write stream."""
from bench import harness

_read = harness.load_module("plans", "fig7_locality")
served = _read.served


def points(request: dict, config: dict) -> list:
    return [dict(p, op="write") for p in _read.points(request, config)]
