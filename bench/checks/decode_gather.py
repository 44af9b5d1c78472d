"""How ``correct`` is decided for the decode cell.

Every decode step served in the window leaves what its gathers returned:
``ops.measure_gather_bandwidth`` is wrapped, so each step's batch, step
index, chained checksum, byte count and GB/s are kept here, and a host
span (``bench.kernel_call``) marks it in the profiler's trace.  Once the
window has closed, a sample of the steps drawn from the seed is replayed
by the plain reference (``references/decode_gather.py``): the widest
checksum gap, the share of the checksum's words that differ, which is 0
for a step that read exactly its blocks, every bit of every word, and the
steps whose byte count is not the reference's.  Each
answer is also held to the step it was served from: a request whose GB/s
is not its own step's counts as a mismatch.
"""
from __future__ import annotations

from typing import List

from bench.references import decode_gather as reference

KERNEL_SPAN = "bench.kernel_call"


class Capture:
    def __init__(self, config: dict):
        from repro.kernels import ops
        self.config = config
        self.calls: List[dict] = []
        self._measure = ops.measure_gather_bandwidth
        ops.measure_gather_bandwidth = self._wrapped
        self.clear()

    def _wrapped(self, step, **kw):
        import jax
        with jax.profiler.TraceAnnotation(KERNEL_SPAN):
            sample = self._measure(step, **kw)
        self.calls.append({
            "seed": step.seed, "contexts": tuple(step.contexts),
            "step": step.step, "checksum": sample.checksum,
            "bytes": sample.bytes_moved, "gbps": sample.gbps})
        return sample

    def clear(self) -> None:
        self.calls.clear()
        self._seen = 0
        self.answers_off = 0

    def answered(self, values) -> None:
        calls = self.calls[self._seen:]
        self._seen = len(self.calls)
        self.answers_off += int(sorted(values)
                                != sorted(c["gbps"] for c in calls))

    def sample(self, rng) -> list:
        k = min(int(self.config["check_calls"]), len(self.calls))
        return [self.calls[int(i)] for i in
                sorted(rng.choice(len(self.calls), size=k, replace=False))]

    def check(self, rng, control: bool = False) -> dict:
        calls = self.sample(rng)
        if control:
            calls = reference.control(calls, self.config)
        readings = reference.compare(calls, self.config)
        if not calls:       # a window that served no step fails
            readings["checksum_gap"] = float("inf")
        readings["answer_mismatch"] = float(self.answers_off)
        return {name: {"value": float(value),
                       "limit": float(self.config["limits"][name])}
                for name, value in readings.items()}

    def close(self) -> None:
        from repro.kernels import ops
        ops.measure_gather_bandwidth = self._measure
