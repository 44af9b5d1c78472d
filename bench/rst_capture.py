"""Keeps what each RST kernel call of the timed path returned.

The served path hands back only seconds and bytes; the kernels' checksums
stop at ``repro.kernels.ops``.  :class:`KernelCapture` wraps the ``ops``
measurement functions that the ``pallas`` backend calls, so every call
served in the window leaves its parameters, its checksum and the GB/s it
measured here, and a host span in the profiler's trace.  Nothing else about the call changes.
"""
from __future__ import annotations

from typing import List

KERNEL_SPAN = "bench.kernel_call"
_WRAPPED = (("measure_read_bandwidth", "read"),
            ("measure_contended_bandwidth", "contend"))


class KernelCapture:
    def __init__(self):
        self.calls: List[dict] = []
        self._saved = {}

    def install(self) -> "KernelCapture":
        from repro.kernels import ops
        for name, _ in _WRAPPED:
            self._saved[name] = getattr(ops, name)
            setattr(ops, name, self._wrap(self._saved[name]))
        return self

    def uninstall(self) -> None:
        from repro.kernels import ops
        for name, fn in self._saved.items():
            setattr(ops, name, fn)
        self._saved.clear()

    def _wrap(self, fn):
        import jax

        def measured(p, **kw):
            with jax.profiler.TraceAnnotation(KERNEL_SPAN):
                sample = fn(p, **kw)
            self.calls.append({
                "s": p.s, "w": p.w, "a": p.a, "n": p.n, "b": p.b,
                "engines": kw.get("num_engines", 1),
                "arbitration": kw.get("arbitration", "round_robin"),
                "burst_beats": kw.get("burst_beats", 1),
                "checksum": sample.checksum, "bytes": sample.bytes_moved,
                "gbps": sample.gbps})
            return sample
        return measured
