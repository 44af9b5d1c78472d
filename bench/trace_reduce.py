"""From the profiler's trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb`` file.  Its device planes
(``/device:TPU:<i>``) hold one line of the operations that ran on the
chip (``XLA Ops``) and one of the programs they belong to
(``XLA Modules``); the host plane (``/host:CPU``) holds the harness's own
spans (``bench.*``, from ``jax.profiler.TraceAnnotation``) on the clock of
the device lines.  The traced window runs from the start of the first
request span to the end of the last.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
REQUEST_SPAN = "bench.request"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns
    end: float          # ns
    where: str = ""     # device plane, or host line


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


@dataclasses.dataclass
class Trace:
    ops: List[Event]               # device operations, every device plane
    modules: List[Event]           # device programs, every device plane
    spans: List[Event]             # the harness's host spans
    devices: int                   # device planes read

    @classmethod
    def from_xspace(cls, profile, devices: Optional[int] = None) -> "Trace":
        ops, modules, spans = [], [], []
        planes = sorted((p for p in profile.planes
                         if p.name.startswith(DEVICE_PLANE)),
                        key=lambda p: p.name)
        if devices is not None:
            planes = planes[:devices]
        for plane in planes:
            for line in plane.lines:
                into = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if into is None:
                    continue
                into.extend(Event(e.name, e.start_ns, e.end_ns, plane.name)
                            for e in line.events)
        for plane in profile.planes:
            if not plane.name.startswith(HOST_PLANE):
                continue
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.end_ns, line.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
        return cls(ops=ops, modules=modules, spans=spans,
                   devices=len(planes))

    @classmethod
    def from_file(cls, path: str, devices: Optional[int] = None) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_xspace(ProfileData.from_file(path), devices)

    # -- the window ---------------------------------------------------------
    @property
    def requests(self) -> List[Event]:
        return sorted((s for s in self.spans if s.name == REQUEST_SPAN),
                      key=lambda s: s.start)

    @property
    def window(self) -> Tuple[float, float]:
        req = self.requests
        if not req:
            raise ValueError("the trace holds no request span")
        return req[0].start, max(r.end for r in req)

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def busy(self, lo: float, hi: float, plane: Optional[str] = None
             ) -> List[Tuple[float, float]]:
        """Union of the device-operation intervals within [lo, hi)."""
        return union(clip([(o.start, o.end) for o in self.ops
                           if plane is None or o.where == plane], lo, hi))

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran on a device, averaged over
        the device planes read."""
        lo, hi = self.window
        planes = sorted({o.where for o in self.ops})
        if not planes:
            return 0.0
        return sum(length(self.busy(lo, hi, p)) for p in planes) * 1e-9 / max(
            self.devices, 1)

    def idle_share_percent(self) -> Optional[float]:
        if not self.ops:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def ops_matching(self, pattern) -> List[Event]:
        """Device operations whose own name, or the name of the program
        they ran in, matches the compiled regex `pattern`."""
        programs = [m for m in self.modules if pattern.search(m.name)]
        return [o for o in self.ops if pattern.search(o.name) or any(
            m.where == o.where and m.start <= o.start and o.end <= m.end
            for m in programs)]

    # -- what the host was doing --------------------------------------------
    def host_label(self, t: float) -> str:
        """The innermost harness span open at time t."""
        open_spans = [s for s in self.spans if s.start <= t < s.end]
        if not open_spans:
            return "outside any request"
        return min(open_spans, key=lambda s: s.end - s.start).name

    def gaps(self) -> List[Tuple[float, float]]:
        lo, hi = self.window
        busy = self.busy(lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def breakdown(self) -> Dict[str, list]:
        """The device operations that took most time, and the longest idle
        gaps by the harness span the host was in at their middle."""
        lo, hi = self.window
        per_op: Dict[str, float] = {}
        for o in self.ops:
            s, e = max(o.start, lo), min(o.end, hi)
            if e > s:
                per_op[o.name] = per_op.get(o.name, 0.0) + (e - s) * 1e-9
        top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in top_ops],
                "idle_gaps": [[self.host_label((s + e) / 2), (e - s) * 1e-9]
                              for s, e in gaps]}


class Tracer:
    """Records the profiler's trace of the window in a temporary
    directory, and removes it once read.  Python function calls are not
    traced: the harness's own spans say what the host was doing, at a
    fraction of the cost."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def path(self) -> str:
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        return paths[0]

    def reduce(self, devices: int) -> Trace:
        try:
            return Trace.from_file(self.path(), devices)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
