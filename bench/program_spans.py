"""The program's own spans in the profiler's trace, and what they say.

The program marks each phase of its served path with a host span named
``repro.*`` (``src/repro/spans.py``) that carries the phase's counts as
stats.  The spans land in the same ``.xplane.pb`` as the device lines and
the harness's ``bench.*`` spans.  :class:`SpanTrace` is the harness's
reduction (:class:`bench.trace_reduce.Trace`) with these spans kept beside
it: it names each idle gap by the innermost span of either kind, and reads
from them the numbers below.  A number whose spans are absent, as in a
trace of a program that records none, is None.

Every ``*_ms`` number is a median over the request spans of the window.

The profiler puts the host spans and the device lines on one clock, but
not exactly: on a TPU v5e the device lines ran 0.2-1.9 ms ahead of the
host's (a kernel shows before the runtime's own event that launches it),
by a lead that changes from one profiling session to the next and holds
within one.  The warm-up and timed spans each hold one kernel call from
launch to completion, so pairing them in order with the kernel's device
events bounds the lead (:meth:`SpanTrace.device_lead_ns`), and
:func:`report` moves the device lines back by it before it labels gaps or
takes device time out of host spans.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import math
import statistics
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench.trace_reduce import HOST_PLANE, Trace, clip, length, union

PROGRAM_PREFIX = "repro."
GRID_HOST = ("repro.grid.rows", "repro.grid.columns")
NUMPY_LANES = "repro.grid.numpy_lanes"
DISPATCH = "repro.grid.dispatch"
WARMUP = "repro.ops.warmup"
CALLS = (WARMUP, "repro.ops.timed")
SERVICE = ("repro.service.", "repro.sweep.")
BELOW_SERVICE = ("repro.grid.", "repro.ops.")

Intervals = List[Tuple[float, float]]


@dataclasses.dataclass(frozen=True)
class Span:
    """One program span: its phase, its host interval (ns) and its stats."""

    name: str
    start: float
    end: float
    where: str = ""                                  # host line (thread)
    stats: Tuple[Tuple[str, object], ...] = ()

    def stat(self, key: str, default=None):
        return dict(self.stats).get(key, default)


def intersect(a: Intervals, b: Intervals) -> Intervals:
    """The intersection of two unions of disjoint, sorted intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


@dataclasses.dataclass
class SpanTrace(Trace):
    program: List[Span] = dataclasses.field(default_factory=list)

    @classmethod
    def from_xspace(cls, profile, devices: Optional[int] = None
                    ) -> "SpanTrace":
        base = Trace.from_xspace(profile, devices)
        program = [Span(e.name, e.start_ns, e.end_ns, line.name,
                        tuple(e.stats))
                   for plane in profile.planes
                   if plane.name.startswith(HOST_PLANE)
                   for line in plane.lines for e in line.events
                   if e.name.startswith(PROGRAM_PREFIX)]
        return cls(ops=base.ops, modules=base.modules, spans=base.spans,
                   devices=base.devices, program=program)

    @classmethod
    def from_file(cls, path: str, devices: Optional[int] = None
                  ) -> "SpanTrace":
        from jax.profiler import ProfileData
        return cls.from_xspace(ProfileData.from_file(path), devices)

    # -- what the host was doing ----------------------------------------------
    def host_label(self, t: float) -> str:
        """The innermost span, the harness's or the program's, open at t."""
        open_spans = [s for s in [*self.spans, *self.program]
                      if s.start <= t < s.end]
        if not open_spans:
            return "outside any request"
        return min(open_spans, key=lambda s: s.end - s.start).name

    def named(self, *names: str) -> List[Span]:
        """Program spans with one of `names`; a name ending in "." is a
        prefix."""
        return [s for s in self.program if _is(s.name, names)]

    @functools.cached_property
    def _by_start(self) -> Tuple[list, List[float]]:
        spans = sorted([*self.spans, *self.program], key=lambda s: s.start)
        return spans, [s.start for s in spans]

    def starting_in(self, lo: float, hi: float) -> list:
        """Spans of either kind that start in [lo, hi)."""
        spans, starts = self._by_start
        return spans[bisect.bisect_left(starts, lo):
                     bisect.bisect_left(starts, hi)]

    def covered(self, lo: float, hi: float, *names: str) -> Intervals:
        """Union of the program spans with one of `names` that start in
        [lo, hi), clipped to it."""
        return union(clip([(s.start, s.end)
                           for s in self.starting_in(lo, hi)
                           if _is(s.name, names)], lo, hi))

    def per_request_ms(self, names: Sequence[str],
                       measure: Callable[[float, float], float]
                       ) -> Optional[float]:
        """Median over requests of `measure` (ns), in ms; None when no
        program span has one of `names`."""
        if not self.named(*names) or not self.requests:
            return None
        return statistics.median(measure(r.start, r.end) * 1e-6
                                 for r in self.requests)

    # -- the numbers ------------------------------------------------------------
    def grid_rows_ms(self) -> Optional[float]:
        """Host row and column building of the grid tier per request."""
        return self.per_request_ms(GRID_HOST, lambda lo, hi: length(
            self.covered(lo, hi, *GRID_HOST)))

    def grid_numpy_lanes_ms(self) -> Optional[float]:
        """The grid tier's NumPy lanes on the host per request."""
        return self.per_request_ms((NUMPY_LANES,), lambda lo, hi: length(
            self.covered(lo, hi, NUMPY_LANES)))

    def service_ms(self) -> Optional[float]:
        """Self time of the service and Sweep spans per request: their
        union less the spans of the layers below them (grid tier, ops)."""
        def own(lo: float, hi: float) -> float:
            mine = self.covered(lo, hi, *SERVICE)
            return length(mine) - length(intersect(
                mine, self.covered(lo, hi, *BELOW_SERVICE)))
        return self.per_request_ms(SERVICE, own)

    def lane_padding_share(self) -> Optional[float]:
        """Device lanes spent on padding, in % of the lanes dispatched."""
        lo, hi = self.window
        spans = [s for s in self.named(DISPATCH) if lo <= s.start < hi]
        lanes = sum(int(s.stat("lanes", 0)) for s in spans)
        if not lanes:
            return None
        real = sum(int(s.stat("real", 0)) for s in spans)
        return 100.0 * (lanes - real) / lanes

    def kernel_calls(self, kernel) -> Optional[List[Tuple[Span, object]]]:
        """Each warm-up and timed span of a kernel matching the regex
        `kernel` (its ``kernel`` stat), with the device event of the call
        it made: the k-th span in host order with the k-th event in device
        order.  None when there are none, or their numbers differ."""
        calls = sorted((s for s in self.named(*CALLS)
                        if kernel.search(str(s.stat("kernel", "")))),
                       key=lambda s: s.start)
        events = sorted(self.ops_matching(kernel), key=lambda o: o.start)
        if not calls or len(calls) != len(events):
            return None
        return list(zip(calls, events))

    def warmup_share(self, kernel) -> Optional[float]:
        """Device time of the kernels matching the regex `kernel` that the
        warm-up calls made, in % of all their device time."""
        pairs = self.kernel_calls(kernel)
        total = sum(o.end - o.start for _, o in pairs or ())
        if not total:
            return None
        warm = sum(o.end - o.start for s, o in pairs if s.name == WARMUP)
        return 100.0 * warm / total

    def device_lead_ns(self, kernel) -> Optional[Tuple[float, float]]:
        """How far the device lines run ahead of the host's clock: the
        range (lo, hi) of constant leads, in ns, that put each kernel event
        inside the span that made the call.  lo > hi when no constant lead
        does; None without kernel calls."""
        pairs = self.kernel_calls(kernel)
        if not pairs:
            return None
        return (max(s.start - o.start for s, o in pairs),
                min(s.end - o.end for s, o in pairs))

    def shifted(self, ns: float) -> "SpanTrace":
        """This trace with the device lines moved `ns` later."""
        def move(events):
            return [dataclasses.replace(e, start=e.start + ns,
                                        end=e.end + ns) for e in events]
        return dataclasses.replace(self, ops=move(self.ops),
                                   modules=move(self.modules))

    # -- where a request's host time goes ------------------------------------
    def host_split_ms(self) -> Dict[str, float]:
        """Median over requests of the host time (not device-busy) spent
        with each span innermost, by span name."""
        per_request: List[Dict[str, float]] = []
        device = self.busy(-math.inf, math.inf)
        for r in self.requests:
            inside = self.starting_in(r.start, r.end)
            busy = clip(device, r.start, r.end)
            edges = sorted({r.start, r.end, *(s.start for s in inside),
                            *(min(s.end, r.end) for s in inside)})
            split: Dict[str, float] = {}
            for a, b in zip(edges, edges[1:]):
                mid = (a + b) / 2
                label = min((s for s in inside if s.start <= mid < s.end),
                            key=lambda s: s.end - s.start).name
                host = (b - a) - length(clip(busy, a, b))
                split[label] = split.get(label, 0.0) + host * 1e-6
            per_request.append(split)
        names = sorted({n for split in per_request for n in split})
        return {n: statistics.median(split.get(n, 0.0)
                                     for split in per_request)
                for n in names}

    def child_cover(self, top: str) -> Optional[float]:
        """Median over the spans named `top` of the share of their host
        time (length less device-busy time) that their child spans
        cover."""
        covers = []
        device = self.busy(-math.inf, math.inf)
        for t in self.named(top):
            kids = [(s.start, s.end) for s in self.starting_in(t.start, t.end)
                    if s is not t and s.name.startswith(PROGRAM_PREFIX)
                    and s.where == t.where and s.end <= t.end]
            busy = clip(device, t.start, t.end)
            host = (t.end - t.start) - length(busy)
            if host <= 0:
                continue
            covered = union(kids)
            covered_host = length(covered) - length(intersect(covered, busy))
            covers.append(covered_host / host)
        return statistics.median(covers) if covers else None

    def outside_program_ms(self) -> Optional[float]:
        """Request time outside any program span: the entry's own work."""
        return self.per_request_ms((PROGRAM_PREFIX,), lambda lo, hi: (
            hi - lo) - length(self.covered(lo, hi, PROGRAM_PREFIX)))

    def lanes_by_route(self) -> Dict[str, float]:
        """Mean real lanes per request by route, from the dispatch and
        NumPy-lane spans."""
        if not self.requests:
            return {}
        lanes: Dict[str, float] = {}
        for s in self.named(DISPATCH, NUMPY_LANES):
            route = str(s.stat("route"))
            lanes[route] = lanes.get(route, 0) + int(
                s.stat("real", s.stat("lanes", 0)))
        return {k: v / len(self.requests) for k, v in sorted(lanes.items())}


def _is(name: str, names: Sequence[str]) -> bool:
    return any(name.startswith(n) if n.endswith(".") else name == n
               for n in names)


def report(trace: SpanTrace, kernel) -> dict:
    """Everything this module reads from one trace, as one JSON object;
    the device lines are first moved by the middle of the lead that the
    calls of the kernels matching the regex `kernel` show, when they show
    one."""
    lead = trace.device_lead_ns(kernel)
    if lead is not None and lead[0] <= lead[1]:
        trace = trace.shifted((lead[0] + lead[1]) / 2)
    numbers = {
        "grid_rows_ms_per_request": trace.grid_rows_ms(),
        "grid_numpy_lanes_ms_per_request": trace.grid_numpy_lanes_ms(),
        "service_ms_per_request.grid": trace.service_ms(),
        "grid_lane_padding_share": trace.lane_padding_share(),
        "rst_warmup_share": trace.warmup_share(kernel),
    }
    cover = {top: trace.child_cover(top) for top in (
        "repro.service.submit", "repro.sweep.run", "repro.grid.evaluate")}
    return {"program_spans": len(trace.program),
            "device_lead_ms": lead and [x * 1e-6 for x in lead],
            "numbers": {k: v for k, v in numbers.items() if v is not None},
            "child_cover": {k: v for k, v in cover.items() if v is not None},
            "outside_program_ms": trace.outside_program_ms(),
            "host_split_ms": trace.host_split_ms(),
            "lanes_by_route": trace.lanes_by_route(),
            "idle_gaps": trace.breakdown()["idle_gaps"]}
