"""The reduction of the program's own spans (``bench/program_spans.py``):
the harness's readers give the values pinned here, on the recorded chip
trace and on a SpanTrace of it; hand-made traces give the hand-computed
numbers; a trace recorded on the chip with the program's spans reads."""
import dataclasses
import os
import re

import pytest

from bench_cells import ROOT  # puts the benchmark on the path

from bench import harness, program_spans
from bench.program_spans import Span, SpanTrace
from bench.trace_reduce import Event, Trace

MS = 1_000_000
DEV = "/device:TPU:0"
TESTDATA = os.path.join(ROOT, "bench", "testdata")
CHIP_TRACE = os.path.join(TESTDATA, "chip_trace.xplane.pb")
SPAN_TRACE = os.path.join(TESTDATA, "chip_trace_spans.xplane.pb")
RST_KERNEL = re.compile(r"rst_(read|contend)")

# Each reader of the accepted benchmark on bench/testdata/chip_trace.xplane.pb
# (one fig7_locality request of two points, one grid_cross_product request
# of five), with the records below.
PINNED = {
    "device_idle_share.rst": 83.289770354832,
    "device_idle_share.grid": 83.289770354832,
    "rst_kernel_roofline": 2.813340496032513,
    "grid_kernel_us_per_point": 565.4944,
    "host_ms_per_request.grid": 6.982450999999999,
    "rst_reported_gbps": 1.5,
}
FIG7 = harness.Record(0.0, 1.0, {"points": 2, "stream_bytes": 2 * 1024 * 4096,
                                 "reported_gbps": [1.0, 2.0]})
GRID = harness.Record(1.0, 2.0, {"points": 5})


def _read(name, trace):
    records = [FIG7] if name.startswith("rst") or name.endswith(".rst") \
        else [GRID]
    run = harness.Run(cell=None, setup_s=0.0, window_s=trace.window_s,
                      records=records, peaks={"hbm_bytes_per_s": 819e9},
                      trace=trace)
    return harness.load_module("metrics", name).read(run)


@pytest.mark.parametrize("name", sorted(PINNED))
@pytest.mark.parametrize("kind", [Trace, SpanTrace])
def test_accepted_readers_keep_their_values_on_the_chip_trace(name, kind):
    trace = kind.from_file(CHIP_TRACE, devices=1)
    assert _read(name, trace) == pytest.approx(PINNED[name], rel=1e-12)


def test_a_span_trace_reduces_a_trace_without_program_spans_as_before():
    old = Trace.from_file(CHIP_TRACE, devices=1)
    new = SpanTrace.from_file(CHIP_TRACE, devices=1)
    assert new.program == []
    assert (new.ops, new.modules, new.spans) == (old.ops, old.modules,
                                                 old.spans)
    assert new.breakdown() == old.breakdown()
    for number in ("grid_rows_ms", "grid_numpy_lanes_ms", "service_ms",
                   "lane_padding_share", "outside_program_ms"):
        assert getattr(new, number)() is None
    assert new.warmup_share(RST_KERNEL) is None
    assert new.child_cover("repro.service.submit") is None


# --------------------------------------------------------- hand-made traces
def _grid_request(at, dispatch_lanes, real, numpy_ms):
    """One served grid request of 10 ms starting at `at` (ms): submit over
    plan and attempt; the Sweep's prefill holds the grid tier's phases."""
    t = at * MS

    def span(name, lo, hi, **stats):
        return Span(name, t + lo * MS, t + hi * MS, "python",
                    tuple(stats.items()))
    return [
        span("repro.service.submit", 0.5, 9.5),
        span("repro.service.plan", 0.5, 1.5),
        span("repro.service.attempt", 1.5, 9.0),
        span("repro.sweep.run", 2.0, 9.0),
        span("repro.sweep.prefill", 2.0, 8.0),
        span("repro.grid.evaluate", 2.5, 8.0),
        span("repro.grid.rows", 2.5, 3.5),
        span("repro.grid.columns", 3.5, 4.0),
        span("repro.grid.dispatch", 4.0, 6.0, route="full",
             lanes=dispatch_lanes, real=real),
        span("repro.grid.numpy_lanes", 6.0, 6.0 + numpy_ms, route="numpy",
             lanes=2),
        span("repro.sweep.serve", 8.0, 9.0),
    ]


READ = (("kernel", "rst_read"),)


def _hand_made():
    """Two grid requests (0-10 ms, 10-20 ms) and one RST point (20-30 ms):
    buffer, a warm-up kernel of 3 ms, a timed kernel of 3 ms."""
    spans = [Event("bench.request", 0, 10 * MS, "python"),
             Event("bench.request", 10 * MS, 20 * MS, "python"),
             Event("bench.request", 20 * MS, 30 * MS, "python"),
             Event("bench.kernel_call", 21 * MS, 29 * MS, "python")]
    program = (_grid_request(0, 8, 6, 1.0) + _grid_request(10, 16, 12, 1.5)
               + [Span("repro.ops.measure", 21 * MS, 29 * MS, "python"),
                  Span("repro.ops.buffer", 21 * MS, 22 * MS, "python"),
                  Span("repro.ops.warmup", 22 * MS, 25.5 * MS, "python",
                       READ),
                  Span("repro.ops.timed", 25.5 * MS, 28.8 * MS, "python",
                       READ),
                  Span("repro.ops.checksum", 28.8 * MS, 29 * MS, "python")])
    ops = [Event("fusion", 4.5 * MS, 5.5 * MS, DEV),         # grid kernels
           Event("fusion", 14.5 * MS, 5.5 * MS + 10 * MS, DEV),
           Event("iota", 22 * MS, 22.5 * MS, DEV),          # buffer build
           Event("rst_read.1", 22.4 * MS, 25.4 * MS, DEV),  # warm-up call
           Event("rst_read.1", 25.6 * MS, 28.6 * MS, DEV)]  # timed call
    return SpanTrace(ops=ops, modules=[], spans=spans, devices=1,
                     program=program)


def test_gaps_are_labelled_by_the_innermost_span_of_either_kind():
    t = _hand_made()
    assert t.host_label(0.2 * MS) == "bench.request"
    assert t.host_label(3.0 * MS) == "repro.grid.rows"
    assert t.host_label(6.5 * MS) == "repro.grid.numpy_lanes"
    assert t.host_label(8.5 * MS) == "repro.sweep.serve"
    assert t.host_label(21.5 * MS) == "repro.ops.buffer"
    assert t.host_label(28.9 * MS) == "repro.ops.checksum"
    assert t.host_label(35 * MS) == "outside any request"
    # Each idle gap by the span innermost at its middle: 0-4.5 ms (the
    # prefill, 2.25 ms), 5.5-14.5 ms (between requests), 15.5-22 ms (the
    # second request's serve), 25.4-25.6 ms (the timed call, which starts
    # at 25.5 ms), 28.6-30 ms (after the kernel call).
    gaps = [(label, round(s * 1e3, 6))
            for label, s in t.breakdown()["idle_gaps"]]
    assert gaps == [("bench.request", 9.0), ("repro.sweep.serve", 6.5),
                    ("repro.sweep.prefill", 4.5), ("bench.request", 1.4),
                    ("repro.ops.timed", 0.2)]


def test_the_five_numbers_of_a_hand_made_trace():
    t = _hand_made()
    # rows 1 ms + columns 0.5 ms in each grid request, none in the RST one.
    assert t.grid_rows_ms() == pytest.approx(1.5)
    # 1.0 and 1.5 ms in the grid requests, 0 in the RST one.
    assert t.grid_numpy_lanes_ms() == pytest.approx(1.0)
    # service and Sweep spans cover 0.5-9.5 ms (9 ms), the grid tier's
    # 2.5-8 ms (5.5 ms) of it: 3.5 ms of self time; 0 in the RST request.
    assert t.service_ms() == pytest.approx(3.5)
    # lanes 8 + 16, real 6 + 12.
    assert t.lane_padding_share() == pytest.approx(100 * 6 / 24)
    # one warm-up and one timed kernel of 3 ms each.
    assert t.warmup_share(RST_KERNEL) == pytest.approx(50.0)


def test_numbers_whose_spans_are_absent_are_none():
    t = _hand_made()
    t.program = [s for s in t.program if not s.name.startswith(
        ("repro.grid.numpy_lanes", "repro.grid.dispatch", "repro.ops."))]
    assert t.grid_numpy_lanes_ms() is None
    assert t.lane_padding_share() is None
    assert t.warmup_share(RST_KERNEL) is None
    assert t.grid_rows_ms() == pytest.approx(1.5)


def test_stats_are_read_from_the_trace():
    class Obj:
        def __init__(self, **kw):
            self.__dict__.update(kw)

    def ev(name, lo, hi, stats=()):
        return Obj(name=name, start_ns=lo, end_ns=hi, stats=stats)
    profile = Obj(planes=[
        Obj(name="/host:CPU", lines=[Obj(name="python", events=[
            ev("bench.request", 0, 10 * MS),
            ev("repro.grid.dispatch", 1 * MS, 2 * MS,
               [("route", "full"), ("lanes", 128), ("real", 120)]),
            ev("PjitFunction(point)", 1 * MS, 2 * MS)])]),
        Obj(name=DEV, lines=[Obj(name="XLA Ops", events=[
            ev("fusion", 1.5 * MS, 1.8 * MS)])])])
    t = SpanTrace.from_xspace(profile, devices=1)
    assert [s.name for s in t.spans] == ["bench.request"]
    (d,) = t.program
    assert (d.name, d.start, d.end, d.where) == (
        "repro.grid.dispatch", 1 * MS, 2 * MS, "python")
    assert d.stat("route") == "full" and d.stat("real") == 120
    assert d.stat("missing", 7) == 7
    assert t.lane_padding_share() == pytest.approx(100 * 8 / 128)


def test_kernel_calls_pair_in_order_whatever_the_clocks_say():
    """The device lines 1 ms ahead of the host's: the warm-up kernel shows
    before its span and the timed one inside the warm-up span.  Pairing in
    order still gives each call its kernel; the lead is found, and the
    report moves the device lines back before it labels the gaps."""
    t = _hand_made().shifted(-1 * MS)
    (warm, k1), (timed, k2) = t.kernel_calls(RST_KERNEL)
    assert (warm.name, timed.name) == ("repro.ops.warmup", "repro.ops.timed")
    assert k1.start == 21.4 * MS and k2.start == 24.6 * MS
    assert t.warmup_share(RST_KERNEL) == pytest.approx(50.0)
    # The warm-up kernel (21.4-24.4 ms) fits its span (22-25.5 ms) with a
    # lead of 0.6-1.1 ms, the timed one (24.6-27.6 in 25.5-28.8) with
    # 0.9-1.2 ms: both with 0.9-1.1 ms, whose middle is the 1 ms shift.
    lo, hi = t.device_lead_ns(RST_KERNEL)
    assert (lo, hi) == (pytest.approx(0.9 * MS), pytest.approx(1.1 * MS))
    gaps = dict((round(s * 1e3, 6), label)
                for label, s in t.breakdown()["idle_gaps"])
    assert gaps[0.2] == "repro.ops.warmup"          # on the shifted lines
    aligned = dict((round(s * 1e3, 6), label) for label, s in
                   program_spans.report(t, RST_KERNEL)["idle_gaps"])
    assert aligned[0.2] == "repro.ops.timed"        # moved back by 1 ms
    assert aligned == dict((round(s * 1e3, 6), label) for label, s in
                           _hand_made().breakdown()["idle_gaps"])
    assert program_spans.report(t, RST_KERNEL)["device_lead_ms"] == [
        pytest.approx(0.9), pytest.approx(1.1)]


def test_kernel_calls_that_do_not_pair_give_no_share():
    t = _hand_made()
    t.ops = t.ops[:-1]                       # the timed kernel is missing
    assert t.kernel_calls(RST_KERNEL) is None
    assert t.warmup_share(RST_KERNEL) is None
    assert t.device_lead_ns(RST_KERNEL) is None
    t = _hand_made()
    t.program = [dataclasses.replace(s, stats=(("kernel", "rst_write"),))
                 if s.name.startswith("repro.ops.") else s
                 for s in t.program]         # calls of another kernel
    assert t.kernel_calls(RST_KERNEL) is None


def test_where_a_request_goes():
    t = _hand_made()
    split = t.host_split_ms()
    # Medians over three requests (two grid, one RST) of the host time
    # with each span innermost, device-busy time taken out.
    assert split["repro.grid.rows"] == pytest.approx(1.0)
    assert split["repro.grid.dispatch"] == pytest.approx(1.0)  # 2 - 1 busy
    assert split["repro.service.plan"] == pytest.approx(1.0)
    assert split["repro.ops.warmup"] == pytest.approx(0.0)
    total = sum(split.values())
    assert 0 < total < 10
    # the service's submit span: 9 ms, 1 ms device-busy; its children
    # cover 0.5-9.0 ms, so 7.5 of its 8 host ms.
    assert t.child_cover("repro.service.submit") == pytest.approx(7.5 / 8)
    # 1 ms of each grid request lies outside the program's spans; the RST
    # request has 2 ms (20-21 and 29-30).
    assert t.outside_program_ms() == pytest.approx(1.0)
    assert t.lanes_by_route() == {"full": pytest.approx(6.0),
                                  "numpy": pytest.approx(4 / 3)}
    report = program_spans.report(t, RST_KERNEL)
    assert set(report["numbers"]) == {
        "grid_rows_ms_per_request", "grid_numpy_lanes_ms_per_request",
        "service_ms_per_request.grid", "grid_lane_padding_share",
        "rst_warmup_share"}


def test_intersect():
    assert program_spans.intersect([(0, 5), (7, 9)], [(1, 2), (4, 8)]) == [
        (1, 2), (4, 5), (7, 8)]
    assert program_spans.intersect([], [(1, 2)]) == []


# ------------------------------------------------- the chip, with spans
def test_a_trace_recorded_on_the_chip_with_the_programs_spans():
    """``bench/testdata/record_trace_spans.py`` on one TPU v5e: a few
    grid.xp_default requests, then one RST point in an 8 KiB window."""
    assert os.path.getsize(SPAN_TRACE) < 1 << 20
    t = SpanTrace.from_file(SPAN_TRACE, devices=1)
    assert len(t.requests) >= 2
    assert all(s.name.startswith("repro.") for s in t.program)
    names = {s.name for s in t.program}
    assert {"repro.service.submit", "repro.sweep.run", "repro.grid.evaluate",
            "repro.grid.dispatch", "repro.grid.numpy_lanes",
            "repro.ops.warmup", "repro.ops.timed"} <= names
    # Every idle gap that falls inside a program span is labelled by one.
    for lo, hi in t.gaps():
        mid = (lo + hi) / 2
        if any(s.start <= mid < s.end for s in t.program):
            assert t.host_label(mid).startswith("repro.")
    labels = {label for label, _ in t.breakdown()["idle_gaps"]}
    assert any(label.startswith("repro.") for label in labels)
    report = program_spans.report(t, RST_KERNEL)
    assert set(report["numbers"]) == {
        "grid_rows_ms_per_request", "grid_numpy_lanes_ms_per_request",
        "service_ms_per_request.grid", "grid_lane_padding_share",
        "rst_warmup_share"}
    # The RST point: one warm-up and one timed call of 0.18 ms each.  The
    # device lines run 0.98-1.49 ms ahead of the host's spans here (each
    # kernel shows before the runtime's own launch event on the host); a
    # trace whose spans did not hold their calls would have no such lead.
    assert 40 <= report["numbers"]["rst_warmup_share"] <= 60
    lo, hi = t.device_lead_ns(RST_KERNEL)
    assert 0.9 * MS < lo <= hi < 1.6 * MS
    assert report["device_lead_ms"] == [lo * 1e-6, hi * 1e-6]
    dispatch = t.named("repro.grid.dispatch")
    lanes = sum(s.stat("lanes") for s in dispatch)
    real = sum(s.stat("real") for s in dispatch)
    assert report["numbers"]["grid_lane_padding_share"] == pytest.approx(
        100 * (lanes - real) / lanes)


# ------------------------------------------------------------ on the CPU
def test_the_span_report_of_a_traced_cpu_run(monkeypatch):
    """A traced run of a cut-down grid.xp_default through the span report's
    tracer: the padding share is what the lane counts of the window's
    requests and ``timing_jax._bucket`` imply."""
    import collections

    from bench_cells import run, tiny

    from bench import span_report, trace_reduce
    from repro.core import timing_jax
    calls = []
    run_rows = timing_jax._run_rows

    def counted(spec, rows, mesh=None):
        calls.append(collections.Counter(timing_jax._route(r) for r in rows))
        return run_rows(spec, rows, mesh)
    monkeypatch.setattr(timing_jax, "_run_rows", counted)
    monkeypatch.setattr(trace_reduce, "Tracer", span_report.SpanTracer)
    res = run(tiny("grid.xp_default"), trace=True)
    assert res["correct"]
    t = span_report.SpanTracer.last
    window = calls[-res["attempted"]:]
    device = [c for counts in window for route, c in counts.items()
              if route not in ("numpy", "mixnumpy")]
    lanes = sum(timing_jax._bucket(c, 1) for c in device)
    assert t.lane_padding_share() == pytest.approx(
        100 * (lanes - sum(device)) / lanes)
    report = program_spans.report(t, RST_KERNEL)
    assert {"grid_rows_ms_per_request", "service_ms_per_request.grid",
            "grid_lane_padding_share"} <= set(report["numbers"])
    assert report["child_cover"]["repro.service.submit"] > 0.5
    # No device plane on the CPU: the window is one gap, named by the span
    # open at its middle.
    (label, _), = report["idle_gaps"]
    assert label.startswith(("repro.", "bench."))
