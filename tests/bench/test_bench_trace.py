"""The reduction from the profiler's trace to per-layer numbers, on
hand-made traces and on a small trace recorded on the chip."""
import os

import pytest

from bench_cells import ROOT  # puts the benchmark on the path

from bench import trace_reduce
from bench.trace_reduce import Event, Trace


def _hand_made():
    ms = 1_000_000
    ops = [Event("rst_read", 1 * ms, 4 * ms, "/device:TPU:0"),
           Event("rst_read", 3 * ms, 5 * ms, "/device:TPU:0"),
           Event("copy", 7 * ms, 8 * ms, "/device:TPU:0")]
    spans = [Event("bench.request", 0, 6 * ms, "python"),
             Event("bench.kernel_call", 1 * ms, 5 * ms, "python"),
             Event("bench.request", 6 * ms, 10 * ms, "python")]
    return Trace(ops=ops, modules=[], spans=spans, devices=1)


def test_union_and_clip():
    assert trace_reduce.union([(3, 5), (1, 4), (7, 8)]) == [(1, 5), (7, 8)]
    assert trace_reduce.clip([(1, 5), (7, 8)], 2, 7.5) == [(2, 5), (7, 7.5)]
    assert trace_reduce.length([(1, 5), (7, 8)]) == 5


def test_busy_idle_and_gaps():
    t = _hand_made()
    assert t.window == (0, 10_000_000)
    assert t.window_s == pytest.approx(0.010)
    assert t.busy_s == pytest.approx(0.005)        # 1-5 ms and 7-8 ms
    assert t.idle_share_percent() == pytest.approx(50.0)
    assert t.gaps() == [(0, 1_000_000), (5_000_000, 7_000_000),
                        (8_000_000, 10_000_000)]


def test_breakdown_labels_gaps_by_the_innermost_span():
    b = _hand_made().breakdown()
    assert b["device_ops"] == [["rst_read", pytest.approx(0.005)],
                               ["copy", pytest.approx(0.001)]]
    labels = [label for label, _ in b["idle_gaps"]]
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert labels == ["bench.request", "bench.request", "bench.request"]
    assert _hand_made().host_label(2_000_000) == "bench.kernel_call"
    assert _hand_made().host_label(20_000_000) == "outside any request"


def test_kernel_events_by_their_own_or_their_programs_name():
    import re
    ms = 1_000_000
    dev = "/device:TPU:0"
    t = Trace(ops=[Event("_rst_read_kernel", 1 * ms, 2 * ms, dev),
                   Event("custom-call.7", 3 * ms, 4 * ms, dev),
                   Event("fusion.2", 5 * ms, 6 * ms, dev)],
              modules=[Event("jit_rst_contend_read(12)", 2.5 * ms, 4.5 * ms,
                             dev),
                       Event("jit_evaluate(3)", 4.8 * ms, 6.2 * ms, dev)],
              spans=[], devices=1)
    got = t.ops_matching(re.compile(r"rst_(read|contend)"))
    assert [o.name for o in got] == ["_rst_read_kernel", "custom-call.7"]


CHIP_TRACE = os.path.join(ROOT, "bench", "testdata", "chip_trace.xplane.pb")


def test_reduction_of_a_trace_recorded_on_the_chip():
    """``bench/testdata/record_trace.py`` on one TPU v5e: one fig7_locality
    request of two points (a warm-up and a timed rst_read call each) and one
    small grid_cross_product request, each in a request span."""
    import re

    from bench import harness
    t = Trace.from_file(CHIP_TRACE, devices=1)
    assert t.devices == 1
    assert [r.name for r in t.requests] == [trace_reduce.REQUEST_SPAN] * 2
    assert 0 < t.busy_s < t.window_s
    assert 0 < t.idle_share_percent() < 100
    kernels = t.ops_matching(re.compile(r"rst_(read|contend)"))
    assert len(kernels) == 4
    assert all("rst_read" in o.name for o in kernels)
    assert any(m.name.startswith("jit_point") for m in t.modules)
    b = t.breakdown()
    assert len(b["device_ops"]) == trace_reduce.TOP
    assert {label for label, _ in b["idle_gaps"]} == {"bench.request"}

    fig7 = harness.Record(0.0, 1.0, {"points": 2,
                                      "stream_bytes": 2 * 1024 * 4096})
    run = harness.Run(cell=None, setup_s=0.0, window_s=t.window_s,
                      records=[fig7], peaks={"hbm_bytes_per_s": 819e9},
                      trace=t)
    share = harness.load_module("metrics", "rst_kernel_roofline").read(run)
    assert 0 < share < 100
