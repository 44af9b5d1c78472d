"""The benchmark's own reckoning of the points and bytes each request of a
cell must give."""
import json
import os

from bench_cells import ROOT

from bench import harness, reckon

GIB = 1 << 30


def _mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_fig7_points_and_bytes():
    plan = harness.load_module("plans", "fig7_locality")
    req = {**_mix("fig7_read")["request"], "strides": [4096, 8192, 65536]}
    pts = plan.points(req, _config("tpu_v5e_hbm_rst"))
    # 8 KiB holds the 4 KiB and 8 KiB strides; 256 MiB holds all three.
    assert sorted(p["key"] for p in pts) == sorted(
        [(8192, 4096, 4096), (8192, 4096, 8192), (1 << 28, 4096, 4096),
         (1 << 28, 4096, 8192), (1 << 28, 4096, 65536)])
    assert [reckon.stream_bytes(p) for p in pts] == [GIB] * 5
    req["strides"] = [16384, 1 << 20]
    assert len(plan.points(req, _config("tpu_v5e_hbm_rst"))) == 2


def test_contend4_bytes():
    mix = _mix("contend4")
    entry_mod = harness.load_module("entries", "sweep_contention")
    pts = entry_mod.Entry._points(
        type("E", (), {"config": _config("tpu_v5e_hbm_rst")})(),
        {**mix["request"], "s": 8192})
    assert [(p["arbitration"], p["burst_beats"]) for p in pts] == [
        ("round_robin", 1), ("burst", 16)]
    assert [reckon.stream_bytes(p) for p in pts] == [GIB, GIB]


def test_duplex_moves_both_directions():
    pt = {"n": 1000, "b": 64, "engines": 3}
    assert reckon.stream_bytes(pt) == 192000
    assert reckon.stream_bytes({**pt, "op": "write"}) == 192000
    assert reckon.stream_bytes({**pt, "op": "duplex"}) == 384000


def test_grid_cross_product_points():
    plan = harness.load_module("plans", "grid_cross_product")
    req = {**_mix("xp_default")["request"], "n": 3000}
    pts = plan.points(req, _config("u280_hbm_grid"))
    assert len(pts) == 540
    assert len({p["key"] for p in pts}) == 540
    assert {p["b"] for p in pts} == {32}


def test_ladder_points_follow_lane_order():
    from bench.references.timing_model import ladder_points
    axes = _mix("ladder")["request"]["axes"]
    pts = ladder_points(axes, [100 + i for i in range(18)], 32)
    assert len(pts) == 10368
    # the last axis (placements) runs fastest, the params slowest
    assert [p["placement"] for p in pts[:3]] == axes["placements"]
    assert pts[576]["n"] == 101 and pts[575]["n"] == 100
