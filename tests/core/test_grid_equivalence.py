"""Grid-equivalence: `evaluate_grid` vs per-point Sweep, element for element.

The contract (DESIGN.md §12): lane ``i`` of a :class:`GridResult` is the
point ``axes.sweep_points()[i]`` — the same ``itertools.product`` order as
the Sweep memo keys — and its value matches what a per-point Sweep returns
for that point within the documented tolerances:

* vs ``Sweep(backend="sim")`` (the NumPy mid-level oracle): rel 1e-9;
* vs ``Sweep(backend="jaxgrid")`` (the same compiled path, served through
  the prefilled memo caches): rel 1e-12 (placement recombination order is
  the only difference).

Sharded-vs-unsharded equality runs in a subprocess so this process keeps
seeing exactly one device (same pattern as tests/launch/test_launch.py).
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import HBM, RSTParams, Sweep
from repro.core import timing_jax as tj
from repro.core.address_mapping import policies_for

ROOT = Path(__file__).resolve().parents[2]

MB = 1024**2


def _small_axes():
    return tj.GridAxes(
        params=tuple(RSTParams(n=512, b=32, s=64 << i, w=16 * MB)
                     for i in range(3)),
        policies=(None, "RBC"),
        ops=("read", "write"),
        num_engines=(1, 2, 4),
        arbitrations=(("round_robin", 1), ("burst", 4)),
        placements=("same_channel", "same_switch", "cross_switch"))


def _sweep_values(axes, backend):
    sw = Sweep(HBM, backend=backend)
    for pt in axes.sweep_points():
        sw.add_point(pt)
    return sw.run()


class TestGridMatchesPerPointSweep:
    def test_element_for_element_vs_sim(self):
        axes = _small_axes()
        grid = tj.evaluate_grid(HBM, axes)
        swept = _sweep_values(axes, "sim")
        assert grid.size == len(swept) == axes.size
        pts = axes.sweep_points()
        for i, sr in enumerate(swept):
            assert sr.point == pts[i]     # same ordering as cache keys
            assert grid.gbps[i] == pytest.approx(
                sr.value.aggregate_gbps, rel=1e-9), (i, pts[i])
            assert grid.bound[i] == sr.value.bound, (i, pts[i])
            assert grid.queueing_delay_cycles[i] == pytest.approx(
                sr.value.queueing_delay_cycles, rel=1e-9, abs=1e-9)

    def test_element_for_element_vs_jaxgrid_sweep(self):
        axes = _small_axes()
        grid = tj.evaluate_grid(HBM, axes)
        swept = _sweep_values(axes, "jaxgrid")
        for i, sr in enumerate(swept):
            assert grid.gbps[i] == pytest.approx(
                sr.value.aggregate_gbps, rel=1e-12), i

    def test_lazy_results_match_flat_arrays(self):
        axes = _small_axes()
        grid = tj.evaluate_grid(HBM, axes)
        res = grid.results()
        assert len(res) == grid.size
        for i, r in enumerate(res):
            assert r.aggregate_gbps == pytest.approx(grid.gbps[i],
                                                     rel=1e-12)
            assert r.bound == grid.bound[i]

    def test_throughput_kind_matches_sweep(self):
        axes = tj.GridAxes(
            params=tuple(RSTParams(n=512, b=32, s=128 << i, w=16 * MB)
                         for i in range(3)),
            policies=(None,) + tuple(policies_for(HBM))[:2],
            ops=("read", "write", "duplex"),
            kind="throughput")
        grid = tj.evaluate_grid(HBM, axes)
        swept = _sweep_values(axes, "sim")
        for i, sr in enumerate(swept):
            assert grid.gbps[i] == pytest.approx(sr.value.gbps,
                                                 rel=1e-9), i
            assert grid.bound[i] == sr.value.bound, i


def test_grid_acceptance_ten_thousand_points():
    """Acceptance: a >=10,000-point cross-product matches the per-point
    Sweep path within the documented rel 1e-9 everywhere."""
    params = tuple(RSTParams(n=256, b=32, s=64 << (i % 5),
                             w=MB << (i // 5))
                   for i in range(25))
    axes = tj.GridAxes(
        params=params,
        policies=(None,) + tuple(policies_for(HBM)),
        ops=("read", "write", "duplex"),
        num_engines=(1, 2, 4),
        arbitrations=(("round_robin", 1), ("burst", 2), ("burst", 8)),
        placements=("same_channel", "same_switch", "cross_switch"))
    assert axes.size >= 10_000
    grid = tj.evaluate_grid(HBM, axes)
    swept = _sweep_values(axes, "sim")
    got = grid.gbps
    want = np.array([sr.value.aggregate_gbps for sr in swept])
    np.testing.assert_allclose(got, want, rtol=1e-9)
    want_q = np.array([sr.value.queueing_delay_cycles for sr in swept])
    np.testing.assert_allclose(grid.queueing_delay_cycles, want_q,
                               rtol=1e-9, atol=1e-9)
    bounds = np.array([sr.value.bound for sr in swept])
    assert (grid.bound == bounds).all()


SHARDED_EQUALITY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from repro.core import HBM, RSTParams
from repro.core import timing_jax as tj
from repro.launch.mesh import grid_mesh

assert jax.device_count() == 8
# 3 params x 1 policy x 3 ops x 3 counts x 1 arb -> 27 unit lanes: not a
# multiple of 8, so the mesh path must pad the lane axis explicitly.
axes = tj.GridAxes(
    params=tuple(RSTParams(n=512, b=32, s=64 << i, w=16 * 1024**2)
                 for i in range(3)),
    ops=("read", "write", "duplex"),
    num_engines=(1, 2, 4),
    placements=("same_channel", "same_switch", "cross_switch"))
base = tj.evaluate_grid(HBM, axes)
sharded = tj.evaluate_grid(HBM, axes, mesh=grid_mesh())
np.testing.assert_allclose(sharded.gbps, base.gbps, rtol=1e-12)
np.testing.assert_array_equal(sharded.bound, base.bound)
np.testing.assert_allclose(sharded.queueing_delay_cycles,
                           base.queueing_delay_cycles,
                           rtol=1e-12, atol=1e-12)
# The sharded kernel's outputs live on all 8 devices; every lane of this
# grid runs in a compiled kernel, none on the host fallback.
assert (base.output_devices, sharded.output_devices) == (1, 8)
assert sharded.lanes_by_route == base.lanes_by_route
assert sum(base.lanes_by_route.values()) == 27
assert "numpy" not in base.lanes_by_route
print("SHARDED_OK", base.size)
"""


def test_sharded_matches_unsharded_on_8_device_mesh():
    """evaluate_grid(mesh=grid_mesh()) on a forced 8-device CPU equals the
    unsharded evaluation, including a lane count that does not divide the
    device count (exercises the explicit pad path)."""
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_EQUALITY],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": str(ROOT / "src"),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", str(ROOT)),
             "JAX_PLATFORMS": "cpu"},
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_OK" in out.stdout
