"""Exhaustive-oracle pinning of the layout autotuner.

The tuner's contract is exactness-with-savings: on any knob space the
winner must equal the argmax of the full `evaluate_grid` cross-product
while issuing strictly fewer backend evaluations than the grid has
points.  These tests enforce that on small grids (<= 256 points) over
every registered memory spec, plus the determinism / cache-reuse /
service-routing properties the search relies on.
"""

import numpy as np
import pytest

import repro.core.engine as engine_mod
from repro.core import (DDR3, DDR4, HBM, HBM3, RSTParams, Sweep, get_backend,
                        run_experiment, tune_layout)
from repro.core.address_mapping import policies_for
from repro.core.autotune import TuneReport
from repro.core.roofline_empirical import config_ceiling_gbps
from repro.core.sweep import KIND_CONTENTION, SweepPoint
from repro.core.timing_jax import GridAxes, evaluate_grid
from repro.service import CampaignService, ExperimentRequest
from repro.service.faults import register_fault_injected

ALL_SPECS = (HBM, DDR4, HBM3, DDR3)
# (arbitration, burst_beats) pairs shared between the grid axes and the
# tuner options — the timing model only accepts burst_beats != 1 under
# the "burst" grant policy.
GRID_ARBS = (("round_robin", 1), ("burst", 4), ("exclusive", 1))
TRI_PLACEMENTS = ("same_channel", "same_switch", "cross_switch")


def _small_params(spec):
    b = max(64, spec.min_burst)
    return RSTParams(n=512, b=b, s=b, w=1 << 22)


def _tune_kwargs():
    return dict(arbitrations=("round_robin", "burst", "exclusive"),
                burst_beats=(4,), placements=TRI_PLACEMENTS, mixes=(1, 4))


@pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: s.name)
def test_winner_matches_exhaustive_grid(spec):
    """Tuner winner == grid argmax, with strictly fewer evaluations."""
    p = _small_params(spec)
    axes = GridAxes(params=(p,), policies=tuple(policies_for(spec)),
                    ops=("read",), num_engines=(1, 4),
                    arbitrations=GRID_ARBS, placements=TRI_PLACEMENTS)
    assert axes.size <= 256, "keep the exhaustive oracle small"
    grid = evaluate_grid(spec, axes)
    report = tune_layout(p, spec, "sim", **_tune_kwargs())

    grid_max = float(np.max(grid.gbps))
    # The grid evaluates through the JAX kernel, the tuner through the
    # sim backend; the two towers agree to ~1e-9 relative.
    assert report.winner_gbps == pytest.approx(grid_max, rel=1e-8)
    assert report.evaluations < axes.size
    # The winner's own lane in the grid must score what the tuner says.
    lane = [i for i, pt in enumerate(grid.sweep_points())
            if (pt.policy, pt.arbitration, pt.burst_beats, pt.placement,
                pt.num_engines) == (report.winner.policy,
                                    report.winner.arbitration,
                                    report.winner.burst_beats,
                                    report.winner.placement,
                                    report.winner.engines)]
    assert lane, "tuner winner must be a grid point"
    assert float(grid.gbps[lane[0]]) == pytest.approx(report.winner_gbps,
                                                      rel=1e-8)


@pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: s.name)
def test_ceiling_bound_is_sound(spec):
    """No measured grid point exceeds its capacity ceiling (the invariant
    that makes bound-guided pruning exact)."""
    p = _small_params(spec)
    axes = GridAxes(params=(p,), policies=tuple(policies_for(spec)),
                    ops=("read",), num_engines=(1, 4),
                    arbitrations=GRID_ARBS, placements=TRI_PLACEMENTS)
    grid = evaluate_grid(spec, axes)
    for gbps, pt in zip(grid.gbps, grid.sweep_points()):
        ceiling = config_ceiling_gbps(spec, pt.placement, pt.num_engines)
        assert float(gbps) <= ceiling * (1 + 1e-9), (pt.placement,
                                                     pt.num_engines)


def test_single_engine_arbitration_collapse():
    """N=1 scores are identical under every grant policy — the spelling
    collapse the tuner's structural savings rest on."""
    p = _small_params(HBM)
    sweep = Sweep(HBM, "sim")
    for arb, bb in (("round_robin", 1), ("exclusive", 1), ("burst", 8)):
        sweep.add_point(SweepPoint(p, "RBC", kind=KIND_CONTENTION,
                                   num_engines=1, arbitration=arb,
                                   burst_beats=bb, placement="same_switch"))
    vals = [r.value.aggregate_gbps for r in sweep.run()]
    assert vals[0] == vals[1] == vals[2]


def test_same_seed_bit_identical_report():
    p = _small_params(HBM)
    r1 = tune_layout(p, HBM, "sim", seed=3, **_tune_kwargs())
    r2 = tune_layout(p, HBM, "sim", seed=3, **_tune_kwargs())
    assert r1 == r2          # full trajectory, winner, and scores
    # A different seed reorders ties but cannot change the optimum.
    r3 = tune_layout(p, HBM, "sim", seed=11, **_tune_kwargs())
    assert r3.winner_gbps == r1.winner_gbps


def test_warm_sweep_retune_hits_cache():
    """Re-tuning against a warm Sweep issues zero new backend calls."""
    name = "counting-sim-autotune"
    backend = register_fault_injected("sim", name=name, rate=0.0,
                                      override=True)
    try:
        p = _small_params(HBM)
        sweep = Sweep(HBM, name, coalesce=True)
        r1 = tune_layout(p, HBM, name, sweep=sweep, **_tune_kwargs())
        calls_after_first = backend.calls
        assert calls_after_first == r1.evaluations
        r2 = tune_layout(p, HBM, name, sweep=sweep, **_tune_kwargs())
        assert backend.calls == calls_after_first
        assert r2 == r1
    finally:
        engine_mod._BACKEND_REGISTRY.pop(name, None)


def test_budget_truncates_bracket():
    p = _small_params(HBM)
    full = tune_layout(p, HBM, "sim", **_tune_kwargs())
    capped = tune_layout(p, HBM, "sim", 10, **_tune_kwargs())
    assert capped.evaluations <= 10 < full.evaluations
    assert capped.winner_gbps <= full.winner_gbps
    # The bracket is ceiling-ordered, so even a tight budget lands on a
    # tier that can reach the global optimum here.
    assert capped.candidates == full.candidates


def test_engine_mix_configs_tune():
    """EngineMix grammar strings ride the same knob axis as counts."""
    p = _small_params(HBM)
    report = tune_layout(p, HBM, "sim", mixes=(1, "2r+1w"),
                         arbitrations=("round_robin",), burst_beats=(1,))
    assert report.winner.engines in (1, "2r+1w")
    assert report.evaluations <= report.candidates


def test_service_roundtrip_and_dedup():
    """layout_autotune flows through the CampaignService: derived
    TuneReport, duplicate requests coalesced, and the offline replay
    matches the direct search bit for bit."""
    svc = CampaignService("sim", "sim")
    req = ExperimentRequest.make("layout_autotune", "hbm", quick=True)
    resp = svc.submit(req)
    assert resp.ok and isinstance(resp.result, TuneReport)
    assert resp.result.evaluations <= resp.result.candidates
    dup = svc.submit(req)
    assert dup.coalesced and dup.result == resp.result

    direct = run_experiment("layout_autotune", HBM, "sim", quick=True)
    assert direct == resp.result

    env_resp = svc.submit(
        ExperimentRequest.make("roofline_empirical", "hbm", quick=True))
    assert env_resp.ok and env_resp.result.peak_gbps > 0


def test_tuner_probes_share_the_sweep_memo():
    """Two tuners over one Sweep: the second's probes all memo-hit."""
    p = _small_params(HBM)
    sweep = Sweep(HBM, "sim", coalesce=True)
    tune_layout(p, HBM, "sim", sweep=sweep, **_tune_kwargs())
    evaluated_once = sweep.stats.evaluated
    tune_layout(p, HBM, "sim", sweep=sweep, **_tune_kwargs())
    assert sweep.stats.evaluated == evaluated_once
    assert sweep.stats.cache_hits > 0


def test_backend_registry_unknown_backend_still_errors():
    with pytest.raises(ValueError):
        get_backend("no-such-backend")
