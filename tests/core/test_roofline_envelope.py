"""Envelope invariants for the measured roofline (REPRO-O005 coverage).

Property tests (hypothesis, via the optional shim) pin the closed-form
envelope math — attainable(AI) monotone and bounded, the envelope an
upper bound on every probe that fed it — and measured-envelope tests pin
the placement-tier ordering Shuhai/Choi report: same_channel >=
same_switch >= cross_switch per engine, strictly on capped fabrics.

This module is also the designated coverage tier for the public
envelope math: repro-lint's REPRO-O005 checks that every public
function of `repro.core.roofline_empirical` (and every public
`RooflineEnvelope` method) is exercised here.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st  # noqa: E402

from repro.core import (DDR3, DDR4, HBM, HBM3, chip_by_name)  # noqa: E402
from repro.core import roofline_empirical as rf  # noqa: E402
from repro.core.switch import PLACEMENTS  # noqa: E402

CHIP = chip_by_name("tpu_v5e")
ALL_SPECS = (HBM, DDR4, HBM3, DDR3)


def _synthetic_envelope(gbps_values):
    points = tuple(
        rf.EnvelopePoint(policy="RBC", placement="same_channel",
                         num_engines=1, burst=64, stride=64, gbps=g)
        for g in gbps_values)
    return rf.build_envelope(HBM, CHIP, points)


if HAVE_HYPOTHESIS:
    ai_lists = st.lists(st.floats(min_value=1e-3, max_value=1e6,
                                  allow_nan=False, allow_infinity=False),
                        min_size=2, max_size=24)
    gbps_lists = st.lists(st.floats(min_value=1e-3, max_value=500.0,
                                    allow_nan=False, allow_infinity=False),
                          min_size=1, max_size=16)
else:                                        # pragma: no cover
    ai_lists = gbps_lists = None


@given(ais=ai_lists)
@settings(max_examples=50, deadline=None)
def test_attainable_monotone_and_bounded(ais):
    env = _synthetic_envelope([10.0, 20.0])
    for ai in ais:
        val = env.attainable(ai)
        assert val <= env.peak_flops
        assert val <= ai * env.peak_gbps * 1e9 * (1 + 1e-12)
    ordered = sorted(ais)
    vals = [env.attainable(ai) for ai in ordered]
    assert all(lo <= hi for lo, hi in zip(vals, vals[1:]))


@given(gbps=gbps_lists)
@settings(max_examples=50, deadline=None)
def test_envelope_upper_bounds_its_points(gbps):
    env = _synthetic_envelope(gbps)
    assert env.peak_gbps == max(gbps)
    for pt in env.points:
        assert pt.gbps <= env.peak_gbps
        # Bandwidth-bound region: the roofline at this point's rate never
        # exceeds the roofline at the peak rate.
        assert env.attainable(1.0, gbps=pt.gbps) <= env.attainable(1.0)


def test_knee_is_the_bend():
    env = _synthetic_envelope([16.0])
    knee = env.knee_ai()
    assert env.attainable(knee) == pytest.approx(env.peak_flops)
    assert env.attainable(knee / 2) == pytest.approx(env.peak_flops / 2)
    assert env.attainable(knee * 8) == env.peak_flops
    # A slower bandwidth tier bends later.
    assert env.knee_ai(gbps=8.0) > knee


def test_ladder_matches_attainable():
    env = _synthetic_envelope([16.0])
    rungs = env.ladder()
    assert len(rungs) == len(env.ai_ladder)
    for ai, flops in rungs:
        assert flops == env.attainable(ai)


def test_build_envelope_rejects_empty():
    with pytest.raises(ValueError):
        rf.build_envelope(HBM, CHIP, ())


@pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: s.name)
def test_measured_placement_tiers_ordered(spec):
    """Per-engine tiers obey same_channel >= same_switch >= cross_switch."""
    env = rf.measure_envelope(spec, quick=True)
    sc = env.placement_gbps["same_channel"]
    ss = env.placement_gbps["same_switch"]
    cs = env.placement_gbps["cross_switch"]
    assert sc >= ss >= cs
    assert set(env.placement_gbps) == set(PLACEMENTS)
    assert env.spec_name == spec.name and env.chip_name == CHIP.name


def test_capped_fabric_orders_strictly():
    """HBM3's lateral bridge (12.8 GB/s) sits below its single-stream
    rate, so the cross_switch tier must drop strictly."""
    env = rf.measure_envelope(HBM3, quick=True)
    assert env.placement_gbps["cross_switch"] < \
        env.placement_gbps["same_switch"]


@pytest.mark.parametrize("spec", ALL_SPECS, ids=lambda s: s.name)
def test_ceiling_bounds_every_probe(spec):
    """config_ceiling_gbps upper-bounds every measured envelope point."""
    env = rf.measure_envelope(spec, quick=True)
    for pt in env.points:
        ceiling = rf.config_ceiling_gbps(spec, pt.placement, pt.num_engines)
        assert pt.gbps <= ceiling * (1 + 1e-9)


def test_fraction_of_nominal_matches_shuhai():
    """Single-stream HBM lands at Shuhai's ~92% of the 14.4 GB/s wire."""
    env = rf.measure_envelope(HBM, quick=True)
    frac = env.fraction_of_nominal(env.placement_gbps["same_channel"])
    assert 0.85 <= frac <= 1.0
    agg = env.placement_aggregate_gbps["same_switch"]
    assert env.fraction_of_nominal(agg, ports=4) <= 1.0


def test_policy_knees_cover_every_policy():
    env = rf.measure_envelope(HBM, quick=True)
    from repro.core.address_mapping import policies_for
    assert set(env.policy_gbps) == set(policies_for(HBM))
    # Every per-policy bandwidth defines its own knee, ordered opposite
    # to the bandwidths themselves.
    knees = {pol: env.knee_ai(gbps=g) for pol, g in env.policy_gbps.items()}
    best = max(env.policy_gbps, key=lambda k: env.policy_gbps[k])
    assert knees[best] == min(knees.values())


def test_backend_agnostic_envelope():
    """The jaxgrid backend derives the same envelope as sim."""
    sim_env = rf.measure_envelope(HBM, "sim", quick=True)
    jax_env = rf.measure_envelope(HBM, "jaxgrid", quick=True)
    assert jax_env.peak_gbps == pytest.approx(sim_env.peak_gbps, rel=1e-6)
    for plc in PLACEMENTS:
        assert jax_env.placement_gbps[plc] == pytest.approx(
            sim_env.placement_gbps[plc], rel=1e-6)


# -- chip peaks are looked up by the device's kind --------------------------

class _Device:
    def __init__(self, kind, platform="tpu"):
        self.device_kind, self.platform = kind, platform


def test_chip_for_device_covers_v5e():
    from repro.core import TPU_V5E, chip_for_device
    assert chip_for_device(_Device("TPU v5 lite")) is TPU_V5E
    assert TPU_V5E.hbm_bandwidth == 819e9


@pytest.mark.parametrize("kind,platform", [
    ("TPU v4", "tpu"), ("TPU v5", "tpu"), ("cpu", "cpu")])
def test_chip_for_device_rejects_unknown_kind(kind, platform):
    # An unknown device is an error, never another chip's peaks.
    from repro.core import chip_for_device
    with pytest.raises(ValueError, match="no registered chip"):
        chip_for_device(_Device(kind, platform))


def test_chip_for_device_defaults_to_the_first_jax_device():
    # The tests run on the CPU (conftest.py), whose kind has no peaks.
    from repro.core import chip_for_device
    with pytest.raises(ValueError, match="'cpu'"):
        chip_for_device()
