"""CampaignService: dedup, retry, breakers, degradation, validation, soak."""
import pytest

from repro.core import engine as engine_mod
from repro.service import (CampaignService, ExperimentRequest, Fault,
                           FaultScript, RetryPolicy, register_fault_injected)

QUICK_TP = dict(experiment="fig6_address_mapping", quick=True)


@pytest.fixture
def flaky(request):
    """Register a fault-injected sim backend; yields its name, cleans up.

    Parametrize indirectly with FaultScript kwargs (or {'script': ...})."""
    kwargs = dict(getattr(request, "param", {}) or {})
    name = kwargs.pop("name", "sim+test")
    be = register_fault_injected("sim", name=name, override=True, **kwargs)
    yield be
    engine_mod._BACKEND_REGISTRY.pop(name, None)


def scripted(*faults, name="sim+test"):
    be = register_fault_injected("sim", name=name,
                                 script=FaultScript().script(*faults),
                                 override=True)
    return be


class TestDedupAndCoalescing:
    def test_duplicate_requests_served_from_one_evaluation(self):
        svc = CampaignService("sim", "sim", validate_fraction=0.0)
        reqs = [ExperimentRequest.make(**QUICK_TP)] * 6 + [
            ExperimentRequest.make("table4_idle_latency", n=512)] * 4
        out = svc.submit_all(reqs)
        assert all(r.ok for r in out)
        assert svc.stats.requests == 10 and svc.stats.executed == 2
        assert svc.stats.deduped == 8 and svc.stats.dropped == 0
        assert sum(r.coalesced for r in out) == 8
        # Coalesced copies carry the same result object.
        assert out[1].result == out[0].result

    def test_distinct_overrides_are_distinct_keys(self):
        svc = CampaignService("sim", "sim", validate_fraction=0.0)
        svc.submit(ExperimentRequest.make("table4_idle_latency", n=512))
        svc.submit(ExperimentRequest.make("table4_idle_latency", n=256))
        assert svc.stats.executed == 2 and svc.stats.deduped == 0

    def test_unhashable_override_values_are_frozen(self):
        r = ExperimentRequest.make("fig7_locality", strides=[64, 1024],
                                   quick=True)
        assert r.overrides == (("strides", (64, 1024)),)
        hash(r)                              # the request IS the dedup key


class TestRetry:
    def test_transient_failures_retry_to_success(self):
        try:
            be = scripted(Fault("transient"), Fault("timeout", seconds=0.5))
            svc = CampaignService("sim+test", "sim", validate_fraction=0.0)
            r = svc.submit(ExperimentRequest.make(**QUICK_TP))
            assert r.ok and not r.degraded
            assert r.attempts == 3 and r.retries == 2
            assert svc.stats.retries == 2
            # The injected timeout + both backoffs were charged virtually.
            assert svc.now >= 0.5
            assert r.elapsed_s == pytest.approx(svc.now)
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+test", None)

    def test_retries_resume_from_coalesced_points(self):
        # fig6 quick plans >1 point; a transient on the second attempt's
        # first call must not force re-evaluating points already served.
        try:
            be = scripted(None, Fault("transient"))
            svc = CampaignService("sim+test", "sim", validate_fraction=0.0)
            r = svc.submit(ExperimentRequest.make(**QUICK_TP))
            assert r.ok and r.retries == 1
            # calls = points + 1 (the failed call), NOT 2x points.
            distinct_points = be.calls - 1
            assert be.injected["transient"] == 1
            assert distinct_points >= 2
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+test", None)

    def test_permanent_failure_fails_fast_no_retry(self):
        try:
            be = scripted(Fault("permanent"))
            svc = CampaignService("sim+test", "sim", validate_fraction=0.0)
            r = svc.submit(ExperimentRequest.make(**QUICK_TP))
            assert not r.ok and r.retries == 0
            assert "PermanentBackendError" in r.error
            assert svc.stats.failed == 1 and svc.stats.dropped == 0
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+test", None)

    def test_retry_exhaustion_degrades_to_fallback(self):
        try:
            register_fault_injected("sim", name="sim+dead", rate=1.0,
                                    kinds=("transient",), override=True)
            svc = CampaignService("sim+dead", "sim",
                                  retry=RetryPolicy(max_attempts=3),
                                  validate_fraction=0.0)
            r = svc.submit(ExperimentRequest.make(**QUICK_TP))
            assert r.ok and r.degraded and r.backend == "sim"
            assert "retry budget exhausted" in r.degraded_reason
            assert svc.stats.degraded == 1 and svc.stats.dropped == 0
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+dead", None)

    def test_retry_exhaustion_without_fallback_fails(self):
        try:
            register_fault_injected("sim", name="sim+dead", rate=1.0,
                                    kinds=("transient",), override=True)
            svc = CampaignService("sim+dead", fallback=None,
                                  retry=RetryPolicy(max_attempts=2),
                                  validate_fraction=0.0)
            r = svc.submit(ExperimentRequest.make(**QUICK_TP))
            assert not r.ok and "retry budget exhausted" in r.error
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+dead", None)

    def test_deadline_exceeded_degrades(self):
        try:
            register_fault_injected("sim", name="sim+slow", rate=1.0,
                                    kinds=("timeout",), timeout_s=10.0,
                                    override=True)
            svc = CampaignService("sim+slow", "sim", deadline_s=15.0,
                                  retry=RetryPolicy(max_attempts=10),
                                  validate_fraction=0.0)
            r = svc.submit(ExperimentRequest.make(**QUICK_TP))
            assert r.ok and r.degraded
            assert "deadline" in r.degraded_reason
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+slow", None)


class TestBreakerAndDegradation:
    def test_breaker_opens_and_routes_around_backend(self):
        try:
            register_fault_injected("sim", name="sim+down", rate=1.0,
                                    kinds=("transient",), override=True)
            svc = CampaignService("sim+down", "sim",
                                  retry=RetryPolicy(max_attempts=2),
                                  breaker_threshold=2, breaker_reset_s=1e9,
                                  validate_fraction=0.0)
            r1 = svc.submit(ExperimentRequest.make(**QUICK_TP))
            assert r1.ok and r1.degraded
            assert svc.breaker("sim+down").state == "open"
            assert svc.stats.breaker_opens == 1
            # Next distinct request: breaker refuses up front, straight to
            # fallback — the dead backend is not hit again.
            down = engine_mod.get_backend("sim+down")
            calls_before = down.calls
            r2 = svc.submit(ExperimentRequest.make("table4_idle_latency",
                                                   n=512))
            assert r2.ok and r2.degraded
            assert "circuit breaker" in r2.degraded_reason
            assert down.calls == calls_before
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+down", None)

    def test_half_open_probe_recovers_backend(self):
        try:
            be = scripted(Fault("transient"))
            svc = CampaignService("sim+test", "sim",
                                  retry=RetryPolicy(max_attempts=1,
                                                    base_delay_s=0.0),
                                  breaker_threshold=1, breaker_reset_s=0.5,
                                  validate_fraction=0.0)
            svc.submit(ExperimentRequest.make(**QUICK_TP))   # opens breaker
            assert svc.breaker("sim+test").state == "open"
            svc.now += 1.0                   # past the reset timeout
            r = svc.submit(ExperimentRequest.make("table4_idle_latency",
                                                  n=512))
            assert r.ok and not r.degraded   # probe succeeded, recovered
            assert svc.breaker("sim+test").state == "closed"
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+test", None)

    def test_capability_gap_degrades_pallas_to_sim(self):
        # pallas has no per-transaction timers: a latency experiment on a
        # pallas-primary service degrades to sim instead of erroring.
        svc = CampaignService("pallas", "sim", validate_fraction=0.0)
        r = svc.submit(ExperimentRequest.make("table4_idle_latency", n=512))
        assert r.ok and r.degraded and r.backend == "sim"
        assert "serial-latency" in r.degraded_reason
        assert svc.stats.degraded == 1

    def test_unsupported_fault_degrades_without_breaker_damage(self):
        try:
            be = scripted(Fault("unsupported"))
            svc = CampaignService("sim+test", "sim", breaker_threshold=1,
                                  validate_fraction=0.0)
            r = svc.submit(ExperimentRequest.make(**QUICK_TP))
            assert r.ok and r.degraded
            assert svc.breaker("sim+test").state == "closed"
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+test", None)

    def test_bad_request_is_a_clean_failure(self):
        svc = CampaignService("sim", "sim")
        r = svc.submit(ExperimentRequest.make("no_such_experiment"))
        assert not r.ok and "unknown experiment" in r.error
        r2 = svc.submit(ExperimentRequest.make(**QUICK_TP, nope=3))
        assert not r2.ok and "bad request" in r2.error
        assert svc.stats.dropped == 0


class TestValidation:
    def test_clean_backend_validates_true(self):
        svc = CampaignService("sim", "sim", validate_fraction=1.0)
        r = svc.submit(ExperimentRequest.make(**QUICK_TP))
        assert r.ok and r.validated is True
        assert svc.stats.validated == 1
        assert svc.stats.validation_mismatches == 0

    def test_corrupt_backend_is_quarantined_and_degraded(self):
        try:
            register_fault_injected("sim", name="sim+lying", rate=1.0,
                                    kinds=("corrupt",), override=True)
            svc = CampaignService("sim+lying", "sim", validate_fraction=1.0)
            r = svc.submit(ExperimentRequest.make(**QUICK_TP))
            # The corruption is invisible to retry/breaker logic — only the
            # oracle catches it; the response is re-served from sim.
            assert r.ok and r.degraded and r.backend == "sim"
            assert "validation mismatch" in r.degraded_reason
            assert r.validated is True       # the fallback's result checked
            assert svc.stats.validation_mismatches == 1
            assert svc.stats.quarantines == 1
            br = svc.breaker("sim+lying")
            assert br.quarantined and not br.allow(1e12)
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+lying", None)

    def test_validate_fraction_zero_never_validates(self):
        svc = CampaignService("sim", "sim", validate_fraction=0.0)
        r = svc.submit(ExperimentRequest.make(**QUICK_TP))
        assert r.validated is None and svc.stats.validated == 0

    def test_rejects_bad_fraction(self):
        with pytest.raises(ValueError, match="validate_fraction"):
            CampaignService("sim", validate_fraction=1.5)

    def test_unknown_backend_fails_at_build_time(self):
        with pytest.raises(ValueError, match="unknown backend"):
            CampaignService("no_such_backend")


class TestAcceptanceSoak:
    """1000 mixed requests over 18 distinct keys through a fault-injected
    sim primary with a sim fallback and full validation."""

    MIX = tuple(
        req for spec in ("hbm", "ddr4") for req in (
            ExperimentRequest.make("fig6_address_mapping", spec, quick=True),
            ExperimentRequest.make("table4_idle_latency", spec, n=512),
            ExperimentRequest.make("fig4_refresh", spec, quick=True),
            ExperimentRequest.make("fig7_locality", spec, quick=True),
            ExperimentRequest.make("fig9_channel_contention", spec,
                                   quick=True),
            ExperimentRequest.make("table5_total_throughput", spec, n=2048),
            ExperimentRequest.make("duplex_rw_sweep", spec, quick=True),
            # Contended and mixed-engine points: validated against the
            # contended oracles, not the homogeneous one.
            ExperimentRequest.make("contention_scaling_sweep", spec,
                                   quick=True),
            ExperimentRequest.make("engine_mix_sweep", spec, quick=True),
        ))

    @pytest.mark.parametrize("rate,degraded_min,clean", [
        (0.0, 0, True), (0.01, 0, False), (0.10, 1, False)])
    def test_1000_requests_at_fault_rate(self, rate, degraded_min, clean):
        """1000 mixed requests at injected fault `rate`: zero dropped,
        every response validated or degraded with a reason, duplicates
        provably coalesced; a clean primary retries and degrades nothing,
        and at 10 % the faults reach the fallback."""
        try:
            register_fault_injected(
                "sim", name="sim+soak", rate=rate, seed=7,
                kinds=("transient", "timeout", "corrupt", "unsupported"),
                weights=(0.5, 0.2, 0.15, 0.15), timeout_s=0.2,
                override=True)
            svc = CampaignService("sim+soak", "sim",
                                  retry=RetryPolicy(max_attempts=8),
                                  validate_fraction=1.0, seed=11)
            mix = self.MIX
            reqs = [mix[i % len(mix)] for i in range(1000)]
            out = svc.submit_all(reqs)
            st = svc.stats
            assert len(out) == 1000 and st.dropped == 0
            assert all(r.ok for r in out)
            # Every response: oracle-validated, or degraded with a reason
            # (validated None = plan had no oracle-checkable point; every
            # request of the mix has one).
            assert all(r.validated is True
                       or (r.degraded and r.degraded_reason)
                       for r in out)
            # Duplicates provably coalesced: one execution per key.
            assert st.executed == len(mix)
            assert st.deduped == 1000 - len(mix)
            assert st.degraded >= degraded_min
            if clean:
                assert st.retries == 0 and st.degraded == 0
                assert st.validation_mismatches == 0
        finally:
            engine_mod._BACKEND_REGISTRY.pop("sim+soak", None)
