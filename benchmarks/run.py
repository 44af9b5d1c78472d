"""Benchmark harness over the declarative experiment registry.

Every paper table/figure is a registered `Experiment`
(core/experiments.py); `bench_experiments` times each one per applicable
memory spec and prints ``name,us_per_call,derived`` CSV rows.
`us_per_call` is the wall time of running the suite through the calibrated
engine model (the measurement machinery itself); `derived` carries the
headline quantity the paper reports for that artifact (each experiment's
`summarize`).  The TPU-analogue and framework-integration benches below
are not paper artifacts and stay hand-written.

With ``--json PATH`` the same rows (plus totals) are written as a
``BENCH_*.json`` perf-trajectory file so successive PRs can track the
sim-backend speedup (CI writes ``BENCH_ci.json`` on every push).
``--experiments name1,name2`` restricts the registry suite (unknown names
fail with the registered list).  ``--engines N`` replaces the contention
experiments' engine-count ladder with powers of two up to N.
``--arbitration POLICY`` / ``--burst B`` select the shared-port grant
granularity (round_robin / burst / exclusive, DESIGN.md §9) for every
experiment that exposes the axis (CI runs one burst-grant ladder —
``--engines 4 --arbitration burst --burst 8`` — on every push).
``--catalog [PATH]`` emits the registry-generated experiment-catalog
table instead of benchmarking — to stdout, or spliced into README.md's
catalog markers.

``--service`` switches to the campaign-service soak (DESIGN.md §10): a
mixed batch of duplicate-heavy experiment requests is served through
`CampaignService` against a fault-injected primary backend at each
``--fault-rate`` (comma list, default ``0,0.01,0.1``), with sim
fallback.  Each soak asserts the service invariants — zero dropped
requests, duplicates coalesced (backend executions < requests), and at
the highest non-zero rate at least one degraded (fallback) response —
and records sustained QPS per rate (``--qps-target`` makes a floor of it).
CI uploads this as ``BENCH_ci_service.json``.

Run: PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]
         [--experiments NAMES] [--engines N]
         [--arbitration POLICY] [--burst B] [--catalog [PATH]]
         [--service] [--fault-rate RATES] [--qps-target QPS]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


#: JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
#: unset: one fixed directory inside the checkout (listed in .gitignore).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set here.  Otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`, a fixed path, so a later run in the same
    checkout finds what an earlier one compiled.  Every compilation is
    kept, however short: the RST kernels compile in about a second.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    dt = (time.perf_counter() - t0) * 1e6
    return out, dt


# Specs the registry-driven benches run over by default: the paper's
# measured pair, keeping the historical perf-trajectory row names stable.
# The modeled HBM3/DDR3 generalization targets are pinned by tier-1 tests
# and the example campaign driver instead — adding them here would suffix
# the single-spec rows (table6/fig8) and break BENCH_*.json comparability.
# Experiments that set `bench_specs` (the write/duplex family runs on all
# four registered systems) override this default per experiment.
BENCH_SPEC_NAMES = ("hbm", "ddr4")


def resolve_experiments(names):
    """Resolve a comma-separated experiment filter against the registry;
    no filter means every experiment the sim backend serves (on HBM, whose
    switch every experiment's hardware needs at most).

    Exits with a clear message (listing every registered name) instead of
    surfacing a traceback when a name is unknown.
    """
    from repro.core.experiments import experiments_for, get_experiment
    from repro.core.hwspec import HBM

    if not names:
        return experiments_for(HBM)
    try:
        return [get_experiment(n.strip()) for n in names.split(",")]
    except ValueError as e:
        raise SystemExit(f"benchmarks.run: {e}")


def engine_ladder(max_engines):
    """The --engines N override: powers of two up to (and including) N."""
    if max_engines < 1:
        raise SystemExit(
            f"benchmarks.run: --engines must be >= 1, got {max_engines}")
    ladder = []
    k = 1
    while k < max_engines:
        ladder.append(k)
        k *= 2
    ladder.append(max_engines)
    return tuple(ladder)


def parse_engines_arg(text):
    """Resolve the --engines value: a bare integer N (engine-count ladder)
    or a heterogeneous mix spec like '2r+1w+1d' (DESIGN.md §13).

    Returns the int for the ladder form, the validated spec string for the
    mix form; exits with the accepted grammar on anything else — the same
    UX as an unknown --experiments name.
    """
    from repro.core.engine_mix import parse_mix_spec

    if text.isdigit():
        n = int(text)
        engine_ladder(n)        # validates >= 1 up front, not per suite
        return n
    try:
        parse_mix_spec(text)
    except ValueError as e:
        raise SystemExit(f"benchmarks.run: --engines: {e}")
    return text


def bench_experiments(quick=False, experiments=None, engines=None,
                      arbitration=None, burst=None):
    """One row per (registered experiment, applicable spec).

    All grid/derive/summary logic lives on the Experiment objects
    (core/experiments.py); this harness only iterates the registry.
    Single-spec experiments (the switch suites) keep their bare row name;
    multi-spec ones are suffixed with the spec, matching the historical
    row names so BENCH_*.json trajectories stay comparable.  `engines`
    (the --engines flag) replaces the engine-count ladder of the
    contention experiments — every experiment with an "engines" option —
    when given as an int, or (as a mix spec like '2r+1w+1d') the custom
    blend of every experiment with a "custom_mix" option (the engine-mix
    family, DESIGN.md §13); `arbitration`/`burst` (--arbitration/--burst)
    select the shared-port grant granularity for every experiment
    exposing that axis.
    """
    from repro.core import spec_by_name
    from repro.core.experiments import run_experiment

    rows = []
    for exp in resolve_experiments(experiments):
        specs = [spec_by_name(n)
                 for n in (exp.bench_specs or BENCH_SPEC_NAMES)]
        available = [s for s in specs if exp.available_on(s)]
        label = exp.bench_label or exp.name
        overrides = {}
        if isinstance(engines, int) and "engines" in exp.defaults:
            overrides["engines"] = engine_ladder(engines)
        elif isinstance(engines, str) and "custom_mix" in exp.defaults:
            overrides["custom_mix"] = engines
        if arbitration is not None and "arbitration" in exp.defaults:
            overrides["arbitration"] = arbitration
            if arbitration != "burst" and "burst_beats" in exp.defaults:
                # round_robin/exclusive fix the grant size; leaving an
                # experiment's default burst_beats (e.g. the contended-
                # latency classes' 8) in place would fail validation.
                overrides["burst_beats"] = 1
        if burst is not None and "burst_beats" in exp.defaults:
            overrides["burst_beats"] = burst
        for spec in available:
            res, dt = _timed(lambda: run_experiment(
                exp, spec, quick=quick, bench=True, **overrides))
            name = label if len(available) == 1 else f"{label}_{spec.name}"
            rows.append((name, dt, exp.summary(spec, res)))
    return rows


def bench_table3_resources():
    """Table III analogue: engine 'resource' footprint on TPU = VMEM bytes
    per RST engine tile + params-register bytes (vs FPGA LUTs/BRAM)."""
    import jax.numpy as jnp

    from repro.kernels import ops

    def run():
        tile = ops.tile_bytes(jnp.float32)                 # VMEM per burst
        regs = 2 * 32                                       # 2x256-bit regs
        return {"vmem_tile_bytes": tile, "register_bytes": regs}

    res, dt = _timed(run)
    return [("table3_resources_tpu_analogue", dt,
             f"vmem_tile_bytes={res['vmem_tile_bytes']};"
             f"register_bytes={res['register_bytes']}")]


def bench_tpu_rst_kernel(quick=False):
    """TPU-native RST engines (compiled on a TPU, interpreted elsewhere):
    checksum-validated bandwidth samples for sequential vs strided
    traversals."""
    import jax
    import jax.numpy as jnp

    from repro.core.params import RSTParams
    from repro.kernels import ops
    n = 32 if quick else 128
    rows = []
    for name, (s_mult, w_tiles) in {
        "seq": (1, 64), "strided4": (4, 64), "hammer": (64, 64),
    }.items():
        tile = ops.tile_bytes(jnp.float32)
        p = RSTParams(n=n, b=tile, s=tile * s_mult, w=tile * w_tiles)
        sample, dt = _timed(
            lambda p=p: ops.measure_read_bandwidth(p, dtype=jnp.float32))
        rows.append((f"tpu_rst_read_{name}", dt,
                     f"bytes={sample.bytes_moved};"
                     f"platform={jax.default_backend()};"
                     f"gbps={sample.gbps:.4f}"))
    return rows


def bench_sweep_grid(quick=False):
    """Sweep planner: one batched (policy x stride x channel) campaign grid,
    exercising memoization + channel broadcast (core/sweep.py)."""
    from repro.core import HBM, RSTParams, Sweep

    strides = (64, 1024) if quick else (64, 256, 1024, 4096)
    channels = range(0, 32, 4)
    n = 1024 if quick else 4096

    def run():
        sweep = Sweep(HBM)
        sweep.add_grid(
            [RSTParams(n=n, b=64, s=s, w=0x10000000) for s in strides],
            policies=("RGBCG", "RBC", "BRC"), channels=tuple(channels))
        results = sweep.run()
        return sweep.stats, results

    (stats, results), dt = _timed(run)
    gbps = [r.value.gbps for r in results]
    return [("sweep_grid_hbm", dt,
             f"points={stats.points};evaluated={stats.evaluated};"
             f"cache_hits={stats.cache_hits};max_gbps={max(gbps):.2f}")]


def grid_ladder_axes(quick=False):
    """The --grid ladder's HBM cross-product: 10,368 points at n=2^17
    (864 at n=2^15 with `quick`).

    Long streams are where batching pays: every lane is exactly periodic
    (pow2 everything, no exclusive grants), so the compiled grid
    evaluates a 2-window steady-state kernel per lane while the
    per-point NumPy path expands all 2^17 commands.
    """
    from repro.core import HBM, RSTParams
    from repro.core import timing_jax
    from repro.core.address_mapping import policies_for

    n = 1 << 15 if quick else 1 << 17
    nparams = 6 if quick else 18
    params = tuple(RSTParams(n=n, b=32, s=256 << (i % 6),
                             w=(256 << (i % 6)) * (1 << (i // 6)))
                   for i in range(nparams))
    return timing_jax.GridAxes(
        params=params,
        policies=(None,) + tuple(policies_for(HBM))[:3],
        ops=("read", "write", "duplex"),
        num_engines=(1, 4) if quick else (1, 2, 4, 8),
        arbitrations=((("round_robin", 1), ("burst", 4)) if quick else
                      (("round_robin", 1), ("burst", 2), ("burst", 4),
                       ("burst", 8))),
        placements=("same_channel", "same_switch", "cross_switch"))


def bench_grid(quick=False):
    """Grid-evaluation ladder (DESIGN.md §12): one policy x stride x op x
    engines x arbitration x placement cross-product priced four ways —
    per-point NumPy, per-point jit, one jit+vmap compiled grid, and the
    mesh-sharded grid.  The jit+vmap : per-point-NumPy ratio is the PR's
    acceptance number (>= 100x on the >= 10k-point default grid).
    """
    import jax
    from repro.core import HBM, get_mapping
    from repro.core import timing_jax, timing_model
    from repro.launch.mesh import grid_mesh

    spec = HBM
    axes = grid_ladder_axes(quick)
    params = axes.params

    # Rung 1: the uncached naive path — one host-side NumPy evaluation
    # per point, timed on an evenly-spaced sample (the full product at
    # ~ms/point is exactly what this ladder exists to retire).
    pts = axes.sweep_points()
    sample = pts[::max(1, len(pts) // (8 if quick else 24))]
    def run_numpy():
        for pt in sample:
            timing_model.contended_throughput(
                pt.params, get_mapping(spec, pt.policy), spec,
                num_engines=pt.num_engines, op=pt.op,
                arbitration=pt.arbitration, burst_beats=pt.burst_beats)
    _, numpy_us = _timed(run_numpy)
    numpy_pps = len(sample) / (numpy_us * 1e-6)
    rows = [("grid_per_point_numpy", numpy_us,
             f"sampled={len(sample)};pts_per_s={numpy_pps:.0f}")]

    # Rung 2: per-point jit — same sample through the JAX single-point
    # wrapper (one compile per shape bucket, then per-call dispatch).
    timing_jax.contended_throughput(
        sample[0].params, get_mapping(spec, sample[0].policy), spec,
        num_engines=sample[0].num_engines, op=sample[0].op,
        arbitration=sample[0].arbitration,
        burst_beats=sample[0].burst_beats)          # warm the jit cache
    def run_jit_pp():
        for pt in sample:
            timing_jax.contended_throughput(
                pt.params, get_mapping(spec, pt.policy), spec,
                num_engines=pt.num_engines, op=pt.op,
                arbitration=pt.arbitration, burst_beats=pt.burst_beats)
    _, jitpp_us = _timed(run_jit_pp)
    rows.append(("grid_jit_per_point", jitpp_us,
                 f"sampled={len(sample)};"
                 f"pts_per_s={len(sample) / (jitpp_us * 1e-6):.0f}"))

    # Rung 3: jit+vmap — the whole cross-product as one compiled program.
    cold, cold_us = _timed(lambda: timing_jax.evaluate_grid(spec, axes))
    warm, warm_us = _timed(lambda: timing_jax.evaluate_grid(spec, axes))
    vmap_pps = warm.size / (warm_us * 1e-6)
    rows.append(("grid_jit_vmap", warm_us,
                 f"points={warm.size};pts_per_s={vmap_pps:.0f};"
                 f"cold_s={cold_us * 1e-6:.2f};"
                 f"speedup_vs_numpy={vmap_pps / numpy_pps:.0f}x"))

    # Rung 4: mesh-sharded grid (1 device locally; CI forces 8 host
    # devices via XLA_FLAGS so the sharded rung exercises real sharding).
    mesh = grid_mesh()
    timing_jax.evaluate_grid(spec, axes, mesh=mesh)   # compile + place
    shard, shard_us = _timed(
        lambda: timing_jax.evaluate_grid(spec, axes, mesh=mesh))
    rows.append(("grid_sharded", shard_us,
                 f"points={shard.size};devices={jax.device_count()};"
                 f"pts_per_s={shard.size / (shard_us * 1e-6):.0f}"))

    # Rung 5: heterogeneous engine-mix lanes (DESIGN.md §13) — per-engine
    # (params, op) blends batched through the same compiled evaluator.
    # Short streams keep every blend on the stacked mixed-lane kernel.
    import dataclasses as _dc

    from repro.core.engine_mix import EngineMix

    mix_reqs = []
    for p in params[: 3 if quick else 6]:
        mp = _dc.replace(p, n=1 << 11)
        for spec_str in ("3r+1w", "2r+2w", "2r+1w+1d"):
            mix = EngineMix.from_spec(spec_str, mp)
            mix_reqs.append(("cont", mp, None, "read", len(mix),
                             "round_robin", 1, "same_channel", mix))
    timing_jax.evaluate_points(spec, mix_reqs)            # compile + place
    _, mix_us = _timed(lambda: timing_jax.evaluate_points(spec, mix_reqs))
    rows.append(("grid_hetero_mix", mix_us,
                 f"points={len(mix_reqs)};"
                 f"pts_per_s={len(mix_reqs) / (mix_us * 1e-6):.0f}"))
    return rows


def bench_oracle_autotune():
    """Framework integration: oracle efficiency + KV layout choice."""
    from repro.core import AccessPattern, MemoryOracle, choose_layout
    oracle = MemoryOracle()

    def run():
        eff = oracle.efficiency(AccessPattern(4096, 4096, 1 << 28))
        lay = choose_layout(oracle, {"seq": 32768, "kv_heads": 8,
                                     "head_dim": 128}, 2,
                            iterate_dim="seq",
                            fetch_dims=("kv_heads", "head_dim"))
        return eff, lay
    (eff, lay), dt = _timed(run)
    return [("oracle_autotune", dt,
             f"seq_eff={eff:.3f};kv_layout={'/'.join(lay.dims)}")]


def bench_roofline(quick):
    """Measured-envelope rungs: the empirical roofline per spec."""
    from repro.core import spec_by_name
    from repro.core.roofline_empirical import measure_envelope

    rows = []
    for name in BENCH_SPEC_NAMES:
        spec = spec_by_name(name)
        env, dt = _timed(lambda: measure_envelope(spec, quick=quick))
        tiers = ";".join(
            f"{''.join(w[0] for w in plc.split('_'))}"
            f"={env.placement_gbps[plc]:.2f}"
            for plc in ("same_channel", "same_switch", "cross_switch"))
        rows.append((f"roofline_envelope_{name}", dt,
                     f"peak_gbps={env.peak_gbps:.2f};"
                     f"knee_ai={env.knee_ai():.0f};{tiers}"))
    return rows


def bench_tune(quick):
    """Layout-autotune rungs, routed through the CampaignService so the
    rung exercises the dedup/coalescing path the tuner ships with.

    Asserts the service invariants on every run: responses ok, reports
    carry a measured winner, duplicate requests coalesce, and the search
    measured no more configs than its candidate space."""
    from repro.service import CampaignService, ExperimentRequest

    svc = CampaignService("sim", "sim")
    rows = []
    for name in BENCH_SPEC_NAMES:
        req = ExperimentRequest.make("layout_autotune", name, quick=quick)
        resp, dt = _timed(lambda: svc.submit(req))
        assert resp.ok, f"layout_autotune[{name}] failed: {resp.error}"
        rep = resp.result
        assert rep.evaluations <= rep.candidates
        rows.append((f"layout_autotune_{name}", dt,
                     f"winner={rep.winner.describe()};"
                     f"gbps={rep.winner_gbps:.2f};"
                     f"evals={rep.evaluations}/{rep.candidates};"
                     f"nominal={rep.nominal_fraction:.2f}"))
        dup, dup_dt = _timed(lambda: svc.submit(req))
        assert dup.coalesced and dup.result == rep
        rows.append((f"layout_autotune_{name}_dedup", dup_dt,
                     "coalesced=True"))
    return rows


def parse_fault_rates(text):
    """Parse the --fault-rate comma list; exits cleanly on bad values."""
    rates = []
    for part in text.split(","):
        part = part.strip()
        try:
            rate = float(part)
        except ValueError:
            raise SystemExit(
                f"benchmarks.run: --fault-rate: {part!r} is not a number "
                f"(expected a comma list like '0,0.01,0.1')")
        if not 0.0 <= rate <= 1.0:
            raise SystemExit(
                f"benchmarks.run: --fault-rate must be in [0, 1], got "
                f"{rate}")
        rates.append(rate)
    if not rates:
        raise SystemExit("benchmarks.run: --fault-rate: empty rate list")
    return tuple(rates)


def _service_request_mix(quick, n_requests):
    """A duplicate-heavy mixed batch over the hbm/ddr4 registry: ~16
    distinct request keys cycled (deterministically shuffled) out to
    `n_requests`, so coalescing has something to prove."""
    import numpy as np

    from repro.service import ExperimentRequest

    templates = []
    for spec in BENCH_SPEC_NAMES:
        templates += [
            ExperimentRequest.make("fig6_address_mapping", spec, quick=True),
            ExperimentRequest.make("table4_idle_latency", spec, n=512),
            ExperimentRequest.make("fig4_refresh", spec, quick=True),
            ExperimentRequest.make("fig7_locality", spec, quick=True),
            ExperimentRequest.make("fig9_channel_contention", spec,
                                   quick=True),
            ExperimentRequest.make("table5_total_throughput", spec, n=2048),
            ExperimentRequest.make("duplex_rw_sweep", spec, quick=True),
            ExperimentRequest.make("contention_scaling_sweep", spec,
                                   quick=True),
            ExperimentRequest.make("engine_mix_sweep", spec, quick=True),
        ]
    reqs = [templates[i % len(templates)] for i in range(n_requests)]
    order = np.random.default_rng(0).permutation(len(reqs))
    return [reqs[i] for i in order]


def bench_service(quick=False, fault_rates=(0.0, 0.01, 0.1),
                  qps_target=None):
    """Campaign-service soak: one row per fault rate (DESIGN.md §10).

    Serves the mixed batch through `CampaignService` with a
    fault-injected sim primary (transient/timeout/corrupt mix) and a
    clean sim fallback, full oracle validation, then asserts the service
    invariants before reporting: zero dropped requests at every rate,
    duplicates coalesced (executed < requests), every response either
    oracle-validated or degraded-with-reason, and >= 1 exercised
    fallback at the highest non-zero rate.
    """
    from repro.core import engine as engine_mod
    from repro.service import (CampaignService, RetryPolicy,
                               register_fault_injected)

    n_requests = 200 if quick else 1000
    requests = _service_request_mix(quick, n_requests)
    max_rate = max(fault_rates)
    rows = []
    for rate in fault_rates:
        primary = f"sim+faults@{rate:g}"
        register_fault_injected(
            "sim", name=primary, rate=rate, seed=7,
            kinds=("transient", "timeout", "corrupt", "unsupported"),
            weights=(0.5, 0.2, 0.15, 0.15), timeout_s=0.2, override=True)
        try:
            svc = CampaignService(
                primary, "sim", retry=RetryPolicy(max_attempts=8),
                validate_fraction=1.0, seed=11)
            responses, dt = _timed(lambda: svc.submit_all(requests))
            st = svc.stats
            qps = len(responses) / (dt * 1e-6)   # dt in microseconds
            assert st.dropped == 0, (
                f"service dropped {st.dropped} requests at rate {rate}")
            assert all(r.ok for r in responses), (
                f"non-ok responses at rate {rate}: "
                f"{[r.error for r in responses if not r.ok][:3]}")
            assert st.executed < st.requests and st.deduped > 0, (
                f"no coalescing at rate {rate}: {st}")
            assert all(r.validated is True or r.validated is None
                       or (r.degraded and r.degraded_reason)
                       for r in responses), (
                f"unvalidated, undegraded response at rate {rate}")
            if rate == max_rate and rate > 0:
                assert st.degraded >= 1, (
                    f"no fallback exercised at rate {rate}: {st}")
            if qps_target is not None:
                assert qps >= qps_target, (
                    f"sustained QPS {qps:.0f} below target "
                    f"{qps_target:.0f} at rate {rate}")
            rows.append((
                f"service_soak_fault{rate:g}", dt,
                f"requests={st.requests};executed={st.executed};"
                f"deduped={st.deduped};retries={st.retries};"
                f"degraded={st.degraded};breaker_opens={st.breaker_opens};"
                f"quarantines={st.quarantines};validated={st.validated};"
                f"dropped={st.dropped};qps={qps:.0f}"))
        finally:
            engine_mod._BACKEND_REGISTRY.pop(primary, None)
    return rows


def bench_lint_report():
    """Timed run of the repro-lint invariant pass (DESIGN.md §11).

    One row per invariant family (`lint_<family>`, analyzer runtime and
    finding count) plus a `lint_total` row carrying the new/stale split
    against the committed `analysis_baseline.json` — so BENCH_lint.json
    tracks both the analyzer's cost and the tree's finding trajectory.
    """
    from repro.analysis import lint as lint_mod
    from repro.analysis.findings import diff_baseline, load_baseline

    root = lint_mod.default_root()
    rows = []
    findings = []
    total_us = 0.0
    for family, runner in lint_mod.FAMILIES:
        family_findings, dt = _timed(lambda runner=runner: runner(root))
        findings.extend(family_findings)
        total_us += dt
        rows.append((f"lint_{family}", dt,
                     f"findings={len(family_findings)}"))
    baseline = load_baseline(root / "analysis_baseline.json")
    diff = diff_baseline(findings, baseline)
    rows.append(("lint_total", total_us,
                 f"findings={len(findings)};baseline={len(baseline)};"
                 f"new={len(diff.new)};stale={len(diff.stale)};"
                 f"clean={diff.clean}"))
    assert diff.clean, (
        f"repro-lint not clean vs analysis_baseline.json: "
        f"{len(diff.new)} new, {len(diff.stale)} stale — run "
        f"`python -m repro.analysis.lint --baseline analysis_baseline.json`")
    return rows


def emit_catalog(target: str) -> None:
    """Print the registry-generated experiment catalog ("-") or splice it
    between the catalog markers of a markdown file (e.g. README.md)."""
    from repro.core.experiments import (CATALOG_BEGIN, CATALOG_END,
                                        catalog_markdown)
    md = catalog_markdown()
    if target == "-":
        print(md)
        return
    with open(target) as f:
        text = f.read()
    lo, hi = text.find(CATALOG_BEGIN), text.find(CATALOG_END)
    if lo < 0 or hi < 0:
        raise SystemExit(
            f"--catalog: {target} has no '{CATALOG_BEGIN}' .. "
            f"'{CATALOG_END}' markers to splice between")
    with open(target, "w") as f:
        f.write(text[:lo] + md + text[hi + len(CATALOG_END):])
    print(f"updated experiment catalog in {target}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as a BENCH_*.json perf-trajectory "
                         "file at PATH")
    ap.add_argument("--experiments", metavar="NAMES", default=None,
                    help="comma-separated experiment names to benchmark "
                         "(default: every registered experiment); unknown "
                         "names fail with the registered list")
    ap.add_argument("--engines", metavar="N|MIX", default=None,
                    help="override the engine-count ladder of the "
                         "contention experiments with powers of two up to "
                         "N (e.g. 16 -> 1,2,4,8,16), or — as a mix spec "
                         "like 2r+1w+1d — the custom blend of the "
                         "engine-mix experiments (DESIGN.md §13)")
    ap.add_argument("--arbitration", metavar="POLICY", default=None,
                    choices=("round_robin", "burst", "exclusive"),
                    help="shared-port arbitration granularity for every "
                         "experiment exposing the axis (DESIGN.md §9): "
                         "round_robin, burst, or exclusive")
    ap.add_argument("--burst", type=int, metavar="B", default=None,
                    help="beats per arbitration grant (with "
                         "--arbitration burst)")
    ap.add_argument("--catalog", metavar="PATH", nargs="?", const="-",
                    default=None,
                    help="emit the registry-generated experiment catalog "
                         "and exit: to stdout, or spliced between the "
                         "catalog markers of PATH (e.g. README.md)")
    ap.add_argument("--lint-report", action="store_true",
                    help="time the repro.analysis invariant pass per "
                         "family instead of the registry benches "
                         "(DESIGN.md §11); --json defaults to "
                         "BENCH_lint.json")
    ap.add_argument("--service", action="store_true",
                    help="run the campaign-service fault-injection soak "
                         "instead of the registry benches (DESIGN.md §10)")
    ap.add_argument("--grid", action="store_true",
                    help="run the grid-evaluation ladder (per-point NumPy "
                         "vs jit vs jit+vmap vs sharded, DESIGN.md §12) "
                         "instead of the registry benches; --json defaults "
                         "to BENCH_grid.json")
    ap.add_argument("--roofline", action="store_true",
                    help="run the measured-envelope rungs "
                         "(core/roofline_empirical.py) instead of the "
                         "registry benches; --json defaults to "
                         "BENCH_roofline.json")
    ap.add_argument("--tune", action="store_true",
                    help="run the layout-autotune rungs through the "
                         "campaign service instead of the registry "
                         "benches; --json defaults to BENCH_roofline.json")
    ap.add_argument("--fault-rate", metavar="RATES", default=None,
                    help="comma list of injected fault rates in [0, 1] for "
                         "--service (default: 0,0.01,0.1)")
    ap.add_argument("--qps-target", type=float, metavar="QPS", default=None,
                    help="with --service: fail if sustained QPS falls "
                         "below this at any fault rate")
    args, _ = ap.parse_known_args()
    if not args.service:
        if args.fault_rate is not None:
            ap.error("--fault-rate only applies with --service")
        if args.qps_target is not None:
            ap.error("--qps-target only applies with --service")
    if sum((args.lint_report, args.service, args.grid, args.roofline,
            args.tune)) > 1:
        ap.error("--lint-report, --service, --grid, --roofline and --tune "
                 "are separate modes")
    if args.lint_report and args.json is None:
        args.json = "BENCH_lint.json"
    if args.grid and args.json is None:
        args.json = "BENCH_grid.json"
    if (args.roofline or args.tune) and args.json is None:
        args.json = "BENCH_roofline.json"
    fault_rates = parse_fault_rates(args.fault_rate) \
        if args.fault_rate is not None else (0.0, 0.01, 0.1)
    if args.qps_target is not None and args.qps_target <= 0:
        ap.error(f"--qps-target must be > 0, got {args.qps_target:g}")
    if args.engines is not None:
        args.engines = parse_engines_arg(args.engines)
    if args.burst is not None and args.burst < 1:
        ap.error(f"--burst must be >= 1, got {args.burst}")
    if args.burst is not None and args.arbitration != "burst":
        ap.error("--burst only applies with --arbitration burst "
                 "(round_robin and exclusive fix the grant size)")
    if args.catalog is not None:
        emit_catalog(args.catalog)
        return
    q = args.quick
    if args.json:
        # Fail before the (minutes-long, non-quick) run, not at write time.
        if os.path.isdir(args.json) or args.json.endswith(os.sep):
            ap.error(f"--json: {args.json!r} is a directory, expected a file "
                     "path")
        json_dir = os.path.dirname(os.path.abspath(args.json)) or "."
        if not os.path.isdir(json_dir):
            ap.error(f"--json: directory {json_dir!r} does not exist")
        if not os.access(json_dir, os.W_OK):
            ap.error(f"--json: directory {json_dir!r} is not writable")

    setup_compile_cache()
    print("name,us_per_call,derived")
    if args.lint_report:
        suites = [bench_lint_report]
    elif args.grid:
        suites = [lambda: bench_grid(q)]
    elif args.service:
        suites = [
            lambda: bench_service(q, fault_rates, args.qps_target),
        ]
    elif args.roofline:
        suites = [lambda: bench_roofline(q)]
    elif args.tune:
        suites = [lambda: bench_tune(q)]
    else:
        suites = [
            lambda: bench_experiments(q, args.experiments, args.engines,
                                      args.arbitration, args.burst),
            lambda: bench_sweep_grid(q),
            bench_table3_resources,
            lambda: bench_tpu_rst_kernel(q),
            bench_oracle_autotune,
        ]
    rows = []
    failures = 0
    t0 = time.perf_counter()
    for suite in suites:
        try:
            for name, us, derived in suite():
                print(f"{name},{us:.0f},{derived}")
                rows.append({"name": name, "us_per_call": round(us, 1),
                             "derived": derived})
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"ERROR,{suite},{type(e).__name__}: {e}", file=sys.stderr)
    wall_us = (time.perf_counter() - t0) * 1e6

    if args.json:
        payload = {
            "benchmark": ("shuhai-lint" if args.lint_report
                          else "shuhai-campaign-service" if args.service
                          else "shuhai-grid" if args.grid
                          else "shuhai-roofline" if args.roofline
                          else "shuhai-tune" if args.tune
                          else "shuhai-campaign"),
            "quick": q,
            "unix_time": time.time(),
            "wall_us": round(wall_us, 1),
            "suite_us_total": round(sum(r["us_per_call"] for r in rows), 1),
            "failures": failures,
            "rows": rows,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")

    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
