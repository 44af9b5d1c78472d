"""Command line over the declarative experiment registry.

Every paper table/figure is a registered `Experiment`
(core/experiments.py); `bench_experiments` runs each one per applicable
memory spec and prints ``name,us_per_call,derived`` CSV rows.
`us_per_call` is the host wall time of running the experiment through the
calibrated engine model; `derived` carries the headline quantity the
paper reports for that artifact (each experiment's `summarize`).  It is
not a device measurement: the benchmark of the chip is ``bench/run.py``.

``--experiments name1,name2`` restricts the registry suite (unknown names
fail with the registered list).  ``--engines N`` replaces the contention
experiments' engine-count ladder with powers of two up to N, or, as a mix
spec like ``2r+1w+1d``, sets the engine-mix experiments' custom blend.
``--arbitration POLICY`` / ``--burst B`` select the shared-port grant
granularity (round_robin / burst / exclusive, DESIGN.md §9) for every
experiment that exposes the axis.  ``--catalog [PATH]`` emits the
registry-generated experiment-catalog table instead of running anything:
to stdout, or spliced between README.md's catalog markers.

Run: PYTHONPATH=src python -m benchmarks.run [--quick]
         [--experiments NAMES] [--engines N|MIX]
         [--arbitration POLICY] [--burst B] [--catalog [PATH]]
"""
from __future__ import annotations

import argparse
import time


# Specs the registry runs over by default: the paper's measured pair,
# keeping the historical row names stable.  The modeled HBM3/DDR3 specs
# are pinned by tier-1 tests and the example campaign driver instead;
# adding them here would suffix the single-spec rows (table6/fig8).
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
    (core/experiments.py); this only iterates the registry.
    Single-spec experiments (the switch suites) keep their bare row name;
    multi-spec ones are suffixed with the spec.  `engines` (the --engines
    flag) replaces the engine-count ladder of the contention experiments
    — every experiment with an "engines" option — when given as an int,
    or (as a mix spec like '2r+1w+1d') the custom blend of every
    experiment with a "custom_mix" option (the engine-mix family,
    DESIGN.md §13); `arbitration`/`burst` (--arbitration/--burst) select
    the shared-port grant granularity for every experiment exposing that
    axis.
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
            t0 = time.perf_counter()
            res = run_experiment(exp, spec, quick=quick, bench=True,
                                 **overrides)
            dt = (time.perf_counter() - t0) * 1e6
            name = label if len(available) == 1 else f"{label}_{spec.name}"
            rows.append((name, dt, exp.summary(spec, res)))
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


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.run",
        description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="run each experiment's quick grid")
    ap.add_argument("--experiments", metavar="NAMES", default=None,
                    help="comma-separated experiment names to run "
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
    args = ap.parse_args(argv)
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

    print("name,us_per_call,derived")
    for name, us, derived in bench_experiments(
            args.quick, args.experiments, args.engines, args.arbitration,
            args.burst):
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    main()
