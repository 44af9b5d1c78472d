"""The paper-native end-to-end driver: run the FULL Shuhai benchmarking
campaign — every registered experiment (Sec. V and VI), every requested
memory system — exactly as the released tool does against a U280, here
against the calibrated simulator.

The campaign is declarative: each table/figure is an `Experiment` spec in
`repro.core.experiments`; this driver only iterates the registry, so a
newly registered spec (e.g. your board's memory) or experiment shows up
here with no changes.  `--specs hbm,ddr4,hbm3,ddr3` exercises the paper's
generalization claim: the same campaign on HBM3 and DDR3.

Run: PYTHONPATH=src python examples/shuhai_campaign.py \
        [--csv out.csv] [--specs hbm,ddr4] [--experiments table5_total_throughput,duplex_rw_sweep] \
        [--backend sim] [--full]
"""
import argparse
import sys

from repro.core import available_specs, spec_by_name
from repro.core.experiments import (backend_capability_gap,
                                    experiments_for, get_experiment,
                                    run_experiment)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--csv", default=None)
    ap.add_argument("--specs", default="hbm,ddr4",
                    help="comma-separated memory specs "
                         f"(registered: {','.join(available_specs())}); "
                         "'all' runs every registered spec")
    ap.add_argument("--experiments", default=None,
                    help="comma-separated experiment names (default: every "
                         "registered experiment applicable to the spec)")
    ap.add_argument("--backend", default="sim")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grids (default: quick grids)")
    args = ap.parse_args()

    names = (available_specs() if args.specs == "all"
             else args.specs.split(","))
    # Resolve every requested name up front: an unknown spec or experiment
    # exits with the registered choices, not a traceback mid-campaign.
    try:
        specs = [spec_by_name(n.strip()) for n in names]
        wanted = (None if args.experiments is None else
                  [get_experiment(n.strip())
                   for n in args.experiments.split(",")])
    except ValueError as e:
        raise SystemExit(f"shuhai_campaign: {e}")

    rows = [("system", "experiment", "key", "value")]
    for spec in specs:
        applicable = experiments_for(spec, args.backend)
        selected = applicable if wanted is None else wanted
        for exp in selected:
            if exp not in applicable:
                # Explicitly requested but not runnable on this spec (e.g.
                # a switch suite on DDR) or backend (a device-only
                # experiment): report it like the backend skips below
                # instead of silently producing no rows.
                why = (backend_capability_gap(args.backend, exp.plan(
                    spec, exp.options(quick=not args.full)))
                       if exp.available_on(spec) else "needs an "
                       "inter-channel switch this spec does not have")
                print(f"skipping {exp.name} on {spec.name}: {why}",
                      file=sys.stderr)
                continue
            try:
                res = run_experiment(exp, spec, args.backend,
                                     quick=not args.full)
            except (ValueError, NotImplementedError) as e:
                # e.g. latency experiments on a backend without
                # per-transaction timers — skip, don't abort the campaign.
                print(f"skipping {exp.name} on {spec.name}/{args.backend}: "
                      f"{e}", file=sys.stderr)
                continue
            for key, value in exp.rows(spec, res):
                rows.append((spec.name, exp.name, key, value))

    out = "\n".join(",".join(r) for r in rows)
    if args.csv:
        with open(args.csv, "w") as f:
            f.write(out + "\n")
        print(f"wrote {len(rows) - 1} measurements to {args.csv}")
    else:
        print(out)


if __name__ == "__main__":
    main()
