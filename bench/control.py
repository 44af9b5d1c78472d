"""Readings of the numbers that decide ``correct``: the program's, and the
control's, seed by seed, in one process on the chip.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1,2,3

For each seed the cell's traffic is served for the window as in a run, and
the same sample of answers is compared with the plain reference twice: as
the program served it, and with the reference computed in the next lower
precision in the program's place (the control, which must fail).  One JSON
line per seed.  The benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell, seed: int, seconds: float) -> dict:
    import numpy as np

    from bench import harness, traffic
    entry = harness.load_module("entries", cell.traffic["entry"],
                                cell.bench_dir).Entry(cell.config,
                                                      cell.traffic)
    try:
        entry.warm(seed)
        records = harness.serve_window(
            entry, traffic.requests(cell.traffic, seed), seconds)
        out = {"seed": seed, "requests": len(records),
               "failed": sum(not r.answer["ok"] for r in records)}
        for label, control in (("program", False), ("control", True)):
            rng = np.random.default_rng([int(seed), 7])
            out[label] = entry.capture.check(rng, control=control)
        return out
    finally:
        entry.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    from bench import harness
    try:
        cell = harness.Cell.load(args.workload)
        harness.setup_compile_cache()
        harness.tpu_devices(cell.chips)
    except harness.CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
