"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

from the root of a checkout.  The cell, its configuration, its traffic mix
and its metrics are read from ``BENCHMARK.json`` and the files it names.
The last line of standard output is the result as one JSON object; the
numbers that decide ``correct`` are the last lines of standard error.
"""
import time

STARTED = time.perf_counter()   # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# The TPU runtime would otherwise log to a fixed directory under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], started=STARTED))
