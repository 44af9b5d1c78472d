"""Runs one cell as ``bench/run.py --trace 1`` does, and reports what the
program's own spans say about the same window.

    python3 bench/span_report.py --workload <name> --seed <n> --seconds <s>
                                 [--keep <out.xplane.pb>]

from the root of a checkout, on the cell's chips.  Standard output ends
with the harness's result line, as ``bench/run.py`` prints it, and then
one more line: the span report of ``bench/program_spans.report`` as one
JSON object.  ``--keep`` copies the trace before it is reduced.
"""
import time

STARTED = time.perf_counter()   # set-up is timed from here, as in run.py

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # The TPU runtime would otherwise log to a fixed directory under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness, program_spans, trace_reduce  # noqa: E402


class SpanTracer(trace_reduce.Tracer):
    """The harness's tracer, reducing to a :class:`SpanTrace` and keeping
    it (and, with `keep`, the trace file) for the report."""

    keep = None
    last = None

    def reduce(self, devices: int) -> program_spans.SpanTrace:
        try:
            path = self.path()
            if SpanTracer.keep:
                shutil.copy(path, SpanTracer.keep)
            SpanTracer.last = program_spans.SpanTrace.from_file(path, devices)
            return SpanTracer.last
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def main(argv, started: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--keep", default=None)
    args, rest = ap.parse_known_args(argv)
    SpanTracer.keep = args.keep
    trace_reduce.Tracer = SpanTracer     # run_cell finds its tracer here
    rc = harness.main(rest + ["--trace", "1"], started=started)
    if rc == 0:
        kernel = harness.load_module("metrics",
                                     "rst_kernel_roofline").RST_KERNEL
        print(json.dumps(program_spans.report(SpanTracer.last, kernel)),
              flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], STARTED))
