"""grid_kernel_us_per_point: device time of the grid tier's compiled
programs in the traced window (profiler trace), summed, over the points
answered in it, in microseconds."""


def read(run):
    if run.trace is None or not run.trace.modules:
        return None
    lo, hi = run.trace.window
    seconds = sum(min(m.end, hi) - max(m.start, lo)
                  for m in run.trace.modules if m.end > lo and m.start < hi)
    points = sum(r.answer["points"] for r in run.records)
    return seconds * 1e-9 / points * 1e6 if points else None
