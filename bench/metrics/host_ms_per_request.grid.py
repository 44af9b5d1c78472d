"""host_ms_per_request.grid: the median, over the requests of the traced
window, of each request span's length less the device-busy time inside it
(profiler trace), in milliseconds: the host's part of a request, from the
service down to the backend and the host's NumPy lanes."""
import statistics

from bench.trace_reduce import length


def read(run):
    if run.trace is None:
        return None
    host = [(r.end - r.start - length(run.trace.busy(r.start, r.end))) * 1e-6
            for r in run.trace.requests]
    return statistics.median(host) if host else None
