"""grid_request_p95_ms: the 95th percentile, over every request of the
window, of the time from the call into the cell's entry to its answer
(host clock)."""
import statistics


def read(run):
    lat = run.latencies_s
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=100, method="inclusive")[94] * 1e3
