"""rst_reported_gbps: the median of the GB/s that the answers of the
window carry, one per point: the number users read (a host-clock sample
the program takes around one blocked kernel call)."""
import statistics


def read(run):
    values = [v for r in run.records for v in r.answer.get("reported_gbps", [])]
    return statistics.median(values) if values else None
