"""rst_gbps: stream bytes of every point answered in the window over the
window's seconds (host clock), in GB/s.  The bytes are the benchmark's own
reckoning from each point's parameters (bench/reckon.py)."""


def read(run):
    total = sum(r.answer.get("stream_bytes", 0) for r in run.records)
    return total / run.window_s / 1e9 if total else None
