"""rst_write_kernel_roofline: the RST write engine's share of the chip's HBM
roofline (profiler trace), in %.

The bytes are the benchmark's reckoning of every point answered in the
window (``stream_bytes``, n x B each, bench/reckon.py); the time is the
summed device time of the window's ``rst_write`` events (the operations
named after it, or that ran in its jitted program, ``jit_rst_write``); the
peak is the chip's HBM bandwidth from bench/peaks.json.
"""
import re

WRITE_KERNEL = re.compile(r"rst_write")


def read(run):
    if run.trace is None:
        return None
    events = run.trace.ops_matching(WRITE_KERNEL)
    total = sum(r.answer.get("stream_bytes", 0) for r in run.records)
    if not events or not total:
        return None
    seconds = sum(o.end - o.start for o in events) * 1e-9
    return 100.0 * total / (seconds * run.peaks["hbm_bytes_per_s"])
