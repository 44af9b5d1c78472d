"""gather_kernel_roofline: the rst_gather engine's share of the chip's HBM
roofline (profiler trace), in %.

The bytes are the benchmark's reckoning of the window's decode steps
(``stream_bytes``: the held weights' bf16 bytes and every page read,
``plans/decode_step.py``); the time is the summed device time of the
window's ``rst_gather`` events (the operations named after it, or that ran
in its jitted program); the peak is the chip's HBM bandwidth from
bench/peaks.json.
"""
import re

GATHER_KERNEL = re.compile(r"rst_gather")


def read(run):
    if run.trace is None:
        return None
    events = run.trace.ops_matching(GATHER_KERNEL)
    total = sum(r.answer.get("stream_bytes", 0) for r in run.records)
    if not events or not total:
        return None
    seconds = sum(o.end - o.start for o in events) * 1e-9
    return 100.0 * total / (seconds * run.peaks["hbm_bytes_per_s"])
