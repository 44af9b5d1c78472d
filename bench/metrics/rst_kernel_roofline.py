"""rst_kernel_roofline: the RST kernels' share of the chip's HBM roofline
(profiler trace), in %.

Each RST kernel call must move its stream's bytes (n x B x engines, the
benchmark's reckoning), whatever implements it.  The share is those bytes,
times the RST kernel events of the traced window (the per-point warm-up
call counts as a call), over the events' summed device time and the
chip's peak HBM bandwidth from bench/peaks.json.  A kernel's events are
the device operations named after it, or that ran in its jitted program
(``jit_rst_read``, ``jit_rst_contend_read``).
"""
import re

RST_KERNEL = re.compile(r"rst_(read|contend)")


def read(run):
    if run.trace is None:
        return None
    events = run.trace.ops_matching(RST_KERNEL)
    points = sum(r.answer["points"] for r in run.records)
    if not events or not points:
        return None
    bytes_per_call = sum(r.answer["stream_bytes"] for r in run.records) / points
    seconds = sum(o.end - o.start for o in events) * 1e-9
    return (100.0 * len(events) * bytes_per_call
            / (seconds * run.peaks["hbm_bytes_per_s"]))
