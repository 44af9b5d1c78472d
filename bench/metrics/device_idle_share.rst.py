"""device_idle_share.rst: share of the traced window in which no
operation ran on the device (profiler trace), in %."""


def read(run):
    return run.trace.idle_share_percent() if run.trace else None
