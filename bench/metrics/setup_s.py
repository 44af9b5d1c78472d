"""setup_s: process start to the first timed request (host clock):
JAX and TPU start-up, loading or compiling every program, and warming
every shape the window uses."""


def read(run):
    return run.setup_s
