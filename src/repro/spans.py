"""Named host spans in the profiler's trace.

``with span("repro.grid.dispatch", lanes=128, real=120):`` marks one phase
of the served path.  When the JAX profiler is running, the span and its
stats land in the host plane of its trace, beside the device lines;
otherwise a span costs about a microsecond and records nothing.  Spans
sit at phase boundaries, never per row, lane or point, and carry only
values the caller already has.

A process that has not imported JAX (the ``sim`` backend) gets a no-op
span: this module never imports JAX itself.
"""
from __future__ import annotations

import contextlib
import sys

PREFIX = "repro."

_OFF = contextlib.nullcontext()     # the span of a process without JAX


def span(name: str, **stats):
    """A context manager for one phase; `name` starts with ``repro.``."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **stats)
