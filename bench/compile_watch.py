"""Compile spans and persistent-cache events, as JAX reports them.

JAX records a span while it traces, lowers and compiles a program (or loads
it from the persistent cache), and an event for each persistent-cache hit
and miss.  The watch keeps them, so the harness can count what compiled
inside the measured window, where nothing should.
"""
from __future__ import annotations

import dataclasses

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}


@dataclasses.dataclass
class _Event:
    name: str
    time: float


class CompileWatch:
    """Listens to JAX's monitoring events until :meth:`close`."""

    def __init__(self):
        import time

        import jax
        self._monitoring = jax.monitoring
        # JAX times its spans with time.time(); keep them on perf_counter's
        # clock, the harness's.
        self._offset = time.perf_counter() - time.time()
        self.spans = []          # (event, start, end) on the perf_counter clock
        self.events = []
        self._monitoring.register_event_time_span_listener(self._on_span)
        self._monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((event, start + self._offset,
                               end + self._offset))

    def _on_event(self, event, **_):
        if event in CACHE_EVENTS:
            import time
            self.events.append(_Event(CACHE_EVENTS[event], time.perf_counter()))

    def close(self):
        self._monitoring.unregister_event_time_span_listener(self._on_span)
        self._monitoring.unregister_event_listener(self._on_event)

    def counts(self, since: float = float("-inf"),
               until: float = float("inf")) -> dict:
        """Traces, lowerings, backend compiles and cache hits and misses
        that started in [since, until)."""
        out = {"traces": 0, "lowerings": 0, "compiles": 0,
               "cache_hits": 0, "cache_misses": 0}
        keys = dict(zip(COMPILE_EVENTS, ("traces", "lowerings", "compiles")))
        for event, start, _ in self.spans:
            if since <= start < until:
                out[keys[event]] += 1
        for ev in self.events:
            if since <= ev.time < until:
                out[ev.name] += 1
        return out

    def seconds(self, since: float = float("-inf")) -> float:
        """Length of the union of compile spans after `since` (nested
        spans overlap, so their durations do not add)."""
        total, reach = 0.0, since
        for _, start, end in sorted(self.spans, key=lambda s: s[1]):
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total
