"""How ``correct`` is decided for the RST write cell.

``ops.measure_write_bandwidth``, the measurer the ``pallas`` backend calls
for a write point, is wrapped as ``rst_capture`` wraps the read measurers:
each call served in the window leaves its parameters, the first tile of
its buffer, its byte count and its GB/s, and a host span
(``bench.kernel_call``).  The write kernel the measurer calls
(``ops.rst_write``) is wrapped too, so that the buffer its last call (the
timed one) leaves is kept until the measurer returns; then, outside the
timed call, the device adds each of the buffer's tiles into one int32 sum
(``window_sums``, mod 2^32 over the words' bits) and the call keeps those
sums.  Once the window has closed, a sample of the calls drawn from the
seed is compared with the plain reference
(``references/rst_write_checksum.py``): the widest gap, 0 when the first
tile and every tile's sum are exactly what the stream leaves, and the
calls whose byte count is not n x B.  A request whose GB/s are not, one
for one, its own calls' counts as a mismatch.
"""
from __future__ import annotations

import functools
from typing import List

from bench.references import rst_write_checksum as reference

KERNEL_SPAN = "bench.kernel_call"


@functools.lru_cache(maxsize=None)
def _window_sums(words: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def window_sums(buf):
        bits = jax.lax.bitcast_convert_type(buf, jnp.int32)
        return jnp.sum(bits.reshape(-1, words), axis=1, dtype=jnp.int32)
    return window_sums


class Capture:
    def __init__(self, config: dict):
        from repro.kernels import ops
        self.config = config
        self.calls: List[dict] = []
        self._measure = ops.measure_write_bandwidth
        self._kernel = ops.rst_write
        self._out = None
        ops.measure_write_bandwidth = self._wrapped
        ops.rst_write = self._kernel_wrapped
        self.clear()

    def _kernel_wrapped(self, *args, **kw):
        self._out = self._kernel(*args, **kw)
        return self._out

    def _wrapped(self, p, **kw):
        import jax
        import numpy as np
        with jax.profiler.TraceAnnotation(KERNEL_SPAN):
            sample = self._measure(p, **kw)
        out, self._out = self._out, None
        sums = np.asarray(_window_sums(p.b // reference.WORD_BYTES)(out))
        self.calls.append({"s": p.s, "w": p.w, "a": p.a, "n": p.n,
                           "b": p.b, "checksum": sample.checksum,
                           "tile_sums": sums,
                           "bytes": sample.bytes_moved, "gbps": sample.gbps})
        return sample

    def clear(self) -> None:
        self.calls.clear()
        self._seen = 0
        self.answers_off = 0

    def answered(self, lookup, values) -> None:
        calls = self.calls[self._seen:]
        self._seen = len(self.calls)
        self.answers_off += int(sorted(values)
                                != sorted(c["gbps"] for c in calls))

    def sample(self, rng) -> list:
        k = min(int(self.config["check_calls"]), len(self.calls))
        return [self.calls[int(i)] for i in
                sorted(rng.choice(len(self.calls), size=k, replace=False))]

    def check(self, rng, control: bool = False) -> dict:
        calls = self.sample(rng)
        if control:
            calls = reference.control(calls, self.config)
        readings = reference.compare(calls, self.config)
        if not calls:       # a window that served no kernel call fails
            readings["checksum_gap"] = float("inf")
        readings["answer_mismatch"] = float(self.answers_off)
        return {name: {"value": float(value),
                       "limit": float(self.config["limits"][name])}
                for name, value in readings.items()}

    def close(self) -> None:
        from repro.kernels import ops
        ops.measure_write_bandwidth = self._measure
        ops.rst_write = self._kernel
