"""Records a small trace with the program's own spans, on the chip.

    python3 bench/testdata/record_trace_spans.py <out.xplane.pb> [requests]

on a TPU: three (or `requests`) ``grid.xp_default`` requests through the
cell's own entry (``CampaignService("jaxgrid")``), then one RST point of
1024 transactions in an 8 KiB window through ``Sweep(.., "pallas")``, its
kernel calls wrapped as the RST cells wrap them (``bench.kernel_call``).
Each request is a ``bench.request`` span.  Everything was served once
untraced first, so that nothing compiles in the trace.  The
``/host:metadata`` plane (the programs' HLO, about 0.8 MB, which nothing
reads) is left out of the file.
"""
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SEED = 20261017
GRID_REQUESTS = 3
DROPPED_PLANE = b"/host:metadata"


def _varint(data: bytes, i: int):
    value = shift = 0
    while True:
        byte = data[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _fields(data: bytes):
    """(field number, the field's bytes) of one serialized message."""
    i = 0
    while i < len(data):
        start = i
        key, i = _varint(data, i)
        kind = key & 7
        if kind == 0:
            _, i = _varint(data, i)
            value = None
        elif kind == 2:
            size, i = _varint(data, i)
            value, i = data[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            value = None
        else:
            raise ValueError(f"wire type {kind} in the trace")
        yield key >> 3, value, data[start:i]


def without_plane(data: bytes, name: bytes = DROPPED_PLANE) -> bytes:
    """The serialized XSpace `data` without its plane (field 1) named
    `name` (the plane's field 2)."""
    def named(plane):
        return any(f == 2 and v == name for f, v, _ in _fields(plane))
    return b"".join(raw for f, v, raw in _fields(data)
                    if not (f == 1 and named(v)))


def rst_point():
    from repro.core import RSTParams, Sweep
    from repro.core.hwspec import spec_by_name
    sweep = Sweep(spec_by_name("hbm"), "pallas")
    sweep.add(RSTParams(n=1024, b=4096, s=4096, w=8192))
    return sweep.run()


def main(out: str, grid_requests: int = GRID_REQUESTS) -> None:
    import jax

    from bench import harness, traffic, trace_reduce
    from bench.rst_capture import KernelCapture
    harness.setup_compile_cache()
    harness.tpu_devices(1)
    cell = harness.Cell.load("grid.xp_default")
    entry = harness.load_module("entries", cell.traffic["entry"]).Entry(
        cell.config, cell.traffic)
    capture = KernelCapture().install()
    try:
        entry.warm(SEED)
        rst_point()
        requests = traffic.requests(cell.traffic, SEED)
        tracer = trace_reduce.Tracer()
        try:
            tracer.start()
            for _ in range(grid_requests):
                with jax.profiler.TraceAnnotation(trace_reduce.REQUEST_SPAN):
                    if not entry.serve(next(requests))["ok"]:
                        raise RuntimeError("a grid request failed")
            with jax.profiler.TraceAnnotation(trace_reduce.REQUEST_SPAN):
                rst_point()
            tracer.stop()
            with open(tracer.path(), "rb") as f:
                data = without_plane(f.read())
            with open(out, "wb") as f:
                f.write(data)
        finally:
            shutil.rmtree(tracer.dir, ignore_errors=True)
    finally:
        capture.uninstall()
        entry.close()
    print(f"wrote {out}: {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main(sys.argv[1], *map(int, sys.argv[2:]))
