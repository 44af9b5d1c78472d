"""Records a small trace on the chip, to check the reduction against.

    python3 bench/testdata/record_trace.py <out.xplane.pb>

on a TPU: one ``fig7_locality`` request of one stride (two points, each a
warm-up and a timed ``rst_read`` call) and one small ``grid_cross_product``
request, each in a ``bench.request`` span, after both were served once
untraced so that nothing compiles in the trace.
"""
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

REQUESTS = (
    ("pallas", "fig7_locality",
     {"bursts": (4096,), "strides": (4096,), "n": 1024}),
    ("jaxgrid", "grid_cross_product",
     {"n": 256, "strides": (64,), "ops": ("read",), "engines": (1,),
      "arbitrations": (("round_robin", 1),),
      "placements": ("same_channel",)}),
)


def serve_all() -> None:
    import jax

    from bench.trace_reduce import REQUEST_SPAN
    from repro.service import CampaignService, ExperimentRequest
    for backend, experiment, overrides in REQUESTS:
        svc = CampaignService(backend, fallback=None, validate_fraction=0.0)
        req = ExperimentRequest.make(experiment, "hbm", **overrides)
        with jax.profiler.TraceAnnotation(REQUEST_SPAN):
            resp = svc.submit(req)
        if not resp.ok:
            raise RuntimeError(f"{experiment} failed: {resp.error}")


def main(out: str) -> None:
    from bench import harness, trace_reduce
    harness.setup_compile_cache()
    harness.tpu_devices(1)
    serve_all()
    tracer = trace_reduce.Tracer()
    try:
        tracer.start()
        serve_all()
        tracer.stop()
        shutil.copy(tracer.path(), out)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    print(f"wrote {out}: {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
