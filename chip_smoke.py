"""Drive the system's device path once on a TPU and check what it returns.

Run from the root of a checkout:

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded grid only

One process holds the chip throughout.  Each phase raises on the first
wrong answer, and the script then exits non-zero.

1. device      The platform must be a TPU whose ``device_kind`` has
               registered peaks (core/hwspec.py).  Nothing falls back to
               the CPU.
2. engines     fig7_locality, fig7_write_locality and fig6_address_mapping
               requests served by ``CampaignService("pallas",
               fallback=None)`` -> Sweep -> PallasBackend -> the compiled
               RST kernels: 4 KiB bursts (the kernel tile), 2^18
               transactions (1 GiB moved per sample), working sets from
               8 KiB to 1 GiB.
3. contention  Four read engines sharing one port, round robin and
               16-beat grants, and duplex traffic, through
               ``Sweep(HBM, "pallas")``: no registered experiment plans
               these at a 4 KiB burst.
4. correctness One point per kernel (rst_read, rst_write,
               rst_contend_read, rst_contend_mix_read) against the NumPy
               replays of kernels/ref.py, on a 1 GiB buffer.
5. grid        ``evaluate_grid`` over the --grid ladder (10,368 points at
               n=2^17) and a grid_cross_product request served by
               ``CampaignService("jaxgrid", fallback=None)``, both checked
               against the NumPy timing model within REL_TOLERANCE.

With ``--chips 4`` only the sharded grid runs: ``evaluate_grid`` over a
four-device mesh, compared with the same grid on one device.

GB/s figures are single samples timed on the host clock around a blocked
call: a bring-up check, not a benchmark.  The last line of standard output
is one JSON object, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
KIB, MIB, GIB = 1 << 10, 1 << 20, 1 << 30
TILE = 4 * KIB          # the RST kernels' f32 burst tile (8 x 128 x 4 B)
ENGINES = 4
GRANT = 16

# What JAX records while it traces, lowers and compiles (or loads from the
# persistent cache): a phase's set-up time is the union of their spans.
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass(frozen=True)
class Sizes:
    n: int                  # transactions per engine sample
    w: int                  # largest single-engine working set (bytes)
    contend_w: int          # per-engine window under contention (bytes)
    check_tiles: int        # tiles summed per correctness checksum
    grid_quick: bool        # the --grid ladder's quick axes
    grid_sample: int        # ladder points checked against NumPy
    grid_xp_n: int          # stream length of the grid_cross_product request


# The chip's sizes.  A checksum sums check_tiles tiles of integers below
# 251 (ops.make_working_buffer), so 2^16 tiles keep every f32 sum below
# 2^24, where it is exact: the kernels must match the replays bit for bit.
CHIP = Sizes(n=1 << 18, w=GIB, contend_w=256 * MIB, check_tiles=1 << 16,
             grid_quick=False, grid_sample=256, grid_xp_n=1 << 17)
# Interpret-mode sizes for a CPU rehearsal of the same code path.
TINY = Sizes(n=16, w=64 * TILE, contend_w=16 * TILE, check_tiles=64,
             grid_quick=True, grid_sample=32, grid_xp_n=1024)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke: {what}")


class _Phases:
    """Prints each phase with its set-up (compile) and run time, and counts
    persistent compile-cache hits and misses."""

    def __init__(self):
        import jax
        self._monitoring = jax.monitoring
        self._spans = []
        self.cache = {"hits": 0, "misses": 0}
        self._monitoring.register_event_time_span_listener(self._on_span)
        self._monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start, end, **_):
        if event in _COMPILE_EVENTS:
            self._spans.append((start, end))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def close(self):
        self._monitoring.unregister_event_time_span_listener(self._on_span)
        self._monitoring.unregister_event_listener(self._on_event)

    def _setup_seconds(self, since: float) -> float:
        """Length of the union of compile spans after `since` (nested
        traces overlap, so their durations do not add)."""
        total, reach = 0.0, since
        for start, end in sorted(self._spans):
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total

    @contextlib.contextmanager
    def phase(self, name: str):
        print(f"[{name}]", flush=True)
        t0 = time.time()
        yield
        wall = time.time() - t0
        setup = self._setup_seconds(t0)
        print(f"[{name}] ok: set-up (compile) {setup:.3f} s, run "
              f"{wall - setup:.3f} s", flush=True)


def _served(resp, backend: str) -> None:
    req = resp.request
    _check(resp.ok, f"{req.experiment} failed: {resp.error}")
    _check(not resp.degraded,
           f"{req.experiment} degraded: {resp.degraded_reason}")
    _check(resp.backend == backend,
           f"{req.experiment} served by {resp.backend!r}, not {backend!r}")


def _sample_line(label: str, gbps: float, peak_gbps) -> str:
    _check(gbps > 0 and gbps == gbps and gbps != float("inf"),
           f"{label}: bandwidth {gbps!r} is not a positive finite number")
    line = f"  {label}: {gbps!r} GB/s"
    if peak_gbps:
        line += f", {gbps / peak_gbps!r} of the {peak_gbps:g} GB/s peak"
    return line + " (one sample, host clock)"


def phase_engines(sz: Sizes, peak_gbps) -> None:
    from repro.service import CampaignService, ExperimentRequest
    svc = CampaignService("pallas", fallback=None, validate_fraction=0.0)
    common = {"bursts": (TILE,), "n": sz.n}
    requests = [
        ExperimentRequest.make("fig7_locality", "hbm", **common),
        ExperimentRequest.make("fig7_write_locality", "hbm", **common),
        ExperimentRequest.make("fig6_address_mapping", "hbm", w=sz.w,
                               **common),
    ]
    for req in requests:
        resp = svc.submit(req)
        _served(resp, "pallas")
        for outer, per_b in resp.result.items():
            for s, gbps in per_b[TILE].items():
                key = f"W={outer}" if isinstance(outer, int) else outer
                print(_sample_line(f"{req.experiment} {key} B={TILE} S={s}",
                                   gbps, peak_gbps))


def phase_contention(sz: Sizes, peak_gbps) -> None:
    from repro.core import HBM, RSTParams, Sweep
    p = RSTParams(n=sz.n, b=TILE, s=TILE, w=sz.contend_w)
    sweep = Sweep(HBM, "pallas")
    sweep.add_contention(p, num_engines=ENGINES, arbitration="round_robin")
    sweep.add_contention(p, num_engines=ENGINES, arbitration="burst",
                         burst_beats=GRANT)
    duplex = RSTParams(n=sz.n, b=TILE, s=TILE, w=sz.w)
    sweep.add(duplex, op="duplex")
    rr, burst, dup = (r.value for r in sweep.run())
    for label, res in ((f"{ENGINES} engines round robin", rr),
                       (f"{ENGINES} engines {GRANT}-beat grants", burst)):
        _check(res.bound == "measured" and res.num_engines == ENGINES,
               f"{label}: not a measurement of {ENGINES} engines: {res}")
        _check(res.detail["bytes"] == ENGINES * sz.n * TILE,
               f"{label}: moved {res.detail['bytes']} bytes")
        print(_sample_line(f"{label} W={sz.contend_w} each",
                           res.aggregate_gbps, peak_gbps))
    _check(dup.bound == "measured" and dup.detail["bytes"] == 2 * sz.n * TILE,
           f"duplex: {dup}")
    print(_sample_line(f"duplex read+write W={sz.w}", dup.gbps, peak_gbps))


def _exact(name: str, got, want) -> None:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    _check(got.shape == want.shape, f"{name}: shape {got.shape} != "
           f"{want.shape}")
    bad = int(np.count_nonzero(got != want))
    _check(bad == 0, f"{name}: {bad} of {got.size} values differ from the "
           f"kernels/ref.py replay")
    print(f"  {name}: {got.size} values equal the kernels/ref.py replay")


def phase_correctness(sz: Sizes) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import RSTParams, block_params
    from repro.core.engine_mix import EngineMix
    from repro.kernels import ops, ref
    from repro.kernels.rst_read import SUBLANE
    from repro.kernels.rst_write import rst_write
    f32 = jnp.float32

    # rst_read: check_tiles transactions spread over the whole buffer.
    n = sz.check_tiles
    p = RSTParams(n=n, b=TILE, s=sz.w // n, w=sz.w)
    got = ops.measure_read_bandwidth(p).checksum
    buf = np.asarray(ops.make_working_buffer(p, f32))
    stride, wset, base = block_params(p, TILE)
    _exact("rst_read", got, ref.rst_read_checksum_ref(
        buf, stride, wset, base, n, SUBLANE))

    # rst_write: the stream wraps its window twice, so every tile it
    # touches is written twice and the last write must win.
    p = RSTParams(n=n, b=TILE, s=2 * sz.w // n, w=sz.w)
    stride, wset, base = block_params(p, TILE)
    buf = ops.make_working_buffer(p, f32)
    before = np.asarray(buf)
    out = rst_write(ops.params_operand(p, f32, grid_txns=n), buf,
                    grid_txns=n)
    _exact("rst_write", out, ref.rst_write_ref(
        before, stride, wset, base, n, SUBLANE))
    del buf, before, out

    # rst_contend_read: ENGINES disjoint windows under 16-beat grants.
    n = sz.check_tiles // ENGINES
    p = RSTParams(n=n, b=TILE, s=sz.contend_w // n, w=sz.contend_w)
    got = ops.measure_contended_bandwidth(
        p, num_engines=ENGINES, arbitration="burst",
        burst_beats=GRANT).checksum
    buf = np.asarray(ops.make_working_buffer(p, f32, num_engines=ENGINES))
    stride, wset, base = block_params(p, TILE)
    _exact("rst_contend_read", got, sum(
        ref.rst_read_checksum_ref(buf, stride, wset, base + k * wset, n,
                                  SUBLANE) for k in range(ENGINES)))

    # rst_contend_mix_read: four readers with their own stride, window
    # and stream length, round robin.
    w = sz.contend_w
    mix = EngineMix(tuple((RSTParams(n=nk, b=TILE, s=sk, w=wk), "read")
                          for nk, sk, wk in ((n, w // n, w),
                                             (n // 2, TILE, w // 4),
                                             (n, 2 * TILE, w // 2),
                                             (n // 4, 4 * TILE, w))))
    grid = max(q.n for q in mix.params)
    got = ops.measure_contended_mix_bandwidth(mix).checksum
    table = np.asarray(ops.mix_params_operand(mix, f32, grid_txns=grid))
    buf = np.asarray(ops.make_mix_working_buffer(mix, f32, grid_txns=grid))
    _exact("rst_contend_mix_read", got, sum(
        ref.rst_read_checksum_ref(buf, int(s), int(wk), int(b),
                                  min(int(nk), grid), SUBLANE)
        for s, wk, b, nk in table[1:]))


def _max_rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, float), np.asarray(want, float)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want),
                                                        1e-300)))


def phase_grid(sz: Sizes) -> None:
    import numpy as np

    from benchmarks.run import grid_ladder_axes
    from repro.core import HBM, Sweep
    from repro.core.timing_jax import REL_TOLERANCE, evaluate_grid
    from repro.service import CampaignService, ExperimentRequest

    axes = grid_ladder_axes(quick=sz.grid_quick)
    cold = evaluate_grid(HBM, axes)
    warm = evaluate_grid(HBM, axes)
    print(f"  evaluate_grid: {warm.size} points, lanes by route "
          f"{warm.lanes_by_route} (numpy lanes run on the host), outputs "
          f"on {warm.output_devices} device(s)")
    print(f"  evaluate_grid: cold {cold.elapsed_seconds!r} s, warm "
          f"{warm.elapsed_seconds!r} s = {warm.points_per_second!r} "
          f"points/s (host clock)")
    _check(np.array_equal(cold.gbps, warm.gbps), "cold and warm grids differ")
    _check(warm.output_devices == 1, "the grid did not run on the device")

    idx = np.unique(np.linspace(0, warm.size - 1, sz.grid_sample).astype(int))
    pts = axes.sweep_points()
    sweep = Sweep(HBM, "sim")
    for i in idx:
        sweep.add_point(pts[int(i)])
    want = [r.value for r in sweep.run()]
    err = _max_rel_err(warm.gbps[idx], [v.aggregate_gbps for v in want])
    print(f"  evaluate_grid vs NumPy timing_model on {len(idx)} points: max "
          f"relative error {err!r} (bound {REL_TOLERANCE})")
    _check(err <= REL_TOLERANCE, f"grid error {err!r} > {REL_TOLERANCE}")
    _check(list(warm.bound[idx]) == [v.bound for v in want],
           "grid bounds differ from the NumPy model")

    # Strides of at most four bursts per window keep every lane exactly
    # periodic, so the served request runs in the compiled kernel; at its
    # default 16 MiB window and n=2^17 every lane would go to the host's
    # NumPy fallback instead.
    req = ExperimentRequest.make("grid_cross_product", "hbm", n=sz.grid_xp_n,
                                 w=1024, strides=(256, 512, 1024))
    t0 = time.perf_counter()
    resp = CampaignService("jaxgrid", fallback=None,
                           validate_fraction=0.0).submit(req)
    served_s = time.perf_counter() - t0
    _served(resp, "jaxgrid")
    ref_resp = CampaignService("sim", fallback=None,
                               validate_fraction=0.0).submit(req)
    _served(ref_resp, "sim")
    keys = sorted(ref_resp.result["gbps"], key=str)
    err = _max_rel_err([resp.result["gbps"][k] for k in keys],
                       [ref_resp.result["gbps"][k] for k in keys])
    print(f"  grid_cross_product via CampaignService('jaxgrid'): "
          f"{len(keys)} points in {served_s!r} s (host clock, compile "
          f"included); max relative error vs sim {err!r}")
    _check(err <= REL_TOLERANCE, f"served grid error {err!r} > "
           f"{REL_TOLERANCE}")


def phase_sharded_grid(sz: Sizes, devices: int) -> None:
    import numpy as np

    from benchmarks.run import grid_ladder_axes
    from repro.core import HBM
    from repro.core.timing_jax import REL_TOLERANCE, evaluate_grid
    from repro.launch.mesh import grid_mesh

    axes = grid_ladder_axes(quick=sz.grid_quick)
    mesh = grid_mesh(devices)
    runs = {}
    for label, kw in (("one device", {}), (f"{devices} devices",
                                           {"mesh": mesh})):
        evaluate_grid(HBM, axes, **kw)                    # compile
        runs[label] = res = evaluate_grid(HBM, axes, **kw)
        print(f"  {label}: {res.size} points, outputs on "
              f"{res.output_devices} device(s), lanes by route "
              f"{res.lanes_by_route}, warm {res.elapsed_seconds!r} s "
              f"(host clock)")
    one, many = runs.values()
    _check(one.output_devices == 1, "the unsharded grid left one device")
    _check(many.output_devices == devices,
           f"sharded outputs on {many.output_devices} devices, not "
           f"{devices}")
    err = _max_rel_err(many.gbps, one.gbps)
    print(f"  sharded vs one device: max relative error {err!r} (bound "
          f"{REL_TOLERANCE})")
    _check(err <= REL_TOLERANCE, f"sharded grid error {err!r}")
    _check(np.array_equal(many.bound, one.bound), "sharded bounds differ")


def run(sz: Sizes, peak_gbps=None, chips: int = 1) -> None:
    """Every phase after the device check, at sizes `sz`.  With
    ``chips > 1`` only the sharded grid runs, over that many devices."""
    phases = _Phases()
    try:
        if chips > 1:
            with phases.phase(f"sharded grid on {chips} devices"):
                phase_sharded_grid(sz, chips)
            return
        with phases.phase("engines via CampaignService('pallas')"):
            phase_engines(sz, peak_gbps)
        with phases.phase("contention and duplex via Sweep('pallas')"):
            phase_contention(sz, peak_gbps)
        with phases.phase("kernel correctness vs kernels/ref.py"):
            phase_correctness(sz)
        with phases.phase("grid tier via evaluate_grid and jaxgrid"):
            phase_grid(sz)
    finally:
        print(f"persistent compile cache: {phases.cache['hits']} hits, "
              f"{phases.cache['misses']} misses", flush=True)
        phases.close()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded grid over four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); there is no CPU fallback")
    if len(devices) < args.chips:
        raise SystemExit(f"chip smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX found {len(devices)}")

    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from benchmarks.run import setup_compile_cache
    from repro.core import chip_for_device

    chip = chip_for_device(dev)
    print(f"device: {dev.device_kind} ({dev.platform}) x {len(devices)}; "
          f"peaks of {chip.name}: {chip.hbm_bandwidth / 1e9:g} GB/s HBM")
    print(f"compile cache: {setup_compile_cache()}", flush=True)
    run(CHIP, peak_gbps=chip.hbm_bandwidth / 1e9, chips=args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
