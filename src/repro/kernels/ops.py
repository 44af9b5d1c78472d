"""Jitted high-level wrappers around the RST Pallas engines.

This is the device-side counterpart of the paper's parameter module: it
packs :class:`repro.core.params.RSTParams` (byte-level, as the host thinks
of them) into the scalar-prefetch operand (tile-level, as the engine
consumes them) and runs the kernels.  ``measure_read_bandwidth`` is what the
`pallas` backend of core/engine.py calls; on a real TPU the wall-clock
number is the achieved HBM bandwidth of one core's engine, on any other
platform the kernels run in interpret mode (`rst_read.interpret_mode`)
and validate correctness only.

Each measure_* call times one kernel call.  The first call with a new
signature (kernel, static arguments, operand shapes) in a process is
preceded by one untimed call (`_warm_once`), so compilation is never
timed.

``measure_gather_bandwidth`` times one decode step of a deployment
(core/decode_traffic.py): its table-driven `rst_gather` calls over a
`DecodeArena`, the deployment's weights and page pool, which unlike the
RST working buffers is built once a process and kept across requests.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.engine_mix import EngineMix
from repro.core.decode_traffic import (DecodeDeployment, GatherStep,
                                       deployment)
from repro.core.params import RSTParams
from repro.core.rst import block_params
from repro.core.timing_model import _grant_beats
from repro.kernels.rst_contend import rst_contend_mix_read, rst_contend_read
from repro.kernels.rst_gather import blocks_per_step, rst_gather
from repro.kernels.rst_read import LANE, SUBLANE, interpret_mode, rst_read
from repro.kernels.rst_write import rst_write


def tile_bytes(dtype, burst_rows: int = SUBLANE) -> int:
    return burst_rows * LANE * jnp.dtype(dtype).itemsize


def grid_bucket(n_txns: int, floor: int = 16) -> int:
    """Round a transaction count up to the next power of two.

    The grid size is a *static* argument of the jitted RST kernels, so every
    distinct value costs a fresh trace+compile (~0.5 s in interpret mode —
    it dominated non-quick benchmark wall time).  The actual transaction
    count N is a *runtime* scalar (`pl.when(i < n)` gates the excess grid
    steps), so bucketing the grid to powers of two lets every RST variant
    within a bucket share one compiled kernel.

    The excess grid steps still occupy the pipeline (they re-fetch the last
    block), so a bucketed grid *biases a wall-clock bandwidth measurement
    low* — up to 2x, or floor/N for tiny N.  The measure_* wrappers
    therefore bucket only in interpret mode, where the gbps number is
    documented as correctness-validation-only and the trace/compile cost is
    what matters; compiled (real-TPU) runs keep the exact grid.
    """
    if n_txns <= 0:
        raise ValueError(f"n_txns must be positive, got {n_txns}")
    return max(floor, 1 << (n_txns - 1).bit_length())


def default_grid(n_txns: int) -> int:
    """Grid the measure_* wrappers use when the caller passes none:
    bucketed in interpret mode (compile sharing; gbps is validation-only),
    exact in compiled mode (gbps is a real measurement)."""
    return grid_bucket(n_txns) if interpret_mode() else n_txns


_INT32_MAX = 2 ** 31 - 1


def _require_int32_index_range(stride_b: int, wset_b: int, base_b: int,
                               n: int, num_engines: int = 1) -> None:
    """Reject configurations whose index-map arithmetic overflows int32.

    The BlockSpec index maps run in int32 and compute
    ``base + k * wset + (t * stride) % wset`` with ``t <= n - 1`` and
    ``k < num_engines``; the raw product ``t * stride`` and the window
    span ``base + num_engines * wset`` must both stay representable, or
    a large sweep (Fig. 7/8 ceilings) silently wraps to a wrong — and
    possibly out-of-bounds — block index on the device.
    """
    worst_product = max(n - 1, 0) * stride_b
    worst_block = base_b + num_engines * wset_b
    if worst_product > _INT32_MAX or worst_block > _INT32_MAX:
        raise ValueError(
            f"RST operand overflows the int32 index maps: "
            f"(n-1)*stride_blocks={worst_product}, base+span="
            f"{worst_block} (limit {_INT32_MAX}); shrink N/S/W/A or "
            f"split the sweep")


def params_operand(p: RSTParams, dtype, burst_rows: int = SUBLANE,
                   grid_txns: int | None = None) -> jax.Array:
    """Pack byte-level RST params into the int32[4] scalar operand."""
    tb = tile_bytes(dtype, burst_rows)
    if p.b != tb:
        raise ValueError(
            f"burst B={p.b} does not match tile bytes {tb} "
            f"(burst_rows={burst_rows}, dtype={jnp.dtype(dtype).name}); on "
            f"TPU the burst is the BlockSpec tile (DESIGN.md §2)")
    stride_b, wset_b, base_b = block_params(p, tb)
    n = p.n if grid_txns is None else min(p.n, grid_txns)
    _require_int32_index_range(stride_b, wset_b, base_b, n)
    return jnp.array([stride_b, wset_b, base_b, n], dtype=jnp.int32)


def make_working_buffer(p: RSTParams, dtype, key=None, *,
                        num_engines: int = 1) -> jax.Array:
    """Allocate the working set as (rows, LANE): A + W bytes of the given
    dtype (the index maps address from ``base_block = A // tile`` upward,
    so the buffer must cover the base offset too), with W times
    `num_engines` for the contention kernel's disjoint per-engine
    windows."""
    itemsize = jnp.dtype(dtype).itemsize
    span = p.a + num_engines * p.w
    rows = span // (LANE * itemsize)
    if rows * LANE * itemsize != span:
        raise ValueError(
            f"A+{num_engines}*W={span} not a whole number of ({LANE},) rows")
    if key is None:
        # Deterministic, cheap, nonconstant content.
        base = jnp.arange(rows * LANE, dtype=jnp.float32) % 251.0
        return base.reshape(rows, LANE).astype(dtype)
    return jax.random.normal(key, (rows, LANE), dtype=jnp.float32).astype(dtype)


@dataclasses.dataclass(frozen=True)
class BandwidthSample:
    bytes_moved: int
    seconds: float
    checksum: np.ndarray

    @property
    def gbps(self) -> float:
        return self.bytes_moved / self.seconds / 1e9 if self.seconds > 0 else 0.0


# Signatures this process has run: kernel, static arguments, and the shape
# and dtype of each operand.  jax.jit compiles once per signature, so
# only its first call needs an untimed call before the timed one.  Kept
# for the process, as jax.jit's own cache is: callers such as a fresh
# Sweep per request share the programs it holds.
_WARMED: set = set()


def _warm_once(name: str, kernel, *operands: jax.Array, **static) -> None:
    """Run `kernel` once, untimed, the first time this process calls it
    with this signature, so that no timed call includes compilation.
    ``rst_write`` donates its buffer, the last operand, so it is warmed on
    a copy."""
    key = (name, tuple(sorted(static.items())),
           tuple((x.shape, x.dtype) for x in operands))
    if key in _WARMED:
        return
    with spans.span("repro.ops.warmup", kernel=name):
        if name == "rst_write":
            operands = operands[:-1] + (jnp.array(operands[-1]),)
        kernel(*operands, **static).block_until_ready()
    _WARMED.add(key)


def measure_read_bandwidth(p: RSTParams, *, dtype=jnp.float32,
                           burst_rows: int = SUBLANE,
                           grid_txns: int | None = None) -> BandwidthSample:
    grid = grid_txns or default_grid(p.n)
    nbytes = min(p.n, grid) * p.b
    with spans.span("repro.ops.measure"):
        operand = params_operand(p, dtype, burst_rows, grid)
        with spans.span("repro.ops.buffer"):
            buf = make_working_buffer(p, dtype)
            # The timed call must not wait for the buffer's build.
            jax.block_until_ready((operand, buf))
        _warm_once("rst_read", rst_read, operand, buf, grid_txns=grid,
                   burst_rows=burst_rows)
        with spans.span("repro.ops.timed", kernel="rst_read"):
            t0 = time.perf_counter()
            out = rst_read(operand, buf, grid_txns=grid,
                           burst_rows=burst_rows)
            out.block_until_ready()
            dt = time.perf_counter() - t0
        with spans.span("repro.ops.checksum"):
            checksum = np.asarray(out)
    return BandwidthSample(bytes_moved=nbytes, seconds=dt, checksum=checksum)


def contended_params_operand(p: RSTParams, num_engines: int, dtype,
                             burst_rows: int = SUBLANE,
                             grid_txns: int | None = None,
                             burst_beats: int = 1) -> jax.Array:
    """Pack byte-level RST params + engine count + grant size into the
    int32[6] scalar operand of the concurrent-access kernel."""
    base = params_operand(p, dtype, burst_rows, grid_txns)
    # The N disjoint per-engine windows span base + N*wset blocks — wider
    # than the single-engine range params_operand already validated.
    stride_b, wset_b, base_b = block_params(p, tile_bytes(dtype, burst_rows))
    n = p.n if grid_txns is None else min(p.n, grid_txns)
    _require_int32_index_range(stride_b, wset_b, base_b, n,
                               num_engines=num_engines)
    return jnp.concatenate(
        [base, jnp.array([num_engines, burst_beats], dtype=jnp.int32)])


def _resolve_grant_beats(arbitration: str, burst_beats: int,
                         grid_txns: int) -> int:
    """Map the arbitration-policy axis onto the kernel's grant size via
    the timing model's shared `_grant_beats` table (one set of policy
    names and validations), clamped to the per-engine grid: a grant
    cannot exceed the stream, and an unclamped grant would pad the grid
    with checksum-gated dummy steps that still occupy the pipeline and
    bias the wall-clock bandwidth low."""
    return min(_grant_beats(arbitration, burst_beats, grid_txns), grid_txns)


def measure_contended_bandwidth(p: RSTParams, *, num_engines: int,
                                arbitration: str = "round_robin",
                                burst_beats: int = 1,
                                dtype=jnp.float32,
                                burst_rows: int = SUBLANE,
                                grid_txns: int | None = None) -> BandwidthSample:
    """N read engines sharing one memory port (DESIGN.md §8/§9): the
    grant-interleaved traversal of `timing_model.contended_throughput`
    run on the device, at the requested arbitration granularity
    (round-robin beats, `burst_beats`-sized grants, or exclusive
    whole-stream grants).  Each engine owns a disjoint W-byte window of
    one shared buffer; bytes moved counts every engine (N·n·B over the
    wall time), so `gbps` is the port's *aggregate* under contention."""
    if num_engines < 1:
        raise ValueError(f"num_engines must be >= 1, got {num_engines}")
    grid = grid_txns or default_grid(p.n)
    bb = _resolve_grant_beats(arbitration, burst_beats, grid)
    nbytes = num_engines * min(p.n, grid) * p.b
    with spans.span("repro.ops.measure"):
        operand = contended_params_operand(p, num_engines, dtype,
                                           burst_rows, grid, bb)
        with spans.span("repro.ops.buffer"):
            buf = make_working_buffer(p, dtype, num_engines=num_engines)
            # The timed call must not wait for the buffer's build.
            jax.block_until_ready((operand, buf))
        _warm_once("rst_contend_read", rst_contend_read, operand, buf,
                   grid_txns=grid, num_engines=num_engines, burst_beats=bb,
                   burst_rows=burst_rows)
        with spans.span("repro.ops.timed", kernel="rst_contend_read"):
            t0 = time.perf_counter()
            out = rst_contend_read(operand, buf, grid_txns=grid,
                                   num_engines=num_engines, burst_beats=bb,
                                   burst_rows=burst_rows)
            out.block_until_ready()
            dt = time.perf_counter() - t0
        with spans.span("repro.ops.checksum"):
            checksum = np.asarray(out)
    return BandwidthSample(bytes_moved=nbytes, seconds=dt, checksum=checksum)


def _mix_block_rows(mix: EngineMix, dtype, burst_rows: int,
                    grid_txns: int | None) -> Tuple[list, int]:
    """Per-engine (stride, wset, base, n) block rows for the mix kernel.

    Engine k's disjoint window is laid out directly after engine k-1's:
    its row's base block folds in the cumulative working-set offset, so
    the device index map stays the three-term homogeneous form.  Every
    row is int32-guarded individually — one oversized entry must name
    itself rather than hide behind the mix's aggregate span.

    Returns (rows, span_blocks) where span_blocks is the buffer extent
    in tiles.
    """
    tb = tile_bytes(dtype, burst_rows)
    rows = []
    offset_b = 0
    span_b = 0
    for k, (p, op) in enumerate(mix.entries):
        if op != "read":
            raise ValueError(
                f"the contention kernel measures read engines only; entry "
                f"{k} of mix {mix.describe()!r} is {op!r} — route "
                f"write/duplex engines through the sim/jaxgrid placement "
                f"paths (DESIGN.md §13)")
        if p.b != tb:
            raise ValueError(
                f"entry {k} burst B={p.b} does not match tile bytes {tb} "
                f"(burst_rows={burst_rows}, dtype={jnp.dtype(dtype).name}); "
                f"on TPU the burst is the BlockSpec tile shared by every "
                f"engine in the mix (DESIGN.md §2/§13)")
        stride_b, wset_b, base_b = block_params(p, tb)
        base_k = base_b + offset_b
        n = p.n if grid_txns is None else min(p.n, grid_txns)
        _require_int32_index_range(stride_b, wset_b, base_k, n)
        rows.append([stride_b, wset_b, base_k, n])
        offset_b += wset_b
        span_b = max(span_b, base_k + wset_b)
    return rows, span_b


def mix_params_operand(mix: EngineMix, dtype, burst_rows: int = SUBLANE,
                       grid_txns: int | None = None,
                       burst_beats: int = 1) -> jax.Array:
    """Pack a heterogeneous EngineMix into the int32[N+1, 4] scalar table
    of `rst_contend_mix_read`: a header row (num_engines, burst_beats,
    0, 0) followed by one per-engine row, each int32-guarded on its own
    index arithmetic."""
    rows, _ = _mix_block_rows(mix, dtype, burst_rows, grid_txns)
    header = [len(mix), burst_beats, 0, 0]
    return jnp.array([header] + rows, dtype=jnp.int32)


def make_mix_working_buffer(mix: EngineMix, dtype, key=None, *,
                            burst_rows: int = SUBLANE,
                            grid_txns: int | None = None) -> jax.Array:
    """Allocate one shared working buffer covering every engine's
    disjoint window under the `_mix_block_rows` layout (engine k's
    window directly after engine k-1's, past its own base offset)."""
    _, span_b = _mix_block_rows(mix, dtype, burst_rows, grid_txns)
    rows = span_b * burst_rows
    if key is None:
        base = jnp.arange(rows * LANE, dtype=jnp.float32) % 251.0
        return base.reshape(rows, LANE).astype(dtype)
    return jax.random.normal(key, (rows, LANE), dtype=jnp.float32).astype(dtype)


def measure_contended_mix_bandwidth(mix: EngineMix, *,
                                    arbitration: str = "round_robin",
                                    burst_beats: int = 1,
                                    dtype=jnp.float32,
                                    burst_rows: int = SUBLANE,
                                    grid_txns: int | None = None) -> BandwidthSample:
    """A heterogeneous mix of read engines sharing one memory port: the
    per-engine generalization of `measure_contended_bandwidth`.  A
    uniform mix delegates to the homogeneous wrapper outright (the same
    reduction rule every layer of the contention stack applies), so the
    mixed kernel only ever runs for genuinely heterogeneous traffic.
    Bytes moved counts every engine's own burst size over its own
    stream, so `gbps` is the port's aggregate under the mixed load."""
    uni = mix.uniform_entry()
    if uni is not None:
        p, op = uni
        if op != "read":
            raise ValueError(
                f"the contention kernel measures read engines only; mix "
                f"{mix.describe()!r} is all-{op} — route write/duplex "
                f"engines through the sim/jaxgrid placement paths "
                f"(DESIGN.md §13)")
        return measure_contended_bandwidth(
            p, num_engines=len(mix), arbitration=arbitration,
            burst_beats=burst_beats, dtype=dtype, burst_rows=burst_rows,
            grid_txns=grid_txns)
    grid = grid_txns or default_grid(max(p.n for p in mix.params))
    bb = _resolve_grant_beats(arbitration, burst_beats, grid)
    nbytes = sum(min(p.n, grid) * p.b for p in mix.params)
    with spans.span("repro.ops.measure"):
        table = mix_params_operand(mix, dtype, burst_rows, grid,
                                   burst_beats=bb)
        with spans.span("repro.ops.buffer"):
            buf = make_mix_working_buffer(mix, dtype, burst_rows=burst_rows,
                                          grid_txns=grid)
            # The timed call must not wait for the buffer's build.
            jax.block_until_ready((table, buf))
        _warm_once("rst_contend_mix_read", rst_contend_mix_read, table, buf,
                   grid_txns=grid, num_engines=len(mix), burst_beats=bb,
                   burst_rows=burst_rows)
        with spans.span("repro.ops.timed", kernel="rst_contend_mix_read"):
            t0 = time.perf_counter()
            out = rst_contend_mix_read(table, buf, grid_txns=grid,
                                       num_engines=len(mix), burst_beats=bb,
                                       burst_rows=burst_rows)
            out.block_until_ready()
            dt = time.perf_counter() - t0
        with spans.span("repro.ops.checksum"):
            checksum = np.asarray(out)
    return BandwidthSample(bytes_moved=nbytes, seconds=dt, checksum=checksum)


def measure_write_bandwidth(p: RSTParams, *, dtype=jnp.float32,
                            burst_rows: int = SUBLANE,
                            grid_txns: int | None = None) -> BandwidthSample:
    grid = grid_txns or default_grid(p.n)
    nbytes = min(p.n, grid) * p.b
    with spans.span("repro.ops.measure"):
        operand = params_operand(p, dtype, burst_rows, grid)
        with spans.span("repro.ops.buffer"):
            buf = make_working_buffer(p, dtype)
            # The timed call must not wait for the buffer's build.
            jax.block_until_ready((operand, buf))
        _warm_once("rst_write", rst_write, operand, buf, grid_txns=grid,
                   burst_rows=burst_rows)
        with spans.span("repro.ops.timed", kernel="rst_write"):
            t0 = time.perf_counter()
            out = rst_write(operand, buf, grid_txns=grid,
                            burst_rows=burst_rows)
            out.block_until_ready()
            dt = time.perf_counter() - t0
        with spans.span("repro.ops.checksum"):
            checksum = np.asarray(out[:8])
    return BandwidthSample(bytes_moved=nbytes, seconds=dt, checksum=checksum)


def measure_duplex_bandwidth(p: RSTParams, *, dtype=jnp.float32,
                             burst_rows: int = SUBLANE,
                             grid_txns: int | None = None) -> BandwidthSample:
    """Mixed read/write traffic: both RST engines traverse one working
    buffer (the paper's duplex mode, Sec. III-C-1 — read and write modules
    run concurrently on one channel).  Off-TPU the two kernels run back to
    back; bytes moved counts both directions (2·N·B over the wall time).
    """
    grid = grid_txns or default_grid(p.n)
    nbytes = 2 * min(p.n, grid) * p.b
    with spans.span("repro.ops.measure"):
        operand = params_operand(p, dtype, burst_rows, grid)
        with spans.span("repro.ops.buffer"):
            buf = make_working_buffer(p, dtype)
            # The timed call must not wait for the buffer's build.
            jax.block_until_ready((operand, buf))
        _warm_once("rst_read", rst_read, operand, buf, grid_txns=grid,
                   burst_rows=burst_rows)
        _warm_once("rst_write", rst_write, operand, buf, grid_txns=grid,
                   burst_rows=burst_rows)
        with spans.span("repro.ops.timed", kernel="rst_duplex"):
            t0 = time.perf_counter()
            chk = rst_read(operand, buf, grid_txns=grid,
                           burst_rows=burst_rows)
            chk.block_until_ready()   # the write donates buf; read first
            out = rst_write(operand, buf, grid_txns=grid,
                            burst_rows=burst_rows)
            out.block_until_ready()
            dt = time.perf_counter() - t0
        with spans.span("repro.ops.checksum"):
            checksum = np.asarray(chk)
    return BandwidthSample(bytes_moved=nbytes, seconds=dt, checksum=checksum)


# ------------------------------------------------------------------ decode
ARENA_SALT = 0xA7E4A
ARENA_MIX = 0x045D9F3B


def arena_words(seed: int) -> np.ndarray:
    """The arena formula's two uint32 words ``(m, c)`` for `seed`."""
    m, c = np.random.default_rng([int(seed), ARENA_SALT]).integers(
        0, 1 << 32, size=2, dtype=np.uint64)
    return np.array([m | 1, c], dtype=np.uint32)


@functools.partial(jax.jit, static_argnames=("rows",))
def _arena_content(words: jax.Array, *, rows: int) -> jax.Array:
    """Word ``f`` (row-major over the whole arena) holds the bits of
    ``mix(m * f + c)``, where ``mix(h)`` is ``h ^= h >> 16; h *= ARENA_MIX;
    h ^= h >> 16``, all in uint32 (mod 2^32): every bit of every word
    depends on the seed and the word's place."""
    row = jax.lax.broadcasted_iota(jnp.uint32, (rows, LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.uint32, (rows, LANE), 1)
    h = (row * LANE + lane) * words[0] + words[1]
    h = h ^ (h >> 16)
    h = h * jnp.uint32(ARENA_MIX)
    h = h ^ (h >> 16)
    return jax.lax.bitcast_convert_type(h, jnp.int32)


@dataclasses.dataclass
class DecodeArena:
    """A deployment's weights and page pool on the device, for one seed:
    built once a process and kept across requests (`decode_arena`)."""

    deployment: DecodeDeployment
    seed: int
    array: jax.Array
    warmed: set = dataclasses.field(default_factory=set)

    @classmethod
    def build(cls, dep: DecodeDeployment, seed: int) -> "DecodeArena":
        if dep.arena_rows * LANE > 1 << 32:
            raise ValueError(f"the arena of {dep.name!r} has more words "
                             f"than uint32 counts")
        with spans.span("repro.ops.arena"):
            array = _arena_content(jnp.asarray(arena_words(seed)),
                                   rows=dep.arena_rows)
            array.block_until_ready()
        return cls(dep, int(seed), array)

    def warm(self, contexts: Tuple[int, ...]) -> None:
        """Compile every grid a step of this batch can use."""
        if contexts in self.warmed:
            return
        acc = jnp.zeros((SUBLANE, LANE), jnp.int32)
        count = jnp.ones((1,), jnp.int32)
        for rows, grid in sorted(self.deployment.grids(contexts)):
            _warm_once("rst_gather", rst_gather, count,
                       jnp.zeros((grid,), jnp.int32), acc, self.array,
                       block_rows=rows)
        self.warmed.add(contexts)


# The arena this process holds: one at a time, as it fills most of a chip.
_ARENA: list = []


def decode_arena(dep: DecodeDeployment, seed: int) -> DecodeArena:
    """The arena of `dep` and `seed`, built on first use; another
    deployment or seed frees the one held before its own is built."""
    if _ARENA and (_ARENA[0].deployment is dep
                   and _ARENA[0].seed == int(seed)):
        return _ARENA[0]
    while _ARENA:
        _ARENA.pop().array.delete()
    _ARENA.append(DecodeArena.build(dep, seed))
    return _ARENA[0]


@dataclasses.dataclass(frozen=True)
class GatherSample(BandwidthSample):
    """One decode step's gathers: real blocks' bytes, the chained
    checksum, and the counters of the engine calls."""

    grid_steps: int = 0
    pad_steps: int = 0
    calls: int = 0


def measure_gather_bandwidth(step: GatherStep) -> GatherSample:
    """Run one decode step's gather calls, chained into one checksum, and
    time them from the first launch to the last ``block_until_ready``."""
    dep = deployment(step.deployment)
    arena = decode_arena(dep, step.seed)
    contexts = tuple(int(c) for c in step.contexts)
    with spans.span("repro.ops.measure"):
        calls = dep.plan(step.seed, contexts, step.step)
        with spans.span("repro.ops.buffer"):
            host = []
            for call in calls:
                n = len(call.blocks)
                table = np.full(dep.grid(n), call.blocks[-1], np.int32)
                table[:n] = call.blocks
                host.append((np.array([n], np.int32), table))
            # One transfer for the whole step: a transfer an array costs
            # a host-to-device round trip each.
            operands = jax.device_put(host)
            acc = jnp.zeros((SUBLANE, LANE), jnp.int32)
            jax.block_until_ready((acc, operands))
        arena.warm(contexts)
        with spans.span("repro.ops.timed", kernel="rst_gather"):
            t0 = time.perf_counter()
            for call, (count, table) in zip(calls, operands):
                n = len(call.blocks)
                with spans.span("repro.ops.gather", kind=call.kind,
                                blocks=n, pad=table.shape[0] - n,
                                block_bytes=call.block_bytes,
                                per_step=blocks_per_step(call.block_bytes)):
                    acc = rst_gather(count, table, acc, arena.array,
                                     block_rows=call.block_rows)
            acc.block_until_ready()
            dt = time.perf_counter() - t0
        with spans.span("repro.ops.checksum"):
            checksum = np.asarray(acc)
    real = sum(len(c.blocks) for c in calls)
    grid = sum(t.shape[0] for _, t in host)
    return GatherSample(
        bytes_moved=sum(len(c.blocks) * c.block_bytes for c in calls),
        seconds=dt, checksum=checksum, grid_steps=grid,
        pad_steps=grid - real, calls=len(calls))
