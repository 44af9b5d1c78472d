"""Concurrent-access RST engines as one Pallas TPU kernel (DESIGN.md §8/§9).

The multi-engine contention scenario of Choi et al. 2020 / Zohouri &
Matsuoka 2019 on the device side: N read engines share one memory port
under *grant-based* arbitration.  The grant size is the arbitration-
granularity axis of `timing_model.contended_throughput`:

* ``burst_beats=1`` — per-transaction round robin, the worst case: grid
  step ``j = t * N + k`` is engine k's t-th transaction;
* ``burst_beats=B`` — burst grants: each rotation hands engine k B
  consecutive transactions (``j = g*(B*N) + k*B + b`` is beat b of
  engine k's grant in rotation g), preserving row-buffer locality inside
  a grant — the lever that moves multi-PE designs between ~30% and ~90%
  of nominal bandwidth;
* ``burst_beats >= n`` — exclusive whole-stream grants, the serialized
  bound (`ops.measure_contended_bandwidth` maps ``arbitration=
  "exclusive"`` onto this).

Engine k traverses its own W-byte window at block offset
``base + k * wset`` (Eq. 1 per engine, disjoint windows) — the same
interleaved stream the timing model analyses.

The kernel body is the read engine's single VPU checksum add, so the
pipeline stays DMA-bound and the wall-clock number on a real TPU is the
shared port's aggregate bandwidth under contention; in interpret mode it
validates the interleaved traversal only.  Runtime parameterization is
preserved: ``(stride, wset, base, n, num_engines, burst_beats)`` arrive
via scalar prefetch, so one compiled image serves every engine count and
grant size up to the static grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rst_read import LANE, SUBLANE, interpret_mode


def _grant_position(j, params_ref):
    """(engine k, transaction t_raw) of grid step j under burst grants.

    Rotation ``g = j // (bb * N)`` hands each engine a grant of ``bb``
    consecutive beats: within the rotation, ``k = r // bb`` owns beat
    ``r % bb``, so its transaction index is ``t_raw = g * bb + r % bb``.
    ``bb = 1`` reduces to the round-robin decomposition ``k = j % N``,
    ``t_raw = j // N`` position for position.  ``t_raw`` may overhang the
    real stream (grid padding, or n not a multiple of bb in the last
    rotation) — callers clamp for the index map and gate the checksum.
    """
    engines = params_ref[4]
    bb = params_ref[5]
    per_round = bb * engines
    g = j // per_round
    r = j % per_round
    return r // bb, g * bb + r % bb


def _contend_index_map(j, params_ref):
    """Block index of grid step j: engine k's t-th transaction, Eq. 1 over
    its own window at ``base + k * wset``.  Overhanging steps revisit the
    engine's last real block (cheap, pipelined) and are excluded from the
    checksum by the body's gate."""
    stride, wset, base, n = (params_ref[0], params_ref[1],
                             params_ref[2], params_ref[3])
    k, t_raw = _grant_position(j, params_ref)
    t = jnp.minimum(t_raw, n - 1)
    return base + k * wset + (t * stride) % wset, 0


def _rst_contend_kernel(params_ref, buf_ref, out_ref, acc_ref):
    j = pl.program_id(0)
    n = params_ref[3]
    _, t_raw = _grant_position(j, params_ref)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t_raw < n)
    def _accumulate():
        acc_ref[...] += buf_ref[...].astype(jnp.float32)

    @pl.when(j == pl.num_programs(0) - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def _mix_grant_position(j, table_ref):
    """(engine k, transaction t_raw) of grid step j from the mix table.

    Same rotation decomposition as `_grant_position`, but the engine
    count and grant size come from the table's header row (row 0) so one
    compiled image serves every mix shape up to the static grid.
    """
    engines = table_ref[0, 0]
    bb = table_ref[0, 1]
    per_round = bb * engines
    g = j // per_round
    r = j % per_round
    return r // bb, g * bb + r % bb


def _mix_index_map(j, table_ref):
    """Block index of grid step j under a heterogeneous mix: engine k's
    own (stride, wset, base, n) row is gathered from the scalar-prefetch
    table — the per-engine Eq. 1 over its own pre-offset window.  The
    window offset is folded into each row's base block by
    `ops.mix_params_operand`, so the map stays the three-term form the
    homogeneous kernel uses."""
    k, t_raw = _mix_grant_position(j, table_ref)
    row = k + 1
    stride = table_ref[row, 0]
    wset = table_ref[row, 1]
    base = table_ref[row, 2]
    n = table_ref[row, 3]
    t = jnp.minimum(t_raw, n - 1)
    return base + (t * stride) % wset, 0


def _rst_contend_mix_kernel(table_ref, buf_ref, out_ref, acc_ref):
    j = pl.program_id(0)
    k, t_raw = _mix_grant_position(j, table_ref)
    n = table_ref[k + 1, 3]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t_raw < n)
    def _accumulate():
        acc_ref[...] += buf_ref[...].astype(jnp.float32)

    @pl.when(j == pl.num_programs(0) - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("grid_txns", "num_engines", "burst_beats", "burst_rows"))
def rst_contend_mix_read(table: jax.Array, buf: jax.Array, *, grid_txns: int,
                         num_engines: int, burst_beats: int = 1,
                         burst_rows: int = SUBLANE) -> jax.Array:
    """Run a heterogeneous mix of grant-interleaved RST read engines.

    The per-engine generalization of `rst_contend_read`: instead of one
    (stride, wset, base, n) shared by every engine, each engine carries
    its own row of the scalar-prefetch operand table, so engines in one
    arbitration rotation may traverse differently-shaped windows
    (different stride/working-set/transaction-count — the byte-level
    burst is the static tile, shared by construction).

    Args:
      table: int32[num_engines + 1, 4] scalar operand.  Row 0 is the
        header ``(num_engines, burst_beats, 0, 0)``; row k+1 is engine
        k's ``(stride_blocks, wset_blocks, base_block, n_txns)`` with
        its disjoint-window offset already folded into ``base_block``
        (see `ops.mix_params_operand`).
      buf: shared working buffer covering every engine's window:
        shape (rows, LANE) with rows % burst_rows == 0 and at least
        ``max_k(base_block_k + wset_blocks_k)`` blocks.
      grid_txns: static per-engine grid size (every n_txns <= grid_txns).
      num_engines: static engine count (== table rows - 1).
      burst_beats: static grant size, as in `rst_contend_read`.
      burst_rows: rows per burst tile.

    Returns:
      float32[burst_rows, LANE] elementwise checksum of every tile read
      by every engine (each engine's overhang beats past its own n are
      gated out independently).
    """
    rows, lane = buf.shape
    if lane != LANE:
        raise ValueError(f"buffer minor dim must be {LANE}, got {lane}")
    if rows % burst_rows:
        raise ValueError(f"rows ({rows}) % burst_rows ({burst_rows}) != 0")
    if burst_rows % SUBLANE:
        raise ValueError(f"burst_rows must be a multiple of {SUBLANE}")
    if num_engines < 1:
        raise ValueError(f"num_engines must be >= 1, got {num_engines}")
    if burst_beats < 1:
        raise ValueError(f"burst_beats must be >= 1, got {burst_beats}")
    if table.shape != (num_engines + 1, 4):
        raise ValueError(
            f"mix table must be int32[{num_engines + 1}, 4] "
            f"(header + one row per engine), got {table.shape}")

    grid_per_engine = -(-grid_txns // burst_beats) * burst_beats
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_per_engine * num_engines,),
        in_specs=[pl.BlockSpec((burst_rows, LANE), _mix_index_map)],
        out_specs=pl.BlockSpec((burst_rows, LANE), lambda j, p: (0, 0)),
        scratch_shapes=[pltpu.VMEM((burst_rows, LANE), jnp.float32)],
    )
    return pl.pallas_call(
        _rst_contend_mix_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((burst_rows, LANE), jnp.float32),
        interpret=interpret_mode(),
    )(table, buf)


@functools.partial(
    jax.jit,
    static_argnames=("grid_txns", "num_engines", "burst_beats", "burst_rows"))
def rst_contend_read(params: jax.Array, buf: jax.Array, *, grid_txns: int,
                     num_engines: int, burst_beats: int = 1,
                     burst_rows: int = SUBLANE) -> jax.Array:
    """Run N grant-interleaved RST read engines over `buf`.

    Args:
      params: int32[6] = (stride_blocks, wset_blocks, base_block, n_txns,
        num_engines, burst_beats); blocks are `(burst_rows, LANE)` tiles
        and engine k's window starts at block ``base_block + k *
        wset_blocks``.
      buf: the shared working buffer covering every engine's window:
        shape (rows, LANE) with rows % burst_rows == 0 and at least
        ``num_engines * wset_blocks`` blocks past `base_block`.
      grid_txns: static per-engine grid size (n_txns <= grid_txns).
      num_engines: static engine count.
      burst_beats: static grant size — transactions one engine issues per
        arbitration rotation (1 = round robin; >= n_txns = exclusive).
        The per-engine grid is padded up to a whole number of grants so
        every rotation covers each engine; padded steps are gated out of
        the checksum.
      burst_rows: rows per burst tile.

    Returns:
      float32[burst_rows, LANE] elementwise checksum of every tile read
      by every engine.
    """
    rows, lane = buf.shape
    if lane != LANE:
        raise ValueError(f"buffer minor dim must be {LANE}, got {lane}")
    if rows % burst_rows:
        raise ValueError(f"rows ({rows}) % burst_rows ({burst_rows}) != 0")
    if burst_rows % SUBLANE:
        raise ValueError(f"burst_rows must be a multiple of {SUBLANE}")
    if num_engines < 1:
        raise ValueError(f"num_engines must be >= 1, got {num_engines}")
    if burst_beats < 1:
        raise ValueError(f"burst_beats must be >= 1, got {burst_beats}")

    # Whole grant rotations only: a ragged final rotation would hand some
    # engines fewer grid steps than transactions (the grant decomposition
    # would skip their tail beats), so pad the per-engine grid up to the
    # grant size and let the `t_raw < n` gate discard the overhang.
    grid_per_engine = -(-grid_txns // burst_beats) * burst_beats
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_per_engine * num_engines,),
        in_specs=[pl.BlockSpec((burst_rows, LANE), _contend_index_map)],
        out_specs=pl.BlockSpec((burst_rows, LANE), lambda j, p: (0, 0)),
        scratch_shapes=[pltpu.VMEM((burst_rows, LANE), jnp.float32)],
    )
    return pl.pallas_call(
        _rst_contend_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((burst_rows, LANE), jnp.float32),
        interpret=interpret_mode(),
    )(params, buf)
