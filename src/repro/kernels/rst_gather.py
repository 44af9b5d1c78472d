"""Table-driven RST read engine as a Pallas TPU kernel.

The affine engines (rst_read.py) compute each transaction's block from
Shuhai's Eq. 1.  A served LLM's decode step reads memory through a table
instead: weight blocks in layer order, then each sequence's KV-cache pages
in page-table order, scattered over a page pool.  This engine reads the
blocks a table lists.

Grid step ``i`` DMAs the ``(block_rows, 128)`` int32 block at block index
``table[i]`` of the arena.  The table and its runtime count ``n`` arrive by
scalar prefetch, so one compiled kernel serves every table of its length;
``block_rows`` is static (72 rows for a 36 KiB KV page, 2048 for a 1 MiB
weight block).  Steps past ``n`` repeat the last real index, so the
pipeline does not fetch again, and the checksum leaves them out.

The checksum: the body adds the block's 8 x 128 sub-tiles into an int32
(8, 128) accumulator, wrapping mod 2^32, so the sum is exact and the same
in any order, and it depends on all 32 bits of every word read: a copy of
the arena held in fewer bits gives another sum.  The kernel takes the
accumulator in and hands it out, so the calls of one decode step chain
into one checksum.

The whole table sits in SMEM, which holds 1 MiB on a TPU v5e: a table of
2^18 int32 entries is refused by the compiler, so callers keep each call's
table well below that (``MAX_TABLE``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rst_read import LANE, SUBLANE, interpret_mode

# Entries of one call's table: half of the v5e's 1 MiB of SMEM.
MAX_TABLE = 1 << 17
_UNROLLED_TILES = 16        # blocks of more sub-tiles loop in groups of 8


def _index_map(i, count_ref, table_ref):
    return table_ref[jnp.minimum(i, count_ref[0] - 1)], 0


def add_tiles(acc, block_ref):
    """`acc` plus the block's 8-row sub-tiles."""
    tiles = block_ref.shape[0] // SUBLANE
    if tiles <= _UNROLLED_TILES:
        for j in range(tiles):
            acc = acc + block_ref[j * SUBLANE:(j + 1) * SUBLANE, :]
        return acc

    def group(g, acc):
        for k in range(8):
            row = pl.multiple_of((g * 8 + k) * SUBLANE, SUBLANE)
            acc = acc + block_ref[pl.ds(row, SUBLANE), :]
        return acc
    return jax.lax.fori_loop(0, tiles // 8, group, acc)


def _rst_gather_kernel(count_ref, table_ref, acc_in_ref, block_ref,
                       acc_out_ref, acc_ref):
    del table_ref  # read by the index map
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _start():
        acc_ref[...] = acc_in_ref[...]

    @pl.when(i < count_ref[0])
    def _accumulate():
        acc_ref[...] = add_tiles(acc_ref[...], block_ref)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        acc_out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_rows",))
def rst_gather(count: jax.Array, table: jax.Array, acc: jax.Array,
               arena: jax.Array, *, block_rows: int) -> jax.Array:
    """Read the blocks `table[:count]` of `arena` into the checksum `acc`.

    Args:
      count: int32[1], the real entries of `table`, at least 1.
      table: int32[grid], block indices in units of `block_rows` rows; the
        grid has one step per entry.
      acc: int32[8, 128], the checksum so far.
      arena: int32[rows, 128].
      block_rows: rows per block, a multiple of 8 (and of 64 above 128).

    Returns:
      int32[8, 128]: `acc` plus every sub-tile of every real block, mod
      2^32.
    """
    rows, lane = arena.shape
    if arena.dtype != jnp.int32 or acc.dtype != jnp.int32:
        raise ValueError(f"rst_gather adds int32 words, got an arena of "
                         f"{arena.dtype} and a checksum of {acc.dtype}")
    if lane != LANE or block_rows % SUBLANE or block_rows > rows:
        raise ValueError(f"arena {arena.shape} cannot be read in blocks of "
                         f"({block_rows}, {LANE})")
    tiles = block_rows // SUBLANE
    if tiles > _UNROLLED_TILES and tiles % 8:
        raise ValueError(f"block_rows={block_rows}: blocks of more than "
                         f"{_UNROLLED_TILES * SUBLANE} rows come in 64-row "
                         f"groups")
    grid = table.shape[0]
    if grid > MAX_TABLE:
        raise ValueError(f"a table of {grid} entries exceeds MAX_TABLE "
                         f"({MAX_TABLE}); split the call")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(grid,),
        in_specs=[pl.BlockSpec((SUBLANE, LANE), lambda i, c, t: (0, 0)),
                  pl.BlockSpec((block_rows, LANE), _index_map)],
        out_specs=pl.BlockSpec((SUBLANE, LANE), lambda i, c, t: (0, 0)),
        scratch_shapes=[pltpu.VMEM((SUBLANE, LANE), jnp.int32)],
    )
    return pl.pallas_call(
        _rst_gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((SUBLANE, LANE), jnp.int32),
        name="rst_gather",
        interpret=interpret_mode(),
    )(count, table, acc, arena)
