"""Table-driven RST read engine as a Pallas TPU kernel.

The affine engines (rst_read.py) compute each transaction's block from
Shuhai's Eq. 1.  A served LLM's decode step reads memory through a table
instead: weight blocks in layer order, then each sequence's KV-cache pages
in page-table order, scattered over a page pool.  This engine reads the
blocks a table lists.

The table and its runtime count ``n`` arrive by scalar prefetch, so one
compiled kernel serves every table of its length; ``block_rows`` is static
(72 rows for a 36 KiB KV page, 2048 for a 1 MiB weight block).  The arena
stays in HBM and the kernel copies the blocks itself: grid step ``i`` reads
the ``k`` blocks ``table[i * k:(i + 1) * k]``, each a ``(block_rows, 128)``
int32 block of the arena.  A grid step has a fixed cost of its own (about
0.2 us on a TPU v5e, several times a 36 KiB page's 0.045 us of HBM time),
which ``k`` spreads over its blocks: ``k`` is the largest power of two with
``k`` blocks in at most ``STEP_BYTES``, and at least 1 (``blocks_per_step``).
A call of 36 KiB pages reads 16 a step, one of 1 MiB weight blocks one.

The copies run through a two-slot VMEM ring of ``k`` blocks a slot, with
one DMA semaphore a slot: step ``i`` starts the copies of step ``i + 1``
into the other slot, then waits for every copy of its own slot and sums
its blocks, so the next step's ``k`` DMAs are in flight while this step's
blocks are summed (step 0 starts its own first).  Only the entries below
``n`` are read: a padded entry issues no DMA and adds nothing, and the
table past ``n`` is never looked at, so a ragged last step and the steps
past it cost only their fixed cost.

The checksum: each block's 8 x 128 sub-tiles are added into an int32
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
# Bytes a grid step reads at most, when its blocks are smaller than that
# (`blocks_per_step`).  On a TPU v5e 36 KiB pages read at 170, 392, 584
# and 606 GB/s at 1, 4, 16 and 64 a step; 1 MiB blocks at about 550 GB/s
# at 1, 2 or 4 a step.
STEP_BYTES = 1 << 20
_UNROLLED_TILES = 16        # blocks of more sub-tiles loop in groups of 8


def blocks_per_step(block_bytes: int) -> int:
    """Blocks a grid step reads: the largest power of two of them in
    `STEP_BYTES`, and at least 1."""
    k = 1
    while 2 * k * block_bytes <= STEP_BYTES:
        k *= 2
    return k


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


def _rst_gather_kernel(count_ref, table_ref, acc_in_ref, arena_ref,
                       acc_out_ref, ring, sems, acc_ref):
    _, per_step, block_rows, _ = ring.shape
    i = pl.program_id(0)

    def copy(step, j):
        """The copy of block `j` of `step` into its slot of the ring."""
        row = pl.multiple_of(table_ref[step * per_step + j] * block_rows,
                             SUBLANE)
        return pltpu.make_async_copy(arena_ref.at[pl.ds(row, block_rows)],
                                     ring.at[step % 2, j], sems.at[step % 2])

    def each_real_block(step, body):
        """body(j) for each block `j` of `step` that lies below the count."""
        def block(j, carry):
            body(j)
            return carry
        real = jnp.clip(count_ref[0] - step * per_step, 0, per_step)
        jax.lax.fori_loop(0, real, block, 0)

    def start(step):
        each_real_block(step, lambda j: copy(step, j).start())

    def add(j):
        acc_ref[...] = add_tiles(acc_ref[...], ring.at[i % 2, j])

    @pl.when(i == 0)
    def _first():
        acc_ref[...] = acc_in_ref[...]
        start(i)

    @pl.when(i + 1 < pl.num_programs(0))
    def _prefetch():
        start(i + 1)

    # A slot's copies share one semaphore, so a block is whole only once
    # every copy of its slot has been waited for.
    each_real_block(i, lambda j: copy(i, j).wait())
    each_real_block(i, add)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        acc_out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_rows",))
def rst_gather(count: jax.Array, table: jax.Array, acc: jax.Array,
               arena: jax.Array, *, block_rows: int) -> jax.Array:
    """Read the blocks `table[:count]` of `arena` into the checksum `acc`.

    Args:
      count: int32[1], the real entries of `table`, at least 1.
      table: int32[entries], block indices in units of `block_rows` rows;
        the grid has one step per `blocks_per_step` entries.
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
    entries = table.shape[0]
    if entries > MAX_TABLE:
        raise ValueError(f"a table of {entries} entries exceeds MAX_TABLE "
                         f"({MAX_TABLE}); split the call")
    per_step = blocks_per_step(block_rows * LANE * arena.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(-(-entries // per_step),),
        in_specs=[pl.BlockSpec((SUBLANE, LANE), lambda i, c, t: (0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((SUBLANE, LANE), lambda i, c, t: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, per_step, block_rows, LANE), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((SUBLANE, LANE), jnp.int32)],
    )
    return pl.pallas_call(
        _rst_gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((SUBLANE, LANE), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="rst_gather",
        interpret=interpret_mode(),
    )(count, table, acc, arena)
