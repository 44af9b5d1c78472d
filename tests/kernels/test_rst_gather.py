"""The table-driven read engine in interpret mode against NumPy: the blocks
a table lists, padded steps left out of the checksum, chained calls, sums
that wrap mod 2^32, and the decode arena and step measurer built on it."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decode_traffic import GatherStep, deployment
from repro.kernels import ops
from repro.kernels.rst_gather import MAX_TABLE, rst_gather

ROWS = 1152     # a small arena: 16 blocks of 72 rows, 6 of 192


def _arena(seed=0):
    """Words of all 32 bits, so that the sums wrap."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << 32, (ROWS, 128), dtype=np.uint32) \
        .view(np.int32)


def _numpy(acc, arena, blocks, rows):
    """acc plus each block's 8-row sub-tiles, mod 2^32."""
    acc = np.array(acc, np.int32).view(np.uint32)
    words = arena.view(np.uint32)
    for b in blocks:
        for j in range(rows // 8):
            acc = acc + words[b * rows + 8 * j:b * rows + 8 * j + 8]
    return acc.view(np.int32)


def _call(acc, arena, blocks, rows, grid):
    table = np.full(grid, blocks[-1], np.int32)
    table[:len(blocks)] = blocks
    return np.asarray(rst_gather(jnp.array([len(blocks)], jnp.int32),
                                 jnp.asarray(table), jnp.asarray(acc),
                                 jnp.asarray(arena), block_rows=rows))


@pytest.mark.parametrize("rows, blocks, grid", [
    (72, [3, 0, 15, 3, 7], 5),            # a page-sized block, repeats
    (72, [9, 2], 16),                     # 14 padded steps
    (128, [1, 8], 8),                     # 16 sub-tiles, unrolled
    (192, [5, 0, 2], 4),                  # 24 sub-tiles, in a loop
])
def test_reads_the_listed_blocks(rows, blocks, grid):
    arena = _arena()
    acc = np.zeros((8, 128), np.int32)
    np.testing.assert_array_equal(_call(acc, arena, blocks, rows, grid),
                                  _numpy(acc, arena, blocks, rows))


def test_padded_steps_do_not_count():
    arena = _arena(1)
    acc = np.zeros((8, 128), np.int32)
    np.testing.assert_array_equal(_call(acc, arena, [4], 72, 32),
                                  _call(acc, arena, [4], 72, 1))


def test_calls_chain_into_one_checksum():
    arena = _arena(2)
    acc = np.full((8, 128), -7, np.int32)
    first = _call(acc, arena, [1, 2, 3], 72, 4)
    second = _call(first, arena, [0, 5], 192, 2)
    want = _numpy(_numpy(acc, arena, [1, 2, 3], 72), arena, [0, 5], 192)
    np.testing.assert_array_equal(second, want)


def test_refuses_what_it_cannot_read():
    arena = jnp.zeros((ROWS, 128), jnp.int32)
    acc = jnp.zeros((8, 128), jnp.int32)
    one = jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="int32"):
        rst_gather(one, jnp.zeros((2,), jnp.int32), acc,
                   arena.astype(jnp.float32), block_rows=72)
    with pytest.raises(ValueError, match="64-row"):
        rst_gather(one, jnp.zeros((2,), jnp.int32), acc, arena,
                   block_rows=136)
    with pytest.raises(ValueError, match="MAX_TABLE"):
        rst_gather(one, jnp.zeros((MAX_TABLE + 1,), jnp.int32), acc,
                   arena, block_rows=72)


def test_arena_holds_the_seeded_formula():
    dep = deployment("deepseek-v2-lite-smoke")
    arena = ops.decode_arena(dep, 11)
    got = np.asarray(arena.array).reshape(-1)
    assert got.dtype == np.int32
    m, c = (int(w) for w in ops.arena_words(11))
    f = np.arange(got.size, dtype=np.uint64)
    h = (f * m + c) % (1 << 32)
    h ^= h >> 16
    h = h * ops.ARENA_MIX % (1 << 32)
    h ^= h >> 16
    np.testing.assert_array_equal(got.view(np.uint32), h)
    # Every bit of a word varies: no narrower copy holds the arena.
    bits = (got.view(np.uint32)[:, None] >> np.arange(32, dtype=np.uint32)) & 1
    assert np.all((bits.mean(axis=0) > 0.45) & (bits.mean(axis=0) < 0.55))
    assert ops.decode_arena(dep, 11) is arena       # kept across requests
    assert ops.decode_arena(dep, 12) is not arena   # another seed: rebuilt


def test_a_step_counts_its_calls_and_pads():
    dep = deployment("deepseek-v2-lite-smoke")
    contexts = (300, 700, 2048, 256)
    calls = dep.plan(4, contexts, 9)
    sample = ops.measure_gather_bandwidth(GatherStep(dep.name, 4, contexts,
                                                     9))
    real = sum(len(c.blocks) for c in calls)
    grid = sum(dep.grid(len(c.blocks)) for c in calls)
    assert (sample.calls, sample.grid_steps, sample.pad_steps) == \
        (len(calls), grid, grid - real)
    assert sample.bytes_moved == sum(len(c.blocks) * c.block_bytes
                                     for c in calls)
    arena = np.asarray(ops.decode_arena(dep, 4).array)
    acc = np.zeros((8, 128), np.int32)
    for c in calls:
        acc = _numpy(acc, arena, c.blocks, c.block_rows)
    np.testing.assert_array_equal(sample.checksum, acc)
