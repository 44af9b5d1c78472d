"""The table-driven read engine in interpret mode against NumPy: the blocks
a table lists, k of them a grid step, padded entries left out of the
checksum and never read, chained calls, sums that wrap mod 2^32, and the
decode arena and step measurer built on it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decode_traffic import GatherStep, deployment
from repro.kernels import ops
from repro.kernels.rst_gather import (MAX_TABLE, STEP_BYTES, blocks_per_step,
                                      rst_gather)

ROW_BYTES = 128 * 4
WHOLE = STEP_BYTES // ROW_BYTES     # a block of STEP_BYTES: one a grid step
ROWS = 4 * WHOLE    # a small arena: 4 blocks of WHOLE rows, many of 72
PAD = 1 << 30       # a pad entry the engine must never read: past the arena


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


def _call(acc, arena, blocks, rows, grid, pad=None):
    table = np.full(grid, blocks[-1] if pad is None else pad, np.int32)
    table[:len(blocks)] = blocks
    return np.asarray(rst_gather(jnp.array([len(blocks)], jnp.int32),
                                 jnp.asarray(table), jnp.asarray(acc),
                                 jnp.asarray(arena), block_rows=rows))


def _counts(k):
    """1, k - 1, k, k + 1, a count that ends mid-step, and a full ragged
    table of 3k + 2 entries."""
    return sorted({1, max(k - 1, 1), k, k + 1, 2 * k + (k + 1) // 2,
                   3 * k + 2})


def _stepped_cases():
    """Tables of blocks read several a step (72-row pages) and one a step
    (WHOLE rows), in shuffled order with repeats."""
    cases = []
    for rows in (72, WHOLE):
        k = blocks_per_step(rows * ROW_BYTES)
        for count in _counts(k):
            rng = np.random.default_rng([rows, count])
            blocks = rng.integers(0, ROWS // rows, count).tolist()
            blocks[-1] = blocks[0]
            cases.append(pytest.param(rows, blocks, 3 * k + 2,
                                      id=f"k{k}-rows{rows}-count{count}"))
    return cases


@pytest.mark.parametrize("rows, blocks, grid", [
    (72, [3, 0, 15, 3, 7], 5),            # a page-sized block, repeats
    (72, [9, 2], 16),                     # 14 padded steps
    (128, [1, 8], 8),                     # 16 sub-tiles, unrolled
    (192, [5, 0, 2], 4),                  # 24 sub-tiles, in a loop
] + _stepped_cases())
def test_reads_the_listed_blocks(rows, blocks, grid):
    arena = _arena()
    acc = np.zeros((8, 128), np.int32)
    np.testing.assert_array_equal(_call(acc, arena, blocks, rows, grid),
                                  _numpy(acc, arena, blocks, rows))
    # Chained: the checksum so far comes in, and pads past the arena are
    # never read.
    again = _call(_numpy(acc, arena, blocks, rows), arena, blocks[::-1],
                  rows, grid, pad=PAD)
    np.testing.assert_array_equal(again, _numpy(acc, arena, blocks * 2, rows))


@pytest.mark.parametrize("rows, blocks, grid", [
    (72, [4], 32),
    (72, [7, 1, 7], 48),                  # ends mid-step, many pad steps
    (WHOLE, [3, 0], 9),                   # one a step: 7 pad steps
])
def test_padded_steps_do_not_count(rows, blocks, grid):
    arena = _arena(1)
    acc = np.zeros((8, 128), np.int32)
    exact = _call(acc, arena, blocks, rows, len(blocks))
    np.testing.assert_array_equal(_call(acc, arena, blocks, rows, grid),
                                  exact)
    np.testing.assert_array_equal(
        _call(acc, arena, blocks, rows, grid, pad=PAD), exact)


@pytest.mark.parametrize("block_bytes", [4096, 36864, 1 << 20, STEP_BYTES,
                                         STEP_BYTES + 1, 3 * STEP_BYTES])
def test_blocks_per_step_follow_the_block_bytes(block_bytes):
    k = blocks_per_step(block_bytes)
    assert k >= 1 and k & (k - 1) == 0
    assert k == 1 or k * block_bytes <= STEP_BYTES
    assert 2 * k * block_bytes > STEP_BYTES


@pytest.mark.parametrize("rows, entries", [(72, 256), (72, 250),
                                           (WHOLE, 16), (8, 48)])
def test_the_grid_has_one_step_per_k_entries(rows, entries):
    k = blocks_per_step(rows * ROW_BYTES)
    jaxpr = jax.make_jaxpr(lambda c, t, a, x: rst_gather(
        c, t, a, x, block_rows=rows))(
        jnp.ones((1,), jnp.int32), jnp.zeros((entries,), jnp.int32),
        jnp.zeros((8, 128), jnp.int32), jnp.zeros((ROWS, 128), jnp.int32))
    grids = [e.params["grid_mapping"].grid for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert grids == [(-(-entries // k),)]


def _eqns(jaxpr):
    """Every equation of `jaxpr`, those of its sub-jaxprs too."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


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


def test_a_step_counts_its_calls_and_pads(monkeypatch):
    dep = deployment("deepseek-v2-lite-smoke")
    contexts = (300, 700, 2048, 256)
    calls = dep.plan(4, contexts, 9)
    opened = []
    span = ops.spans.span

    def recording_span(name, **stats):
        opened.append((name, stats))
        return span(name, **stats)
    monkeypatch.setattr(ops.spans, "span", recording_span)
    sample = ops.measure_gather_bandwidth(GatherStep(dep.name, 4, contexts,
                                                     9))
    # Each call's span says how many of its blocks a grid step reads.
    assert [s["per_step"] for name, s in opened
            if name == "repro.ops.gather"] == \
        [blocks_per_step(c.block_bytes) for c in calls]
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
