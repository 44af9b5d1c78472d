"""The main path's device programs compile for a described TPU v5e.

Nothing runs: the TPU compiler installed with jaxlib compiles each
program for one chip of a described ``v5e:2x2`` topology, and refuses
what the chip would refuse (unaligned slices, too much VMEM, a program
larger than HBM).  Sizes are the ones the chip runs: a 1 GiB working
buffer, far above the 128 MiB of VMEM, traversed by 2^16-step grids, and
the grid tier's lane batches at the --grid ladder's widths under x64.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the worker that runs
this file keeps it until it exits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import HBM, RSTParams, get_mapping, timing_jax
from repro.core.decode_traffic import deployment
from repro.kernels import ops
from repro.kernels.rst_contend import rst_contend_mix_read, rst_contend_read
from repro.kernels.rst_gather import rst_gather
from repro.kernels.rst_read import LANE, SUBLANE, rst_read
from repro.kernels.rst_write import rst_write

BUF_ROWS = (1 << 30) // (LANE * 4)      # 1 GiB of f32
GRID = 1 << 16
ENGINES = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # The kernels pick compiled mode from the platform; this process is on
    # the CPU, so steer that one decision to the chip being described.
    # A compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not lowered"


def test_rst_read_compiles(one_chip):
    compiled = rst_read.lower(
        _sds((4,), jnp.int32, one_chip),
        _sds((BUF_ROWS, LANE), jnp.float32, one_chip),
        grid_txns=GRID, burst_rows=SUBLANE).compile()
    _assert_kernel(compiled)


def test_rst_write_compiles(one_chip):
    compiled = rst_write.lower(
        _sds((4,), jnp.int32, one_chip),
        _sds((BUF_ROWS, LANE), jnp.float32, one_chip),
        grid_txns=GRID, burst_rows=SUBLANE).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("burst_beats", [1, 16])
def test_rst_contend_read_compiles(one_chip, burst_beats):
    compiled = rst_contend_read.lower(
        _sds((6,), jnp.int32, one_chip),
        _sds((BUF_ROWS, LANE), jnp.float32, one_chip),
        grid_txns=GRID, num_engines=ENGINES, burst_beats=burst_beats,
        burst_rows=SUBLANE).compile()
    _assert_kernel(compiled)


def test_rst_contend_mix_read_compiles(one_chip):
    compiled = rst_contend_mix_read.lower(
        _sds((ENGINES + 1, 4), jnp.int32, one_chip),
        _sds((BUF_ROWS, LANE), jnp.float32, one_chip),
        grid_txns=GRID, num_engines=ENGINES, burst_beats=1,
        burst_rows=SUBLANE).compile()
    _assert_kernel(compiled)


# The decode cell's arena: 10.7 GB of pages and weights, read in 36 KiB
# pages by a layer's longest page table (16 x 4,224 entries) and its
# smallest (one bucket), in 1 MiB weight blocks, and in 4 KiB tiles (the
# most blocks and DMAs a grid step).  No view may make the compiler copy
# the arena.
@pytest.mark.parametrize("block_rows, grid", [("page_rows", 67584),
                                              ("page_rows", 256),
                                              ("weight_rows", 256),
                                              (SUBLANE, 256)])
def test_rst_gather_compiles_over_the_decode_arena(one_chip, block_rows,
                                                   grid):
    dep = deployment("deepseek-v2-lite-ep8")
    if isinstance(block_rows, str):
        block_rows = getattr(dep, block_rows)
    compiled = rst_gather.lower(
        _sds((1,), jnp.int32, one_chip), _sds((grid,), jnp.int32, one_chip),
        _sds((SUBLANE, LANE), jnp.int32, one_chip),
        _sds((dep.arena_rows, LANE), jnp.int32, one_chip),
        block_rows=block_rows).compile()
    _assert_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_decode_arena_is_built_without_a_copy(one_chip):
    dep = deployment("deepseek-v2-lite-ep8")
    compiled = ops._arena_content.lower(
        _sds((2,), jnp.uint32, one_chip), rows=dep.arena_rows).compile()
    stats = compiled.memory_analysis()
    assert stats.output_size_in_bytes == dep.arena_rows * LANE * 4
    assert stats.temp_size_in_bytes < 1 << 20


def _grid_kernel_compiles(one_chip, unit, route, lanes):
    row = timing_jax._unit_row(HBM, unit)
    assert timing_jax._route(row) == route
    nseg = len(row["seg"])
    cols = timing_jax._batch_columns(HBM, [row] * lanes, nseg)
    if route == "periodic":
        cap = 2 * timing_jax._WIN
    else:
        cap = timing_jax._bucket(row["txns"] * row["eng"] * row["cmds"],
                                 timing_jax._WIN)
    kernel = timing_jax._grid_kernel(HBM, cap, nseg, route == "periodic")
    with jax.enable_x64(True):
        shapes = {k: _sds(v.shape, v.dtype, one_chip)
                  for k, v in cols.items()}
        compiled = kernel.lower(shapes).compile()
    out = compiled.as_text()
    assert "f64" in out or "s64" in out     # the x64 program, not f32
    return compiled


def test_grid_periodic_kernel_compiles(one_chip):
    # A --grid ladder lane: n=2^17, exactly periodic; 8,192 lanes is the
    # ladder's unit batch rounded to its pow2 bucket.
    p = RSTParams(n=1 << 17, b=32, s=1024, w=1024 * 4)
    _grid_kernel_compiles(
        one_chip, (p, get_mapping(HBM), "read", 4, "burst", 4),
        "periodic", 8192)


def test_grid_full_kernel_compiles(one_chip):
    # A non-periodic lane (exclusive grants) in the 1,024-command bucket;
    # 2,048 lanes is one lane chunk of that bucket.  Under x64 each grid
    # kernel takes 20-60 s to compile for the chip, whatever its size.
    p = RSTParams(n=256, b=64, s=192, w=192 * 1000)
    unit = (p, get_mapping(HBM), "write", 2, "exclusive", 1)
    compiled = _grid_kernel_compiles(one_chip, unit, "full", 2048)
    assert np.isfinite(compiled.memory_analysis().temp_size_in_bytes)
