"""RST write engine as a Pallas TPU kernel (paper Sec. III-C-1, write module).

This is the pallas backend's write direction: `ops.measure_write_bandwidth`
wraps it for ``op="write"`` sweep points, and `ops.measure_duplex_bandwidth`
pairs it with the read engine for mixed read/write traffic — the same write
and duplex workloads the sim backend models with tWR / turnaround segments
(core/timing_model.py, DESIGN.md §7).

One grid step = one write transaction: fill the tile at block index
``base + (i * stride) % wset`` with a value derived from i.  The working
buffer is donated (input/output aliased) so tiles the traversal never
touches keep their previous contents — the same semantics as the AXI write
engine mutating DRAM in place.

Revisited tiles (N > W/S) are overwritten in transaction order, so "last
write wins" — property-tested against the replay oracle in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rst_read import LANE, SUBLANE, _index_map, interpret_mode


def _rst_write_kernel(params_ref, buf_ref, out_ref):
    del buf_ref  # aliased with out_ref; in-place update
    i = pl.program_id(0)
    n = params_ref[3]

    @pl.when(i < n)
    def _write():
        # Payload: transaction index + 1 (nonzero so untouched tiles are
        # distinguishable), cast to the buffer dtype.
        out_ref[...] = jnp.full_like(out_ref, (i + 1).astype(jnp.float32))


@functools.partial(
    jax.jit, static_argnames=("grid_txns", "burst_rows"),
    donate_argnums=(1,))
def rst_write(params: jax.Array, buf: jax.Array, *, grid_txns: int,
              burst_rows: int = SUBLANE) -> jax.Array:
    """Run the RST write engine over `buf` (donated), returning the new buf.

    params: int32[4] = (stride_blocks, wset_blocks, base_block, n_txns).
    """
    rows, lane = buf.shape
    if lane != LANE:
        raise ValueError(f"buffer minor dim must be {LANE}, got {lane}")
    if rows % burst_rows:
        raise ValueError(f"rows ({rows}) % burst_rows ({burst_rows}) != 0")
    if burst_rows % SUBLANE:
        raise ValueError(f"burst_rows must be a multiple of {SUBLANE}")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_txns,),
        in_specs=[pl.BlockSpec((burst_rows, LANE), _index_map)],
        out_specs=pl.BlockSpec((burst_rows, LANE), _index_map),
    )
    return pl.pallas_call(
        _rst_write_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        input_output_aliases={1: 0},
        interpret=interpret_mode(),
    )(params, buf)
