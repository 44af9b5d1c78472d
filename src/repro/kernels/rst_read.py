"""RST read engine as a Pallas TPU kernel (paper Sec. III-C-1, read module).

One grid step = one RST transaction: the Pallas pipeline DMAs a
``(burst_rows, 128)`` tile from HBM into VMEM at block index
``base + (i * stride) % wset`` (Eq. 1 at tile granularity) and the kernel
body only accumulates an elementwise checksum — a single VPU add — so the
engine is DMA-bound and never the bottleneck, the paper's design requirement
for the hardware component.

Runtime parameterization (paper challenge C2) is preserved through scalar
prefetch: ``(stride_blocks, wset_blocks, base_block, n_txns)`` arrive as a
scalar operand consumed by the BlockSpec index map, so a single compiled
kernel serves every (N <= grid, S, W, A) without recompilation.  Only the
burst size B (the tile shape) is compile-time, because TPU tile shapes are
static — see DESIGN.md §2.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128          # TPU lane width
SUBLANE = 8         # minimum sublane tile for f32


def interpret_mode() -> bool:
    """Whether the RST kernels run in Pallas interpret mode.

    The platform decides, in this one place: compiled on a TPU, where the
    wall-clock number is an HBM measurement, and interpreted everywhere
    else, where the kernels validate correctness only.  No caller can ask
    for the interpreter on a TPU.
    """
    return jax.default_backend() != "tpu"


def _index_map(i, params_ref):
    """Block index of transaction i: base + (i * stride) mod wset.

    Transactions past n revisit the last real block (cheap, pipelined) and
    are excluded from the checksum by the `pl.when` gate in the body.
    """
    stride, wset, base, n = (params_ref[0], params_ref[1], params_ref[2],
                             params_ref[3])
    i_eff = jnp.minimum(i, n - 1)
    return base + (i_eff * stride) % wset, 0


def _rst_read_kernel(params_ref, buf_ref, out_ref, acc_ref):
    i = pl.program_id(0)
    n = params_ref[3]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n)
    def _accumulate():
        acc_ref[...] += buf_ref[...].astype(jnp.float32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("grid_txns", "burst_rows"))
def rst_read(params: jax.Array, buf: jax.Array, *, grid_txns: int,
             burst_rows: int = SUBLANE) -> jax.Array:
    """Run the RST read engine over `buf`.

    Args:
      params: int32[4] = (stride_blocks, wset_blocks, base_block, n_txns);
        blocks are `(burst_rows, LANE)` tiles.  n_txns <= grid_txns.
      buf: the working buffer, shape (rows, LANE) with rows % burst_rows == 0.
      grid_txns: static grid size (max transactions of this engine image).
      burst_rows: rows per burst tile; burst bytes = burst_rows*LANE*itemsize.

    Returns:
      float32[burst_rows, LANE] elementwise checksum of every tile read.
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
        out_specs=pl.BlockSpec((burst_rows, LANE), lambda i, p: (0, 0)),
        scratch_shapes=[pltpu.VMEM((burst_rows, LANE), jnp.float32)],
    )
    return pl.pallas_call(
        _rst_read_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((burst_rows, LANE), jnp.float32),
        interpret=interpret_mode(),
    )(params, buf)
