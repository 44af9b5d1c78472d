"""RST address-stream generation (paper Eq. 1), host- and device-side.

The address computation is deliberately trivial — `A + (i*S) % W` — because
the paper's engine computes it "with simple arithmetic, which in turn leads
to fewer FPGA resources and potentially higher frequency".  On TPU the same
property matters for a different reason: the index map must be cheap scalar
arithmetic so the Pallas grid pipeline can prefetch the next block while the
current one is in flight.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.core.params import RSTParams


def addresses_np(p: RSTParams, count: int | None = None) -> np.ndarray:
    """First `count` (default: one period, capped at N) transaction addresses."""
    if count is None:
        count = min(p.n, p.period)
    i = np.arange(count, dtype=np.int64)
    return p.a + (i * p.s) % p.w


def addresses_jnp(p: RSTParams, count: int) -> "jax.Array":
    import jax.numpy as jnp  # deferred: keeps the sim path jax-free
    i = jnp.arange(count, dtype=jnp.int64)
    return p.a + (i * p.s) % p.w


def block_params(p: RSTParams, block_bytes: int) -> Tuple[int, int, int]:
    """Translate byte-level RST params into Pallas block-index terms.

    Returns (stride_blocks, wset_blocks, base_block) such that the block
    index of transaction i is `base_block + (i * stride_blocks) % wset_blocks`
    when S >= block_bytes, matching Eq. 1 at block granularity.  These three
    integers are exactly what we feed the kernel through scalar prefetch.
    """
    if block_bytes <= 0 or block_bytes & (block_bytes - 1):
        raise ValueError(f"block_bytes must be a power of 2, got {block_bytes}")
    stride_blocks = max(1, p.s // block_bytes)
    wset_blocks = max(1, p.w // block_bytes)
    base_block = p.a // block_bytes
    return stride_blocks, wset_blocks, base_block


def checksum_ref(data: np.ndarray, p: RSTParams, elem_bytes: int) -> np.ndarray:
    """Oracle for the read-engine checksum: sum of every element each burst
    touches, over all N transactions (with wraparound repeats).

    `data` is the flat working buffer; the engine reads B bytes at each
    address T[i] and accumulates.  Used to validate the Pallas kernels.
    """
    flat = np.asarray(data).reshape(-1)
    epb = p.b // elem_bytes                      # elements per burst
    total = np.zeros((), dtype=np.float64)
    addrs = p.a + (np.arange(p.n, dtype=np.int64) * p.s) % p.w
    starts = addrs // elem_bytes
    for st in starts:
        total += flat[st:st + epb].astype(np.float64).sum()
    return total
