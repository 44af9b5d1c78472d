"""Plain reference for the RST write engine: what a write stream leaves in
its working buffer.

An engine issues ``n`` write transactions; transaction ``i`` fills the
``B``-byte burst at byte address ``A + (i * S) mod W`` (Shuhai Eq. 1) with
``float32(i + 1)``, and a tile written again holds the last write.  The
buffer is ``A + W`` bytes of float32 words, word ``f`` holding its index,
counted in float32, mod the configuration's modulus by the device's own
float32 remainder (``references/rst_checksum.py``, whose ``burst_values``
builds it).  So after the stream, tile ``t`` (``B`` bytes) holds
``float32(max{i < n : A/B + (i * S/B mod W/B) = t} + 1)`` in every word
when some transaction wrote it, and else its original content.

Two things are compared with a call's result:

* the first tile, which the measurer hands back (8 rows of 128 words);
* the whole buffer after the timed call, as one sum a tile: the tile's
  words taken as uint32 bit patterns and added mod 2^32, exact and
  independent of order, so a write that lands on the wrong tile, carries
  the wrong value, or is left out changes some tile's sum.
"""
from __future__ import annotations

import functools

import numpy as np

from bench.references import rst_checksum

WORD_BYTES = 4


def last_writers(call: dict) -> np.ndarray:
    """For each tile of the buffer, the last transaction that wrote it, or
    -1 when none did."""
    b = int(call["b"])
    i = np.arange(int(call["n"]), dtype=np.int64)
    tile = call["a"] // b + (i * (call["s"] // b)) % (call["w"] // b)
    last = np.full((call["a"] + call["w"]) // b, -1, np.int64)
    np.maximum.at(last, tile, i)
    return last


def _values(last: np.ndarray, dtype) -> np.ndarray:
    """float32(i + 1) for each writer `i`, held in `dtype`."""
    return (last + 1).astype(np.float32).astype(dtype).astype(np.float32)


def first_tile(call: dict, config: dict, dtype=np.float32) -> np.ndarray:
    """The (8, 128) words the call should have returned, in `dtype`."""
    words = int(call["b"]) // WORD_BYTES
    last = last_writers(call)[0]
    if last >= 0:
        tile = np.full(words, _values(np.array([last]), dtype)[0])
    else:
        tile = rst_checksum.burst_values(np.array([0]), words,
                                         config["buffer_modulus"])[0]
    return tile.astype(np.float32).reshape(-1, 128)


@functools.lru_cache(maxsize=4)
def original_sums(buffer_bytes: int, b: int, modulus: int) -> np.ndarray:
    """Each tile's sum, mod 2^32, of the buffer as built, before a write."""
    words = b // WORD_BYTES
    first = np.arange(buffer_bytes // b, dtype=np.int64) * words
    values = rst_checksum.burst_values(first, words, modulus)
    return np.add.reduce(values.view(np.uint32), axis=1, dtype=np.uint32)


def tile_sums(call: dict, config: dict, dtype=np.float32) -> np.ndarray:
    """Each tile's sum, mod 2^32, of the buffer the call should leave, the
    written values held in `dtype`."""
    b = int(call["b"])
    last = last_writers(call)
    written = last >= 0
    bits = _values(last[written], dtype).view(np.uint32).astype(np.uint64)
    out = original_sums(call["a"] + call["w"], b,
                        config["buffer_modulus"]).copy()
    out[written] = (bits * (b // WORD_BYTES)) % (1 << 32)
    return out


def gap(got, want) -> float:
    """Largest elementwise gap, as a share of the largest expected value."""
    got = np.asarray(got, np.float64).reshape(-1)
    want = np.asarray(want, np.float64).reshape(-1)
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1.0))


def sums_gap(got, want) -> float:
    """The share of the buffer's tiles whose sum is not the reference's."""
    got = np.asarray(got).reshape(-1)
    if got.shape != want.shape:
        return float("inf")
    return float(np.mean(got.view(np.uint32) != want))


def control(calls: list, config: dict) -> list:
    """The calls with the reference's buffer, its written values held in
    the next lower precision (bfloat16), in place of the kernel's: the
    control that must fail."""
    import ml_dtypes
    return [dict(c, checksum=first_tile(c, config, ml_dtypes.bfloat16),
                 tile_sums=tile_sums(c, config, ml_dtypes.bfloat16)
                 .view(np.int32))
            for c in calls]


def compare(calls: list, config: dict) -> dict:
    """The widest gap over the sampled calls, of the first tile or of the
    share of tiles whose sum is off, and the calls whose byte count is not
    n x B."""
    worst = max((max(gap(c["checksum"], first_tile(c, config)),
                     sums_gap(c["tile_sums"], tile_sums(c, config)))
                 for c in calls), default=0.0)
    bytes_off = sum(int(c["bytes"] != c["n"] * c["b"]) for c in calls)
    return {"checksum_gap": worst, "bytes_mismatch": float(bytes_off)}
