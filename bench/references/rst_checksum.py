"""Plain reference for the RST read engines: the checksum a stream must give.

An engine issues ``n`` transactions; transaction ``i`` reads the ``B``-byte
burst at byte address ``A + (i * S) mod W`` (Shuhai Eq. 1).  With several
engines sharing one port, engine ``k`` owns the window that starts ``k * W``
bytes further on, and grants rotate over the engines in order, each grant
``g`` consecutive transactions of one engine (``g = 1`` is round robin).
The engine adds every burst it reads, element by element, into one
accumulator of the burst's shape: a running sum in float32, one burst after
the other in the order the port serves them.

The working buffer holds float32 values: element ``f`` (counted from the
start of the buffer) holds ``float32(f) mod m`` for the configuration's
modulus ``m``: the index counted in float32, which above 2^24 holds only
every 2nd, 4th, ... integer and rounds ``f`` to the nearest of them, then
reduced by the float32 remainder of the device the run is on.  A TPU's
float32 remainder is not exact for such large operands, so the reference
takes the remainder from the device, through ``jax.numpy`` (its own code,
not the program's), and everything else on the host.  So the reference
needs no buffer: it builds the bursts a stream reads from their addresses.

Sums of this size pass 2^24, where float32 stops holding every integer, so
the order of the additions matters.  The reference adds in the same order
in the same precision, and the checksum must then agree exactly.
"""
from __future__ import annotations

import numpy as np

ELEM_BYTES = 4            # float32
_CHUNK = 4096             # bursts added per block, to bound host memory


def grant_beats(arbitration: str, burst_beats: int, n: int) -> int:
    """Transactions per grant: 1 for round robin, the grant size for burst
    grants (never past the stream), the whole stream when exclusive."""
    if arbitration == "round_robin":
        return 1
    if arbitration == "burst":
        return max(1, min(int(burst_beats), n))
    if arbitration == "exclusive":
        return max(1, n)
    raise ValueError(f"unknown arbitration {arbitration!r}")


def burst_addresses(s: int, w: int, a: int, n: int, engines: int = 1,
                    grant: int = 1) -> np.ndarray:
    """Byte address of every burst read, in the order the port serves it."""
    t = np.arange(n, dtype=np.int64)
    own = a + (t * s) % w                               # one engine's stream
    rounds = -(-n // grant)
    pad = rounds * grant - n
    # (round, engine, beat): engine k reads its own window, k * W further on.
    per_engine = np.concatenate([own, np.full(pad, -1, np.int64)])
    grid = per_engine.reshape(rounds, 1, grant) + np.zeros(
        (1, engines, 1), np.int64)
    offsets = (np.arange(engines, dtype=np.int64) * w).reshape(1, engines, 1)
    order = np.where(grid >= 0, grid + offsets, -1).reshape(-1)
    return order[order >= 0]


def burst_values(first: np.ndarray, elems: int, modulus: int) -> np.ndarray:
    """The bursts whose first elements are `first`, one row each: every
    element's index counted in float32 on the host, then reduced mod
    `modulus` by the device's own float32 remainder."""
    import jax.numpy as jnp
    rows = []
    for lo in range(0, len(first), _CHUNK):
        flat = (first[lo:lo + _CHUNK, None]
                + np.arange(elems, dtype=np.int64)).astype(np.float32)
        rows.append(np.asarray(jnp.asarray(flat) % float(modulus)))
    return np.concatenate(rows) if rows else np.zeros((0, elems), np.float32)


def addresses(call: dict) -> np.ndarray:
    """Byte address of every burst one captured kernel call read."""
    n = int(call["n"])
    grant = grant_beats(call["arbitration"], call["burst_beats"], n)
    return burst_addresses(call["s"], call["w"], call["a"], n,
                           call["engines"], grant)


def table(addrs: np.ndarray, burst_bytes: int, modulus: int) -> tuple:
    """The distinct bursts at `addrs`: their first elements, in order, and
    their values, one row each."""
    elems = burst_bytes // ELEM_BYTES
    first = np.unique(addrs // ELEM_BYTES)          # flat index of element 0
    if np.any(first % elems):
        raise ValueError("bursts must start at a multiple of their size")
    return first, burst_values(first, elems, modulus)


def checksum(addrs: np.ndarray, burst_bytes: int, modulus: int,
             dtype=np.float32, bursts: tuple | None = None) -> np.ndarray:
    """Running elementwise sum, in `dtype`, of the bursts at `addrs`, taken
    from `bursts` (a `table` that holds them all) or else built here."""
    first, values = bursts or table(addrs, burst_bytes, modulus)
    row = np.searchsorted(first, addrs // ELEM_BYTES)
    acc = np.zeros(burst_bytes // ELEM_BYTES, dtype)
    for lo in range(0, len(row), _CHUNK):
        block = np.concatenate([acc[None],
                                values[row[lo:lo + _CHUNK]].astype(dtype)])
        acc = np.add.reduce(block, axis=0, dtype=dtype)
    return acc


def expected(call: dict, config: dict, dtype=np.float32,
             bursts: tuple | None = None) -> np.ndarray:
    """The checksum one captured kernel call should have returned."""
    return checksum(addresses(call), call["b"], config["buffer_modulus"],
                    dtype, bursts)


def sample_table(calls: list, config: dict) -> tuple | None:
    """One `table` of every burst that a sample of calls read: the device
    reduces each distinct burst once."""
    if not calls:
        return None
    b = {int(c["b"]) for c in calls}
    if len(b) != 1:
        raise ValueError(f"calls of several burst sizes: {sorted(b)}")
    return table(np.concatenate([addresses(c) for c in calls]), b.pop(),
                 config["buffer_modulus"])


def gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest elementwise gap, as a share of the largest expected value."""
    got = np.asarray(got, np.float64).reshape(-1)
    want = np.asarray(want, np.float64).reshape(-1)
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1.0))


def control(calls: list, config: dict) -> list:
    """The calls with the reference's checksum in the next lower precision
    (bfloat16) in place of the kernel's: the control that must fail."""
    import ml_dtypes
    bursts = sample_table(calls, config)
    return [dict(c, checksum=expected(c, config, ml_dtypes.bfloat16, bursts)
                 .astype(np.float32)) for c in calls]


def compare(calls: list, config: dict) -> dict:
    """The numbers compared for a sample of captured calls: the widest
    checksum gap and the calls whose byte count differs from the stream's."""
    worst = 0.0
    bytes_off = 0
    bursts = sample_table(calls, config)
    for c in calls:
        worst = max(worst, gap(c["checksum"], expected(c, config,
                                                       bursts=bursts)))
        bytes_off += int(c["bytes"] != c["n"] * c["b"] * c["engines"])
    return {"checksum_gap": worst, "bytes_mismatch": float(bytes_off)}
