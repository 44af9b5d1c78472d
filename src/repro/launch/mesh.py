"""Production mesh construction + grid-axis sharding.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (tests see one CPU device; only the dry-run process
sets the 512-device XLA flag before its first jax import).

The grid helpers (`grid_mesh`, `grid_padding`, `shard_grid`) carry the
timing-model grid evaluator (core/timing_jax.py): a 1-D ``"grid"`` mesh
over every visible device, with *explicit* pad-or-error divisibility
handling — a grid whose leading axis doesn't divide the device count is
padded by repeating its last row (and the caller told by how much), or
rejected with the exact remainder, never silently truncated or
implicitly reshaped.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for elastic rungs / tests; every axis Auto-typed."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def data_axes(mesh) -> tuple:
    """Mesh axes that carry data parallelism (pod + data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def dp_degree(mesh) -> int:
    out = 1
    for a in data_axes(mesh):
        out *= mesh.shape[a]
    return out


# ---------------------------------------------------------------- grid axis
def grid_mesh(num_devices: int | None = None):
    """1-D mesh over the ``"grid"`` axis for batched grid evaluation.

    Uses every visible device by default; pass `num_devices` to restrict
    (must not exceed the visible count — jax.make_mesh validates).
    """
    n = jax.device_count() if num_devices is None else int(num_devices)
    if n < 1:
        raise ValueError(f"num_devices must be >= 1, got {n}")
    return make_mesh((n,), ("grid",))


def grid_padding(n: int, parts: int, *, pad: bool = True) -> int:
    """Rows to append so `n` divides into `parts` equal shards.

    Returns 0 when already divisible.  With ``pad=False`` a remainder is
    an error carrying the exact numbers — the explicit contract that
    replaces silent truncation/implicit reshapes.
    """
    if parts < 1:
        raise ValueError(f"parts must be >= 1, got {parts}")
    if n < 1:
        raise ValueError(f"grid size must be >= 1, got {n}")
    rem = n % parts
    if rem == 0:
        return 0
    if not pad:
        raise ValueError(
            f"grid size {n} does not divide over {parts} devices "
            f"(remainder {rem}); pass pad=True to pad with "
            f"{parts - rem} repeated rows, or resize the grid")
    return parts - rem


def shard_grid(array, mesh, *, axis: str = "grid", pad: bool = True):
    """Shard `array`'s leading dimension across `mesh`'s `axis`.

    Returns ``(sharded, extra)`` where `extra` is the number of padding
    rows appended (repeats of the last row) to make the leading
    dimension divide the axis size; callers slice ``[:-extra]`` (or
    ``[:n]``) off any result computed from the sharded operand.  With
    ``pad=False`` a non-divisible leading dimension raises instead —
    never a silent truncation.
    """
    arr = np.asarray(array)
    if arr.ndim == 0:
        raise ValueError("shard_grid needs at least one array dimension")
    parts = int(mesh.shape[axis])
    extra = grid_padding(arr.shape[0], parts, pad=pad)
    if extra:
        arr = np.concatenate([arr, np.repeat(arr[-1:], extra, axis=0)])
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(axis))
    return jax.device_put(arr, sharding), extra
