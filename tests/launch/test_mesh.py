"""Grid-axis sharding helpers: explicit pad-or-error divisibility.

Regression tests for the remainder case `launch/mesh.py` used to leave to
implicit reshapes: a grid whose leading axis does not divide the device
count must either be padded by an explicitly-reported number of repeated
rows, or rejected with the exact remainder — never silently truncated.
"""
import os
from pathlib import Path

import numpy as np
import pytest

from repro.launch.mesh import grid_mesh, grid_padding, shard_grid

ROOT = Path(__file__).resolve().parents[2]


class TestGridPadding:
    def test_divisible_needs_no_padding(self):
        assert grid_padding(16, 8) == 0
        assert grid_padding(8, 8) == 0
        assert grid_padding(5, 1) == 0

    def test_remainder_pad_count(self):
        # 27 rows over 8 devices: remainder 3, so 5 repeated rows pad it.
        assert grid_padding(27, 8) == 5
        assert grid_padding(9, 8) == 7
        assert grid_padding(1, 8) == 7

    def test_remainder_errors_when_pad_disabled(self):
        with pytest.raises(ValueError) as exc:
            grid_padding(27, 8, pad=False)
        # The error carries the exact numbers, not a generic complaint.
        msg = str(exc.value)
        assert "27" in msg and "8" in msg
        assert "remainder 3" in msg
        assert "5 repeated rows" in msg

    def test_rejects_degenerate_inputs(self):
        with pytest.raises(ValueError):
            grid_padding(0, 8)
        with pytest.raises(ValueError):
            grid_padding(8, 0)


class TestShardGrid:
    def _mesh(self):
        return grid_mesh(1)   # tests see exactly one device

    def test_round_trips_divisible_array(self):
        arr = np.arange(12, dtype=np.float64).reshape(6, 2)
        sharded, extra = shard_grid(arr, self._mesh())
        assert extra == 0
        np.testing.assert_array_equal(np.asarray(sharded), arr)

    def test_pads_by_repeating_last_row(self):
        mesh = grid_mesh(1)
        arr = np.arange(6).reshape(3, 2)
        # Single device: everything divides; exercise the pad arithmetic
        # through grid_padding directly plus a 1-device identity check.
        sharded, extra = shard_grid(arr, mesh)
        assert extra == 0
        np.testing.assert_array_equal(np.asarray(sharded), arr)

    def test_scalar_rejected(self):
        with pytest.raises(ValueError):
            shard_grid(np.float64(3.0), self._mesh())

    def test_pad_false_is_strict(self):
        # grid_padding is the single divisibility gate shard_grid uses;
        # the strict path must surface its error unchanged.
        with pytest.raises(ValueError, match="remainder"):
            grid_padding(10, 8, pad=False)


MULTI_DEVICE_REMAINDER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from repro.launch.mesh import grid_mesh, grid_padding, shard_grid

assert jax.device_count() == 8
mesh = grid_mesh()
arr = np.arange(27 * 3, dtype=np.float64).reshape(27, 3)

# pad=True: 5 repeated last rows, value-preserving on the first 27.
sharded, extra = shard_grid(arr, mesh)
assert extra == grid_padding(27, 8) == 5
host = np.asarray(sharded)
assert host.shape == (32, 3)
np.testing.assert_array_equal(host[:27], arr)
np.testing.assert_array_equal(host[27:], np.repeat(arr[-1:], 5, axis=0))

# pad=False: the remainder is an error, never a truncation.
try:
    shard_grid(arr, mesh, pad=False)
except ValueError as e:
    assert "remainder 3" in str(e)
else:
    raise SystemExit("expected ValueError for 27 % 8 != 0")
print("REMAINDER_OK")
"""


def test_remainder_on_real_8_device_mesh():
    """The 27-rows-over-8-devices remainder case on a real multi-device
    mesh: padded shapes, preserved values, strict-mode error."""
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c", MULTI_DEVICE_REMAINDER],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": str(ROOT / "src"),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", str(ROOT)),
             "JAX_PLATFORMS": "cpu"},
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "REMAINDER_OK" in out.stdout
