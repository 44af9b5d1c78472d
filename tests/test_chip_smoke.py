"""chip_smoke.py off the chip: the refusal paths, and every phase at a
tiny size with the kernels in interpret mode (the CPU rehearsal of the
chip run)."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_refuses_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""        # no result line


def test_refuses_alone_outside_the_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_tiny_phases_pass_on_the_interpreter(capsys):
    chip_smoke.run(chip_smoke.TINY)
    out = capsys.readouterr().out
    assert out.count("] ok: set-up (compile)") == 4
    for kernel in ("rst_read", "rst_write", "rst_contend_read",
                   "rst_contend_mix_read"):
        assert f"  {kernel}: " in out and "equal the kernels/ref.py" in out


SHARDED = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{root!r}, {src!r}]
import chip_smoke
chip_smoke.run(chip_smoke.TINY, chips=4)
print("SHARDED_SMOKE_OK")
"""


def test_tiny_sharded_grid_phase_on_four_host_devices():
    # The --chips 4 phase on four virtual CPU devices, in a subprocess so
    # this process keeps seeing one device.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         SHARDED.format(root=str(ROOT), src=str(ROOT / "src"))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "outputs on 4 device(s)" in out.stdout
    assert "SHARDED_SMOKE_OK" in out.stdout
