"""The harness end to end, on the CPU: it refuses to measure anywhere but
on a TPU, and past the device check every cell runs, reads its metrics and
decides ``correct``."""
import io
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_cells import CELLS, ROOT, harness, run, tiny


def _bench(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rst.fig7_read",
         "--seed", "3", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_platform_that_is_not_a_tpu():
    proc = _bench(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_refuses_to_run_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench(tmp_path, "--trace", "1")
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_unknown_workload_and_device_kind_are_errors():
    with pytest.raises(harness.CellError):
        harness.Cell.load("no.such_cell", ROOT)
    with pytest.raises(harness.CellError, match="no peaks"):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_cpu_devices_are_refused():
    with pytest.raises(harness.CellError, match="no TPU"):
        harness.tpu_devices(1)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    c = tiny(cell)
    res = run(c, seconds=1.0)      # a tail needs two requests at least
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    want = {m["name"] for m in c.end_to_end}
    assert set(res["metrics"]) == want
    assert "setup_s" in want and len(want) >= 2
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert sum(res["compiles_in_window"].values()) == 0
    assert list(res)[-1] == "checks"
    out, err = io.StringIO(), io.StringIO()
    harness.emit(res, out, err)
    assert json.loads(out.getvalue().splitlines()[-1])["correct"] is True
    assert err.getvalue().splitlines()[-1].startswith("check ")


def test_traced_run_reports_window_and_breakdown():
    res = run(tiny("grid.xp_default"), trace=True)
    assert res["correct"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # On the CPU no device plane is traced, so the device metrics stay out.
    assert "device_idle_share.grid" not in res["metrics"]
