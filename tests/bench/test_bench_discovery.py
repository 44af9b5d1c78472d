"""A cell, a configuration, a traffic mix and a metric are added as new
files and entries alone: the harness finds each by its name, and no file
that was there changes."""
import hashlib
import json
import os
import shutil

from bench_cells import ROOT, SEED, harness, run


def _digests(root):
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            if "__pycache__" not in base:
                path = os.path.join(base, f)
                with open(path, "rb") as fh:
                    out[os.path.relpath(path, root)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path / "bench")

    # A configuration: the RST engines with a smaller check sample.
    with open(tmp_path / "bench" / "configs" / "tpu_v5e_hbm_rst.json") as f:
        config = json.load(f)
    config.update(name="tpu_v5e_hbm_rst_small", check_calls=1)
    with open(tmp_path / "bench" / "configs" / "small.json", "w") as f:
        json.dump(config, f)
    # A traffic mix for an entry the harness already has.
    mix = {"entry": "sweep_contention",
           "request": {"n": 16, "w": 1 << 20, "engines": 2,
                       "arbitrations": [["burst", 4]]},
           "draws": {"s": {"choice": [4096, 8192]}},
           "warm": [{"s": 4096}]}
    with open(tmp_path / "bench" / "traffic" / "duo.json", "w") as f:
        json.dump(mix, f)
    # A per-layer metric with a reader of its own.
    with open(tmp_path / "bench" / "metrics" / "points_seen.py", "w") as f:
        f.write("def read(run):\n"
                "    return sum(r.answer['points'] for r in run.records)\n")

    with open(tmp_path / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tpu_v5e_hbm_rst_small",
                            "source": "test", "file": "bench/configs/small.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "rst.duo", "config":
                              "tpu_v5e_hbm_rst_small", "traffic": "duo",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "rst_gbps":
            m["workloads"].append("rst.duo")
    spec["per_layer"].append({"name": "points_seen", "unit": "points",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "rst_gbps",
                              "workloads": ["rst.duo"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)

    cell = harness.Cell.load("rst.duo", str(tmp_path))
    assert cell.config["check_calls"] == 1
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "rst_gbps"]
    assert [m["name"] for m in cell.per_layer] == ["points_seen"]
    res = run(cell, seed=SEED, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["points_seen"]["value"] >= 1
    res = run(cell, seed=SEED)
    assert set(res["metrics"]) == {"setup_s", "rst_gbps"}

    after = _digests(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/small.json", "traffic/duo.json", "metrics/points_seen.py"}
