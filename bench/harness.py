"""The harness: one cell, once per process.

It finds the cell in ``BENCHMARK.json``, loads the files that belong to it
by name, checks the device, warms up every shape the cell's traffic uses,
serves the traffic in a closed loop with one client for the measured
window, reads the metrics, checks the answers against the plain reference
and prints the result.  Nothing here knows a particular cell.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import trace_reduce

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# JAX's persistent compilation cache: a fixed directory inside the
# checkout, so only the first run of a cell in a checkout compiles.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class CellError(Exception):
    """The cell cannot run here; the process exits non-zero, with no result."""


# ------------------------------------------------------------ discovery
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The module ``<bench_dir>/<kind>/<name>.py``, found by name."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise CellError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str = BENCH_DIR

    @classmethod
    def load(cls, name: str, root: str = ROOT) -> "Cell":
        spec = load_json(os.path.join(root, "BENCHMARK.json"))
        work = {w["name"]: w for w in spec["workloads"]}.get(name)
        if work is None:
            raise CellError(f"no workload {name!r} in BENCHMARK.json; have "
                            f"{sorted(w['name'] for w in spec['workloads'])}")
        conf = {c["name"]: c for c in spec["configs"]}[work["config"]]
        bench_dir = os.path.join(root, "bench")
        config = load_json(os.path.join(root, conf["file"]))
        traffic = load_json(os.path.join(bench_dir, "traffic",
                                         f"{work['traffic']}.json"))

        def mine(metrics):
            return [m for m in metrics
                    if name in m.get("workloads", [name])]
        return cls(name=name, chips=int(work["chips"]), config=config,
                   traffic=traffic, end_to_end=mine(spec["end_to_end"]),
                   per_layer=mine(spec["per_layer"]), bench_dir=bench_dir)


# --------------------------------------------------------------- device
def peaks_for(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    chip = table["chips"].get(device_kind)
    if chip is None:
        raise CellError(f"device kind {device_kind!r} has no peaks in "
                        f"bench/peaks.json; known: {sorted(table['chips'])}")
    return chip


def tpu_devices(chips: int) -> list:
    """The TPU devices JAX finds; any other platform, or too few chips,
    ends the run with no result.  There is no CPU fallback."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise CellError(f"JAX found no accelerator: {e}") from None
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else None
        raise CellError(f"JAX found no TPU (platform {platform!r}); the "
                        f"benchmark runs on TPUs only")
    if len(devices) < chips:
        raise CellError(f"the cell needs {chips} chips, JAX found "
                        f"{len(devices)}")
    return devices


def setup_compile_cache(path: str = CACHE_DIR) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# --------------------------------------------------------------- the run
@dataclasses.dataclass
class Record:
    """One request of the window: host-clock start and end, and what the
    entry made of its answer."""

    start: float
    end: float
    answer: dict


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    setup_s: float
    window_s: float
    records: List[Record]
    peaks: dict
    trace: Optional[trace_reduce.Trace] = None

    @property
    def latencies_s(self) -> List[float]:
        return [r.end - r.start for r in self.records]


def serve_window(entry, requests, seconds: float) -> List[Record]:
    """Closed loop, one client: the next request goes out when the last
    answer is in, until the window has run `seconds`.  Each request is a
    span in the profiler's trace, when one is recorded."""
    import jax
    records: List[Record] = []
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        req = next(requests)
        with jax.profiler.TraceAnnotation(trace_reduce.REQUEST_SPAN):
            t0 = time.perf_counter()
            answer = entry.serve(req)
            t1 = time.perf_counter()
        records.append(Record(t0, t1, answer))
        if t1 >= deadline:
            return records


def read_metrics(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    """Each metric's reader, found by the metric's name; a reader that
    finds nothing returns None and the metric is left out."""
    out = {}
    for m in metrics:
        value = load_module("metrics", m["name"], run.cell.bench_dir).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def phases(marks: List[tuple]) -> str:
    """Seconds from each mark of set-up to the next."""
    return ", ".join(f"{name} {t - marks[i][1]:.3f} s"
                     for i, (name, t) in enumerate(marks[1:]))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             started: float, devices: list, peaks: dict,
             log=sys.stderr, marks: Optional[List[tuple]] = None) -> dict:
    """Everything after the device check: the result object."""
    from bench import compile_watch, traffic
    marks = list(marks or [("start", started)])
    watch = compile_watch.CompileWatch()
    entry = None
    try:
        entry = load_module("entries", cell.traffic["entry"],
                            cell.bench_dir).Entry(cell.config, cell.traffic)
        marks.append(("entry", time.perf_counter()))
        entry.warm(seed)
        marks.append(("warm-up", time.perf_counter()))
        requests = traffic.requests(cell.traffic, seed)
        tracer = None
        if trace:
            tracer = trace_reduce.Tracer()
            tracer.start()
        setup_s = time.perf_counter() - started
        w0 = time.perf_counter()
        records = serve_window(entry, requests, seconds)
        w1 = time.perf_counter()
        if tracer is not None:
            tracer.stop()
        in_window = watch.counts(w0, w1)
        print(f"compiles in window: {sum(in_window.values())} "
              f"({json.dumps(in_window, sort_keys=True)}); set-up compile "
              f"{watch.seconds():.3f} s of {setup_s:.3f} s ({phases(marks)})",
              file=log, flush=True)
        used = devices[:cell.chips]
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": memory_peak_bytes(used)}
        run = Run(cell=cell, setup_s=setup_s,
                  window_s=records[-1].end - w0, records=records,
                  peaks=peaks)
        result = {}
        if tracer is not None:
            run.trace = tracer.reduce(len(used))
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            metrics = read_metrics(run, cell.per_layer)
            result["breakdown"] = run.trace.breakdown()
        else:
            metrics = read_metrics(run, cell.end_to_end)
        failed = sum(not r.answer["ok"] for r in records)
        checks = entry.check(records, np.random.default_rng([int(seed), 7]))
    finally:
        if entry is not None:
            entry.close()
        watch.close()
    checks["failed_requests"] = {"value": float(failed), "limit": 0.0}
    return {"correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "attempted": len(records), "failed": failed,
            "metrics": metrics, "device": device, **result,
            "compiles_in_window": in_window, "checks": checks}


def emit(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The compared numbers as the last lines of standard error, the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)


def main(argv: List[str], started: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    marks = [("start", started), ("python", time.perf_counter())]
    try:
        cell = Cell.load(args.workload)
        setup_compile_cache()
        marks.append(("jax import", time.perf_counter()))
        devices = tpu_devices(cell.chips)
        marks.append(("devices", time.perf_counter()))
        peaks = peaks_for(devices[0].device_kind)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          started, devices, peaks, marks=marks)
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    emit(result)
    return 0
