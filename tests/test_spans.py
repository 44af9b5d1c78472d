"""The program's spans (``repro.spans``): cheap, bounded in number, named
``repro.*``, and absent from a process that never imported JAX."""
import ast
import collections
import os
import subprocess
import sys

import pytest

from repro import spans
from repro.core import RSTParams, Sweep, timing_jax
from repro.core.address_mapping import policies_for
from repro.core.hwspec import HBM
from repro.service import CampaignService, ExperimentRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


class Counting:
    """Stands in for ``repro.spans.span``: counts the spans opened."""

    def __init__(self):
        self.opened = []

    def __call__(self, name, **stats):
        self.opened.append((name, stats))
        return spans._OFF

    def names(self):
        return collections.Counter(name for name, _ in self.opened)


@pytest.fixture
def counting(monkeypatch):
    c = Counting()
    monkeypatch.setattr(spans, "span", c)
    return c


def _axes(policies, ops):
    """2 units (1 policy x 1 op x 2 engine counts) or 10 times as many;
    every lane on the full-expansion kernel."""
    return timing_jax.GridAxes(
        params=(RSTParams(n=4096, b=32, s=64, w=1 << 20),),
        policies=policies, ops=ops, num_engines=(1, 2))


SMALL = _axes((None,), ("read",))
LARGE = _axes((None,) + tuple(policies_for(HBM))[:4], ("read", "write"))


def _grid_request(strides, ops, placements):
    return ExperimentRequest.make(
        "grid_cross_product", "hbm", n=1024, strides=strides, ops=ops,
        engines=(1,), arbitrations=(("round_robin", 1),),
        placements=placements)


def _spans_of(counting, call):
    counting.opened.clear()
    out = call()
    return counting.names(), out


def test_evaluate_grid_opens_as_many_spans_for_ten_times_the_points(
        counting):
    small, g = _spans_of(counting, lambda: timing_jax.evaluate_grid(
        HBM, SMALL))
    large, G = _spans_of(counting, lambda: timing_jax.evaluate_grid(
        HBM, LARGE))
    assert G.size == 10 * g.size
    assert g.lanes_by_route.keys() == G.lanes_by_route.keys() == {"full"}
    assert small == large
    assert small["repro.grid.dispatch"] == 1


def test_evaluate_points_opens_as_many_spans_for_ten_times_the_points(
        counting):
    def reqs(axes):
        return [("cont", p.params, p.policy, p.op, p.num_engines,
                 p.arbitration, p.burst_beats, p.placement)
                for p in axes.sweep_points()]
    small, r = _spans_of(counting, lambda: timing_jax.evaluate_points(
        HBM, reqs(SMALL)))
    large, R = _spans_of(counting, lambda: timing_jax.evaluate_points(
        HBM, reqs(LARGE)))
    assert len(R) == 10 * len(r)
    assert small == large


def test_sweep_run_opens_as_many_spans_for_ten_times_the_points(counting):
    def sweep(axes):
        s = Sweep(HBM, "jaxgrid")
        for pt in axes.sweep_points():
            s.add_point(pt)
        return s.run()
    small, r = _spans_of(counting, lambda: sweep(SMALL))
    large, R = _spans_of(counting, lambda: sweep(LARGE))
    assert len(R) == 10 * len(r)
    assert small == large
    assert small["repro.sweep.run"] == small["repro.sweep.serve"] == 1


def test_submit_opens_as_many_spans_for_ten_times_the_points(counting):
    def submit(req):
        resp = CampaignService("jaxgrid", fallback=None,
                               validate_fraction=0.0).submit(req)
        assert resp.ok
        return resp
    def real_lanes():
        return [stats["real"] for name, stats in counting.opened
                if name == "repro.grid.dispatch"]
    small, _ = _spans_of(counting, lambda: submit(_grid_request(
        (64,), ("read",), ("same_channel",))))
    lanes = real_lanes()
    large, _ = _spans_of(counting, lambda: submit(_grid_request(
        (64, 128, 256, 512, 1024), ("read", "write"), ("same_channel",))))
    lanes += real_lanes()
    assert lanes == [5, 50]
    assert small == large
    assert small["repro.service.submit"] == 1
    assert small["repro.grid.dispatch"] == 1


def test_every_span_the_served_paths_open_is_named_repro(counting):
    timing_jax.evaluate_grid(HBM, SMALL)
    CampaignService("jaxgrid", fallback=None, validate_fraction=1.0).submit(
        _grid_request((64,), ("read",), ("same_channel",)))
    CampaignService("sim").submit(ExperimentRequest.make(
        "fig7_locality", "hbm", quick=True))
    names = set(counting.names())
    assert {"repro.service.submit", "repro.service.plan",
            "repro.service.attempt", "repro.service.derive",
            "repro.service.validate", "repro.sweep.run",
            "repro.sweep.prefill", "repro.sweep.serve",
            "repro.grid.evaluate", "repro.grid.plan", "repro.grid.rows",
            "repro.grid.route", "repro.grid.columns", "repro.grid.dispatch",
            "repro.grid.results"} <= names
    assert all(n.startswith(spans.PREFIX) for n in names)


def _span_names_in_source():
    """The literal name of every ``spans.span(...)`` call under src/."""
    names = []
    for dirpath, _, files in os.walk(os.path.join(SRC, "repro")):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            with open(path) as fh:
                tree = ast.parse(fh.read(), path)
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "span"
                        and isinstance(node.func.value, ast.Name)
                        and node.func.value.id == "spans"):
                    arg = node.args[0]
                    assert isinstance(arg, ast.Constant), path
                    names.append(arg.value)
    return names


def test_every_span_in_the_source_is_named_repro():
    names = _span_names_in_source()
    assert len(set(names)) == 24
    assert {"repro.decode.plan", "repro.ops.arena",
            "repro.ops.gather"} <= set(names)
    assert all(n.startswith(spans.PREFIX) for n in names)


def test_a_sim_process_never_imports_jax():
    code = (
        "import sys\n"
        "import repro.spans\n"
        "assert 'jax' not in sys.modules\n"
        "from repro.service import CampaignService, ExperimentRequest\n"
        "resp = CampaignService('sim').submit(ExperimentRequest.make(\n"
        "    'fig7_locality', 'hbm', quick=True))\n"
        "assert resp.ok\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


def test_a_span_without_jax_records_nothing(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    with spans.span("repro.test", points=3) as s:
        pass
    assert s is None
    assert spans.span("repro.test") is spans._OFF
