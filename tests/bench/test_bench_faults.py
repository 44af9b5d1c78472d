"""``correct`` comes out false when the timed path is broken underneath, for
each fault a cell can have, and for the control: the plain reference in
the next lower precision in the program's place."""
import numpy as np
import pytest

from bench_cells import CELLS, SEED, harness, run, tiny


def _half_stream(kernel):
    """The kernel runs half its transactions (the stream length is the
    fourth scalar of its operand)."""
    def broken(params, buf, **kw):
        return kernel(params.at[3].set(params[3] // 2), buf, **kw)
    return broken


def _altered(kernel):
    """The kernel's checksum is off by one in one element."""
    def broken(params, buf, **kw):
        return kernel(params, buf, **kw).at[0, 0].add(1.0)
    return broken


RST_FAULTS = {"half_stream": _half_stream, "altered_answer": _altered}
RST_KERNELS = {"rst.fig7_read": "rst_read", "rst.contend4": "rst_contend_read"}


@pytest.mark.parametrize("fault", sorted(RST_FAULTS))
@pytest.mark.parametrize("cell", sorted(RST_KERNELS))
def test_rst_faults_are_caught(monkeypatch, cell, fault):
    from repro.kernels import ops
    name = RST_KERNELS[cell]
    monkeypatch.setattr(ops, name, RST_FAULTS[fault](getattr(ops, name)))
    res = run(tiny(cell))
    assert not res["correct"]
    assert res["checks"]["checksum_gap"]["value"] > 0


RST_BACKEND_CALLS = {"rst.fig7_read": "throughput",
                     "rst.contend4": "contended_throughput"}


@pytest.mark.parametrize("cell", sorted(RST_BACKEND_CALLS))
def test_rst_answer_altered_in_the_backend_is_caught(monkeypatch, cell):
    """The kernels run true, but the backend serves 1 % more GB/s than
    they measured."""
    import dataclasses

    from repro.core.engine import PallasBackend
    name = RST_BACKEND_CALLS[cell]
    served = getattr(PallasBackend, name)

    def altered(self, *args, **kw):
        res = served(self, *args, **kw)
        field = "gbps" if name == "throughput" else "aggregate_gbps"
        return dataclasses.replace(res, **{field: getattr(res, field) * 1.01})
    monkeypatch.setattr(PallasBackend, name, altered)
    res = run(tiny(cell))
    assert not res["correct"]
    assert res["checks"]["checksum_gap"]["value"] == 0
    assert res["checks"]["answer_mismatch"]["value"] > 0


def _half_lanes(run_rows):
    """Only the first half of the lanes is evaluated; the rest repeat it."""
    def broken(spec, rows, mesh=None):
        half = max(1, len(rows) // 2)
        out = run_rows(spec, rows[:half], mesh)
        idx = np.arange(len(rows)) % half
        return {k: v[idx] for k, v in out.items()}
    return broken


def _scaled(run_rows):
    """Every answer off by one part in ten million."""
    def broken(spec, rows, mesh=None):
        out = run_rows(spec, rows, mesh)
        return {**out, "gbps": out["gbps"] * (1 + 1e-7)}
    return broken


GRID_FAULTS = {"half_lanes": _half_lanes, "altered_answer": _scaled}


@pytest.mark.parametrize("fault", sorted(GRID_FAULTS))
@pytest.mark.parametrize("cell", ["grid.ladder", "grid.xp_default"])
def test_grid_faults_are_caught(monkeypatch, cell, fault):
    from repro.core import timing_jax
    monkeypatch.setattr(timing_jax, "_run_rows",
                        GRID_FAULTS[fault](timing_jax._run_rows))
    c = tiny(cell)
    c.config["check_points"] = 40
    res = run(c)
    assert not res["correct"]
    assert res["checks"]["grid_rel_err"]["value"] > 1e-9


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(cell):
    # bfloat16 holds integers up to 256 only, so even the tiny cells'
    # checksums (16 bursts of values up to 250) part from float32's.
    c = tiny(cell)
    entry = harness.load_module("entries", c.traffic["entry"]).Entry(
        c.config, c.traffic)
    from bench import traffic
    try:
        entry.warm(SEED)
        harness.serve_window(entry, traffic.requests(c.traffic, SEED), 0.01)
        program = entry.capture.check(np.random.default_rng(1))
        control = entry.capture.check(np.random.default_rng(1), control=True)
    finally:
        entry.close()
    assert all(v["value"] <= v["limit"] for v in program.values()), program
    assert any(v["value"] > v["limit"] for v in control.values()), control
