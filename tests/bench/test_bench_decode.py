"""The decode cell and the write cell on the CPU: the cells cut to sizes the
Pallas interpreter runs, their references against the program, the
controls that must fail, and programs that cut corners failing the
checks."""
import json
import os

import numpy as np
import pytest

from bench_cells import ROOT, SEED, harness, run

from bench import traffic
from bench.references import decode_gather, rst_write_checksum

DECODE = "decode.dsv2lite_long"
WRITE = "rst.fig7_write"


def smoke_config(config: dict) -> dict:
    """The decode configuration at the smoke widths of
    ``configs/deepseek_v2_lite_16b.smoke()``, with the program's smoke
    deployment: 3 held layers, 1 of 8 experts, a pool of 256 pages."""
    from repro.configs.deepseek_v2_lite_16b import smoke
    m = smoke()
    return dict(
        config, hidden_size=m.d_model, num_attention_heads=m.num_heads,
        qk_nope_head_dim=m.mla.qk_nope, qk_rope_head_dim=m.mla.qk_rope,
        v_head_dim=m.mla.v_dim, kv_lora_rank=m.mla.kv_lora,
        intermediate_size=m.dense_d_ff,
        moe_intermediate_size=m.moe.expert_d_ff,
        n_routed_experts=m.moe.num_experts // 8,
        n_shared_experts=m.moe.num_shared, vocab_size=m.vocab_size // 8,
        num_hidden_layers=3,
        deployment=dict(config["deployment"],
                        program="deepseek-v2-lite-smoke", sequences=4,
                        pool_pages=256, page_tokens=256,
                        headroom_tokens=512, weight_block_bytes=4096))


def tiny(name: str) -> harness.Cell:
    cell = harness.Cell.load(name, ROOT)
    if name == DECODE:
        cell.config = smoke_config(cell.config)
        cell.traffic.update(sequences=4, context_tokens=[256, 2048])
    else:
        cell.traffic["request"]["n"] = 16
    cell.config["check_calls"] = 2
    return cell


def _config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "tpu_v5e_hbm_decode_dsv2lite.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [DECODE, WRITE])
def test_cell_runs_and_is_correct(name):
    c = tiny(name)
    res = run(c, seconds=1.0)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "rst_gbps"}
    assert sum(res["compiles_in_window"].values()) == 0


def test_traced_decode_run_reads_its_program_counters():
    res = run(tiny(DECODE), trace=True)
    assert res["correct"], res["checks"]
    metrics = res["metrics"]
    assert 0 < metrics["gather_pad_share"]["value"] < 100
    assert metrics["rst_reported_gbps"]["value"] > 0
    # On the CPU no device plane is traced: the device metrics stay out.
    assert "gather_kernel_roofline" not in metrics


@pytest.mark.parametrize("name", [DECODE, WRITE])
def test_control_in_bfloat16_fails(name):
    cell = tiny(name)
    if name == WRITE:
        # Tile values past 256 that are not multiples of 4 are inexact in
        # bfloat16; at n = 514 the 8 KiB window's calls leave 513 or 514
        # (the 256 MiB window's small strides leave 1), so check them all.
        cell.traffic["request"]["n"] = 514
        cell.config["check_calls"] = 64
    readings = _serve(cell)
    assert readings[False]["checksum_gap"]["value"] == 0.0
    assert readings[True]["checksum_gap"]["value"] > 1e-3
    assert readings[True]["bytes_mismatch"]["value"] == 0.0


def test_parent_without_the_experiment_is_refused(monkeypatch):
    """A program with no decode_step experiment fails the entry's
    construction at once, with a CellError."""
    from repro.core import experiments
    registry = dict(experiments._EXPERIMENT_REGISTRY)
    del registry["decode_step"]
    monkeypatch.setattr(experiments, "_EXPERIMENT_REGISTRY", registry)
    cell = tiny(DECODE)
    entry = harness.load_module("entries", "decode_step", cell.bench_dir)
    with pytest.raises(harness.CellError, match="decode_step"):
        entry.Entry(cell.config, cell.traffic)


def _serve(cell, seconds=0.5, tamper=None):
    """Serve `cell` for `seconds` after its warm-up, `tamper` applied to
    the entry in between; the check's readings, then the control's."""
    entry = harness.load_module("entries", cell.traffic["entry"],
                                cell.bench_dir).Entry(cell.config,
                                                      cell.traffic)
    try:
        entry.warm(SEED)
        if tamper is not None:
            tamper(entry)
        harness.serve_window(entry, traffic.requests(cell.traffic, SEED),
                             seconds)
        readings = {}
        for control in (False, True):
            rng = np.random.default_rng([SEED, 7])
            readings[control] = entry.capture.check(rng, control=control)
    finally:
        entry.close()
    return readings


def _draw(config, seed):
    entry = harness.load_module("entries", "decode_step")
    plan = harness.load_module("plans", "decode_step")
    traffic_ = harness.Cell.load(DECODE, ROOT).traffic
    return entry.draw_contexts(traffic_, config, plan, seed)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, SEED])
def test_program_and_reference_agree_on_page_tables(seed):
    from repro.core.decode_traffic import deployment
    config = _config()
    dep = deployment(config["deployment"]["program"])
    contexts = _draw(config, seed)
    want = decode_gather.page_tables(config, seed, contexts)
    got = dep.page_lists(seed, contexts)
    for layer_want, layer_got in zip(want, got, strict=True):
        for w, g in zip(layer_want, layer_got, strict=True):
            np.testing.assert_array_equal(g, w)
    for step in (0, 31, 32, 4095, 4096):
        reads = decode_gather.step_reads(config, seed, contexts, step)
        calls = dep.plan(seed, contexts, step)
        assert [r for r, _ in reads] == [c.block_rows for c in calls]
        for (_, w), c in zip(reads, calls, strict=True):
            np.testing.assert_array_equal(c.blocks, w)


def test_weight_extents_agree_with_the_program():
    from repro.core.decode_traffic import deployment
    config = _config()
    dep = deployment(config["deployment"]["program"])
    assert decode_gather.layer_bytes(config) == dep.layer_bytes()
    smoke = smoke_config(config)
    dep = deployment(smoke["deployment"]["program"])
    assert decode_gather.layer_bytes(smoke) == dep.layer_bytes()
    assert decode_gather.page_rows(smoke) == dep.page_rows


def test_step_bytes_are_reckoned_from_the_widths():
    plan = harness.load_module("plans", "decode_step")
    config = _config()
    assert plan.page_bytes(config) == 36864
    assert sum(decode_gather.layer_bytes(config)) == 1_017_693_184
    contexts = (16384, 131072)
    pages = 5 * (16384 // 32 + 131072 // 32)
    assert plan.stream_bytes(config, contexts, 0) == \
        1_017_693_184 + pages * 36864
    # Step 1 grows each sequence by a token: one more page each.
    assert plan.stream_bytes(config, contexts, 1) == \
        plan.stream_bytes(config, contexts, 0) + 5 * 2 * 36864


@pytest.mark.parametrize("a, s, w, n, want", [
    (0, 4096, 8192, 16, 15.0),          # tile 0 every other write: i = 14
    (0, 8192, 8192, 16, 16.0),          # every write: i = 15
    (0, 1 << 20, 1 << 28, 1 << 18, (1 << 18) - 255.0),
    (4096, 4096, 8192, 16, None),       # base past tile 0: never written
])
def test_write_reference_first_tile(a, s, w, n, want):
    call = {"a": a, "s": s, "w": w, "n": n, "b": 4096}
    got = rst_write_checksum.first_tile(call, {"buffer_modulus": 251})
    if want is None:
        np.testing.assert_array_equal(
            got.reshape(-1), np.arange(1024) % 251)
    else:
        assert np.all(got == want)


def test_write_reference_matches_the_kernel_with_a_base():
    """Tile 0 not written: the measurer's buffer keeps its content."""
    from repro.core import RSTParams
    from repro.kernels import ops
    p = RSTParams(n=8, b=4096, s=4096, w=8192, a=4096)
    sample = ops.measure_write_bandwidth(p)
    call = {"a": p.a, "s": p.s, "w": p.w, "n": p.n, "b": p.b}
    np.testing.assert_array_equal(
        sample.checksum,
        rst_write_checksum.first_tile(call, {"buffer_modulus": 251}))


@pytest.mark.parametrize("a, s, w, n, unwritten", [
    (0, 4096, 8192, 16, 0),             # both tiles, written again
    (4096, 8192, 32768, 5, 5),          # a base, tiles left unwritten
    (0, 12288, 65536, 40, 0),           # a stride that wraps unevenly
])
def test_write_reference_covers_every_tile_of_the_buffer(a, s, w, n,
                                                         unwritten):
    """The check keeps each tile's sum of the buffer the timed call leaves,
    and the reference gives the same sums: the last writer's value in
    written tiles, the buffer's content in the others."""
    from repro.core import RSTParams
    from repro.kernels import ops
    config = harness.Cell.load(WRITE, ROOT).config
    check = harness.load_module("checks", "rst_write_checksum")
    capture = check.Capture(config)
    try:
        ops.measure_write_bandwidth(RSTParams(n=n, b=4096, s=s, w=w, a=a))
    finally:
        capture.close()
    [call] = capture.calls
    want = rst_write_checksum.tile_sums(call, config)
    assert len(want) == (a + w) // 4096
    assert call["tile_sums"].dtype == np.int32
    np.testing.assert_array_equal(call["tile_sums"].view(np.uint32), want)
    assert (rst_write_checksum.last_writers(call) < 0).sum() == unwritten
    assert rst_write_checksum.compare([call], config)["checksum_gap"] == 0


@pytest.mark.parametrize("fault", ["tile0_only", "wrong_value"])
def test_a_write_engine_that_cuts_corners_fails(fault):
    """A write engine that keeps only the writes to tile 0, or writes a
    wrong value to the other tiles, leaves tile 0 right and fails."""
    import jax.numpy as jnp

    def tamper(entry):
        real = entry.capture._kernel

        def kernel(params, buf, **kw):
            before = jnp.array(buf)
            out = real(params, buf, **kw)
            if fault == "tile0_only":
                return out.at[8:].set(before[8:])
            return out.at[8:].add(1.0)
        entry.capture._kernel = kernel
    cell = tiny(WRITE)
    cell.config["check_calls"] = 64
    readings = _serve(cell, tamper=tamper)
    assert readings[False]["checksum_gap"]["value"] > 0


@pytest.mark.parametrize("narrow", ["bfloat16", "int8"])
def test_an_arena_held_in_fewer_bits_fails(narrow):
    """A program that kept the decode arena in bfloat16 or int8 would read
    half or a quarter of the bytes; its checksum differs from the
    reference's."""
    import jax.numpy as jnp

    from repro.kernels import ops

    def tamper(entry):
        arena = ops._ARENA[0]
        words = arena.array
        if narrow == "bfloat16":
            held = words.astype(jnp.float32).astype(jnp.bfloat16)
            arena.array = held.astype(jnp.float32).astype(jnp.int32)
        else:
            arena.array = words.astype(jnp.int8).astype(jnp.int32)
    readings = _serve(tiny(DECODE), tamper=tamper)
    assert readings[False]["checksum_gap"]["value"] > 0.5


def test_write_roofline_reads_the_write_kernel_events():
    from bench.trace_reduce import Event, Trace
    ms = 1_000_000
    dev = "/device:TPU:0"
    t = Trace(ops=[Event("rst_write", 1 * ms, 3 * ms, dev),
                   Event("rst_read", 3 * ms, 4 * ms, dev),
                   Event("window_sums", 4 * ms, 5 * ms, dev),
                   Event("rst_write", 6 * ms, 8 * ms, dev)],
              modules=[], spans=[Event("bench.request", 0, 10 * ms,
                                       "python")], devices=1)
    records = [harness.Record(0.0, 1.0, {"points": 2,
                                         "stream_bytes": 2 * 819_000})]
    run = harness.Run(cell=None, setup_s=0.0, window_s=0.01,
                      records=records, peaks={"hbm_bytes_per_s": 819e9},
                      trace=t)
    reader = harness.load_module("metrics", "rst_write_kernel_roofline")
    # 1.638 MB in 4 ms of rst_write against 819 GB/s: 0.05 %.
    assert reader.read(run) == pytest.approx(0.05)
    assert reader.read(harness.Run(None, 0.0, 0.01, records, run.peaks,
                                   None)) is None
