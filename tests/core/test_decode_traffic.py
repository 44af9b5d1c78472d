"""The decode-traffic lowering: DeepSeek-V2-Lite's widths, the chip's share
of an MoE layer, the page-allocation rule and the served path's gather
points."""
import dataclasses
import json
import math
import os
import sys

import numpy as np
import pytest

from repro.core import HBM, Sweep, UnsupportedCapability, get_backend
from repro.core.decode_traffic import (CHIPS_PER_LAYER, GatherStep,
                                       deployment)
from repro.core.experiments import (backend_capability_gap,
                                    experiments_for, get_experiment,
                                    plan_experiment, run_experiment)
from repro.core.sweep import KIND_GATHER, SweepPoint

FULL = "deepseek-v2-lite-ep8"
SMOKE = "deepseek-v2-lite-smoke"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _contexts(seed, n=16, lo=16384, hi=131072):
    """A batch of `n` contexts, log-uniform over [lo, hi]."""
    rng = np.random.default_rng(seed)
    return tuple(int(c) for c in np.floor(np.exp(
        rng.uniform(np.log(lo), np.log(hi), n))))


def _gather(step):
    return SweepPoint(None, kind=KIND_GATHER, gather=step)


def test_widths_are_the_catalog_values():
    """huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json."""
    m = deployment(FULL).model
    assert (m.num_layers, m.d_model, m.num_heads, m.vocab_size) == \
        (27, 2048, 16, 102400)
    assert (m.mla.kv_lora, m.mla.qk_rope, m.mla.qk_nope, m.mla.v_dim) == \
        (512, 64, 128, 128)
    assert m.dense_d_ff == 10944 and m.moe_dense_layers == (0,)
    assert (m.moe.num_experts, m.moe.top_k, m.moe.expert_d_ff,
            m.moe.num_shared) == (64, 6, 1408, 2)
    assert m.moe.shared_d_ff == 2 * 1408


def test_held_bytes_of_the_ep8_share():
    dep = deployment(FULL)
    assert (dep.experts_held, dep.vocab_held, dep.layers_held) == \
        (8, 12800, 5)
    attention = sum(e.values for e in dep.attention_extents(0))
    assert attention == 13_767_168                 # about 13.76 M
    layers = dep.layer_bytes()
    assert layers[0] == 162_014_208                # dense layer 0
    assert layers[1:5] == [200_811_520] * 4        # each held MoE layer
    assert layers[5] == 52_432_896                 # the head's slice
    assert dep.weight_bytes() == 1_017_693_184     # 1.018 GB a step
    assert dep.latent_values == 576
    assert (dep.page_tokens, dep.page_rows) == (32, 72)
    assert dep.page_rows * 512 == 36_864 == 9 * 4096


def test_uncut_moe_layer_is_1_170_gb():
    dep = deployment(FULL)
    uncut = sum(e.values for e in dep.attention_extents(1)
                + dep.moe_extents(1, range(64))) * 2
    assert round(uncut / 1e9, 3) == 1.170


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """At the smoke widths: each share's routed experts, with what every
    chip holds alike (attention, router, shared experts) counted once, add
    up to the whole layer."""
    dep = deployment(SMOKE)
    n, held = dep.model.moe.num_experts, dep.experts_held
    assert n == CHIPS_PER_LAYER * held
    whole = {e.name: e.values for e in dep.attention_extents(1)
             + dep.moe_extents(1, range(n))}
    mine = [e for e in dep.extents() if e.layer == 1]
    assert mine == dep.attention_extents(1) + dep.moe_extents(
        1, range(held))
    parts = {}
    for share in range(CHIPS_PER_LAYER):
        for e in dep.attention_extents(1) + dep.moe_extents(
                1, range(share * held, (share + 1) * held)):
            if e.name.startswith("experts."):
                assert e.name not in parts
                parts[e.name] = e.values
            else:
                parts.setdefault(e.name, e.values)
    assert parts == whole
    assert sum(parts.values()) == sum(whole.values())


def test_arena_rows_hold_whole_pages_and_weight_blocks():
    for name in (FULL, SMOKE):
        dep = deployment(name)
        assert dep.arena_rows % dep.page_rows == 0
        assert dep.arena_rows % dep.weight_rows == 0
        assert dep.weight_base * dep.weight_rows >= \
            dep.pool_pages * dep.page_rows
        assert dep.arena_rows >= (dep.weight_base + sum(
            dep.layer_blocks())) * dep.weight_rows


def _rule(dep, seed, contexts):
    """The allocation rule as the module's docstring states it."""
    pi = np.random.default_rng(seed).permutation(dep.pool_pages)
    out, taken = [], 0
    for _ in range(dep.layers_held):
        layer = []
        for c in contexts:
            k = math.ceil((c + dep.headroom) / dep.page_tokens)
            layer.append(pi[taken:taken + k])
            taken += k
        out.append(layer)
    return out


@pytest.mark.parametrize("seed", [0, 1, 12345, 2 ** 33 + 1])
def test_page_lists_follow_the_rule(seed):
    dep = deployment(FULL)
    contexts = _contexts(seed)
    got = dep.page_lists(seed, contexts)
    want = _rule(dep, seed, contexts)
    for g_layer, w_layer in zip(got, want, strict=True):
        for g, w in zip(g_layer, w_layer, strict=True):
            np.testing.assert_array_equal(g, w)
    pages = np.concatenate([p for layer in got for p in layer])
    assert len(np.unique(pages)) == len(pages) <= dep.pool_pages


def test_a_step_reads_a_growing_prefix_of_each_list():
    dep = deployment(FULL)
    contexts = (16384, 16415, 131072)
    assert dep.step_pages(contexts, 0) == [512, 513, 4096]
    assert dep.step_pages(contexts, 1) == [513, 513, 4097]
    assert dep.step_pages(contexts, 4096) == dep.step_pages(contexts, 0)
    calls = dep.plan(3, contexts, 1)
    assert [(c.layer, c.kind) for c in calls[:4]] == [
        (0, "weights"), (0, "kv"), (1, "weights"), (1, "kv")]
    assert calls[-1].kind == "weights" and calls[-1].layer == 5
    lists = dep.page_lists(3, contexts)
    np.testing.assert_array_equal(calls[1].blocks, np.concatenate(
        [lists[0][0][:513], lists[0][1][:513], lists[0][2][:4097]]))
    assert calls[0].blocks[0] == dep.weight_base
    assert calls[2].blocks[0] == dep.weight_base + dep.layer_blocks()[0]


def test_a_batch_that_overflows_the_pool_is_drawn_again():
    """The program refuses a batch its pool cannot hold; the one who
    forms the batch, a server or the benchmark's entry, draws again."""
    dep = dataclasses.replace(deployment(FULL), pool_pages=120_000)
    with pytest.raises(ValueError, match="pool"):
        dep.check_batch((131072,) * 16)
    with pytest.raises(ValueError, match="pool"):
        dep.page_lists(0, (131072,) * 16)
    with pytest.raises(ValueError, match="pool"):
        plan_experiment("decode_step", HBM, contexts=(131072,) * 16)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import harness
    entry = harness.load_module("entries", "decode_step")
    plan = harness.load_module("plans", "decode_step")
    with open(os.path.join(ROOT, "bench", "configs",
                           "tpu_v5e_hbm_decode_dsv2lite.json")) as f:
        config = json.load(f)
    config["deployment"]["pool_pages"] = dep.pool_pages
    traffic = {"sequences": 16, "context_tokens": [16384, 131072]}
    drawn = [entry.draw_contexts(traffic, config, plan, s)
             for s in range(40)]
    assert all(dep.pages_held(c) <= dep.pool_pages for c in drawn)
    # Some seeds' first draws overflowed, and were drawn again.
    assert any(c != entry.draw_contexts(
        traffic, dict(config, deployment=dict(config["deployment"],
                                              pool_pages=1 << 18)), plan, s)
        for s, c in enumerate(drawn))


def test_grids_cover_every_step_of_the_batch():
    dep = deployment(FULL)
    contexts = _contexts(5)
    grids = dep.grids(contexts)
    for step in (0, 1, 1000, 4095):
        for call in dep.plan(5, contexts, step):
            assert (call.block_rows, dep.grid(len(call.blocks))) in grids
    assert all(g % 256 == 0 for _, g in grids)


def test_only_the_pallas_backend_gathers():
    step = GatherStep(SMOKE, 1, (300, 400), 0)
    for name in ("sim", "jaxgrid"):
        with pytest.raises(UnsupportedCapability, match="gather"):
            get_backend(name).gather_throughput(HBM, step)
        with pytest.raises(UnsupportedCapability, match="gather"):
            Sweep(HBM, name).add_point(_gather(step)).run()
    assert get_backend("pallas").supports_gather
    planned, _ = plan_experiment("decode_step", HBM, quick=True)
    assert "supports_gather=False" in backend_capability_gap("sim", planned)
    assert backend_capability_gap("pallas", planned) is None


def test_decode_step_runs_on_pallas_only():
    exp = get_experiment("decode_step")
    assert exp not in experiments_for(HBM)
    assert exp in experiments_for(HBM, "pallas")
    with pytest.raises(ValueError, match="pallas backend"):
        run_experiment(exp, HBM, "sim", quick=True)
    res = run_experiment(exp, HBM, "pallas", quick=True, step=3)
    assert res["gbps"] > 0 and res["calls"] == 7
    assert 0 <= res["pad_steps"] < res["grid_steps"]


def test_a_gather_point_is_coalesced_not_memoized():
    step = GatherStep(SMOKE, 1, (300, 400), 2)
    sweep = Sweep(HBM, "pallas", coalesce=True)
    results = sweep.add_point(_gather(step)).add_point(_gather(step)).run()
    assert [r.cached for r in results] == [False, True]
    assert sweep.stats.evaluated == 1


def test_the_campaign_service_serves_decode_steps():
    from repro.service import CampaignService, ExperimentRequest
    svc = CampaignService("pallas", fallback=None, validate_fraction=0.0)
    req = ExperimentRequest.make("decode_step", "hbm", deployment=SMOKE,
                                 seed=3, contexts=[300, 2048], step=5)
    resp = svc.submit(req)
    assert resp.ok and resp.backend == "pallas" and not resp.degraded
    assert resp.result["bytes"] > 0 and resp.result["calls"] == 7
    assert svc.submit(req).coalesced            # a repeat is deduplicated
    big = ExperimentRequest.make("decode_step", "hbm", deployment=SMOKE,
                                 seed=3, contexts=[10 ** 6], step=0)
    assert "pool" in svc.submit(big).error
    sim = CampaignService("sim", fallback=None)
    resp = sim.submit(req)
    assert not resp.ok and "supports_gather=False" in resp.error
