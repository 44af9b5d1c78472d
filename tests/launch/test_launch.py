"""Launch layer: rules, shapes, HLO parsing, and an 8-device mini dry-run."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.shapes import SHAPES, cell_is_runnable, input_specs

ROOT = Path(__file__).resolve().parents[2]


class TestShapes:
    def test_forty_cells(self):
        assert len(ARCH_IDS) == 10
        assert len(SHAPES) == 4      # 10 x 4 = 40 cells

    def test_assigned_shape_numbers(self):
        assert SHAPES["train_4k"].seq_len == 4096
        assert SHAPES["train_4k"].global_batch == 256
        assert SHAPES["prefill_32k"].seq_len == 32768
        assert SHAPES["prefill_32k"].global_batch == 32
        assert SHAPES["decode_32k"].global_batch == 128
        assert SHAPES["long_500k"].seq_len == 524288
        assert SHAPES["long_500k"].global_batch == 1

    def test_long500k_skips(self):
        runnable = {a: cell_is_runnable(get_config(a), SHAPES["long_500k"])[0]
                    for a in ARCH_IDS}
        assert runnable == {
            "rwkv6-7b": True, "gemma3-1b": True, "hymba-1.5b": True,
            "qwen2-moe-a2.7b": False, "deepseek-v2-lite-16b": False,
            "qwen2-vl-7b": False, "starcoder2-7b": False,
            "nemotron-4-15b": False, "mistral-large-123b": False,
            "whisper-small": False,
        }

    def test_input_specs_no_allocation(self):
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape in SHAPES.values():
                specs = input_specs(cfg, shape)
                for v in specs.values():
                    assert isinstance(v, jax.ShapeDtypeStruct)

    def test_decode_specs_one_token(self):
        cfg = get_config("gemma3-1b")
        specs = input_specs(cfg, SHAPES["decode_32k"])
        assert specs["tokens"].shape == (128, 1)

    def test_vlm_gets_mrope_positions(self):
        cfg = get_config("qwen2-vl-7b")
        specs = input_specs(cfg, SHAPES["train_4k"])
        assert specs["mrope_positions"].shape == (3, 256, 4096)

    def test_audio_gets_frames(self):
        cfg = get_config("whisper-small")
        specs = input_specs(cfg, SHAPES["train_4k"])
        assert specs["frames"].shape == (256, 1500, 768)


class TestHloAnalysis:
    HLO = textwrap.dedent("""\
        %all-reduce.5 = f32[2048,1408]{1,0} all-reduce(%x), replica_groups={}
        %ag = bf16[512,128]{1,0} all-gather(%y), dimensions={0}
        %rs.1 = (f32[64]{0}, f32[32]{0}) reduce-scatter(%a, %b)
        %cp = u32[16]{0} collective-permute(%c)
        %ar-start = f32[100]{0} all-reduce-start(%d)
        %ar-done = f32[100]{0} all-reduce-done(%ar-start)
        %dot.3 = f32[999]{0} dot(%e, %f)
    """)

    def test_collective_bytes(self):
        out = collective_bytes(self.HLO)
        assert out["all-reduce"] == 2048 * 1408 * 4 + 100 * 4
        assert out["all-gather"] == 512 * 128 * 2
        assert out["reduce-scatter"] == (64 + 32) * 4
        assert out["collective-permute"] == 16 * 4
        assert out["total"] == sum(
            out[k] for k in ("all-reduce", "all-gather", "reduce-scatter",
                             "collective-permute"))

    def test_done_not_double_counted(self):
        out = collective_bytes(self.HLO)
        assert out["all-reduce_count"] == 2   # .5 and -start, not -done


class _FakeMesh:
    """make_rules only consumes axis_names; tests run on 1 device."""

    axis_names = ("data", "model")


class TestRules:
    def test_make_rules_filters_missing_axes(self):
        from repro.launch.train import make_rules
        cfg = get_config("gemma3-1b")
        rules = make_rules(cfg, _FakeMesh())          # no "pod" axis
        assert rules["batch"] == ("data",)            # pod dropped
        assert rules["mlp"] == "model"

    def test_arch_overrides_applied(self):
        from repro.launch.train import make_rules
        cfg = get_config("qwen2-vl-7b")
        rules = make_rules(cfg, _FakeMesh())
        assert rules["heads"] is None                 # 28 heads indivisible


MINI_DRYRUN = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import optim
from repro.configs import get_config
from repro.launch import serve as serve_lib
from repro.launch import train as train_lib
from repro.launch.mesh import make_mesh
from repro.models.common import param_sharding, param_shapes
from repro.models.registry import build

cfg = get_config("{arch}", smoke=True)
model = build(cfg)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = train_lib.make_rules(cfg, mesh)
rules.update({{k: None for k in
             ("heads", "act_heads", "kv_heads", "cache_heads", "vocab",
              "act_vocab", "mlp", "act_mlp", "experts", "expert_mlp")}})
with jax.set_mesh(mesh):
    specs = model.param_specs()
    state = train_lib.abstract_state(model)
    s_shard = train_lib.state_shardings(specs, rules, mesh)
    batch = {{"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}}
    b_shard = {{k: NamedSharding(mesh, P(("pod", "data"), None))
               for k in batch}}
    step = train_lib.make_train_step(model, cfg, rules, optim.AdamWConfig(),
                                     n_micro=2)
    low = jax.jit(step, in_shardings=(s_shard, b_shard),
                  out_shardings=(s_shard, None)).lower(state, batch)
    co = low.compile()
    print("PEAK", co.memory_analysis().temp_size_in_bytes)
"""


@pytest.mark.parametrize("arch", ["gemma3-1b", "rwkv6-7b",
                                  "deepseek-v2-lite-16b"])
def test_mini_multipod_dryrun_smoke(arch):
    """Smoke configs lower+compile on an 8-device (2,2,2) pod mesh in a
    subprocess (tests keep seeing 1 device)."""
    out = subprocess.run(
        [sys.executable, "-c", MINI_DRYRUN.format(arch=arch)],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": str(ROOT / "src"),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", str(ROOT)),
             "JAX_PLATFORMS": "cpu"},
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PEAK" in out.stdout
