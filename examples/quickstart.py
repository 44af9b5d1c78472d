"""Quickstart: the paper's tool + the framework around it, in 60 seconds.

1. Benchmark the (simulated) U280 HBM with Shuhai — reproduces Table IV/V.
2. Run the TPU-native RST Pallas engine (compiled on a TPU, interpreted
   elsewhere).
3. Let the memory oracle pick a KV-cache layout (the technique acting as a
   framework feature).
4. Forward + one training step of an assigned architecture (smoke size).

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import (HBM, AccessPattern, MemoryOracle, RSTParams,
                        ShuhaiCampaign, choose_layout)
from repro.kernels import ops

print("=== 1. Shuhai on the simulated U280 ===")
camp = ShuhaiCampaign(HBM)
lat = camp.suite_idle_latency()
print(f"HBM idle latency: hit={lat['page_hit']['ns']:.1f}ns "
      f"closed={lat['page_closed']['ns']:.1f}ns "
      f"miss={lat['page_miss']['ns']:.1f}ns   (paper: 106.7/122.2/137.8)")
tot = camp.suite_total_throughput()
print(f"Aggregate HBM throughput: {tot['total_gbps']:.0f} GB/s over "
      f"{tot['num_channels']} channels   (paper: 425 GB/s)")

print("\n=== 2. TPU-native RST engine (Pallas) ===")
tile = ops.tile_bytes(jnp.float32)
p = RSTParams(n=64, b=tile, s=tile, w=64 * tile)
sample = ops.measure_read_bandwidth(p)
print(f"sequential traversal: {sample.bytes_moved} bytes read, "
      f"checksum[0,0]={float(sample.checksum[0, 0]):.3f}")

print("\n=== 3. Memory-oracle-driven layout choice ===")
oracle = MemoryOracle()
eff = oracle.efficiency(AccessPattern(4096, 4096, 1 << 28))
print(f"contiguous-read efficiency on HBM: {eff:.1%} of wire rate")
layout = choose_layout(oracle, {"seq": 32768, "kv_heads": 8, "head_dim": 128},
                       itemsize=2, iterate_dim="seq",
                       fetch_dims=("kv_heads", "head_dim"))
print(f"best KV-cache layout for decode: {layout.dims}")

print("\n=== 4. One assigned architecture, forward + shapes ===")
from repro.configs import get_config
from repro.models.common import init_params
from repro.models.registry import build

cfg = get_config("gemma3-1b", smoke=True)
model = build(cfg)
params = init_params(jax.random.key(0), model.param_specs())
tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
logits, _ = model.forward(params, {"tokens": tokens})
print(f"{cfg.name}: logits {logits.shape}, "
      f"finite={bool(jnp.isfinite(logits).all())}")
print("\nquickstart OK")
