"""One decode step of a served LLM, lowered to the blocks it reads on one chip.

A deployment is a model (its widths from ``repro.configs``), the chip's share
of it, and the state a server keeps for its batch.  The one built in is
DeepSeek-V2-Lite served as the DeepSeek-V3/R1 inference overview describes:
decode with data-parallel attention and expert parallelism, 8 chips to an
MoE layer.  This chip holds, of each layer it serves, the MLA weights whole,
8 of the 64 routed experts, both shared experts and the router; the dense
layer 0 whole; an eighth of the vocabulary head; and the latent KV cache of
its own 16 sequences.  Weights and cache are bfloat16 (2 bytes a value).
At 128 tokens a step over 8 chips, 6 experts a token, each held expert is
hit every step, so a step reads every held weight once.

**Arena layout** (``kernels/ops.DecodeArena``, rows of 128 int32 words):
the page pool first, page ``p`` at rows ``[p * page_rows, (p + 1) *
page_rows)``; then, from weight block ``weight_base`` on, each held layer's
weight matrices packed in ``extents()`` order, the layer padded to whole
weight blocks, the head last.  A page is the smallest whole number of
tokens whose latent rows fill whole 4 KiB tiles: 32 tokens x 576 values x
2 B = 36,864 B for DeepSeek-V2-Lite.

**Page allocation**, the rule a reference re-derives from this text:

* ``pi = numpy.random.default_rng(seed).permutation(pool_pages)``;
* for each held layer in order, then each sequence in order, the sequence
  takes the next ``ceil((ctx + headroom) / page_tokens)`` entries of ``pi``
  (``headroom`` tokens to grow into); a batch that needs more pages than
  the pool holds is refused;
* step ``t`` reads, of each list, the first ``ceil((ctx + t mod headroom)
  / page_tokens)`` pages.

**A step's calls**, one per layer and kind, in order: for each held layer,
its weight blocks, then the pages of every sequence in page-table order;
then the head's weight blocks.  The longest table, a layer's pages, holds
at most 16 x 4,224 = 67,584 entries for DeepSeek-V2-Lite, within what one
call's table may hold (``kernels/rst_gather.MAX_TABLE``).

The batch (its contexts) is the caller's: a server admits only what its
pool holds, so a batch that needs more pages is refused.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, List, Tuple

import numpy as np

from repro import spans

TILE_BYTES = 4096           # the engines' 8 x 128 tile of 4-byte words
ROW_BYTES = 512             # one float32 row of 128 words
CHIPS_PER_LAYER = 8         # expert parallelism: chips sharing a layer
VALUE_BYTES = 2             # bfloat16 weights and cache


@dataclasses.dataclass(frozen=True)
class Extent:
    """One held weight matrix: its layer (the head is layer
    ``layers_held``), its name and its count of values."""

    layer: int
    name: str
    values: int


@dataclasses.dataclass(frozen=True)
class GatherStep:
    """One decode step of a batch: what a gather point names."""

    deployment: str
    seed: int
    contexts: Tuple[int, ...]
    step: int


@dataclasses.dataclass(frozen=True)
class GatherCall:
    """One engine call: the blocks it reads, in order, in units of
    `block_rows` arena rows."""

    layer: int
    kind: str                   # "weights" or "kv"
    block_rows: int
    blocks: np.ndarray          # int32

    @property
    def block_bytes(self) -> int:
        return self.block_rows * ROW_BYTES


@dataclasses.dataclass(frozen=True, eq=False)
class DecodeDeployment:
    """A model's widths and this chip's share of its decode step."""

    name: str
    model: Any                  # repro.configs.base.ModelConfig
    layers_held: int
    headroom: int = 4096        # tokens a sequence may grow into
    pool_pages: int = 1 << 18
    weight_block_bytes: int = 1 << 20
    grid_bucket: int = 256      # engine grids are multiples of this

    # -- widths --------------------------------------------------------------
    @property
    def experts_held(self) -> int:
        return self.model.moe.num_experts // CHIPS_PER_LAYER

    @property
    def vocab_held(self) -> int:
        return self.model.vocab_size // CHIPS_PER_LAYER

    @property
    def latent_values(self) -> int:
        """Values of the latent KV cache a token and layer: the compressed
        KV and the shared rope key."""
        return self.model.mla.kv_lora + self.model.mla.qk_rope

    @property
    def page_tokens(self) -> int:
        token_bytes = self.latent_values * VALUE_BYTES
        return TILE_BYTES // math.gcd(TILE_BYTES, token_bytes)

    @property
    def page_rows(self) -> int:
        return self.page_tokens * self.latent_values * VALUE_BYTES \
            // ROW_BYTES

    @property
    def weight_rows(self) -> int:
        return self.weight_block_bytes // ROW_BYTES

    def attention_extents(self, layer: int) -> List[Extent]:
        m = self.model
        d, h, mla = m.d_model, m.num_heads, m.mla
        return [Extent(layer, "input_layernorm", d),
                Extent(layer, "q_proj", d * h * (mla.qk_nope + mla.qk_rope)),
                Extent(layer, "kv_a_proj_with_mqa",
                       d * (mla.kv_lora + mla.qk_rope)),
                Extent(layer, "kv_a_layernorm", mla.kv_lora),
                Extent(layer, "kv_b_proj",
                       mla.kv_lora * h * (mla.qk_nope + mla.v_dim)),
                Extent(layer, "o_proj", h * mla.v_dim * d),
                Extent(layer, "post_attention_layernorm", d)]

    @staticmethod
    def _mlp(layer: int, prefix: str, d: int, width: int) -> List[Extent]:
        return [Extent(layer, f"{prefix}.{proj}", d * width)
                for proj in ("gate_proj", "up_proj", "down_proj")]

    def moe_extents(self, layer: int, experts) -> List[Extent]:
        """The router, the given routed experts and the shared experts."""
        m = self.model
        out = [Extent(layer, "gate", m.moe.num_experts * m.d_model)]
        for e in experts:
            out += self._mlp(layer, f"experts.{e}", m.d_model,
                             m.moe.expert_d_ff)
        return out + self._mlp(layer, "shared_experts", m.d_model,
                               m.moe.shared_d_ff)

    def extents(self) -> List[Extent]:
        """Every weight matrix this chip reads a step, in arena order; the
        chip holds routed experts ``[0, experts_held)``, the other chips
        of a layer the next ranges."""
        m = self.model
        out: List[Extent] = []
        for layer in range(self.layers_held):
            out += self.attention_extents(layer)
            if layer in m.moe_dense_layers:
                out += self._mlp(layer, "mlp", m.d_model, m.dense_d_ff)
            else:
                out += self.moe_extents(layer, range(self.experts_held))
        head = self.layers_held
        return out + [Extent(head, "norm", m.d_model),
                      Extent(head, "lm_head", self.vocab_held * m.d_model)]

    def layer_bytes(self) -> List[int]:
        """Weight bytes of each held layer, the head last."""
        out = [0] * (self.layers_held + 1)
        for e in self.extents():
            out[e.layer] += e.values * VALUE_BYTES
        return out

    def weight_bytes(self) -> int:
        return sum(self.layer_bytes())

    def layer_blocks(self) -> List[int]:
        return [-(-b // self.weight_block_bytes) for b in self.layer_bytes()]

    @property
    def weight_base(self) -> int:
        """Weight-block index of the first weight block: the first whole
        weight block past the pool."""
        return -(-self.pool_pages * self.page_rows // self.weight_rows)

    @property
    def arena_rows(self) -> int:
        """Rows of the arena: a whole number of pages and of weight blocks,
        so that neither engine view makes the compiler pad (copy) it."""
        rows = (self.weight_base + sum(self.layer_blocks())) \
            * self.weight_rows
        whole = math.lcm(self.page_rows, self.weight_rows)
        return -(-rows // whole) * whole

    # -- the batch ------------------------------------------------------------
    def pages_held(self, contexts) -> int:
        """Pages the batch holds: every held layer, every sequence."""
        return self.layers_held * sum(
            -(-(int(c) + self.headroom) // self.page_tokens)
            for c in contexts)

    def check_batch(self, contexts) -> None:
        if not contexts or min(int(c) for c in contexts) < 1:
            raise ValueError(f"a batch needs contexts of 1 token or more, "
                             f"got {contexts!r}")
        need = self.pages_held(contexts)
        if need > self.pool_pages:
            raise ValueError(
                f"the batch needs {need} pages of {self.page_tokens} "
                f"tokens; the pool of {self.name!r} holds "
                f"{self.pool_pages}")

    def page_lists(self, seed: int, contexts) -> List[List[np.ndarray]]:
        """``[layer][sequence]``: each list of pages, by the rule above."""
        return _page_lists(self, int(seed), tuple(int(c) for c in contexts))

    def step_pages(self, contexts, step: int) -> List[int]:
        """Pages each sequence reads at step `step`."""
        grow = int(step) % self.headroom
        return [-(-(int(c) + grow) // self.page_tokens) for c in contexts]

    def plan(self, seed: int, contexts, step: int) -> List[GatherCall]:
        """The calls of one decode step, in order."""
        with spans.span("repro.decode.plan"):
            lists = self.page_lists(seed, contexts)
            counts = self.step_pages(contexts, step)
            calls: List[GatherCall] = []
            first = self.weight_base
            for layer, blocks in enumerate(self.layer_blocks()):
                calls.append(GatherCall(
                    layer, "weights", self.weight_rows,
                    np.arange(first, first + blocks, dtype=np.int32)))
                first += blocks
                if layer < self.layers_held:
                    calls.append(GatherCall(
                        layer, "kv", self.page_rows,
                        np.concatenate([pages[:k] for pages, k in
                                        zip(lists[layer], counts)])))
            return calls

    def grids(self, contexts) -> set:
        """Every ``(block_rows, grid)`` a step of this batch can use."""
        kv_pages = {sum(self.step_pages(contexts, t))
                    for t in range(self.headroom)}
        return ({(self.weight_rows, self.grid(n))
                 for n in self.layer_blocks()}
                | {(self.page_rows, self.grid(n)) for n in kv_pages})

    def grid(self, blocks: int) -> int:
        """The engine grid of a call that reads `blocks` blocks."""
        return -(-blocks // self.grid_bucket) * self.grid_bucket


@functools.lru_cache(maxsize=4)
def _page_lists(dep: DecodeDeployment, seed: int, contexts: Tuple[int, ...]
                ) -> List[List[np.ndarray]]:
    dep.check_batch(contexts)
    pi = np.random.default_rng(seed).permutation(dep.pool_pages)
    pi = pi.astype(np.int32)
    out, taken = [], 0
    for _ in range(dep.layers_held):
        lists = []
        for c in contexts:
            k = -(-(c + dep.headroom) // dep.page_tokens)
            lists.append(pi[taken:taken + k])
            taken += k
        out.append(lists)
    return out


# --------------------------------------------------------------- registry
def _deepseek_v2_lite_ep8() -> DecodeDeployment:
    from repro.configs import deepseek_v2_lite_16b
    return DecodeDeployment("deepseek-v2-lite-ep8",
                            deepseek_v2_lite_16b.CONFIG, layers_held=5)


def _deepseek_v2_lite_smoke() -> DecodeDeployment:
    """The smoke widths, a 3-layer share and a pool of 256 pages: a step
    the Pallas interpreter reads in well under a second."""
    from repro.configs import deepseek_v2_lite_16b
    return DecodeDeployment(
        "deepseek-v2-lite-smoke", deepseek_v2_lite_16b.smoke(),
        layers_held=3, headroom=512, pool_pages=256,
        weight_block_bytes=TILE_BYTES, grid_bucket=16)


@functools.lru_cache(maxsize=None)
def deployment(name: str) -> DecodeDeployment:
    """The deployment registered under `name`."""
    builders = {"deepseek-v2-lite-ep8": _deepseek_v2_lite_ep8,
                "deepseek-v2-lite-smoke": _deepseek_v2_lite_smoke}
    if name not in builders:
        raise ValueError(f"unknown deployment {name!r}; known: "
                         f"{sorted(builders)}")
    return builders[name]()
