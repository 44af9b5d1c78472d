"""Plain reference for the decode cell: the checksum one decode step must give.

Everything is re-derived here from the configuration file (its published
widths, its ``deployment`` and its ``arena``), never from the program:

* **Weights.** Each held layer, in order, holds: the two RMS norms and the
  MLA projections (``q_proj`` of hidden x heads x (nope + rope) as
  ``q_lora_rank`` is null, ``kv_a_proj_with_mqa`` of hidden x (kv_lora +
  rope), the ``kv_a_layernorm``, ``kv_b_proj`` of kv_lora x heads x (nope
  + v), ``o_proj`` of heads x v x hidden); then, for the first
  ``first_k_dense_replace`` layers, a dense SwiGLU of ``intermediate_size``,
  and for the others the router (all ``n_routed_experts x chips_per_layer``
  experts by hidden), the held routed experts and the shared ones (three
  hidden x width matrices each).  The head is the final norm and the held
  vocabulary rows.  Each layer's bytes are padded to whole weight blocks;
  the weight blocks start at the first whole block past the page pool.
* **Pages.** ``pi = numpy.random.default_rng(seed).permutation(pool)``;
  for each held layer, then each sequence, the sequence takes the next
  ``ceil((ctx + headroom) / page_tokens)`` entries; step ``t`` reads the
  first ``ceil((ctx + t mod headroom) / page_tokens)`` of each list.
* **Order.** Layer by layer: the layer's weight blocks, then each
  sequence's pages in page-table order; the head's blocks last.
* **Values.** Arena word ``f`` holds the configuration's ``arena.formula``,
  32 bits that all depend on the seed and on ``f``.  Each 4 KiB tile (1024
  words) read is added into one accumulator of 1024 words, mod 2^32.

Sums mod 2^32 are exact and do not depend on the order, so the checksum
must agree exactly, and it depends on every bit of every word read: a
program that held the arena in fewer bits (bfloat16, int8) would read
other words and give another checksum.
"""
from __future__ import annotations

import numpy as np

ROW_WORDS = 128
TILE_WORDS = 1024
WORD_BYTES = 4
_TILES = 256            # tiles added per block of work: 1 MiB, in cache


def _dep(config: dict) -> dict:
    return config["deployment"]


def layer_bytes(config: dict) -> list:
    """Weight bytes of each held layer, the head last."""
    c, dep = config, _dep(config)
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    v, kv = c["v_head_dim"], c["kv_lora_rank"]
    attention = (2 * d + kv + d * h * (nope + rope) + d * (kv + rope)
                 + kv * h * (nope + v) + h * v * d)
    expert = 3 * d * c["moe_intermediate_size"]
    moe = (c["n_routed_experts"] * dep["chips_per_layer"] * d
           + c["n_routed_experts"] * expert
           + c["n_shared_experts"] * expert)
    dense = 3 * d * c["intermediate_size"]
    values = [attention + (dense if layer < c["first_k_dense_replace"]
                           else moe)
              for layer in range(c["num_hidden_layers"])]
    values.append(d + c["vocab_size"] * d)
    return [n * dep["value_bytes"] for n in values]


def page_rows(config: dict) -> int:
    c, dep = config, _dep(config)
    page = (dep["page_tokens"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            * dep["value_bytes"])
    return page // (ROW_WORDS * WORD_BYTES)


def weight_rows(config: dict) -> int:
    return _dep(config)["weight_block_bytes"] // (ROW_WORDS * WORD_BYTES)


def pages_needed(config: dict, contexts) -> int:
    """Pages a batch holds: every held layer, every sequence."""
    dep = _dep(config)
    return config["num_hidden_layers"] * sum(
        -(-(int(c) + dep["headroom_tokens"]) // dep["page_tokens"])
        for c in contexts)


def page_tables(config: dict, seed: int, contexts) -> list:
    """``[layer][sequence]``: each sequence's pages, by the rule."""
    dep = _dep(config)
    if pages_needed(config, contexts) > dep["pool_pages"]:
        raise ValueError("the batch does not fit the pool")
    pi = np.random.default_rng(int(seed)).permutation(dep["pool_pages"])
    tables, taken = [], 0
    for _ in range(config["num_hidden_layers"]):
        layer = []
        for c in contexts:
            k = -(-(int(c) + dep["headroom_tokens"]) // dep["page_tokens"])
            layer.append(pi[taken:taken + k])
            taken += k
        tables.append(layer)
    return tables


def step_reads(config: dict, seed: int, contexts, step: int) -> list:
    """The step's reads in order: ``(block_rows, block indices)`` a call."""
    dep = _dep(config)
    grow = int(step) % dep["headroom_tokens"]
    counts = [-(-(int(c) + grow) // dep["page_tokens"]) for c in contexts]
    tables = page_tables(config, seed, contexts)
    wrows, prows = weight_rows(config), page_rows(config)
    first = -(-dep["pool_pages"] * prows // wrows)
    reads = []
    for layer, nbytes in enumerate(layer_bytes(config)):
        blocks = -(-nbytes // dep["weight_block_bytes"])
        reads.append((wrows, np.arange(first, first + blocks)))
        first += blocks
        if layer < config["num_hidden_layers"]:
            reads.append((prows, np.concatenate(
                [t[:k] for t, k in zip(tables[layer], counts)])))
    return reads


def arena_words(config: dict, seed: int) -> tuple:
    m, c = np.random.default_rng([int(seed), config["arena"]["salt"]]) \
        .integers(0, 1 << 32, size=2, dtype=np.uint64)
    return int(m) | 1, int(c)


def _mixed(h: np.ndarray, mix: int) -> np.ndarray:
    """The formula's mix of the uint32 words `h`, in place, mod 2^32."""
    t = np.right_shift(h, 16)
    np.bitwise_xor(h, t, out=h)
    np.multiply(h, np.uint32(mix), out=h)
    np.right_shift(h, 16, out=t)
    np.bitwise_xor(h, t, out=h)
    return h


def _bfloat16_copy(h: np.ndarray) -> np.ndarray:
    """The words `h` as a bfloat16 copy of the arena holds them: each
    int32 value rounded to bfloat16, then back to 32 bits (mod 2^32)."""
    import ml_dtypes
    value = h.view(np.int32).astype(np.float32).astype(ml_dtypes.bfloat16)
    return value.astype(np.float32).astype(np.int64).astype(np.uint32)


def checksum(config: dict, seed: int, contexts, step: int,
             copy=None) -> np.ndarray:
    """The (8, 128) int32 checksum of one step; `copy` maps each block's
    words to what a lower-precision arena would hold (the control)."""
    m, c = arena_words(config, seed)
    mix = int(config["arena"]["mix"])
    acc = np.zeros(TILE_WORDS, np.uint32)
    for rows, blocks in step_reads(config, seed, contexts, step):
        words = rows * ROW_WORDS
        within = (np.arange(words, dtype=np.uint64) * m % (1 << 32)) \
            .astype(np.uint32)
        per_chunk = max(1, _TILES * TILE_WORDS // words)
        for lo in range(0, len(blocks), per_chunk):
            first = blocks[lo:lo + per_chunk].astype(np.uint64) * words
            base = ((first * m + c) % (1 << 32)).astype(np.uint32)
            h = _mixed(np.add(base[:, None], within[None, :]), mix)
            if copy is not None:
                h = copy(h)
            acc += np.add.reduce(h.reshape(-1, TILE_WORDS), axis=0,
                                 dtype=np.uint32)
    return acc.view(np.int32).reshape(8, ROW_WORDS)


def bytes_read(config: dict, seed: int, contexts, step: int) -> int:
    """Bytes of the blocks the step reads (padding to whole weight blocks
    included, as the engine reads whole blocks)."""
    return sum(len(blocks) * rows * ROW_WORDS * WORD_BYTES
               for rows, blocks in step_reads(config, seed, contexts, step))


def gap(got, want) -> float:
    """The share of the checksum's words that differ from the reference's,
    bit for bit."""
    got = np.asarray(got).reshape(-1)
    want = np.asarray(want).reshape(-1)
    if got.shape != want.shape or got.dtype.itemsize != 4:
        return float("inf")
    return float(np.mean(got.view(np.uint32) != want.view(np.uint32)))


def expected(call: dict, config: dict, copy=None) -> np.ndarray:
    return checksum(config, call["seed"], call["contexts"], call["step"],
                    copy)


def control(calls: list, config: dict) -> list:
    """The calls with the checksum a bfloat16 copy of the arena gives in
    place of the kernel's: the control that must fail."""
    return [dict(c, checksum=expected(c, config, _bfloat16_copy))
            for c in calls]


def compare(calls: list, config: dict) -> dict:
    """The widest checksum gap over the sampled steps, and the steps whose
    byte count differs from the reference's."""
    worst, bytes_off = 0.0, 0
    for c in calls:
        worst = max(worst, gap(c["checksum"], expected(c, config)))
        bytes_off += int(c["bytes"] != bytes_read(
            config, c["seed"], c["contexts"], c["step"]))
    return {"checksum_gap": worst, "bytes_mismatch": float(bytes_off)}
