"""The bytes a decode step must read, as the benchmark reckons them: the
held weights' bf16 bytes, exactly, and every page the step reads, from
the configuration's widths and the batch's contexts (never from the
program): sum over held layers and sequences of ceil((ctx + t mod
headroom) / page_tokens) pages of page_tokens x (kv_lora_rank +
qk_rope_head_dim) x 2 bytes."""
from bench.references import decode_gather as reference


def page_bytes(config: dict) -> int:
    dep = config["deployment"]
    return (dep["page_tokens"] * (config["kv_lora_rank"]
                                  + config["qk_rope_head_dim"])
            * dep["value_bytes"])


def step_pages(config: dict, contexts, step: int) -> int:
    dep = config["deployment"]
    grow = int(step) % dep["headroom_tokens"]
    return config["num_hidden_layers"] * sum(
        -(-(int(c) + grow) // dep["page_tokens"]) for c in contexts)


def stream_bytes(config: dict, contexts, step: int) -> int:
    return (sum(reference.layer_bytes(config))
            + step_pages(config, contexts, step) * page_bytes(config))
