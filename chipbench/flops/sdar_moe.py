"""Model FLOPs of a block-diffusion training step of an SDAR-MoE block
stack, from shapes alone.

A sequence of L data tokens puts 2L positions through the blocks (the
noised copy and the clean copy). 6 FLOPs a position for every parameter
that sits in a matrix multiplication the position sees: the fused QKV,
the output projection and the router for every position, and the three
matrices of an expert for each (position, choice) row routed to an
expert held here: on average `experts_per_tok * held / router_width`
rows a position (with 16 of 128 held and top 8, one). The head reads
the B L noised positions only. Attention under the block-diffusion
mask admits L^2 + L Bd pairs a head and sequence (L Bd noised-noised,
(L^2 - L Bd) / 2 noised-clean, (L^2 + L Bd) / 2 clean-clean), each 2 *
head_dim FLOPs for the score and for the weighted sum in the forward
pass, 3x for training. Nothing recomputed is counted; embedding lookups
and norms are not counted.
"""

from __future__ import annotations


def arch(config: dict) -> dict:
    return {"dim": config["hidden_size"], "layers": config["num_hidden_layers"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "expert_dim": config["moe_intermediate_size"],
            "held": config["num_experts"], "router": config["router_width"],
            "top_k": config["num_experts_per_tok"],
            "vocab": config["vocab_size"]}


def parameters(config: dict) -> dict:
    """Counts as the program holds them (head not tied)."""
    a = arch(config)
    d, dh = a["dim"], a["head_dim"]
    attn = d * (a["heads"] + 2 * a["kv_heads"]) * dh + a["heads"] * dh * d
    router = d * a["router"]
    expert = 3 * d * a["expert_dim"]
    norms = 2 * d + 2 * dh                  # two block norms, q and k norms
    layer = attn + router + a["held"] * expert + norms
    return {"attention": attn, "router": router, "expert": expert,
            "layer": layer,
            "total": a["layers"] * layer + 2 * d * a["vocab"] + d}


def pairs(seq: int, block_length: int) -> int:
    """Admitted (query, key) pairs a head and sequence."""
    return seq * seq + seq * block_length


def train_flops_per_step(config: dict, traffic: dict) -> float:
    a, p = arch(config), parameters(config)
    batch, seq = traffic["batch"], traffic["seq"]
    positions = batch * 2 * seq
    rows_a_position = a["top_k"] * a["held"] / a["router"]
    blocks = 6.0 * positions * a["layers"] * (
        p["attention"] + p["router"] + rows_a_position * p["expert"])
    head = 6.0 * batch * seq * a["dim"] * a["vocab"]
    attention = (3 * 2 * 2 * a["head_dim"] * a["heads"] * batch
                 * pairs(seq, traffic["block_length"]) * a["layers"])
    return blocks + head + attention
