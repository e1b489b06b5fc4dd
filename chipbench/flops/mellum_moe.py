"""Model FLOPs of a next-token training step of a Mellum-2 MoE block
stack, from shapes alone, by layer kind.

6 FLOPs a position for every parameter that sits in a matrix
multiplication the position sees: the fused QKV, the output projection
and the router for every position, and the three matrices of an expert
for each (position, choice) row routed to an expert held here: on
average `experts_per_tok * held / router_width` rows a position (with
16 of 64 held and top 8, two). The head reads every position over the
rows of the vocabulary held. Attention: a query of a sliding layer sees
min(t + 1, window) keys, of a full layer t + 1 (`pairs`), each pair 2 *
head_dim FLOPs for the score and for the weighted sum in the forward
pass, 3x for training. Nothing recomputed is counted; embedding lookups,
norms and the rotary embedding are not counted.
"""

from __future__ import annotations

import os

from loading import HERE, load_module

#: the block's parameters are counted as the other dropless
#: configuration's are (same leaves, same keys of the configuration)
parameters = load_module(os.path.join(HERE, "flops"), "sdar_moe").parameters


def arch(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    return {"dim": config["hidden_size"], "layers": layers,
            "layer_types": config["layer_types"][:layers],
            "window": config["sliding_window"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "expert_dim": config["moe_intermediate_size"],
            "held": config["num_experts"], "router": config["router_width"],
            "top_k": config["num_experts_per_tok"],
            "vocab": config["vocab_size"]}


def pairs(seq: int, window) -> int:
    """Admitted (query, key) pairs a head and sequence: causal, inside
    the window where the layer has one."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def layer_pairs(config: dict, seq: int) -> list:
    """`pairs` of each layer, by its kind."""
    a = arch(config)
    return [pairs(seq, a["window"] if kind == "sliding_attention" else None)
            for kind in a["layer_types"]]


def train_flops_per_step(config: dict, traffic: dict) -> float:
    a, p = arch(config), parameters(config)
    batch, seq = traffic["batch"], traffic["seq"]
    positions = batch * seq
    rows_a_position = a["top_k"] * a["held"] / a["router"]
    blocks = 6.0 * positions * a["layers"] * (
        p["attention"] + p["router"] + rows_a_position * p["expert"])
    head = 6.0 * positions * a["dim"] * a["vocab"]
    attention = (3 * 2 * 2 * a["head_dim"] * a["heads"] * batch
                 * sum(layer_pairs(config, seq)))
    return blocks + head + attention
