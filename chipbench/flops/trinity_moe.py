"""Model FLOPs of a next-token training step of a Trinity (AfMoE) block
stack, from shapes alone, by layer kind.

6 FLOPs a position for every parameter that sits in a matrix
multiplication the position sees: every layer's fused [q | gate | k |
v] projection and output projection; the dense layers' gated-SiLU MLP;
in each expert layer the router, the shared expert, and the three
matrices of an expert for each (position, choice) row routed to an
expert held here: on average `experts_per_tok * held / router_width`
rows a position (16 of 128 held, top 8: one). The head reads every
position over the rows of the vocabulary held. Attention: a query of a
sliding layer sees min(t + 1, window) keys, of a full layer t + 1
(`flops/mellum_moe.pairs`), each pair 2 * head_dim FLOPs for the score
and for the weighted sum in the forward pass, 3x for training. Nothing
recomputed is counted; embedding lookups, norms, the gate's sigmoid,
the rotary embedding and the bias update are not counted.
"""

from __future__ import annotations

import os

from loading import HERE, load_module

pairs = load_module(os.path.join(HERE, "flops"), "mellum_moe").pairs


def arch(config: dict) -> dict:
    layers, dense = config["num_hidden_layers"], config["num_dense_layers"]
    return {"dim": config["hidden_size"], "layers": layers,
            "dense_layers": dense, "expert_layers": layers - dense,
            "layer_types": [config["layer_types"][i]
                            for i in config["layers_kept"]],
            "window": config["sliding_window"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "dense_dim": config["intermediate_size"],
            "expert_dim": config["moe_intermediate_size"],
            "shared_dim": (config["num_shared_experts"]
                           * config["moe_intermediate_size"]),
            "held": config["num_experts"], "router": config["router_width"],
            "top_k": config["num_experts_per_tok"],
            "vocab": config["vocab_size"]}


def parameters(config: dict) -> dict:
    """Counts as the program holds them (head not tied; the expert bias
    is no parameter)."""
    a = arch(config)
    d, dh = a["dim"], a["head_dim"]
    attn = (d * (2 * a["heads"] + 2 * a["kv_heads"]) * dh
            + a["heads"] * dh * d)
    norms = 4 * d + 2 * dh          # four block norms, q and k norms
    dense = 3 * d * a["dense_dim"]
    router = d * a["router"]
    shared = 3 * d * a["shared_dim"]
    expert = 3 * d * a["expert_dim"]
    dense_layer = attn + norms + dense
    expert_layer = attn + norms + router + shared + a["held"] * expert
    return {"attention": attn, "dense": dense, "router": router,
            "shared": shared, "expert": expert, "dense_layer": dense_layer,
            "expert_layer": expert_layer,
            "total": (a["dense_layers"] * dense_layer
                      + a["expert_layers"] * expert_layer
                      + 2 * d * a["vocab"] + d)}


def layer_pairs(config: dict, seq: int) -> list:
    """Admitted (query, key) pairs a head and sequence, by layer."""
    a = arch(config)
    return [pairs(seq, a["window"] if kind == "sliding_attention" else None)
            for kind in a["layer_types"]]


def train_flops_per_step(config: dict, traffic: dict) -> float:
    a, p = arch(config), parameters(config)
    batch, seq = traffic["batch"], traffic["seq"]
    positions = batch * seq
    rows_a_position = a["top_k"] * a["held"] / a["router"]
    blocks = 6.0 * positions * (
        a["layers"] * p["attention"] + a["dense_layers"] * p["dense"]
        + a["expert_layers"] * (p["router"] + p["shared"]
                                + rows_a_position * p["expert"]))
    head = 6.0 * positions * a["dim"] * a["vocab"]
    attention = (3 * 2 * 2 * a["head_dim"] * a["heads"] * batch
                 * sum(layer_pairs(config, seq)))
    return blocks + head + attention
