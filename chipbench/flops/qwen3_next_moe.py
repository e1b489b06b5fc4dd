"""Model FLOPs of a next-token training step of a Qwen3-Next block stack,
from shapes alone, by layer kind.

6 FLOPs a position for every parameter that sits in a matrix
multiplication the position sees: a Gated DeltaNet layer's [q | k | v |
z], [b | a] and output projections, a gated attention layer's [q |
gate | k | v] and output projections, every layer's router, shared
expert and shared expert's scale; and the three matrices of a routed
expert for each (position, choice) row routed to an expert held here:
on average `experts_per_tok * held / router_width` rows a position
(with 32 of 512 held and top 10, 0.625). The head reads every position
over the rows of the vocabulary held. Token mixing: a full layer's
query sees t + 1 keys, each pair 2 * head_dim FLOPs for the score and
for the weighted sum in the forward pass; a Gated DeltaNet layer's
position and value head 6 dk dv (`flops/gated_delta.py`); 3x each for
training. Nothing recomputed is counted; embedding lookups, the
convolution, norms and the rotary embedding are not counted.
"""

from __future__ import annotations

import os

from loading import HERE, load_module

LINEAR, FULL = "linear_attention", "full_attention"


def arch(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    return {"dim": config["hidden_size"], "layers": layers,
            "layer_types": config["layer_types"][:layers],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "key_heads": config["linear_num_key_heads"],
            "value_heads": config["linear_num_value_heads"],
            "key_dim": config["linear_key_head_dim"],
            "value_dim": config["linear_value_head_dim"],
            "conv": config["linear_conv_kernel_dim"],
            "expert_dim": config["moe_intermediate_size"],
            "shared_dim": config["shared_expert_intermediate_size"],
            "held": config["num_experts"], "router": config["router_width"],
            "top_k": config["num_experts_per_tok"],
            "vocab": config["vocab_size"]}


def parameters(config: dict) -> dict:
    """Counts as the program holds them (head not tied)."""
    a = arch(config)
    d, dh = a["dim"], a["head_dim"]
    kd, vd = a["key_heads"] * a["key_dim"], a["value_heads"] * a["value_dim"]
    gated_delta = d * (2 * kd + 2 * vd) + d * 2 * a["value_heads"] + vd * d
    gated_delta_other = (a["conv"] * (2 * kd + vd) + 2 * a["value_heads"]
                         + a["value_dim"])           # conv, A_log, dt_bias, norm
    attention = d * (2 * a["heads"] + 2 * a["kv_heads"]) * dh + a["heads"] * dh * d
    router = d * a["router"]
    expert = 3 * d * a["expert_dim"]
    shared = 3 * d * a["shared_dim"] + d
    ffn = router + a["held"] * expert + shared + 2 * d          # two norms
    layer = {LINEAR: gated_delta + gated_delta_other + ffn,
             FULL: attention + 2 * dh + ffn}                    # q, k norms
    return {"gated_delta": gated_delta, "attention": attention,
            "router": router, "expert": expert, "shared": shared,
            "layer": layer,
            "total": (sum(layer[k] for k in a["layer_types"])
                      + 2 * d * a["vocab"] + d)}


def train_flops_per_step(config: dict, traffic: dict) -> float:
    a, p = arch(config), parameters(config)
    batch, seq = traffic["batch"], traffic["seq"]
    positions = batch * seq
    rows_a_position = a["top_k"] * a["held"] / a["router"]
    ffn = p["router"] + rows_a_position * p["expert"] + p["shared"]
    mixer = {LINEAR: p["gated_delta"], FULL: p["attention"]}
    blocks = 6.0 * positions * sum(mixer[k] + ffn for k in a["layer_types"])
    head = 6.0 * positions * a["dim"] * a["vocab"]
    full = a["layer_types"].count(FULL)
    attention = (3 * 2 * 2 * a["head_dim"] * a["heads"] * batch
                 * seq * (seq + 1) // 2 * full)
    gd = load_module(os.path.join(HERE, "flops"), "gated_delta")
    recurrence = 3 * gd.forward(rows=batch * a["value_heads"], seq=seq,
                                dk=a["key_dim"], dv=a["value_dim"],
                                bytes_per_value=2)[0]
    return (blocks + head + attention
            + recurrence * a["layer_types"].count(LINEAR))
