"""Model FLOPs of a decoder-only LM training step, from shapes alone.

6 FLOPs a token for every parameter that sits in a matrix multiplication
(QKV, output projection, MLP, head; the embedding is a lookup, biases
and norms are not counted), plus causal attention: 2*dim FLOPs a key for
the scores and for the weighted sum in the forward pass, 3x for
training, over the keys a query really sees (its window). Nothing
recomputed is counted.
"""

from __future__ import annotations


def arch(config: dict) -> dict:
    dim, heads = config["hidden_size"], config["num_attention_heads"]
    return {"dim": dim, "layers": config["num_hidden_layers"],
            "heads": heads, "kv_heads": config["num_key_value_heads"],
            "head_dim": dim // heads, "mlp": config["intermediate_size"],
            "vocab": config["vocab_size"], "window": config["sliding_window"]}


def parameters(config: dict) -> dict:
    """Counts as the program holds them (head not tied)."""
    a = arch(config)
    d, qkv = a["dim"], (a["heads"] + 2 * a["kv_heads"]) * a["head_dim"]
    layer_matmul = d * qkv + d * d + 2 * d * a["mlp"]
    layer_other = qkv + d + a["mlp"] + d + 4 * d        # biases, 2 norms
    return {"matmul": a["layers"] * layer_matmul + d * a["vocab"],
            "total": a["layers"] * (layer_matmul + layer_other)
            + 2 * d * a["vocab"] + 2 * d}


def mean_keys(seq: int, window) -> float:
    w = seq if window is None else min(window, seq)
    # query t (from 0) sees min(t + 1, w) keys
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def train_flops_per_token(config: dict, seq: int) -> float:
    a = arch(config)
    attn = 3 * 2 * 2 * a["dim"] * mean_keys(seq, a["window"]) * a["layers"]
    return 6.0 * parameters(config)["matmul"] + attn


def train_flops_per_step(config: dict, traffic: dict) -> float:
    return (train_flops_per_token(config, traffic["seq"])
            * traffic["batch"] * traffic["seq"])
