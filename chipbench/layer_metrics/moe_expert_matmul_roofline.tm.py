"""`moe_expert_matmul_roofline.py` for a stack whose leading layers are
dense: the rows of a product are the window's mean over the expert
layers alone (`num_hidden_layers - num_dense_layers`), not over every
layer."""

import os

from loading import HERE, load_module

shared = load_module(os.path.join(HERE, "layer_metrics"),
                     "moe_expert_matmul_roofline")


def read(ctx):
    c = ctx["config"]
    experts_only = {**c, "num_hidden_layers": (c["num_hidden_layers"]
                                               - c.get("num_dense_layers", 0))}
    return shared.read({**ctx, "config": experts_only})
