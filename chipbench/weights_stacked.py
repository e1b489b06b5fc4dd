"""`weights.py` with one more rule: a stack of expert kernels.

`weights._leaf` has no rule for a leaf `[experts, in, out]` (its dense
rule would take the fan-in from the expert count). Here a stacked kernel
(`w_gate`, `w_up`, `w_down`, rank 3) is drawn +-1/sqrt(in) as a dense
kernel is, and every other leaf but one is handed to `weights._leaf`.

The one: the embedding `table` is drawn N(0, 1), not the initialiser's
N(0, 0.02^2). A router reads the residual stream, and the stream has to
carry the token as a trained model's does. At 0.02 the first attention's
output (a mean of values, alike for every position of uniform random
tokens) is ten times the embedding, every position's hidden state
points the same way, and **all 16,384 positions choose the same 8
experts**: a layer then holds 0 or 16,384 x n rows by the seed, never
the ~1,024 an expert that a deployment routes (measured: the fullest
held expert at 6.8 times the mean; PERF.md section 6, PR 34). At unit
scale the token decides the route, and what concentration is left is
the objective's own: the masked positions share one embedding.

Everything else of `weights` (the seed's key, norms, names, ranks) is
re-exported, so a driver imports this module in its place.
"""

from __future__ import annotations

import math

import jax

import weights
from weights import (change_norms, leaf_norms, named, norms, ranks,  # noqa: F401
                     seed_key)

STACKED = ("w_gate", "w_up", "w_down")


def _leaf(key, path, spec):
    name = str(getattr(path[-1], "key", getattr(path[-1], "idx", path[-1])))
    if name in STACKED and len(spec.shape) == 3:
        lim = 1.0 / math.sqrt(spec.shape[1])
        return jax.random.uniform(key, spec.shape, spec.dtype, -lim, lim)
    if name == "table":
        return jax.random.normal(key, spec.shape, spec.dtype)
    return weights._leaf(key, path, spec)


def generate(shapes, seed_key_):
    """`shapes`: a pytree of ShapeDtypeStruct. Traceable: call under jit."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = [_leaf(jax.random.fold_in(seed_key_, i), path, spec)
           for i, (path, spec) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)
