"""`weights_stacked.py` with the rules of a Gated DeltaNet layer's two
vectors by value head, as Mamba draws them:

    A_log    log U(1, 16)        (the decay rate exp(A_log) in [1, 16])
    dt_bias  softplus^-1 of U(0.001, 0.1)

Every other leaf is `weights_stacked`'s; the seed's key, norms, names
and ranks are re-exported, so a driver imports this module in its
place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import weights_stacked
from weights_stacked import (change_norms, leaf_norms, named, norms,  # noqa: F401
                             ranks, seed_key)


def _leaf(key, path, spec):
    name = str(getattr(path[-1], "key", getattr(path[-1], "idx", path[-1])))
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, spec.shape, spec.dtype, 1.0,
                                          16.0))
    if name == "dt_bias":
        dt = jax.random.uniform(key, spec.shape, spec.dtype, 1e-3, 0.1)
        return dt + jnp.log(-jnp.expm1(-dt))
    return weights_stacked._leaf(key, path, spec)


def generate(shapes, seed_key_):
    """`shapes`: a pytree of ShapeDtypeStruct. Traceable: call under jit."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = [_leaf(jax.random.fold_in(seed_key_, i), path, spec)
           for i, (path, spec) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)
