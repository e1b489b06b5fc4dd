"""Weights and running statistics from `--seed`, made on the device in
one jitted call, in the type the program holds them in. The program and
the plain reference are both handed what this makes; neither makes its
own. The rule for a leaf follows its last key and its rank (the
distributions are those of the program's own initialisers)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for any whole number up to 2**62 (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _leaf(key, path, spec):
    name = str(getattr(path[-1], "key", getattr(path[-1], "idx", path[-1])))
    shape, dtype = spec.shape, spec.dtype
    if name == "kernel" and len(shape) == 4:        # conv, He normal
        fan_in = shape[0] * shape[1] * shape[2]
        return math.sqrt(2.0 / fan_in) * jax.random.normal(key, shape, dtype)
    if name == "kernel":                            # dense, +-1/sqrt(fan_in)
        lim = 1.0 / math.sqrt(shape[0])
        return jax.random.uniform(key, shape, dtype, -lim, lim)
    if name == "table":
        return 0.02 * jax.random.normal(key, shape, dtype)
    if name in ("scale", "var"):
        return jnp.ones(shape, dtype)
    if name in ("bias", "offset", "mean"):
        return jnp.zeros(shape, dtype)
    raise ValueError(f"no rule for leaf {jax.tree_util.keystr(path)}")


def generate(shapes, seed_key_):
    """`shapes`: a pytree of ShapeDtypeStruct. Traceable: call under jit."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = [_leaf(jax.random.fold_in(seed_key_, i), path, spec)
           for i, (path, spec) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def leaf_norms(tree):
    """Per-leaf L2 norms in float32, as one vector in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


norms = jax.jit(leaf_norms)
change_norms = jax.jit(lambda new, old: leaf_norms(
    jax.tree.map(jnp.subtract, new, old)))


def named(tree_shapes, vector) -> dict:
    """{leaf path: float} from `leaf_norms`' vector."""
    paths = [jax.tree_util.keystr(p, simple=True, separator="/")
             for p, _ in jax.tree_util.tree_flatten_with_path(tree_shapes)[0]]
    return {p: float(v) for p, v in zip(paths, vector)}


def ranks(tree_shapes) -> dict:
    """{leaf path: number of dimensions}."""
    return {jax.tree_util.keystr(p, simple=True, separator="/"): len(x.shape)
            for p, x in jax.tree_util.tree_flatten_with_path(tree_shapes)[0]}
