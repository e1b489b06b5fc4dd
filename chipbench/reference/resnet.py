"""Plain reference: ResNet training steps in float32 `jax.numpy`.

He et al., arXiv:1512.03385: stem 7x7/2 conv + BN + ReLU + 3x3/2 max
pool, four stages of residual blocks (two 3x3 convs, or 1x1-3x3-1x1
bottlenecks), global average pool, a linear classifier, softmax cross
entropy, SGD with momentum. Batch normalisation in training mode:
biased batch variance, running statistics moved by 0.1 towards the
batch's. One departure from the paper, shared with the program: a
stage's stride of 2 sits on the block's first 3x3 conv ("v1.5"), not on
the bottleneck's first 1x1.

It imports nothing of the program. Weights, statistics and rows are the
benchmark's (`weights.py`, the driver's pool). The block structure is
read from the names and shapes of the weights. Every block is
rematerialised so that batch 256 in float32 fits one chip.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
from jax import lax

from reference.quant import rounding

HI = lax.Precision.HIGHEST
EPS = 1e-5
BN_MOMENTUM = 0.9
BLOCK_NAME = re.compile(r"^s(\d+)_b(\d+)$")


def _conv(q, x, w, stride):
    operand, out = q
    return out(lax.conv_general_dilated(
        operand(x), operand(w), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI))


def _bn(x, p, relu):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + EPS) * p["scale"] + p["offset"]
    return (jnp.maximum(y, 0.0) if relu else y), {"mean": mean, "var": var}


def _conv_bn(q, x, params, prefix, stride, relu):
    y = _conv(q, x, params[prefix + "_conv"]["kernel"], stride)
    return _bn(y, params[prefix + "_bn"], relu)


def _block(q, name, stride, p, x):
    main, batch = x, {"main": {}}
    convs = sorted(k[:-len("_conv")] for k in p["main"] if k.endswith("_conv"))
    strided = next(c for c in convs
                   if p["main"][c + "_conv"]["kernel"].shape[0] == 3)
    for c in convs:
        main, st = _conv_bn(q, main, p["main"], c,
                            stride if c == strided else 1,
                            relu=c != convs[-1])
        batch["main"][c + "_bn"] = st
    if "shortcut" in p:
        x, st = _conv_bn(q, x, p["shortcut"], name + "_proj", stride, False)
        batch["shortcut"] = {name + "_proj_bn": st}
    return jnp.maximum(main + x, 0.0), batch


def loss_fn(params, x, labels, q):
    """-> (mean cross entropy, batch statistics of every BN)."""
    batch = {}
    y, batch["stem_bn"] = _conv_bn(q, x, params, "stem", 2, True)
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    blocks = sorted((int(m[1]), int(m[2]), m[0]) for m in
                    (BLOCK_NAME.match(k) for k in params) if m)
    for stage, i, name in blocks:
        stride = 2 if (stage > 0 and i == 0) else 1
        y, batch[name] = jax.checkpoint(
            functools.partial(_block, q, name, stride))(params[name], y)
    y = jnp.mean(y, axis=(1, 2))
    operand, out = q
    logits = out(jnp.matmul(operand(y), operand(params["logits"]["kernel"]),
                            precision=HI)) + params["logits"]["bias"]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll), batch


def make_step(optimizer: dict, precision: str):
    """(params, velocity, running stats, x, labels) -> the same after one
    step of momentum SGD, and the loss."""
    q = rounding(precision)
    lr, mu = optimizer["learning_rate"], optimizer["mu"]

    def step(params, velocity, running, x, labels):
        (loss, batch), grads = jax.value_and_grad(
            lambda p: loss_fn(p, x, labels, q), has_aux=True)(params)
        velocity = jax.tree.map(lambda v, g: mu * v + g, velocity, grads)
        params = jax.tree.map(lambda p, v: p - lr * v, params, velocity)
        running = jax.tree.map(
            lambda r, b: BN_MOMENTUM * r + (1.0 - BN_MOMENTUM) * b,
            running, batch)
        return params, velocity, running, loss

    return jax.jit(step, donate_argnums=(0, 1, 2))
