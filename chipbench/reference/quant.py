"""Operand rounding for the control of `correct`: the reference computed
one precision below what a configuration states.

"fp8" is the usual fp8 training recipe. In the forward pass both
operands of every matrix multiplication and convolution are rounded to
float8 e4m3 (one absmax scale per tensor), with a straight-through
gradient; in the backward pass the gradient that enters each of those
operations is rounded to float8 e5m2 the same way. A reference wraps an
operation as `out(op(operand(x), operand(w)))`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rounded(x, dtype, largest):
    scale = jnp.max(jnp.abs(x)) / largest
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


def _e4m3_operand(x):
    return x + jax.lax.stop_gradient(
        _rounded(x, jnp.float8_e4m3fn, 448.0) - x)


@jax.custom_vjp
def _e5m2_gradient(x):
    return x


_e5m2_gradient.defvjp(
    lambda x: (x, None),
    lambda _, g: (_rounded(g, jnp.float8_e5m2, 57344.0),))


def rounding(precision: str):
    """-> (operand, out): what to wrap an operation's operands and its
    result in."""
    if precision == "float32":
        return (lambda x: x), (lambda x: x)
    if precision == "fp8":
        return _e4m3_operand, _e5m2_gradient
    raise ValueError(f"unknown reference precision {precision!r}")
