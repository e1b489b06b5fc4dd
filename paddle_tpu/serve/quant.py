"""Weight-only int8 quantization for serving.

No reference counterpart (its era predates quantized inference); this is
the TPU-native serving lever alongside GQA: autoregressive decode
re-reads every weight matrix once per generated token, so storing
matmul weights as int8 (+ one f32 scale per output channel) shrinks the
stored weights ~4x vs f32 (2x vs bf16).

Whether that also shows up as decode BANDWIDTH depends on WHERE the
dequant is traced. Dequantizing before the generation scan leaves f32
weights as loop invariants — full-precision streaming every step.
`transformer.generate` therefore detects QuantizedTensor leaves and
re-traces the dequant INSIDE the scan body: the while loop then
carries the s8 weights and XLA's loop-invariant code motion declines
to hoist the size-inflating convert back out, so each step streams s8
and fuses convert+scale into the matmul's operand read.
tests/test_compiled_cost.py asserts the compiled loop state stays s8;
the suite's `decode_int8` row measures the resulting throughput.

Usage (one-shot inference — dequant in-jit, hoisting is fine there):

    qparams = quantize_params(params)                  # offline
    fn = jax.jit(lambda qp, x: model_apply(
        dequantize_params(qp), x))                     # dequant IN-jit
    fn(qparams, x)

For decode, pass qparams straight to `transformer.generate` (or
`serve.export_decoder(..., int8_weights=True)`) — it places the
dequant per-step itself.

For the transformer decode loop the whole pattern is packaged by
`serve.export_decoder(..., int8_weights=True)`: the exported artifact
carries int8 constants with the dequant ops in the program.

Per-channel symmetric absmax quantization: q = round(w / s) with
s = absmax / 127 reduced over the INPUT axis only (axis -2) — a 2-D
[in, out] kernel gets one scale per output channel; a stacked
[E, in, out] MoE expert kernel gets per-EXPERT per-channel scales
(shape [E, out]), so one expert's outlier cannot crush every expert's
resolution. Vectors (biases, norms) and integer leaves pass through.
"""

from __future__ import annotations

import re
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class QuantizedTensor(NamedTuple):
    """int8 values + f32 scales reduced over the input axis (-2):
    shape(scale) = shape(q) with axis -2 removed."""
    q: jnp.ndarray       # int8, original shape
    scale: jnp.ndarray   # f32


# the kernel paths export_decoder and the tests share —
# matmul weights only; the embedding table is deliberately excluded (a
# gather, not a matmul; its rows feed rope/layernorm where quantization
# error compounds)
DEFAULT_MATCH = r"(qkv|proj|fc1|fc2|lm_head|w1|w2|router)"


def quantize_tensor(w) -> QuantizedTensor:
    """Symmetric absmax int8, per output channel per leading stack."""
    wf = jnp.asarray(w, jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(wf / scale[..., None, :]), -127, 127) \
        .astype(jnp.int8)
    return QuantizedTensor(q, scale.astype(jnp.float32))


def dequantize_tensor(qt: QuantizedTensor, dtype=jnp.float32):
    """q * scale — call INSIDE jit so XLA can fuse the convert+scale
    into the consuming matmul rather than materializing the tensor
    (subject to the hoisting caveat in the module docstring)."""
    return (qt.q.astype(dtype)
            * qt.scale[..., None, :].astype(dtype)).astype(dtype)


def _should_quantize(name: str, leaf, match: Optional[str]) -> bool:
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    if not jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
        return False
    if match is not None and not re.search(match, name):
        return False
    return True


def quantize_params(params, *, match: Optional[str] = DEFAULT_MATCH):
    """Quantize every matmul-kernel-shaped leaf (ndim >= 2, floating)
    whose path matches `match` (default DEFAULT_MATCH — the matmul
    kernels, embedding excluded; pass r".*" for everything, None means
    no path filter i.e. also everything). Returns the same structure
    with QuantizedTensor leaves where quantized."""
    from paddle_tpu.core.pytree import tree_map_with_name

    def fn(name, leaf):
        if _should_quantize(name, leaf, match):
            return quantize_tensor(leaf)
        return leaf

    return tree_map_with_name(fn, params)


def has_quantized(params) -> bool:
    """True if any leaf is a QuantizedTensor (the signal
    transformer.generate uses to place the dequant inside the decode
    loop body)."""
    return any(isinstance(l, QuantizedTensor) for l in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor)))


def dequantize_params(qparams, dtype=jnp.float32):
    """Inverse of quantize_params — QuantizedTensor leaves dequantize,
    everything else passes through. Call inside jit (see module doc)."""
    return jax.tree.map(
        lambda leaf: dequantize_tensor(leaf, dtype)
        if isinstance(leaf, QuantizedTensor) else leaf,
        qparams,
        is_leaf=lambda x: isinstance(x, QuantizedTensor))


def quantization_error(params, qparams) -> float:
    """Max relative per-tensor L2 error of the quantized leaves — a
    quick sanity number (per-channel int8 on trained nets is typically
    < 1%)."""
    worst = 0.0
    flat_p = jax.tree.leaves(params)
    flat_q = jax.tree.leaves(
        qparams, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    for p, q in zip(flat_p, flat_q):
        if isinstance(q, QuantizedTensor):
            d = dequantize_tensor(q)
            err = float(jnp.linalg.norm(d - p) /
                        jnp.maximum(jnp.linalg.norm(p), 1e-12))
            worst = max(worst, err)
    return worst
