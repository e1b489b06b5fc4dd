"""Plain reference: decoder-only LM training steps in float32 `jax.numpy`.

The StarCoder2 block (Lozhkov et al., arXiv:2402.19173): pre-LayerNorm
with bias, a fused biased QKV projection with grouped-query attention
(query head h reads KV head h // (heads / kv_heads)), rotary position
embedding, causal attention inside one sliding window, a biased output
projection, and a biased 4x MLP with tanh-GELU; a final LayerNorm, a
linear head, next-token cross entropy averaged over all positions, and
Adam with bias correction. Departures, shared with the program: the
head is not tied to the embedding; the rotary pairs are the
interleaved (2i, 2i+1) lanes, not the two halves (the same function up
to a fixed permutation of each head's lanes).

It imports nothing of the program. Weights and token rows are the
benchmark's. Each block is rematerialised, attention runs over blocks of
query rows and the head over blocks of positions, so that 8k tokens at
width 3072 fit one chip beside Adam's state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from reference.quant import rounding

HI = lax.Precision.HIGHEST
LN_EPS = 1e-5
Q_ROWS = 512        # attention: query rows at a time
CE_ROWS = 1024      # head + cross entropy: positions at a time


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * p["scale"] + p["offset"]


def _dense(q, x, p):
    operand, out = q
    return out(jnp.matmul(operand(x), operand(p["kernel"]),
                          precision=HI)) + p["bias"]


def _rope(x, base):
    t, dh = x.shape[1], x.shape[-1]
    freqs = base ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, window):
    """q [B,T,H,D], k/v [B,T,Hkv,D] -> [B,T,H,D]; causal, windowed."""
    b, t, h, d = q.shape
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    rows = min(Q_ROWS, t)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def some_rows(args):
        qb, start = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HI) / jnp.sqrt(
            jnp.float32(d))
        qpos = start + jnp.arange(rows)
        ok = (kpos[None, :] <= qpos[:, None])
        if window is not None:
            ok &= (qpos[:, None] - kpos[None, :] < window)
        w = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HI)

    qs = q.reshape(b, t // rows, rows, h, d).transpose(1, 0, 2, 3, 4)
    out = lax.map(some_rows, (qs, jnp.arange(0, t, rows)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, d)


def _block(arch, qr, p, x):
    b, t, dim = x.shape
    h, hkv = arch["n_heads"], arch["n_kv_heads"]
    dh = dim // h
    qkv = _dense(qr, _layer_norm(x, p["ln1"]), p["qkv"])
    q = qkv[..., :h * dh].reshape(b, t, h, dh)
    k = qkv[..., h * dh:(h + hkv) * dh].reshape(b, t, hkv, dh)
    v = qkv[..., (h + hkv) * dh:].reshape(b, t, hkv, dh)
    a = _attention(_rope(q, arch["rope_base"]), _rope(k, arch["rope_base"]),
                   v, arch["window"])
    x = x + _dense(qr, a.reshape(b, t, dim), p["proj"])
    y = jax.nn.gelu(_dense(qr, _layer_norm(x, p["ln2"]), p["fc1"]),
                    approximate=True)
    return x + _dense(qr, y, p["fc2"])


def loss_fn(params, tokens, arch, qr):
    """tokens [B, T+1] -> mean next-token cross entropy."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = jnp.take(params["embed"]["table"], inputs, axis=0)
    for p in params["blocks"]:
        x = jax.checkpoint(functools.partial(_block, arch, qr))(p, x)
    x = _layer_norm(x, params["ln_f"])
    head = params["lm_head"]["kernel"]
    n = x.shape[0] * x.shape[1]
    rows = min(CE_ROWS, n)

    @jax.checkpoint
    def some_positions(args):
        xb, yb = args
        operand, out = qr
        logits = out(jnp.matmul(operand(xb), operand(head), precision=HI))
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, yb[:, None], axis=-1)[:, 0]

    nll = lax.map(some_positions, (x.reshape(n // rows, rows, -1),
                                   targets.reshape(n // rows, rows)))
    return jnp.mean(nll)


def make_step(arch: dict, optimizer: dict, precision: str):
    """((params, m, v, t), tokens) -> the same after one Adam step, and
    the loss. `arch`: n_heads, n_kv_heads, rope_base, window."""
    qr = rounding(precision)
    lr, b1, b2 = (optimizer["learning_rate"], optimizer.get("beta1", 0.9),
                  optimizer.get("beta2", 0.999))
    eps = optimizer.get("epsilon", 1e-8)

    def step(state, tokens):
        params, m, v, t = state
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, tokens, arch, qr))(params)
        t = t + 1.0
        lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        params = jax.tree.map(
            lambda p, a, c: p - lr_t * a / (jnp.sqrt(c) + eps), params, m, v)
        return (params, m, v, t), loss

    return jax.jit(step, donate_argnums=(0,))
