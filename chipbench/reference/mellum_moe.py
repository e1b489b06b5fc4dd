"""Plain reference: next-token training steps of a Mellum-2 MoE block
stack in float32 `jax.numpy`.

The layer of Mellum2-12B-A2.5B-Instruct (`model_type` mellum; JetBrains;
huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct `config.json`):
pre-RMSNorm (eps 1e-6, weight only), bias-free q, k and v projections
at an explicit head size (query head h reads KV head h // (heads /
kv_heads)), an RMSNorm over each head's lanes of q and of k (one weight
vector each, shared by the heads), rotary position embedding on all
lanes, causal attention, a bias-free output projection; then RMSNorm, a
router that is a softmax over all experts in float32, the k largest
renormalised over the k chosen, and gated-SiLU experts
down(silu(gate x) * (up x)); a final RMSNorm and an untied linear head;
mean next-token cross entropy.

**Kinds of layer.** `arch["layer_types"]` names each layer
`sliding_attention` or `full_attention` (the published pattern: three
sliding, one full, repeating). A sliding layer's query t sees the keys
in (t - window, t] and its rotary angles are pos * base^(-2i/d). A full
layer sees every key <= t and its rotary embedding is YaRN's (Peng et
al., arXiv:2309.00071; `arch["yarn"]` is the published
`rope_parameters.full_attention`: factor,
original_max_position_embeddings, beta_fast, beta_slow,
attention_factor), transcribed below from the formula: with c(n) = d
ln(original / (2 pi n)) / (2 ln base), low = floor(c(beta_fast))
and high = ceil(c(beta_slow)) clipped to [0, d - 1], ramp_i = clip((i -
low) / (high - low), 0, 1), the frequency of lane pair i is inv_i /
factor * ramp_i + inv_i * (1 - ramp_i), and cos and sin are both
multiplied by the attention factor (the scores carry its square). Used
as given at every length.

**The chip's share.** The parameters hold `n_held` of the experts, from
expert `first_held` (an expert-parallel chip's). The router keeps its
published width: every position routes over all experts, and **every
held expert is applied to every position and weighted by that
position's w_e, or by zero** where the position did not choose it (no
sort, no grouping). What the experts not held would add is left out.

Departures, shared with the program: q, k and v are one fused
projection leaf (the same function); the rotary pairs are the
interleaved (2i, 2i+1) lanes, not the two halves (the same function up
to a fixed permutation of each head's lanes, which then also carries
the q / k norm weights and, on full layers, the order of the blended
frequencies); the QK-norm itself is assumed (the configuration's
`assumed` says why); no multi-token-prediction head, no auxiliary
routing term (`config.json` has a key for neither).

It imports nothing of the program. Each block is rematerialised and
runs a sequence at a time, attention over blocks of KV heads' query
groups and of query rows (dense scores, the mask built from positions),
the experts and the head over blocks of positions, so that 2 x 8192
positions at width 2304 fit one chip beside Adam's state (9.5 GB with
the gradient).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from reference.quant import rounding

HI = lax.Precision.HIGHEST
Q_ROWS = 256        # attention: query rows at a time (one KV head's group)
FFN_ROWS = 1024     # experts: positions at a time
CE_ROWS = 1024      # head + cross entropy: positions at a time


def _rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * p["scale"]


def _matmul(qr, x, w):
    operand, out = qr
    return out(jnp.matmul(operand(x), operand(w), precision=HI))


def yarn_frequencies(dh: int, base: float, yarn: dict):
    """(low, high, inv' [dh / 2]) of the formula above."""
    def lane(turns):
        return dh * math.log(yarn["original_max_position_embeddings"]
                             / (2 * math.pi * turns)) / (2 * math.log(base))

    low = max(math.floor(lane(yarn["beta_fast"])), 0)
    high = min(math.ceil(lane(yarn["beta_slow"])), dh - 1)
    inv = base ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ramp = jnp.clip((jnp.arange(dh // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return low, high, inv / yarn["factor"] * ramp + inv * (1.0 - ramp)


def _rope(x, positions, base, yarn=None):
    """x [B,T,H,D], positions [T]: interleaved pairs; `yarn` None: the
    default rotary embedding."""
    dh = x.shape[-1]
    if yarn is None:
        freqs, scale = base ** (
            -jnp.arange(0, dh, 2, dtype=jnp.float32) / dh), 1.0
    else:
        freqs, scale = (yarn_frequencies(dh, base, yarn)[2],
                        yarn["attention_factor"])
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def attends(qpos, kpos, window):
    """[len(qpos), len(kpos)] bool: key <= query, and inside the window
    (query - key < window) where the layer has one."""
    d = qpos[:, None] - kpos[None, :]
    return (d >= 0) if window is None else (d >= 0) & (d < window)


def _attention(q, k, v, window):
    """q [B,T,H,D], k/v [B,T,Hkv,D] -> [B,T,H,D]; query head h reads KV
    head h // (H / Hkv). One KV head's group of query heads and `Q_ROWS`
    query rows at a time, against all keys."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    rows = min(Q_ROWS, t)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def some_rows(args):
        qb, kh, vh, start = args        # [B,rows,G,D], [B,T,D], [B,T,D]
        s = jnp.einsum("bqgd,bkd->bgqk", qb, kh, precision=HI) / jnp.sqrt(
            jnp.float32(d))
        ok = attends(start + jnp.arange(rows), kpos, window)
        w = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", w, vh, precision=HI)

    def one_kv_head(args):
        qh, kh, vh = args               # [B,T,G,D], [B,T,D], [B,T,D]
        qs = qh.reshape(b, t // rows, rows, g, d).transpose(1, 0, 2, 3, 4)
        out = lax.map(lambda a: some_rows((a[0], kh, vh, a[1])),
                      (qs, jnp.arange(0, t, rows)))
        return out.transpose(1, 0, 2, 3, 4).reshape(b, t, g, d)

    qg = q.reshape(b, t, hkv, g, d).transpose(2, 0, 1, 3, 4)
    out = lax.map(one_kv_head, (qg, k.transpose(2, 0, 1, 3),
                                v.transpose(2, 0, 1, 3)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, t, h, d)


def route(arch, qr, p, x):
    """x [P, D] -> (weights [P, n_held]: the position's w_e for each held
    expert or zero, chosen [P, k] expert ids)."""
    s = jax.nn.softmax(_matmul(qr, x, p["router"]["kernel"]), axis=-1)
    top_p, top_e = lax.top_k(s, arch["experts_per_tok"])
    w = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    n_held = p["w_gate"].shape[0]
    held = jax.nn.one_hot(top_e - arch["first_held"], n_held,
                          dtype=jnp.float32)        # zeros where not held
    return jnp.einsum("pk,pke->pe", w, held, precision=HI), top_e


def _experts(arch, qr, p, x):
    """x [P, D] -> [P, D]: every held expert on every position."""
    operand, out = qr
    n, d = x.shape
    rows = min(FFN_ROWS, n)

    @jax.checkpoint
    def some_positions(xb):
        w, _ = route(arch, qr, p, xb)
        xq = operand(xb)
        gate = out(jnp.einsum("pd,edf->epf", xq, operand(p["w_gate"]),
                              precision=HI))
        up = out(jnp.einsum("pd,edf->epf", xq, operand(p["w_up"]),
                            precision=HI))
        y = out(jnp.einsum("epf,efd->epd", operand(jax.nn.silu(gate) * up),
                           operand(p["w_down"]), precision=HI))
        return jnp.einsum("epd,pe->pd", y, w, precision=HI)

    return lax.map(some_positions, x.reshape(n // rows, rows, d)).reshape(
        n, d)


def _attention_part(arch, qr, p, x, positions, kind):
    """The block up to and with the attention's residual; `kind`: the
    layer's entry of `layer_types`."""
    b, t, dim = x.shape
    h, hkv, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps = arch["rms_eps"]
    sliding = kind == "sliding_attention"
    yarn = None if sliding else arch["yarn"]
    qkv = _matmul(qr, _rms_norm(x, p["ln1"], eps), p["qkv"]["kernel"])
    q = qkv[..., :h * dh].reshape(b, t, h, dh)
    k = qkv[..., h * dh:(h + hkv) * dh].reshape(b, t, hkv, dh)
    v = qkv[..., (h + hkv) * dh:].reshape(b, t, hkv, dh)
    q = _rope(_rms_norm(q, p["q_norm"], eps), positions, arch["rope_base"],
              yarn)
    k = _rope(_rms_norm(k, p["k_norm"], eps), positions, arch["rope_base"],
              yarn)
    a = _attention(q, k, v, arch["window"] if sliding else None)
    return x + _matmul(qr, a.reshape(b, t, h * dh), p["proj"]["kernel"])


def _experts_part(arch, qr, p, x):
    """The rest of the block: norm, experts, residual."""
    y = _experts(arch, qr, p["moe"], _rms_norm(
        x, p["ln2"], arch["rms_eps"]).reshape(-1, x.shape[-1]))
    return x + y.reshape(x.shape)


def _block(arch, qr, kind, p, x, positions):
    return _experts_part(arch, qr, p,
                         _attention_part(arch, qr, p, x, positions, kind))


def loss_fn(params, tokens, arch, qr):
    """tokens [B, T + 1] int: position t's logits are scored against
    token t + 1; the mean over all B T positions."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    b, length = inputs.shape
    positions = jnp.arange(length)
    x = jnp.take(params["embed"]["table"], inputs, axis=0)
    for kind, p in zip(arch["layer_types"], params["blocks"]):
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"unknown layer type {kind!r}")
        block = jax.checkpoint(functools.partial(_block, arch, qr, kind))
        # a sequence at a time: nothing of a block crosses sequences
        x = lax.map(lambda one, p=p, block=block:
                    block(p, one[None], positions)[0], x)
    x = _rms_norm(x, params["ln_f"], arch["rms_eps"])
    head = params["lm_head"]["kernel"]
    n = b * length
    rows = min(CE_ROWS, n)

    @jax.checkpoint
    def some_positions(args):
        xb, yb = args
        logits = _matmul(qr, xb, head)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, yb[:, None], axis=-1)[:, 0]

    nll = lax.map(some_positions, (x.reshape(n // rows, rows, -1),
                                   targets.reshape(n // rows, rows)))
    return jnp.sum(nll) / n


def make_step(arch: dict, optimizer: dict, precision: str):
    """((params, m, v, t), tokens) -> the same after one Adam step, and
    the loss. `arch`: n_heads, n_kv_heads, head_dim, rope_base, rms_eps,
    experts_per_tok, first_held, window, layer_types, yarn."""
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
