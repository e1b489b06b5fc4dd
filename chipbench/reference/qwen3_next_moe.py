"""Plain reference: next-token training steps of a Qwen3-Next block stack
in float32 `jax.numpy`.

The layer of Qwen3-Next-80B-A3B-Instruct (`model_type` qwen3_next; Qwen;
huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct `config.json`). Every
norm is RMSNorm, eps 1e-6, in float32. A block is h = N(x; g1), a token
mixer and its residual, then h = N(x; g2) and the expert layer and its
residual. Layer l is `full_attention` where (l + 1) % 4 == 0, else
`linear_attention` (`arch["layer_types"]`).

**Gated DeltaNet** (`linear_attention`): [q | k | v | z] = h W_qkvz (q, k
16 key heads of 128, v, z 32 value heads of 128), [b | a] = h W_ba (32
each); a causal depthwise convolution over time of [q | k | v] (kernel
4, no bias: out[t] = sum_i w[i] x[t - 3 + i]) and SiLU; beta =
sigmoid(b), g = -exp(A_log) softplus(a + dt_bias) by value head; q and
k L2-normalised over their 128 lanes (eps 1e-6), q times 128^-1/2;
value heads 2j and 2j + 1 read key head j. Then **the recurrence
position by position** (`lax.scan`, checkpointed every `SEGMENT`
positions so that its gradient fits at 8,192), per value head with S
in R^{128 x 128} from 0:

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;
    o_t = S^T q_t

and y = N(o_t; w_o) silu(z_t) by head (one w_o of 128 for all heads),
the mixer's output W_out y. Not the chunked form the program computes:
it checks the kernels from an independent formulation.

**Gated attention** (`full_attention`): [q | gate] = h W_q (16 heads of
256 each), k, v = h W_k, h W_v (2 heads of 256), no bias; q = N(q; g_q),
k = N(k; g_k) over each head's lanes; the rotary embedding on the first
`rotary_dim` (64) lanes of each head at base 1e7, the rest unturned;
causal softmax attention, 8 query heads a KV head; W_o (o *
sigmoid(gate)).

**Expert layer**: a softmax over all 512 router outputs in float32, the
top 10 renormalised over the ten; every held expert on every position,
weighted by p~_e or zero (down(silu(gate h) * up h), width 512); plus
sigmoid(h . w_sg) times the shared expert (width 512), counted once.
The experts not held add nothing, as in the program. A final norm, the
untied head over the rows held, mean next-token cross entropy.

Departures, shared with the program (the configuration's `assumed`
says why): norm weights g = 1 + w; the column layouts of W_qkvz, W_ba
and the fused [q | gate | k | v] projection; interleaved rotary pairs;
no multi-token-prediction head, no auxiliary routing term.

It imports nothing of the program; attention in query blocks and the
routed experts come from `reference/mellum_moe.py`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from reference.mellum_moe import _attention, _experts, _matmul, _rms_norm
from reference.quant import rounding

HI = lax.Precision.HIGHEST
SEGMENT = 64        # the recurrence: positions between checkpoints
CE_ROWS = 1024      # head + cross entropy: positions at a time


def _rope(x, positions, base, rotary_dim):
    """x [B,T,H,D], positions [T]: interleaved pairs of the first
    `rotary_dim` lanes at base^(-2i / rotary_dim); the rest pass."""
    turned, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    freqs = base ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                     / rotary_dim)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = turned[..., 0::2], turned[..., 1::2]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                       axis=-1).reshape(turned.shape)
    return jnp.concatenate([turned, rest], axis=-1)


def _gated_attention(arch, qr, p, x, positions):
    b, t, _ = x.shape
    h, hkv, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps = arch["rms_eps"]
    qkv = _matmul(qr, _rms_norm(x, p["ln1"], eps), p["qkv"]["kernel"])
    q = qkv[..., :h * dh].reshape(b, t, h, dh)
    gate = qkv[..., h * dh:2 * h * dh]
    k = qkv[..., 2 * h * dh:(2 * h + hkv) * dh].reshape(b, t, hkv, dh)
    v = qkv[..., (2 * h + hkv) * dh:].reshape(b, t, hkv, dh)
    rope = functools.partial(_rope, positions=positions,
                             base=arch["rope_base"],
                             rotary_dim=arch["rotary_dim"])
    q = rope(_rms_norm(q, p["q_norm"], eps))
    k = rope(_rms_norm(k, p["k_norm"], eps))
    a = _attention(q, k, v, None).reshape(b, t, h * dh) * jax.nn.sigmoid(gate)
    return x + _matmul(qr, a, p["proj"]["kernel"])


def delta_rule(q, k, v, g, beta):
    """One sequence: q, k [T, H, dk] (already by value head), v [T, H,
    dv], g, beta [T, H] -> o [T, H, dv]; the recurrence above."""
    t = q.shape[0]
    seg = min(SEGMENT, t)

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[:, None, None]
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt, precision=HI))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HI)

    @jax.checkpoint
    def segment(s, xs):
        return lax.scan(step, s, xs)

    xs = [x.reshape((t // seg, seg) + x.shape[1:]) for x in (q, k, v, g, beta)]
    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), v.dtype)
    return lax.scan(segment, s0, xs)[1].reshape(v.shape)


def _l2_normalize(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _gated_delta(arch, qr, p, x):
    b, t, _ = x.shape
    nk, nv = arch["key_heads"], arch["value_heads"]
    dk, dv, width = arch["key_dim"], arch["value_dim"], arch["conv"]
    eps = arch["rms_eps"]
    h = _rms_norm(x, p["ln1"], eps)
    qkvz = _matmul(qr, h, p["qkvz"]["kernel"])
    ba = _matmul(qr, h, p["ba"]["kernel"])
    mixed = qkvz[..., :2 * nk * dk + nv * dv]
    padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
    w = p["conv"]["kernel"]
    mixed = jax.nn.silu(sum(padded[:, i:i + t] * w[i] for i in range(width)))
    q = _l2_normalize(mixed[..., :nk * dk].reshape(b, t, nk, dk)) / jnp.sqrt(
        jnp.float32(dk))
    k = _l2_normalize(mixed[..., nk * dk:2 * nk * dk].reshape(b, t, nk, dk))
    v = mixed[..., 2 * nk * dk:].reshape(b, t, nv, dv)
    z = qkvz[..., 2 * nk * dk + nv * dv:].reshape(b, t, nv, dv)
    beta = jax.nn.sigmoid(ba[..., :nv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., nv:] + p["dt_bias"])
    by_value = lambda y: jnp.repeat(y, nv // nk, axis=2)
    o = jax.vmap(delta_rule)(by_value(q), by_value(k), v, g, beta)
    y = _rms_norm(o, p["o_norm"], eps) * jax.nn.silu(z)
    return x + _matmul(qr, y.reshape(b, t, nv * dv), p["proj"]["kernel"])


def _shared_expert(qr, p, x):
    """x [P, D] -> sigmoid(x . w_sg) * down(silu(gate x) * up x)."""
    s = p["shared"]
    hidden = (jax.nn.silu(_matmul(qr, x, s["gate_proj"]["kernel"]))
              * _matmul(qr, x, s["up_proj"]["kernel"]))
    return (jax.nn.sigmoid(_matmul(qr, x, p["shared_scale"]["kernel"]))
            * _matmul(qr, hidden, s["down_proj"]["kernel"]))


def _experts_part(arch, qr, p, x):
    h = _rms_norm(x, p["ln2"], arch["rms_eps"]).reshape(-1, x.shape[-1])
    y = _experts(arch, qr, p["moe"], h) + _shared_expert(qr, p["moe"], h)
    return x + y.reshape(x.shape)


def _block(arch, qr, kind, p, x, positions):
    if kind == "linear_attention":
        x = _gated_delta(arch, qr, p, x)
    elif kind == "full_attention":
        x = _gated_attention(arch, qr, p, x, positions)
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    return _experts_part(arch, qr, p, x)


def loss_fn(params, tokens, arch, qr):
    """tokens [B, T + 1] int: position t's logits are scored against
    token t + 1; the mean over all B T positions."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    b, length = inputs.shape
    positions = jnp.arange(length)
    x = jnp.take(params["embed"]["table"], inputs, axis=0)
    for kind, p in zip(arch["layer_types"], params["blocks"]):
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
    the loss. `arch`: n_heads, n_kv_heads, head_dim, rope_base,
    rotary_dim, rms_eps, experts_per_tok, first_held, layer_types,
    key_heads, value_heads, key_dim, value_dim, conv."""
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
