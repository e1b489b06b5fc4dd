"""Plain reference: block-diffusion training steps of an SDAR-MoE block
stack in float32 `jax.numpy`.

The layer of SDAR-30B-A3B-Chat (`model_type` sdar_moe; JetLM, SDAR,
arXiv:2510.06303): pre-RMSNorm (eps 1e-6, weight only), bias-free q, k
and v projections at an explicit head size (query head h reads KV head
h // (heads / kv_heads)), an RMSNorm over each head's lanes of q and of
k (one weight vector each, shared by the heads), rotary position
embedding, attention under the block-diffusion mask, a bias-free output
projection; then RMSNorm, a router that is a softmax over all experts in
float32, the k largest renormalised over the k chosen, and gated-SiLU
experts down(silu(gate x) * (up x)); a final RMSNorm and an untied
linear head.

**The chip's share.** The parameters hold `n_held` of the experts, from
expert `first_held` (an expert-parallel chip's). The router keeps its
published width: every position routes over all experts, and **every
held expert is applied to every position and weighted by that
position's w_e, or by zero** where the position did not choose it (no
sort, no grouping). What the experts not held would add is left out.

**The objective** (BD3-LM's vectorised form, Arriola et al.,
arXiv:2503.09573, as SDAR trains): the model sees 2L positions, ids
[xt ; x0] at position ids [0..L-1 ; 0..L-1], xt = x0 with the `masked`
tokens replaced by the mask id. With blk(i) = (i mod L) // Bd a query
attends a key iff

    noised query, noised key   blk(k) == blk(q)
    noised query, clean key    blk(k) <  blk(q)
    clean query,  clean key    blk(k) <= blk(q)
    clean query,  noised key   never

and loss = sum over masked (b, i) of nll(b, i) / p(b, i) / (B L), the
logits read at the noised position i itself (no shift). The noise
(`masked`, `p`) is data, handed in with the tokens.

Departures, shared with the program: q, k and v are one fused
projection leaf (the same function); the rotary pairs are the
interleaved (2i, 2i+1) lanes, not the two halves (the same function up
to a fixed permutation of each head's lanes, which then also carries
the q / k norm weights).

It imports nothing of the program. Each block is rematerialised and
runs a sequence at a time, attention over blocks of query rows, the
experts and the head over blocks of positions, so that 16k positions at
width 2048 fit one chip beside Adam's state (10.3 GB with the gradient).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from reference.quant import rounding

HI = lax.Precision.HIGHEST
Q_ROWS = 128        # attention: query rows at a time
FFN_ROWS = 1024     # experts: positions at a time
CE_ROWS = 1024      # head + cross entropy: positions at a time


def _rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * p["scale"]


def _matmul(qr, x, w):
    operand, out = qr
    return out(jnp.matmul(operand(x), operand(w), precision=HI))


def _rope(x, positions, base):
    """x [B,T,H,D], positions [T]: interleaved pairs."""
    dh = x.shape[-1]
    freqs = base ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def attends(qpos, kpos, length, bd):
    """The table above: [len(qpos), len(kpos)] bool."""
    qn, kn = (qpos < length)[:, None], (kpos < length)[None, :]
    qb = ((qpos % length) // bd)[:, None]
    kb = ((kpos % length) // bd)[None, :]
    return ((qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))


def _attention(q, k, v, length, bd):
    """q [B,2L,H,D], k/v [B,2L,Hkv,D] -> [B,2L,H,D]; query head h reads
    KV head h // (H / Hkv)."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    rows = min(Q_ROWS, t)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def some_rows(args):
        qb, start = args                                # [B,rows,Hkv,G,D]
        s = jnp.einsum("bqngd,bknd->bngqk", qb, k, precision=HI) / jnp.sqrt(
            jnp.float32(d))
        ok = attends(start + jnp.arange(rows), kpos, length, bd)
        w = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return jnp.einsum("bngqk,bknd->bqngd", w, v, precision=HI)

    qs = q.reshape(b, t // rows, rows, hkv, h // hkv, d).transpose(
        1, 0, 2, 3, 4, 5)
    out = lax.map(some_rows, (qs, jnp.arange(0, t, rows)))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, h, d)


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


def _attention_part(arch, qr, p, x, positions):
    """The block up to and with the attention's residual."""
    b, t, dim = x.shape
    h, hkv, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps = arch["rms_eps"]
    qkv = _matmul(qr, _rms_norm(x, p["ln1"], eps), p["qkv"]["kernel"])
    q = qkv[..., :h * dh].reshape(b, t, h, dh)
    k = qkv[..., h * dh:(h + hkv) * dh].reshape(b, t, hkv, dh)
    v = qkv[..., (h + hkv) * dh:].reshape(b, t, hkv, dh)
    q = _rope(_rms_norm(q, p["q_norm"], eps), positions, arch["rope_base"])
    k = _rope(_rms_norm(k, p["k_norm"], eps), positions, arch["rope_base"])
    a = _attention(q, k, v, t // 2, arch["block_length"])
    return x + _matmul(qr, a.reshape(b, t, h * dh), p["proj"]["kernel"])


def _experts_part(arch, qr, p, x):
    """The rest of the block: norm, experts, residual."""
    y = _experts(arch, qr, p["moe"], _rms_norm(
        x, p["ln2"], arch["rms_eps"]).reshape(-1, x.shape[-1]))
    return x + y.reshape(x.shape)


def _block(arch, qr, p, x, positions):
    return _experts_part(arch, qr, p,
                         _attention_part(arch, qr, p, x, positions))


def loss_fn(params, batch, arch, qr):
    """batch: (tokens [B, L] int, masked [B, L] bool, p [B, L] float)."""
    tokens, masked, prob = batch
    b, length = tokens.shape
    noised = jnp.where(masked, arch["mask_id"], tokens)
    ids = jnp.concatenate([noised, tokens], axis=1)
    positions = jnp.tile(jnp.arange(length), 2)
    x = jnp.take(params["embed"]["table"], ids, axis=0)
    block = jax.checkpoint(functools.partial(_block, arch, qr))
    for p in params["blocks"]:
        # a sequence at a time: nothing of a block crosses sequences
        x = lax.map(lambda one, p=p: block(p, one[None], positions)[0], x)
    x = _rms_norm(x[:, :length], params["ln_f"], arch["rms_eps"])
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
                                   tokens.reshape(n // rows, rows)))
    weight = masked.astype(jnp.float32) / prob
    return jnp.sum(nll.reshape(b, length) * weight) / n


def chosen_experts(params, batch, arch, qr=None):
    """[layers, B * 2L, k]: the experts each position chose in each
    layer, for counting how often two precisions disagree. `qr`: an
    (operand, out) pair as `quant.rounding` gives; float32 if None."""
    qr = qr or rounding("float32")
    tokens, masked, _ = batch
    length = tokens.shape[1]
    ids = jnp.concatenate(
        [jnp.where(masked, arch["mask_id"], tokens), tokens], axis=1)
    positions = jnp.tile(jnp.arange(length), 2)
    x = jnp.take(params["embed"]["table"], ids, axis=0)
    chosen = []
    for p in params["blocks"]:
        y = _attention_part(arch, qr, p, x, positions)
        chosen.append(route(arch, qr, p["moe"], _rms_norm(
            y, p["ln2"], arch["rms_eps"]).reshape(-1, y.shape[-1]))[1])
        x = _block(arch, qr, p, x, positions)
    return jnp.stack(chosen)


def make_step(arch: dict, optimizer: dict, precision: str):
    """((params, m, v, t), batch) -> the same after one Adam step, and
    the loss. `arch`: n_heads, n_kv_heads, head_dim, rope_base, rms_eps,
    experts_per_tok, first_held, block_length, mask_id."""
    qr = rounding(precision)
    lr, b1, b2 = (optimizer["learning_rate"], optimizer.get("beta1", 0.9),
                  optimizer.get("beta2", 0.999))
    eps = optimizer.get("epsilon", 1e-8)

    def step(state, batch):
        params, m, v, t = state
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, arch, qr))(params)
        t = t + 1.0
        lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        params = jax.tree.map(
            lambda p, a, c: p - lr_t * a / (jnp.sqrt(c) + eps), params, m, v)
        return (params, m, v, t), loss

    return jax.jit(step, donate_argnums=(0,))
