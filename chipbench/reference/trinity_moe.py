"""Plain reference: next-token training steps of a Trinity (AfMoE) block
stack in float32 `jax.numpy`, with the expert bias moved after each step.

The layer of Trinity-Mini (`model_type` afmoe; Arcee;
huggingface.co/arcee-ai/Trinity-Mini `config.json`). Every norm is
RMSNorm, eps `arch["rms_eps"]` (1e-5), weight only. The embedding rows
are multiplied by `arch["embed_scale"]` (sqrt(hidden_size), `mup_enabled`).
A block is

    x <- x + N_post1(attention(N_in(x)))
    x <- x + N_post2(ffn(N_pre_mlp(x)))

(sandwich norms: four a block).

**Attention**: [q | gate | k | v] = y W (q and gate 32 heads of 128, k
and v 4 heads of 128, one fused leaf), no bias; q = N(q; g_q), k = N(k;
g_k) over each head's 128 lanes; a `sliding_attention` layer turns q and
k (rotary at base `rope_base`, interleaved pairs) and its query t sees
the keys in (t - window, t]; a `full_attention` layer does not turn them
(NoPE) and sees every key <= t; softmax(q k^T / sqrt(128)) v, query head
h reading KV head h // 8; W_o (a * sigmoid(gate)).

**FFN**: the first `arch["dense_layers"]` blocks run a gated-SiLU MLP
down(silu(gate y) * (up y)). The others are the expert layer: scores s
= sigmoid(y W_r) in float32 over all the router's experts; the chosen
set S = the top `experts_per_tok` of s + b, b the layer's expert bias;
weights w_e = route_scale * s_e / (sum over S of s + 1e-20), from the
unbiased scores; **every held expert on every position, weighted by w_e
or by zero** (down(silu(gate y) * up y)); plus the shared expert of the
same form, ungated, counted once. The experts not held add nothing, as
in the program. A final norm, the untied head over the rows held, mean
next-token cross entropy.

**The bias update**, after the Adam step and outside it: c_e = the rows
routed to expert e over the step (all the router's experts, held or
not), delta_e = coeff * sign(mean(c) - c_e), b <- b + delta - mean(delta).
No auxiliary loss.

Departures, shared with the program (the configuration's `assumed` says
why): the fused [q | gate | k | v] leaf; interleaved rotary pairs; the
expert's weight on its output.

It imports nothing of the program; attention in query blocks comes from
`reference/mellum_moe.py`. Each block is rematerialised and runs a
sequence at a time, the experts and the head over blocks of positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from reference.mellum_moe import _attention, _matmul, _rms_norm, _rope
from reference.quant import rounding

HI = lax.Precision.HIGHEST
FFN_ROWS = 1024     # experts: positions at a time
CE_ROWS = 1024      # head + cross entropy: positions at a time
SLIDING, FULL = "sliding_attention", "full_attention"


def route(arch, qr, p, bias, x):
    """x [P, D] -> (weights [P, n_held]: the position's w_e for each held
    expert or zero, counts [E]: rows routed to each of the router's
    experts)."""
    s = jax.nn.sigmoid(_matmul(qr, x, p["router"]["kernel"]))
    _, top_e = lax.top_k(s + bias, arch["experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    w = arch["route_scale"] * top_s / (jnp.sum(top_s, axis=-1, keepdims=True)
                                       + 1e-20)
    n_held = p["w_gate"].shape[0]
    held = jax.nn.one_hot(top_e - arch["first_held"], n_held,
                          dtype=jnp.float32)        # zeros where not held
    counts = jnp.sum(jax.nn.one_hot(top_e, s.shape[-1], dtype=jnp.int32),
                     axis=(0, 1))
    return jnp.einsum("pk,pke->pe", w, held, precision=HI), counts


def _experts(arch, qr, p, bias, x):
    """x [P, D] -> ([P, D]: every held expert on every position, counts
    [E])."""
    operand, out = qr
    n, d = x.shape
    rows = min(FFN_ROWS, n)

    @jax.checkpoint
    def some_positions(xb):
        w, counts = route(arch, qr, p, bias, xb)
        xq = operand(xb)
        gate = out(jnp.einsum("pd,edf->epf", xq, operand(p["w_gate"]),
                              precision=HI))
        up = out(jnp.einsum("pd,edf->epf", xq, operand(p["w_up"]),
                            precision=HI))
        y = out(jnp.einsum("epf,efd->epd", operand(jax.nn.silu(gate) * up),
                           operand(p["w_down"]), precision=HI))
        return jnp.einsum("epd,pe->pd", y, w, precision=HI), counts

    y, counts = lax.map(some_positions, x.reshape(n // rows, rows, d))
    return y.reshape(n, d), jnp.sum(counts, axis=0)


def _swiglu(qr, gate, up, down, x):
    return _matmul(qr, jax.nn.silu(_matmul(qr, x, gate["kernel"]))
                   * _matmul(qr, x, up["kernel"]), down["kernel"])


def _shared_expert(qr, p, x):
    """The shared expert, ungated: x [P, D] -> [P, D]."""
    s = p["shared"]
    return _swiglu(qr, s["gate_proj"], s["up_proj"], s["down_proj"], x)


def _attention_part(arch, qr, p, x, positions, kind):
    """The block up to and with the attention's residual; `kind`: the
    layer's entry of `layer_types`."""
    b, t, _ = x.shape
    h, hkv, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps = arch["rms_eps"]
    qkv = _matmul(qr, _rms_norm(x, p["ln1"], eps), p["qkv"]["kernel"])
    q = qkv[..., :h * dh].reshape(b, t, h, dh)
    gate = qkv[..., h * dh:2 * h * dh]
    k = qkv[..., 2 * h * dh:(2 * h + hkv) * dh].reshape(b, t, hkv, dh)
    v = qkv[..., (2 * h + hkv) * dh:].reshape(b, t, hkv, dh)
    q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
    if kind == SLIDING:
        q = _rope(q, positions, arch["rope_base"])
        k = _rope(k, positions, arch["rope_base"])
    a = _attention(q, k, v, arch["window"] if kind == SLIDING else None)
    a = a.reshape(b, t, h * dh) * jax.nn.sigmoid(gate)
    return x + _rms_norm(_matmul(qr, a, p["proj"]["kernel"]),
                         p["post_ln1"], eps)


def _ffn_part(arch, qr, p, bias, x):
    """The rest of the block: norm, FFN (dense, or experts + shared
    expert), post norm, residual; and the rows routed (zeros [E] for a
    dense block)."""
    eps = arch["rms_eps"]
    y = _rms_norm(x, p["ln2"], eps).reshape(-1, x.shape[-1])
    if "mlp" in p:
        m = p["mlp"]
        f = _swiglu(qr, m["gate_proj"], m["up_proj"], m["down_proj"], y)
        counts = jnp.zeros(bias.shape, jnp.int32)
    else:
        f, counts = _experts(arch, qr, p["moe"], bias, y)
        f = f + _shared_expert(qr, p["moe"], y)
    return x + _rms_norm(f.reshape(x.shape), p["post_ln2"], eps), counts


def _block(arch, qr, kind, p, bias, x, positions):
    return _ffn_part(arch, qr, p, bias,
                     _attention_part(arch, qr, p, x, positions, kind))


def hidden(params, bias, inputs, arch, qr):
    """inputs [B, T] int -> (the final norm's output [B, T, D], counts
    [expert layers, E]). bias [expert layers, E]."""
    positions = jnp.arange(inputs.shape[1])
    x = jnp.take(params["embed"]["table"], inputs, axis=0) * arch[
        "embed_scale"]
    counts = []
    for i, (kind, p) in enumerate(zip(arch["layer_types"],
                                      params["blocks"])):
        if kind not in (SLIDING, FULL):
            raise ValueError(f"unknown layer type {kind!r}")
        dense = i < arch["dense_layers"]
        layer_bias = (jnp.zeros(bias.shape[1:], bias.dtype) if dense
                      else bias[i - arch["dense_layers"]])
        block = jax.checkpoint(functools.partial(_block, arch, qr, kind))

        def one_sequence(one, p=p, block=block, lb=layer_bias):
            y, c = block(p, lb, one[None], positions)
            return y[0], c

        # a sequence at a time: nothing of a block crosses sequences
        x, c = lax.map(one_sequence, x)
        if not dense:
            counts.append(jnp.sum(c, axis=0))
    return _rms_norm(x, params["ln_f"], arch["rms_eps"]), jnp.stack(counts)


def loss_fn(params, bias, tokens, arch, qr):
    """tokens [B, T + 1] int: position t's logits are scored against
    token t + 1; the mean over all B T positions. -> (loss, counts
    [expert layers, E])."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, counts = hidden(params, bias, inputs, arch, qr)
    head = params["lm_head"]["kernel"]
    n = inputs.size
    rows = min(CE_ROWS, n)

    @jax.checkpoint
    def some_positions(args):
        xb, yb = args
        logits = _matmul(qr, xb, head)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, yb[:, None], axis=-1)[:, 0]

    nll = lax.map(some_positions, (x.reshape(n // rows, rows, -1),
                                   targets.reshape(n // rows, rows)))
    return jnp.sum(nll) / n, counts


def update_bias(bias, counts, coeff):
    c = counts.astype(jnp.float32)
    delta = coeff * jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)
    return bias + delta - jnp.mean(delta, axis=-1, keepdims=True)


def make_step(arch: dict, optimizer: dict, precision: str):
    """((params, m, v, t, bias), tokens) -> the same after one Adam step
    and one bias update, and the loss. `arch`: n_heads, n_kv_heads,
    head_dim, rope_base, rms_eps, embed_scale, window, layer_types,
    dense_layers, experts_per_tok, first_held, route_scale,
    bias_coeff."""
    qr = rounding(precision)
    lr, b1, b2 = (optimizer["learning_rate"], optimizer.get("beta1", 0.9),
                  optimizer.get("beta2", 0.999))
    eps = optimizer.get("epsilon", 1e-8)

    def step(state, tokens):
        params, m, v, t, bias = state
        (loss, counts), grads = jax.value_and_grad(
            lambda p: loss_fn(p, bias, tokens, arch, qr), has_aux=True)(
                params)
        t = t + 1.0
        lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        params = jax.tree.map(
            lambda p, a, c: p - lr_t * a / (jnp.sqrt(c) + eps), params, m, v)
        bias = update_bias(bias, counts, arch["bias_coeff"])
        return (params, m, v, t, bias), loss

    return jax.jit(step, donate_argnums=(0,))
