"""Train a small transformer LM and sample from it — the modern
flagship's user flow (dense or MoE, long-context ready).

Run: python examples/transformer_lm.py [--steps 200] [--moe]
     python examples/transformer_lm.py --block-diffusion [--steps 200]
     python examples/transformer_lm.py --layer-pattern sliding,sliding,sliding,full

`--block-diffusion` trains the same toy task with the block-diffusion
objective (`T.block_diffusion_loss`) on a toy SDAR-style block: RMSNorm,
bias-free projections, QK-norm, heads of their own size, and a dropless
mixture of 8 gated-SiLU experts, top 2.

`--layer-pattern` trains the next-token objective on a block whose
layers differ in kind: the config's `layer_types` names each layer's
kind and `attention_kinds` gives a kind its window and its rotary
scaling (`T.AttentionKind`). Here "sliding" attends the last `--window`
positions with the plain rotary embedding and "full" attends every
earlier position with YaRN (`rope_scaling="yarn"`: factor 4 over an
original context of half the sequence); the FFN is the dropless expert
layer, whose counts `T.loss_and_aux` hands back beside the loss.
Decoding such a model is not implemented: the run ends with the loss.

The task is character-level copy-structure text (synthetic, zero
egress): sequences follow an order-1 Markov chain, so a small model
learns it quickly and greedy samples show the learned structure.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import optim
from paddle_tpu.models import transformer as T


def make_batch(rng, vocab, batch, seq_len):
    toks = np.empty((batch, seq_len), np.int32)
    toks[:, 0] = rng.randint(0, vocab, batch)
    for t in range(1, seq_len):
        toks[:, t] = (3 * toks[:, t - 1] + rng.randint(0, 5, batch)) % vocab
    return jnp.asarray(toks)


def train_block_diffusion(args, block_length=4):
    """The block-diffusion objective: the noise is drawn a step, the
    loss returns the expert layer's counts beside its value, and the
    loop that reads the loss adds them to the default timeline."""
    from paddle_tpu.obs.trace import default_timeline
    from paddle_tpu.parallel import moe

    cfg = T.TransformerConfig(
        vocab=args.vocab + 1, dim=args.dim, n_layers=args.layers, n_heads=4,
        n_kv_heads=2, head_size=32, norm="rms", bias=False, qk_norm=True,
        moe_router="dropless", moe_experts=8, moe_every=1, moe_k=2,
        moe_dim=2 * args.dim, attn_impl="auto")      # last id: the mask
    params = T.init_params(jax.random.key(0), cfg)
    opt = optim.adam(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, toks, rng, i):
        masked, p = T.block_diffusion_noise(rng, toks, block_length)
        (loss, stats), grads = jax.value_and_grad(
            lambda q: T.block_diffusion_loss(
                q, cfg, toks, masked, p, block_length=block_length),
            has_aux=True)(params)
        params, opt_state = opt.update(grads, opt_state, params, i)
        return params, opt_state, loss, stats

    r = np.random.RandomState(0)
    for i in range(args.steps):
        toks = make_batch(r, args.vocab, args.batch, args.seq_len)
        params, opt_state, loss, stats = step(
            params, opt_state, toks, jax.random.key(i), jnp.asarray(i))
        if i % 50 == 0 or i == args.steps - 1:
            moe.count_dropless_stats(stats, positions=2 * toks.size)
            print(f"step {i:4d}  loss {float(loss):.4f}")
    print("expert layer counters:", {
        k: v for k, v in default_timeline().counters().items()
        if k.startswith("moe.")})


def train_layer_kinds(args):
    """Attention kind by layer, read at trace time by the one block
    body; the loop that reads the loss counts the expert layer's rows."""
    from paddle_tpu.obs.trace import default_timeline
    from paddle_tpu.parallel import moe

    pattern = tuple(args.layer_pattern.split(","))
    kinds = (("sliding", T.AttentionKind(window=args.window)),
             ("full", T.AttentionKind(rope_scaling="yarn", rope_factor=4.0,
                                      rope_original=args.seq_len // 2)))
    cfg = T.TransformerConfig(
        vocab=args.vocab, dim=args.dim, n_layers=len(pattern), n_heads=4,
        n_kv_heads=2, head_size=32, norm="rms", bias=False, qk_norm=True,
        layer_types=pattern,
        attention_kinds=tuple(k for k in kinds if k[0] in pattern),
        moe_router="dropless", moe_experts=8, moe_every=1, moe_k=2,
        moe_dim=2 * args.dim, attn_impl="auto")
    params = T.init_params(jax.random.key(0), cfg)
    opt = optim.adam(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, toks, i):
        (loss, stats), grads = jax.value_and_grad(
            lambda q: T.loss_and_aux(q, cfg, toks), has_aux=True)(params)
        params, opt_state = opt.update(grads, opt_state, params, i)
        return params, opt_state, loss, stats

    r = np.random.RandomState(0)
    for i in range(args.steps):
        toks = make_batch(r, args.vocab, args.batch, args.seq_len)
        params, opt_state, loss, stats = step(params, opt_state, toks,
                                              jnp.asarray(i))
        if i % 50 == 0 or i == args.steps - 1:
            moe.count_dropless_stats(
                stats, positions=toks.shape[0] * (toks.shape[1] - 1))
            print(f"step {i:4d}  loss {float(loss):.4f}")
    print("layer kinds:", ", ".join(
        f"{i}: {name} (window {cfg.attention_kind(i).window}, rope "
        f"{cfg.attention_kind(i).rope_scaling})"
        for i, name in enumerate(pattern)))
    print("expert layer counters:", {
        k: v for k, v in default_timeline().counters().items()
        if k.startswith("moe.")})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--moe", action="store_true",
                    help="sparse FFN blocks (4 experts, top-2)")
    ap.add_argument("--block-diffusion", action="store_true",
                    help="block-diffusion objective on a dropless-MoE block")
    ap.add_argument("--layer-pattern", default=None, metavar="KIND,KIND,...",
                    help="attention kind by layer, one of sliding | full a "
                    "layer (TransformerConfig.layer_types): sliding attends "
                    "the last --window positions, full every earlier one "
                    "with YaRN rotary scaling; dropless-MoE block, "
                    "next-token objective, no decoding")
    ap.add_argument("--window", type=int, default=16,
                    help="window of the sliding kind of --layer-pattern")
    args = ap.parse_args()
    if args.block_diffusion:
        return train_block_diffusion(args)
    if args.layer_pattern:
        return train_layer_kinds(args)

    cfg = T.TransformerConfig(
        vocab=args.vocab, dim=args.dim, n_layers=args.layers, n_heads=4,
        attn_impl="auto",
        moe_experts=4 if args.moe else 0, moe_capacity_factor=2.0)
    params = T.init_params(jax.random.key(0), cfg)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"{'MoE' if args.moe else 'dense'} transformer: "
          f"{n_params:,} parameters")

    opt = optim.adam(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, toks, i):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss(p, cfg, toks))(params)
        params, opt_state = opt.update(grads, opt_state, params, i)
        return params, opt_state, loss

    r = np.random.RandomState(0)
    for i in range(args.steps):
        toks = make_batch(r, args.vocab, args.batch, args.seq_len)
        params, opt_state, loss = step(params, opt_state, toks,
                                       jnp.asarray(i))
        if i % 50 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")

    prompt = make_batch(np.random.RandomState(7), args.vocab, 2, 8)
    out = T.generate(params, cfg, prompt, steps=12)
    print("greedy samples (prompt | continuation):")
    for row in np.asarray(out):
        print(" ", [int(v) for v in row[:8]], "|",
              [int(v) for v in row[8:]])
    # the learned rule is next = (3*tok + U[0,5)) % vocab — check the
    # first continuation step obeys it for both samples
    ok = all((row[8] - 3 * row[7]) % args.vocab < 5 for row in np.asarray(out))
    print("continuations obey the chain rule:", ok)

    # --- serving: the whole decode loop as one int8 artifact ---------
    # (the reference served generation from a live SequenceGenerator;
    # here prefill + scan + weights compile into a single file any
    # jax-only process can run — no model code, quantized weights)
    import tempfile

    from paddle_tpu.serve import export_decoder, load_compiled_model

    path = os.path.join(tempfile.mkdtemp(), "lm_decoder.ptc")
    export_decoder(params, cfg, path, batch=2, prompt_len=8, steps=12,
                   int8_weights=True)
    served = load_compiled_model(path)
    served_out = np.asarray(served.predict(np.asarray(prompt)))
    # agreement over the CONTINUATIONS only (the prompt echo is free)
    match = (served_out[:, 8:] == np.asarray(out)[:, 8:]).mean()
    print(f"served int8 decoder: {os.path.getsize(path)/1e3:.0f} kB "
          f"artifact, {match:.0%} continuation agreement with the "
          "full-precision in-process decode")


if __name__ == "__main__":
    main()
