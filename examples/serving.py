"""The serving user flow, end to end: train a small LM, quantize it,
and serve streaming traffic through the continuous-batching engine.

Run: python examples/serving.py [--steps 120] [--no-quant]

Covers, in order:
  1. train      — transformer LM on synthetic Markov text (zero egress)
  2. quantize   — weight-only int8 (serve.quantize_params) + int8 KV
                  cache (TransformerConfig.kv_cache_dtype)
  3. serve      — DecodeEngine slot pool: mixed-length prompts, bucket
                  padding, eos retirement, admit-on-free
  4. check      — every greedy request token-matches its solo
                  generate() run (the engine's consistency contract)

The reference's closest surface is the lockstep SequenceGenerator
(reference: api/PaddleAPI.h:1025); steps 2-3 are the beyond-reference
serving stack.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import optim
from paddle_tpu.models import transformer as T
from paddle_tpu.serve import DecodeEngine, quantize_params

VOCAB, EOS = 64, 63


def make_batch(rng, batch, seq_len):
    """Order-1 Markov chains: token t+1 = (3*t + noise) % (VOCAB-1),
    easily learned, never emitting the reserved EOS id."""
    toks = np.zeros((batch, seq_len), np.int32)
    toks[:, 0] = rng.randint(0, VOCAB - 1, batch)
    for j in range(1, seq_len):
        noise = rng.randint(0, 3, batch)
        toks[:, j] = (3 * toks[:, j - 1] + noise) % (VOCAB - 1)
    return jnp.asarray(toks)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--no-quant", action="store_true")
    args = ap.parse_args()

    cfg = T.TransformerConfig(vocab=VOCAB, dim=64, n_layers=2,
                              n_heads=4, attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)
    opt = optim.adam(3e-3)
    opt_state = opt.init(params)
    rng = np.random.RandomState(0)

    @jax.jit
    def step(p, s, toks, i):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss(p, cfg, toks))(p)
        p, s = opt.update(grads, s, p, i)
        return p, s, loss

    print(f"[1/4] training {args.steps} steps ...")
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state,
                                       make_batch(rng, 16, 33),
                                       jnp.int32(i))
        if i % 40 == 0:
            print(f"   step {i:4d}  loss {float(loss):.3f}")
    print(f"   final loss {float(loss):.3f}")

    serve_cfg = cfg
    if not args.no_quant:
        print("[2/4] quantizing: int8 weights + int8 KV cache")
        params = quantize_params(params)
        serve_cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    else:
        print("[2/4] quantization skipped (--no-quant)")

    print("[3/4] serving 9 mixed-length requests through 3 slots")
    prompts = [np.asarray(make_batch(rng, 1, l))[0]
               for l in (5, 9, 13, 6, 11, 5, 8, 14, 7)]
    eng = DecodeEngine(params, serve_cfg, slots=3, max_len=48,
                       eos_id=EOS)
    # greedy requests (the consistency check below relies on them)
    # beside two seeded sampled ones — per-request sampling shares the
    # same compiled step, and the seeds make those two reproducible
    # regardless of pool co-tenancy
    sampling = [{}] * 9
    sampling[2] = {"temperature": 0.9, "top_p": 0.95, "seed": 7}
    sampling[6] = {"temperature": 0.7, "top_k": 12, "seed": 8}
    outs = eng.serve(prompts, max_new=12, buckets=(8, 16),
                     sampling=sampling)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        print(f"   req{i} (len {len(p):2d}): +{len(o)} tokens "
              f"{o[:6]}{'...' if len(o) > 6 else ''}")

    print("[4/4] consistency check vs solo generate() (greedy rows)")
    for i, (p, o) in enumerate(zip(prompts, outs)):
        if i in (2, 6):      # the sampled requests follow their own
            continue         # seeded streams, not the greedy path
        ref = T.generate(params, serve_cfg, jnp.asarray(p)[None, :],
                         steps=12, eos_id=EOS)
        ref = [int(t) for t in np.asarray(ref[0, len(p):])]
        if EOS in ref:
            ref = ref[:ref.index(EOS) + 1]
        assert o == ref, (p, o, ref)
    print("   all requests token-equal to their solo decode. done.")


if __name__ == "__main__":
    main()
