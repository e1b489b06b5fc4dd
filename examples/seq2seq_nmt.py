"""Seq2seq NMT with attention + beam-search generation — the
capability the reference exercises through recurrent_group +
simple_attention + generation (reference:
trainer/tests/sample_trainer_rnn_gen.conf, networks.py simple_attention).

Trains on a synthetic copy/reverse task (zero-egress stand-in for WMT)
and decodes with beam search.

Run: python examples/seq2seq_nmt.py [--steps 300] [--beam 4]
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
from paddle_tpu.models import seq2seq_attn

BOS, EOS = 0, 1


def make_batch(rs, batch, max_len, vocab):
    """Task: target = reversed source (forces real attention use)."""
    lens = rs.randint(3, max_len + 1, batch)
    src = np.full((batch, max_len), EOS, np.int32)
    tgt = np.full((batch, max_len + 1), EOS, np.int32)
    for i, n in enumerate(lens):
        toks = rs.randint(2, vocab, n)
        src[i, :n] = toks
        tgt[i, 0] = BOS
        tgt[i, 1:n + 1] = toks[::-1]
    return (jnp.asarray(src), jnp.asarray(lens),
            jnp.asarray(tgt), jnp.asarray(lens + 1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=30)
    ap.add_argument("--beam", type=int, default=4)
    args = ap.parse_args()

    rs = np.random.RandomState(0)
    params = seq2seq_attn.init_params(
        jax.random.key(0), args.vocab, args.vocab, embed_dim=32, hidden=64)
    opt = optim.adam(2e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, src, src_lens, tgt, tgt_lens):
        loss, grads = jax.value_and_grad(
            lambda p: seq2seq_attn.loss(p, src, src_lens, tgt, tgt_lens)
        )(params)
        new_p, new_o = opt.update(grads, opt_state, params,
                                  jnp.zeros((), jnp.int32))
        return new_p, new_o, loss

    for i in range(args.steps):
        batch = make_batch(rs, args.batch, args.max_len, args.vocab)
        params, opt_state, loss = step(params, opt_state, *batch)
        if i % 50 == 0:
            print(f"step {i} loss {float(loss):.4f}")

    # beam-search decode a few held-out sources
    src, src_lens, tgt, _ = make_batch(rs, 4, args.max_len, args.vocab)
    out, scores, out_lens = seq2seq_attn.generate(
        params, src, src_lens, beam_size=args.beam,
        max_len=args.max_len + 1, bos_id=BOS, eos_id=EOS)
    ok = 0
    for i in range(4):
        n = int(src_lens[i])
        want = [int(t) for t in np.asarray(src[i, :n])[::-1]]
        best = np.asarray(out[i, 0]).tolist()  # top beam hypothesis
        got = [t for t in best if t >= 2][:n]
        ok += got == want
        print(f"src {np.asarray(src[i, :n]).tolist()} -> decoded {got} "
              f"(want {want})")
    print(f"exact reversals: {ok}/4")


if __name__ == "__main__":
    main()
