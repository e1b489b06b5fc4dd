"""On-chip check of the Pallas kernels `auto` dispatch can select.

Each kernel is compiled natively (no interpreter), forward and
backward, at its consumer's shapes and dtypes, run, and compared with
the plain-XLA path computed on the same chip. One JSON line per check;
the exit code is non-zero if any check failed or ran past its
wall-clock bound (`faulthandler` then dumps every thread's stack and
ends the process — a compile that never returns must not hold the
chip). docs/KERNELS.md's per-kernel table is this script's output.

    python benchmarks/kernel_check.py            # on the chip, via the tool

The LSTM checks come last: that kernel is the one whose only earlier
compile attempt never returned.

The errors printed are relative Frobenius errors against a float32
`default_matmul_precision("highest")` reference. Smoke timings are
wall-clock for the compiling call and one further call, not metrics.
"""

from __future__ import annotations

import faulthandler
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import dtypes
from paddle_tpu.core.devices import require_chip
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import rnn
from paddle_tpu.ops.flash_attention import _forward_blocks, flash_attention

#: seconds one check (compile + two runs + reference) may take
BOUND_S = 300
#: relative error allowed against the f32 reference. One bound for both
#: dtypes: at the default matmul precision the MXU rounds float32
#: operands to bf16 too (2^-9), in the kernels and in XLA alike, and a
#: 100-step recurrence compounds it. Measured on the v5e: 0.2-0.4%.
TOL = 2e-2


def _rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return out, t1 - t0, time.perf_counter() - t1


def _report(name, dtype, errs, first_s, again_s, **shape) -> bool:
    ok = all(np.isfinite(e) and e <= TOL for e in errs.values())
    print(json.dumps({
        "kernel": name, **shape, "dtype": jnp.dtype(dtype).name,
        "ok": ok, "tol": TOL,
        "rel_err": {k: float(f"{v:.3g}") for k, v in errs.items()},
        "first_call_s": round(first_s, 2), "next_call_s": round(again_s, 4),
    }), flush=True)
    return ok


def check_flash(dtype, *, b, t, h, d, window=None, lens=None,
                block_diffusion=None) -> bool:
    """Forward (on the blocks `_forward_blocks` takes from the shape)
    and both backward kernels against dense attention: causal, or under
    the block-diffusion mask (L, Bd) over t = 2L positions."""
    causal = block_diffusion is None
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v, w = (jax.random.normal(kk, (b, t, h, d), jnp.float32)
                  .astype(dtype) for kk in ks)
    key_lens = None if lens is None else jnp.asarray(lens, jnp.int32)
    mask = None if lens is None else (
        jnp.arange(t, dtype=jnp.int32)[None, :] < key_lens[:, None])

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, window=window,
                            key_lens=key_lens,
                            block_diffusion=block_diffusion)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    def dense_loss(q, k, v):
        o = T._dense_attention(q, k, v, causal, mask, window,
                               block_diffusion=block_diffusion)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    f = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2),
                                   has_aux=True))
    ((_, o), grads), first_s, again_s = _timed(f, q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, o_ref), g_ref = jax.jit(jax.value_and_grad(
            dense_loss, argnums=(0, 1, 2), has_aux=True))(
                *(x.astype(jnp.float32) for x in (q, k, v)))
    errs = {"o": _rel(o, o_ref)}
    errs.update({n: _rel(g, gr)
                 for n, g, gr in zip(("dq", "dk", "dv"), grads, g_ref)})
    return _report("flash_attention", dtype, errs, first_s, again_s,
                   b=b, t=t, heads=h, head_dim=d, window=window,
                   key_lens=lens is not None,
                   block_diffusion=block_diffusion,
                   fwd_blocks=list(_forward_blocks(t, t, d, dtype)))


def check_rnn(name, run_fn, init_fn, *, hidden, b, t, policy) -> bool:
    dtypes.set_default_policy(policy)
    params = init_fn(jax.random.key(1), hidden, hidden)
    x = jax.random.normal(jax.random.key(2), (b, t, hidden), jnp.float32)
    lens = jnp.asarray(
        np.random.RandomState(0).randint(t // 2, t + 1, b), jnp.int32)
    w = jax.random.normal(jax.random.key(3), (b, t, hidden), jnp.float32)

    def loss(impl):
        def f(p, x):
            out, _ = run_fn(p, x, lens, impl=impl)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))

    ((_, out), (gp, gx)), first_s, again_s = _timed(
        loss("pallas"), params, x)
    dtypes.set_default_policy(dtypes.Policy())
    with jax.default_matmul_precision("highest"):
        (_, o_ref), (gp_ref, gx_ref) = loss("xla")(params, x)
    errs = {"out": _rel(out, o_ref), "dx": _rel(gx, gx_ref),
            "dw_hh": _rel(gp["w_hh"], gp_ref["w_hh"]),
            "dw_ih": _rel(gp["w_ih"], gp_ref["w_ih"])}
    return _report(name, policy.compute_dtype, errs, first_s, again_s,
                   hidden=hidden, b=b, t=t)


def main() -> int:
    devices, _ = require_chip()
    print(json.dumps({"platform": devices[0].platform,
                      "device_kind": devices[0].device_kind,
                      "count": len(devices), "jax": jax.__version__}),
          flush=True)
    flash = functools.partial(check_flash, jnp.bfloat16, b=2, t=2048)
    checks = [
        functools.partial(flash, h=8, d=64),
        functools.partial(flash, h=4, d=128),
        functools.partial(flash, h=8, d=64, window=512),
        functools.partial(check_flash, jnp.bfloat16, b=2, t=1024, h=8, d=64,
                          lens=[1024, 300]),
        functools.partial(check_flash, jnp.float32, b=1, t=1024, h=8, d=64),
        # starcoder2_3b_l4.train_seq4k's attention: 4095 positions, head
        # 128, window inert; four backward blocks of 1024 on each axis
        functools.partial(check_flash, jnp.bfloat16, b=1, t=4095, h=12,
                          d=128, window=4096),
        # the two LM cells' forward at the chooser's 1024 x 1024 blocks,
        # all three kinds of block in a row (6 / 4 / 6 and 12 / 12 / 40):
        # the cells' lengths, head size and dtype on fewer heads, for the
        # dense reference's [heads, t, t] float32 scores
        functools.partial(check_flash, jnp.bfloat16, b=1, t=4096, h=6,
                          d=128),
        functools.partial(check_flash, jnp.bfloat16, b=1, t=8192, h=2,
                          d=128, block_diffusion=(4096, 4)),
        # lengths 1024 would pad further than a 256 x 512 grid: odd blocks
        functools.partial(check_flash, jnp.bfloat16, b=2, t=1280, h=8,
                          d=128),
        functools.partial(check_flash, jnp.bfloat16, b=3, t=1536, h=4,
                          d=128, lens=[1536, 1024, 1100]),
    ]
    policies = (dtypes.bf16_compute_policy(), dtypes.Policy())
    for policy in policies:
        checks += [
            # seq2seq-attention encoder/decoder cell (ROADMAP workload
            # 4: hidden 512, batch 64, 30 tokens)
            functools.partial(check_rnn, "fused_gru", rnn.gru,
                              rnn.init_gru_params, hidden=512, b=64, t=30,
                              policy=policy),
            functools.partial(check_rnn, "fused_simple_rnn", rnn.simple_rnn,
                              rnn.init_rnn_params, hidden=512, b=64, t=100,
                              policy=policy),
        ]
    # the LSTM text classifier (ROADMAP workload 4), h256 then h512, last
    checks += [
        functools.partial(check_rnn, "fused_lstm", rnn.lstm,
                          rnn.init_lstm_params, hidden=hidden, b=64, t=100,
                          policy=policy)
        for policy in policies for hidden in (256, 512)]
    ok = True
    for check in checks:
        faulthandler.dump_traceback_later(BOUND_S, exit=True)
        try:
            ok = check() and ok
        except Exception as e:   # report the compiler's words, go on
            ok = False
            print(json.dumps({"ok": False, "error": type(e).__name__,
                              "message": str(e)[:2000]}), flush=True)
        faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
