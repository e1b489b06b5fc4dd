"""Capture and summarize a TPU profile of the ResNet-50 train step.

Usage: python benchmarks/profile_step.py [--batch 256] [--model resnet50]

Dumps a jax.profiler trace, then parses the xplane with xprof's converter
to print the top self-time ops — the evidence the MFU work (VERDICT round
1 item 1) is driven by.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def build_step(name: str, batch: int, hw: int = 224):
    from paddle_tpu import optim
    from paddle_tpu.core import dtypes
    from paddle_tpu.nn.module import ShapeSpec
    from paddle_tpu.ops import losses
    from paddle_tpu.train.state import TrainState
    from paddle_tpu.train.trainer import make_train_step

    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    from benchmarks.suite import _image_model

    model = _image_model(name)
    rng = jax.random.key(0)
    params, mstate = model.init(rng, ShapeSpec((batch, hw, hw, 3)))
    opt = optim.momentum(0.1, mu=0.9)
    state = TrainState.create(params, mstate, opt)
    step = make_train_step(
        model, lambda lo, la: jnp.mean(losses.softmax_cross_entropy(lo, la)),
        opt, donate=True)
    x = jnp.asarray(np.random.RandomState(0).rand(batch, hw, hw, 3), jnp.float32)
    y = jnp.asarray(np.random.RandomState(1).randint(0, 1000, batch))
    return step, state, rng, x, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--logdir", default="/tmp/pt_trace")
    args = ap.parse_args()

    from paddle_tpu.core.devices import require_chip

    require_chip()      # a device profile comes from a chip run only
    step, state, rng, x, y = build_step(args.model, args.batch)
    state, loss, _ = step(state, rng, (x,), (y,))
    float(loss)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        state, loss, _ = step(state, rng, (x,), (y,))
    float(loss)
    dt = (time.perf_counter() - t0) / args.iters
    print(f"ms/batch={1000*dt:.2f} imgs/sec={args.batch/dt:.1f}")

    os.makedirs(args.logdir, exist_ok=True)
    with jax.profiler.trace(args.logdir):
        for _ in range(args.iters):
            state, loss, _ = step(state, rng, (x,), (y,))
        float(loss)

    planes = sorted(glob.glob(args.logdir + "/**/*.xplane.pb", recursive=True))
    if not planes:
        print("no xplane captured", file=sys.stderr)
        return
    plane = planes[-1]
    from xprof.convert import raw_to_tool_data as rtd

    data, _ = rtd.xspace_to_tool_data([plane], "hlo_stats", {})
    if isinstance(data, bytes):
        data = data.decode()
    parsed = json.loads(data)
    tbl = parsed[0] if isinstance(parsed, list) else parsed
    rows = [[c["v"] for c in r["c"]] for r in tbl["rows"]]
    # columns (xprof hlo_stats): 2=category, 4=op text, 9=total self us,
    # 14=model GFLOP/s, 17=HBM GiB/s, 21=bound-by
    rows.sort(key=lambda r: -r[9])
    total_us = sum(r[9] for r in rows)
    print(f"device total: {total_us / 1000 / args.iters:.2f} ms/step")
    print(f"{'self_ms/step':>12s} {'%':>5s} {'GFLOP/s':>8s} {'GiB/s':>7s} "
          f"{'bound':>6s}  op")
    for r in rows[:25]:
        txt = str(r[4])[:90].replace("\n", " ")
        print(f"{r[9] / 1000 / args.iters:12.3f} {100 * r[9] / total_us:5.1f} "
              f"{r[14]:8.0f} {r[17]:7.0f} {str(r[21]):>6s}  {txt}")


if __name__ == "__main__":
    main()
