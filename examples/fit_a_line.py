"""Linear regression on UCI housing — the reference's first book chapter
(reference: python/paddle/v2/fluid/tests/book/test_fit_a_line.py: one fc
to a single output, squared-error cost, SGD) on the TPU-native stack.

Run: python examples/fit_a_line.py [--passes 20] [--batch 32]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

from paddle_tpu import data, nn, optim
from paddle_tpu.data import datasets
from paddle_tpu.nn.module import ShapeSpec
from paddle_tpu.ops import losses
from paddle_tpu.train import Trainer, events as E


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=20)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-2)
    args = ap.parse_args()

    model = nn.Dense(1, name="predict")
    trainer = Trainer(
        model,
        loss_fn=lambda pred, y: jnp.mean(
            losses.squared_error(pred[:, 0], y)),
        optimizer=optim.sgd(args.lr),
    )
    state = trainer.init_state(ShapeSpec((args.batch, 13)))

    feeder = data.DataFeeder()

    def batches():
        return feeder(data.batch_reader(
            data.reader.shuffle(datasets.uci_housing("train"), 512, seed=0),
            args.batch))

    def handler(ev):
        if isinstance(ev, E.EndIteration) and ev.batch_id == 0:
            print(f"pass {ev.pass_id} cost {float(ev.cost):.4f}")

    state = trainer.train(state, batches, num_passes=args.passes,
                          event_handler=handler)

    x, y = next(iter(batches()))
    pred, _ = model.apply(state.params, state.model_state, x,
                          training=False)
    print("sample predictions vs labels:")
    for i in range(5):
        print(f"  pred {float(pred[i, 0]):8.2f}   label {float(y[i]):8.2f}")


if __name__ == "__main__":
    main()
