"""MNIST LeNet training — the reference's v1_api_demo/mnist/api_train.py
rebuilt on the TPU-native stack.

Run: python examples/mnist_train.py [--passes 3] [--batch 64]

Uses real MNIST idx files when PADDLE_TPU_DATA_HOME provides them, the
synthetic surrogate otherwise (zero-egress environments; see README
"Real datasets").
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

from paddle_tpu import data, models, optim
from paddle_tpu.data import datasets
from paddle_tpu.nn.module import ShapeSpec
from paddle_tpu.ops import losses, metrics
from paddle_tpu.train import Trainer, events as E


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    model = models.lenet.lenet(num_classes=10)
    trainer = Trainer(
        model,
        loss_fn=lambda logits, labels: jnp.mean(
            losses.softmax_cross_entropy(logits, labels)),
        optimizer=optim.adam(args.lr),
        metrics_fn=lambda logits, labels: {
            "acc": metrics.accuracy(logits, labels)},
    )
    state = trainer.init_state(ShapeSpec((args.batch, 28, 28, 1)))

    feeder = data.DataFeeder()

    def batches():
        return feeder(data.batch_reader(
            data.reader.shuffle(datasets.mnist("train"), 4096, seed=0), args.batch))

    def handler(ev):
        if isinstance(ev, E.EndIteration) and ev.batch_id % 100 == 0:
            print(f"pass {ev.pass_id} batch {ev.batch_id} "
                  f"cost {float(ev.cost):.4f}")
        if isinstance(ev, E.EndPass):
            print(f"== pass {ev.pass_id} done")

    state = trainer.train(state, batches, num_passes=args.passes,
                          event_handler=handler)

    # held-out evaluation
    test = feeder(data.batch_reader(datasets.mnist("test"), args.batch))
    res = trainer.evaluate(state, lambda: test)
    print(f"test: cost {float(res.cost):.4f} "
          + " ".join(f"{k} {float(v):.4f}" for k, v in res.metrics.items()))


if __name__ == "__main__":
    main()
