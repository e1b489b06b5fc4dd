"""Driver `train_image`: an image classifier trained through the
program's `Trainer.train`, fed by `DataFeeder(batch_reader(reader, B))`
from a Python reader that yields one sample at a time: the path
`cli.cmd_train` drives.

The benchmark owns the reader (a pool of distinct batches made from the
seed in set-up), the weights (`weights.py`), the event handler and the
spans around `next(batch)`, the step call and the handler. The program
owns everything between: batching, stacking, the feeder thread, the
host-to-device copy, the jitted step.
"""

from __future__ import annotations

import collections
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import weights
from loading import HERE, load_module

from paddle_tpu import data, models, optim
from paddle_tpu.core import dtypes
from paddle_tpu.nn.module import ShapeSpec
from paddle_tpu.ops import losses
from paddle_tpu.train import Trainer, events as E
from paddle_tpu.train.state import TrainState


class TimedIterator:
    """Wraps the batch iterator the trainer pulls from: the time the
    training thread spends inside `next()` is the input wait."""

    def __init__(self, it, counters, spans):
        self.it, self.counters, self.spans = iter(it), counters, spans

    def __iter__(self):
        return self

    def __next__(self):
        t = time.perf_counter()
        if self.spans is not None:
            self.spans.begin("next_batch")
        try:
            return next(self.it)
        finally:
            self.counters["input_wait_s"] += time.perf_counter() - t
            if self.spans is not None:
                self.spans.end("next_batch")


class Driver:
    def __init__(self, config, traffic, seed, devices):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.batch = traffic["batch"]
        self.units_per_step = self.batch
        m = config["model"]
        self.hw, self.classes = m["image_hw"], m["num_classes"]
        self.flops = load_module(os.path.join(HERE, "flops"), config["flops"])
        self.reference = load_module(os.path.join(HERE, "reference"),
                                     config["reference"])
        self.trainer = None

    # -- the program's objects -------------------------------------------
    def _build(self):
        cfg, m = self.config, self.config["model"]
        if cfg["compute_dtype"] == "bfloat16":
            dtypes.set_default_policy(dtypes.bf16_compute_policy())
        else:
            dtypes.set_default_policy(dtypes.Policy())
        model = getattr(models.resnet, m["factory"])(
            m["depth"], num_classes=m["num_classes"], width=m["width"])
        o = cfg["optimizer"]
        opt = optim.get(o["name"], **{k: v for k, v in o.items()
                                      if k != "name"})
        loss_fn = lambda lo, la: jnp.mean(
            losses.softmax_cross_entropy(lo, la))
        self.trainer = Trainer(model, loss_fn, opt, seed=0)
        spec = ShapeSpec((self.batch, self.hw, self.hw, 3))
        self.shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), spec))
        self.opt = opt

    def _initial(self):
        """(params, running statistics) from the seed, one jitted call."""
        return jax.jit(lambda k: weights.generate(self.shapes, k))(
            weights.seed_key(self.seed))

    def _make_pool(self):
        rng = np.random.default_rng(self.seed)
        n = self.traffic["pool_batches"]
        self.pool_x = rng.random((n, self.batch, self.hw, self.hw, 3),
                                 dtype=np.float32)
        self.pool_y = rng.integers(0, self.classes, (n, self.batch))

    def _feed(self, batch_ids, spans=None):
        """A batch-iterator factory over pool batches `batch_ids` (an
        iterable, possibly endless), the way a user builds one."""
        def reader():
            for b in batch_ids:
                x, y = self.pool_x[b], self.pool_y[b]
                for i in range(self.batch):
                    yield x[i], int(y[i])

        feeder = data.DataFeeder()
        return lambda: TimedIterator(
            feeder(data.batch_reader(reader, self.batch)), self.counters,
            spans)

    # -- set-up: the steps `correct` compares are the warm-up --------------
    def setup(self):
        if self.trainer is None:
            self._build()
        self._make_pool()
        self.counters = {"input_wait_s": 0.0}
        params, mstate = self._initial()
        params0 = jax.tree.map(jnp.copy, params)
        mstate0 = jax.tree.map(jnp.copy, mstate)
        state = jax.jit(lambda p, s: TrainState.create(p, s, self.opt))(
            params, mstate)
        n = self.traffic["check_steps"]
        costs = []

        def handler(ev):
            if isinstance(ev, E.EndIteration):
                costs.append(ev)

        state = self.trainer.train(state, self._feed([0]),
                                   event_handler=handler)
        grad1 = weights.norms(state.opt_state["velocity"])
        state = self.trainer.train(state, self._feed(range(1, n)),
                                   event_handler=handler)
        p_shapes, s_shapes = self.shapes
        self.program_numbers = {
            "loss": [ev.cost for ev in costs],
            "grad1": weights.named(p_shapes, grad1),
            "dparam": weights.named(p_shapes,
                                    weights.change_norms(state.params, params0)),
            "stats": weights.named(s_shapes,
                                   weights.change_norms(state.model_state, mstate0)),
        }
        self.state = state
        self.counters["input_wait_s"] = 0.0

    # -- the measured window ------------------------------------------------
    def window(self, deadline, watcher, tracer, spans):
        lag = self.traffic["cost_read_lag"]
        n_pool = self.traffic["pool_batches"]
        pending = collections.deque()
        count = [0]

        def batch_ids():
            b = self.traffic["check_steps"]
            while time.perf_counter() < deadline:
                yield b % n_pool
                b += 1

        def handler(ev):
            if isinstance(ev, E.BeginIteration):
                spans.begin("step_call")
            elif isinstance(ev, E.EndIteration):
                spans.end("step_call")
                with spans("handler"):
                    read_cost = lambda ev=ev: ev.cost
                    watcher.put(read_cost)
                    pending.append(ev)
                    if lag is not None and len(pending) > lag:
                        pending.popleft().cost     # a logging handler
                    if tracer is not None:
                        tracer.step_dispatched(count[0], read_cost)
                    count[0] += 1

        self.state = self.trainer.train(
            self.state, self._feed(batch_ids(), spans), event_handler=handler)
        return dict(self.counters)

    def built(self) -> bool:
        return self.trainer is not None

    def free(self):
        self.state = None

    def model_flops_per_step(self):
        return self.flops.train_flops_per_step(self.config, self.traffic)

    # -- the plain reference, on the same weights and rows ------------------
    def reference_numbers(self, precision):
        step = self.reference.make_step(self.config["optimizer"], precision)
        params, running = self._initial()
        params0 = jax.tree.map(jnp.copy, params)
        running0 = jax.tree.map(jnp.copy, running)
        velocity = jax.tree.map(jnp.zeros_like, params)
        loss, grad1 = [], None
        for b in range(self.traffic["check_steps"]):
            params, velocity, running, l = step(
                params, velocity, running, jnp.asarray(self.pool_x[b]),
                jnp.asarray(self.pool_y[b], jnp.int32))
            loss.append(float(l))
            if b == 0:
                grad1 = weights.norms(velocity)
        p_shapes, s_shapes = self.shapes
        return {"loss": loss, "rank": weights.ranks(p_shapes),
                "grad1": weights.named(p_shapes, grad1),
                "dparam": weights.named(p_shapes,
                                        weights.change_norms(params, params0)),
                "stats": weights.named(s_shapes,
                                       weights.change_norms(running, running0))}
