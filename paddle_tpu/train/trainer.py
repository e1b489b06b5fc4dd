"""Event-driven trainer.

The TPU-native replacement for the reference's training drivers: the v2
Python SGD trainer loop (reference: python/paddle/v2/trainer.py:124) on
top, and paddle_trainer's TrainerInternal::trainOneBatch hot loop
(reference: trainer/TrainerInternal.cpp:66) compiled into ONE jitted
train_step — forward, backward, optimizer update and metric accumulation
all fuse into a single XLA program per batch, replacing the reference's
per-layer virtual dispatch + pipelined updater callbacks.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.nn.module import Layer, merge_state
from paddle_tpu.obs.trace import Timeline, default_timeline
from paddle_tpu.optim.optimizers import Optimizer
from paddle_tpu.train import events as E
from paddle_tpu.train.state import TrainState

LossFn = Callable[..., Any]


def make_train_step(
    model: Layer,
    loss_fn: LossFn,
    optimizer: Optimizer,
    *,
    metrics_fn: Optional[Callable] = None,
    donate: bool = True,
    remat: bool = False,
    accum_steps: int = 1,
    constrain_state_fn: Optional[Callable] = None,
    aux_loss_weight: float = 0.0,
):
    """Build the jitted train step.

    loss_fn(outputs, *labels) -> scalar loss.
    aux_loss_weight>0 adds that multiple of every `aux_loss` leaf found
    in the model state to the cost (layers like nn.MoE surface their
    load-balance regularizer this way).
    metrics_fn(outputs, *labels) -> dict of scalar metrics (optional).
    remat=True rematerialises the forward during the backward
    (jax.checkpoint) — trades FLOPs for HBM on long sequences / deep
    nets (the reference had no activation checkpointing; its long-seq
    memory grew linearly, SURVEY §5).
    accum_steps>1 splits the batch into that many microbatches, runs
    forward/backward per microbatch under lax.scan and applies ONE
    optimizer update on the averaged gradients (the batch size must be
    divisible). Loss/metrics are microbatch means.
    constrain_state_fn(new_state) -> new_state may pin shardings on the
    updated state (used by the sharded step builder).
    The returned step: (state: TrainState, rng, inputs, labels) ->
    (new_state, loss, metrics).
    """

    def apply_model(params, mstate, rng, *inputs):
        return model.apply(params, mstate, *inputs, training=True, rng=rng)

    if remat:
        apply_model = jax.checkpoint(apply_model)

    def fwd_bwd(params, mstate, rng, inputs, labels):
        def compute_loss(p):
            out, new_mstate = apply_model(p, mstate, rng, *inputs)
            with jax.named_scope("loss"):
                loss = loss_fn(out, *labels)
            if aux_loss_weight:
                for path, leaf in jax.tree_util.tree_leaves_with_path(
                        new_mstate):
                    key = getattr(path[-1], "key", None) if path else None
                    if key == "aux_loss":
                        loss = loss + aux_loss_weight * leaf
            return loss, (out, new_mstate)

        (loss, (out, new_mstate)), grads = jax.value_and_grad(
            compute_loss, has_aux=True
        )(params)
        metrics = metrics_fn(out, *labels) if metrics_fn else {}
        return loss, new_mstate, grads, metrics

    def step(state: TrainState, rng, inputs, labels):
        inputs = inputs if isinstance(inputs, tuple) else (inputs,)
        labels = labels if isinstance(labels, tuple) else (labels,)

        if accum_steps == 1:
            loss, new_mstate, grads, metrics = fwd_bwd(
                state.params, state.model_state, rng, inputs, labels)
        else:
            def split(x):
                if x.shape[0] % accum_steps != 0:
                    raise ValueError(
                        f"batch {x.shape[0]} not divisible by "
                        f"accum_steps={accum_steps}")
                return x.reshape((accum_steps, -1) + x.shape[1:])

            m_inputs = jax.tree.map(split, inputs)
            m_labels = jax.tree.map(split, labels)
            rngs = jax.random.split(rng, accum_steps)

            def body(carry, xs):
                mstate, grad_acc, loss_acc, metric_acc = carry
                rng_t, inp_t, lab_t = xs
                loss, new_mstate, grads, metrics = fwd_bwd(
                    state.params, mstate, rng_t, inp_t, lab_t)
                grad_acc = jax.tree.map(jnp.add, grad_acc, grads)
                metric_acc = jax.tree.map(jnp.add, metric_acc, metrics)
                return (merge_state(mstate, new_mstate), grad_acc,
                        loss_acc + loss, metric_acc), None

            zeros_like_f32 = lambda p: jnp.zeros(p.shape, jnp.float32)
            metric0 = {}
            if metrics_fn:
                probe = jax.eval_shape(
                    lambda: metrics_fn(
                        model.apply(state.params, state.model_state,
                                    *jax.tree.map(lambda x: x[0], m_inputs),
                                    training=True, rng=rng)[0],
                        *jax.tree.map(lambda x: x[0], m_labels)))
                metric0 = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, jnp.float32), probe)
            init = (state.model_state,
                    jax.tree.map(zeros_like_f32, state.params),
                    jnp.zeros((), jnp.float32), metric0)
            (new_mstate, grads, loss, metrics), _ = jax.lax.scan(
                body, init, (rngs, m_inputs, m_labels))
            inv = 1.0 / accum_steps
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss = loss * inv
            metrics = jax.tree.map(lambda m: m * inv, metrics)

        new_params, new_opt = optimizer.update(
            grads, state.opt_state, state.params, state.step
        )
        new_state = TrainState(
            params=new_params,
            model_state=merge_state(state.model_state, new_mstate),
            opt_state=new_opt,
            step=state.step + 1,
        )
        if constrain_state_fn is not None:
            new_state = constrain_state_fn(new_state)
        return new_state, loss, metrics

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_eval_step(model: Layer, loss_fn: LossFn, *, metrics_fn=None,
                   return_outputs: bool = False):
    def step(state: TrainState, inputs, labels):
        inputs = inputs if isinstance(inputs, tuple) else (inputs,)
        labels = labels if isinstance(labels, tuple) else (labels,)
        out, _ = model.apply(state.params, state.model_state, *inputs, training=False)
        loss = loss_fn(out, *labels)
        metrics = metrics_fn(out, *labels) if metrics_fn else {}
        if return_outputs:
            return loss, metrics, out
        return loss, metrics

    return jax.jit(step)


class Trainer:
    """Event-driven training driver (reference: v2 SGD + TrainerInternal).

    batches are (inputs, labels) pairs or tuples from a DataFeeder; splitting
    a raw tuple is controlled by num_inputs (first num_inputs entries are
    model inputs, the rest go to the loss).

    timeline: where the loop times itself (obs.trace.Timeline; the
    process default unless given): one `trainer.step` row an iteration
    with `trainer.next_batch`, `trainer.dispatch` and `trainer.handler`
    inside it (docs/OBSERVABILITY.md § Training timeline).
    """

    def __init__(
        self,
        model: Layer,
        loss_fn: LossFn,
        optimizer: Optimizer,
        *,
        metrics_fn: Optional[Callable] = None,
        num_inputs: int = 1,
        seed: int = 0,
        remat: bool = False,
        aux_loss_weight: float = 0.0,
        timeline: Optional[Timeline] = None,
    ):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.metrics_fn = metrics_fn
        self.num_inputs = num_inputs
        # kept so wrappers (train.resilience) can rebuild an equivalent
        # step with different donation/optimizer settings
        self.remat = remat
        self.aux_loss_weight = aux_loss_weight
        self._rng = jax.random.key(seed)
        self.timeline = timeline if timeline is not None \
            else default_timeline()
        self._train_step = make_train_step(
            model, loss_fn, optimizer, metrics_fn=metrics_fn, remat=remat,
            aux_loss_weight=aux_loss_weight,
        )
        self._eval_step = make_eval_step(model, loss_fn, metrics_fn=metrics_fn)

    def init_state(self, *input_specs) -> TrainState:
        self._rng, init_rng = jax.random.split(self._rng)
        params, mstate = self.model.init(init_rng, *input_specs)
        return TrainState.create(params, mstate, self.optimizer)

    def check_gradients(self, state: TrainState, batch, *,
                        eps: float = 1e-3, num_directions: int = 4,
                        seed: int = 0) -> float:
        """`--job=checkgrad` equivalent (reference: Trainer::checkGradient,
        trainer/Trainer.cpp:303-377): compare the autodiff directional
        derivative against a central finite difference along random
        parameter directions. Returns the worst relative error."""
        from paddle_tpu.core import dtypes

        inputs, labels = self._split_batch(batch)
        rng = jax.random.key(seed)
        # the check needs double precision: a float32 forward drowns the
        # central difference in rounding noise. Enable x64 for the
        # duration (the reference's checkgrad is likewise its own job).
        x64_was_on = bool(jax.config.jax_enable_x64)
        old_policy = dtypes.default_policy()
        check_dtype = jnp.float64
        try:
            if not x64_was_on:
                jax.config.update("jax_enable_x64", True)
            dtypes.set_default_policy(dtypes.Policy(
                compute_dtype=check_dtype, accum_dtype=check_dtype))
            params0 = jax.tree.map(lambda p: p.astype(check_dtype),
                                   state.params)
            inputs = tuple(
                x.astype(check_dtype) if jnp.issubdtype(
                    jnp.asarray(x).dtype, jnp.floating) else x
                for x in inputs)

            def scalar_loss(params):
                outs, _ = self.model.apply(params, state.model_state,
                                           *inputs, training=False, rng=None)
                # same convention as make_train_step: the raw model output
                # (tuple or single) is loss_fn's first argument
                return jnp.asarray(self.loss_fn(outs, *labels), check_dtype)

            return self._check_gradients_impl(
                scalar_loss, params0, rng, eps, num_directions)
        finally:
            dtypes.set_default_policy(old_policy)
            if not x64_was_on:
                jax.config.update("jax_enable_x64", False)

    def _check_gradients_impl(self, scalar_loss, params0, rng, eps,
                              num_directions) -> float:
        grads = jax.grad(scalar_loss)(params0)
        worst = 0.0
        leaves, treedef = jax.tree_util.tree_flatten(params0)
        for i in range(num_directions):
            rng, sub = jax.random.split(rng)
            dirs = [jax.random.normal(r, l.shape, l.dtype)
                    for r, l in zip(
                        jax.random.split(sub, len(leaves)), leaves)]
            norm = jnp.sqrt(sum(jnp.vdot(d, d).real for d in dirs))
            dirs = [d / norm for d in dirs]
            direction = jax.tree_util.tree_unflatten(treedef, dirs)
            analytic = sum(
                jnp.vdot(g, d).real for g, d in zip(
                    jax.tree_util.tree_leaves(grads), dirs))
            plus = jax.tree.map(lambda p, d: p + eps * d, params0,
                                direction)
            minus = jax.tree.map(lambda p, d: p - eps * d, params0,
                                 direction)
            numeric = (scalar_loss(plus) - scalar_loss(minus)) / (2 * eps)
            denom = max(abs(float(numeric)), abs(float(analytic)), 1e-12)
            rel = abs(float(numeric) - float(analytic)) / denom
            worst = max(worst, rel)
        return worst

    def _split_batch(self, batch):
        if isinstance(batch, tuple) and len(batch) > self.num_inputs:
            return tuple(batch[: self.num_inputs]), tuple(batch[self.num_inputs :])
        raise ValueError(
            f"batch of {len(batch)} fields with num_inputs={self.num_inputs}"
        )

    def train(
        self,
        state: TrainState,
        batch_iter_factory: Callable[[], Iterable],
        *,
        num_passes: int = 1,
        event_handler: Optional[Callable] = None,
        test_iter_factory: Optional[Callable[[], Iterable]] = None,
        checkpoint_manager=None,
        checkpoint_every_n_batches: Optional[int] = None,
        parameter_stats_period: Optional[int] = None,
    ) -> TrainState:
        """checkpoint_manager: train.CheckpointManager; saves every pass
        end, plus every checkpoint_every_n_batches batches if set
        (reference: save_dir + saving_period flags,
        trainer/Trainer.cpp:60-89).
        parameter_stats_period: print per-parameter magnitude dumps every
        N batches (reference: show_parameter_stats_period,
        trainer/TrainerInternal.cpp:186 showParameterStats)."""
        handler = event_handler or (lambda ev: None)
        tl = self.timeline
        end = object()

        def periodic(state, pass_id, batch_id):
            # rare; inside `trainer.step`, so it shows as its self time
            if (parameter_stats_period
                    and (batch_id + 1) % parameter_stats_period == 0):
                from paddle_tpu.metrics.printer import (
                    format_parameter_stats, parameter_stats)

                print(f"--- parameter stats (pass {pass_id} batch "  # graftlint: disable=GL007(user-facing parameter-stats dump, opt-in via parameter_stats_period)
                      f"{batch_id}) ---")
                print(format_parameter_stats(  # graftlint: disable=GL007(user-facing parameter-stats dump, opt-in via parameter_stats_period)
                    parameter_stats(state.params)))
            if (checkpoint_manager is not None
                    and checkpoint_every_n_batches
                    and (batch_id + 1) % checkpoint_every_n_batches == 0):
                checkpoint_manager.save(state)

        for pass_id in range(num_passes):
            handler(E.BeginPass(pass_id))
            # an explicit next(), so that the wait for a batch has an
            # interval of its own
            batches = iter(batch_iter_factory())
            batch_id = 0
            while True:
                with tl.span("trainer.step", batch_id) as step_span:
                    with tl.span("trainer.next_batch", batch_id) as wait:
                        batch = next(batches, end)
                        if batch is end:    # no batch: no row
                            wait.discard()
                            step_span.discard()
                            break
                    with tl.span("trainer.handler", batch_id):
                        handler(E.BeginIteration(pass_id, batch_id))
                    with tl.span("trainer.dispatch", batch_id):
                        inputs, labels = self._split_batch(batch)
                        self._rng, step_rng = jax.random.split(self._rng)
                        state, loss, metrics = self._train_step(
                            state, step_rng, inputs, labels
                        )
                    tl.count("trainer.steps")
                    # loss/metrics stay ON DEVICE: the event materializes
                    # them only if the handler reads .cost/.metrics, so
                    # the hot loop keeps dispatching asynchronously
                    with tl.span("trainer.handler", batch_id):
                        handler(E.EndIteration(pass_id, batch_id, cost=loss,
                                               metrics=metrics))
                    periodic(state, pass_id, batch_id)
                batch_id += 1
            if (checkpoint_manager is not None
                    and checkpoint_manager.latest_step() != int(state.step)):
                checkpoint_manager.save(state)
            results: Dict[str, float] = {}
            if test_iter_factory is not None:
                test_res = self.evaluate(state, test_iter_factory)
                results = {"test_cost": test_res.cost, **test_res.metrics}
                handler(E.TestResult(pass_id, test_res.cost, test_res.metrics))
            handler(E.EndPass(pass_id, results))
        return state

    def evaluate(self, state: TrainState, batch_iter_factory,
                 evaluators=None) -> E.TestResult:
        """Streaming evaluation; `evaluators` (metrics.Evaluator objects,
        reference: gserver/evaluators/) get update(outputs, *labels) per
        batch and their results merged into the returned metrics."""
        total, n = 0.0, 0
        agg: Dict[str, float] = {}
        eval_step = self._eval_step
        if evaluators:
            if not hasattr(self, "_eval_step_with_outputs"):
                self._eval_step_with_outputs = make_eval_step(
                    self.model, self.loss_fn, metrics_fn=self.metrics_fn,
                    return_outputs=True)
            eval_step = self._eval_step_with_outputs
            for ev in evaluators:
                ev.reset()
        for batch in batch_iter_factory():
            inputs, labels = self._split_batch(batch)
            if evaluators:
                loss, metrics, out = eval_step(state, inputs, labels)
                for ev in evaluators:
                    ev.update(np.asarray(out), *[np.asarray(l) for l in labels])
            else:
                loss, metrics = eval_step(state, inputs, labels)
            total += float(loss)
            for k, v in metrics.items():
                agg[k] = agg.get(k, 0.0) + float(v)
            n += 1
        n = max(n, 1)
        results = {k: v / n for k, v in agg.items()}
        if evaluators:
            seen: Dict[str, int] = {}
            for ev in evaluators:
                # disambiguate same-named evaluators: second one becomes
                # "name#1" etc. instead of silently overwriting
                count = seen.get(ev.name, 0)
                seen[ev.name] = count + 1
                base = ev.name if count == 0 else f"{ev.name}#{count}"
                r = ev.result()
                if isinstance(r, dict):
                    for k, v in r.items():
                        results[f"{base}/{k}"] = v
                else:
                    results[base] = r
        return E.TestResult(-1, total / n, results)
