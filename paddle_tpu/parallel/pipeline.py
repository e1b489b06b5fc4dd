"""Pipeline parallelism over a `pipe` mesh axis (GPipe-style).

The reference has NO pipeline engine (SURVEY §2.8: interleaved pipeline
absent — its model parallelism is device-pinned layers,
ParallelNeuralNetwork.cpp); this is the TPU-native extra that completes
the mesh-axis family {data, model, seq, PIPE}: S homogeneous stages'
parameters live stacked on a leading axis sharded over `pipe` (each
device holds ONE stage), microbatches stream through a lax.scan over
ticks with lax.ppermute handing activations to the next stage — the
compiler-friendly pipelining idiom (static shapes, no host control
flow). Backward is jax autodiff through the scan+ppermute program
(ppermute's transpose is the reverse permute), giving a GPipe-schedule
training step without hand-written reverse plumbing.

Constraints (standard for stacked-stage pipelining): all stages share
one structure/shape (e.g. N identical residual/transformer blocks), and
the activation shape is constant across stages.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.parallel import blocked_matmul

PIPE_AXIS = "pipe"


def stack_stage_params(per_stage_params) -> dict:
    """Stack a list of S identical-structure param pytrees into one
    pytree with leading dim S (shard it P('pipe') via
    shard_stage_params)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def _stage_spec(x, axis: str, tp_axis: Optional[str]):
    """PartitionSpec for one stacked-stage leaf: always the stage dim
    over `axis`; with tensor parallelism on, matrix leaves (ndim >= 3:
    [S, K, N]) additionally shard their CONTRACTING dim over `tp_axis`
    (the row-parallel layout `blocked_matmul.tp_dense` consumes) while
    vector leaves (biases) stay replicated over tp."""
    if tp_axis is not None and x.ndim >= 3:
        return P(axis, tp_axis, *([None] * (x.ndim - 2)))
    return P(axis, *([None] * (x.ndim - 1)))


def shard_stage_params(stacked, mesh: Mesh, axis: str = PIPE_AXIS,
                       tp_axis: Optional[str] = None):
    """Place the stacked stage params so each pipe device holds its own
    stage's slice (and, with `tp_axis`, each tp device its weight-row
    block)."""
    return jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, _stage_spec(x, axis, tp_axis))),
        stacked)


def make_pipeline_forward(stage_fn: Callable, mesh: Mesh, *,
                          axis: str = PIPE_AXIS,
                          tp_axis: Optional[str] = None,
                          tp_overlap: bool = True):
    """Build fn(stacked_params, micro_x) -> outputs.

    stage_fn(stage_params, x) -> y with y.shape == x.shape (homogeneous
    activation). stacked_params: pytree with leading dim S = |pipe|.
    micro_x: [M, Bm, ...] microbatches. Returns [M, Bm, ...] outputs
    (replicated over the pipe axis).

    Schedule: M + S - 1 ticks; at tick t stage 0 ingests microbatch t
    (while t < M), stage s computes on what stage s-1 produced at t-1
    (ppermute ring shift), and the last stage's outputs from ticks
    S-1 .. S-2+M are the results, in microbatch order.

    `tp_axis` (opt-in) adds tensor parallelism INSIDE every stage: the
    matrix leaves of the stage params shard their contracting dim over
    that second mesh axis, and stage_fn is called with a third argument
    `mm(x, w_loc) -> x @ w` — `blocked_matmul.tp_dense`, the
    row-parallel dense whose ring form (`tp_overlap=True`) overlaps
    the partial-product matmuls with the accumulator ppermutes. The
    stage body routes every big matmul through `mm` and otherwise
    computes exactly the replicated math (activations stay replicated
    over tp). With tp_axis=None the built fn is the pre-existing
    pipeline, unchanged.
    """
    n_stage = mesh.shape[axis]
    tp_mm = None
    if tp_axis is not None:
        tp_mm = functools.partial(blocked_matmul.tp_dense, axis=tp_axis,
                                  overlap=tp_overlap)

    def body(stacked_local, micro_x):
        # stacked_local: leading dim 1 (this device's stage)
        lead = jax.tree.leaves(stacked_local)[0].shape[0]
        if lead != 1:
            raise ValueError(
                f"stacked stage params have {lead * n_stage} stages but "
                f"the '{axis}' mesh axis has {n_stage} devices — one "
                "stage per device required")
        local_params = jax.tree.map(lambda x: x[0], stacked_local)
        me = lax.axis_index(axis)
        m = micro_x.shape[0]
        ticks = m + n_stage - 1
        # pvary: the carry is device-VARYING over the pipe axis (each
        # stage holds a different activation), so the initial zeros must
        # carry that type too or scan rejects the carry
        act0 = jax.lax.pcast(jnp.zeros_like(micro_x[0]), axis,
                            to='varying')
        perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

        def tick(act, t):
            # activation produced LAST tick moves one stage to the right
            inbound = lax.ppermute(act, axis, perm)
            feed = micro_x[jnp.minimum(t, m - 1)]
            x_in = jnp.where(me == 0, feed, inbound)
            if tp_mm is None:
                out = stage_fn(local_params, x_in)
            else:
                out = stage_fn(local_params, x_in, tp_mm)
            return out, out

        _, outs = lax.scan(tick, act0, jnp.arange(
            ticks, dtype=jnp.int32))  # [T, Bm, ...]
        # the last stage's outputs, ticks S-1 .. S-2+M, are the results;
        # zero elsewhere + psum replicates them to every pipe device
        results = lax.dynamic_slice_in_dim(outs, n_stage - 1, m, axis=0)
        results = jnp.where(me == n_stage - 1, results,
                            jnp.zeros_like(results))
        return lax.psum(results, axis_name=axis)

    def fwd(stacked_params, micro_x):
        param_specs = jax.tree.map(
            lambda x: _stage_spec(x, axis, tp_axis), stacked_params)
        # the tp branch mixes pipe-varying activations with
        # tp-replicated ones through collectives on both axes; the
        # varying-manifest checker can't type that, so it's off there —
        # the default branch keeps the strict check it always had
        kw = {} if tp_axis is None else {"check_vma": False}
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(),
            **kw,
        )
        return fn(stacked_params, micro_x)

    return fwd


def make_pipeline_train_step(stage_fn: Callable, loss_fn: Callable,
                             optimizer, mesh: Mesh, *,
                             axis: str = PIPE_AXIS,
                             tp_axis: Optional[str] = None,
                             tp_overlap: bool = True):
    """Jitted pipeline-parallel training step.

    loss_fn(outputs [M, Bm, ...], labels [M, Bm, ...]) -> scalar.
    Returns step(stacked_params, opt_state, micro_x, micro_y, step_i)
    -> (new_params, new_opt_state, loss). Gradients flow through the
    scan+ppermute pipeline by autodiff; the optimizer update runs
    sharded (each pipe device updates its own stage's slice).
    `tp_axis`/`tp_overlap` forward to make_pipeline_forward (the
    sharded-matmul opt-in; stage_fn then takes the `mm` third arg).
    """
    forward = make_pipeline_forward(stage_fn, mesh, axis=axis,
                                    tp_axis=tp_axis,
                                    tp_overlap=tp_overlap)

    @jax.jit
    def step(stacked_params, opt_state, micro_x, micro_y, step_i):
        def objective(p):
            return loss_fn(forward(p, micro_x), micro_y)

        loss, grads = jax.value_and_grad(objective)(stacked_params)
        new_params, new_opt = optimizer.update(grads, opt_state,
                                               stacked_params, step_i)
        return new_params, new_opt, loss

    return step
