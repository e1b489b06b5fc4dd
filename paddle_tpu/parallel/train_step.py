"""Sharded (pjit) train step builder.

This single module replaces three reference subsystems (see SURVEY §2.8):
- MultiGradientMachine's thread-per-GPU data parallelism with ring
  grad-gather/value-scatter (reference: MultiGradientMachine.h:44-98) →
  batch sharded over the mesh `data` axis, XLA emits the all-reduce;
- the pserver sync-SGD round trip (reference:
  trainer/RemoteParameterUpdater.cpp:105, pserver/ParameterServer2.h:482)
  → the optimizer update runs sharded in the same XLA program;
- NCCL ops inserted into Fluid programs (reference:
  operators/nccl_op.cu.cc:41) → no explicit collective ops at all.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.mesh import DATA_AXIS
from paddle_tpu.nn.module import Layer, merge_state
from paddle_tpu.optim.optimizers import Optimizer
from paddle_tpu.parallel import sharding as shard_lib
from paddle_tpu.train.state import TrainState
from paddle_tpu.train.trainer import make_train_step


def _align_opt_shardings(opt_state, params, param_shardings, mesh: Mesh):
    """Give each optimizer-state leaf its parameter's sharding.

    Our optimizers (optim.optimizers) build every moment tree with the same
    treedef as params ({"m": like-params, ...}), so each top-level entry
    that structurally matches params gets the param shardings; anything
    else (scalars, counts) is replicated."""
    params_def = jax.tree.structure(params)
    repl = shard_lib.replicated(mesh)

    def align(node):
        if jax.tree.structure(node) == params_def:
            return param_shardings
        return jax.tree.map(lambda _: repl, node)

    if isinstance(opt_state, dict):
        return {k: align(v) for k, v in opt_state.items()}
    return jax.tree.map(lambda _: repl, opt_state)


def shard_train_state(state: TrainState, mesh: Mesh,
                      param_rules: Optional[Sequence[shard_lib.Rule]] = None,
                      zero: bool = False) -> TrainState:
    """Place an existing TrainState onto the mesh.

    zero=False: optimizer moments inherit their parameter's sharding
    (params-aligned). zero=True additionally slices otherwise-replicated
    moment buffers across the data axis (ZeRO-style, the pserver-side
    optimizer-state sharding equivalent).
    """
    sh = train_state_shardings(state, mesh, param_rules, zero)
    return jax.tree.map(jax.device_put, state, sh)


def train_state_shardings(state: TrainState, mesh: Mesh,
                          param_rules: Optional[Sequence[shard_lib.Rule]] = None,
                          zero: bool = False) -> TrainState:
    """The canonical sharding tree for a TrainState on this mesh: params
    via name-pattern rules, model statistics and the step counter
    replicated, optimizer moments params-aligned (or ZeRO data-sliced)."""
    param_sh = shard_lib.make_param_shardings(state.params, mesh, param_rules)
    repl = shard_lib.replicated(mesh)
    mstate_sh = jax.tree.map(lambda _: repl, state.model_state)
    if zero:
        opt_sh = shard_lib.zero_shardings(state.opt_state, mesh)
    else:
        opt_sh = _align_opt_shardings(state.opt_state, state.params,
                                      param_sh, mesh)
    return TrainState(param_sh, mstate_sh, opt_sh, repl)


def make_sharded_train_step(
    model: Layer,
    loss_fn: Callable,
    optimizer: Optimizer,
    mesh: Mesh,
    *,
    metrics_fn: Optional[Callable] = None,
    donate: bool = True,
    remat: bool = False,
    param_rules: Optional[Sequence[shard_lib.Rule]] = None,
    zero: bool = False,
    accum_steps: int = 1,
):
    """Jitted train step whose inputs arrive batch-sharded over `data`.

    The step body is the single-chip one (make_train_step); parallelism
    comes from input placements + XLA's partitioner (GSPMD). The updated
    state is PINNED to the canonical shardings (param rules, ZeRO
    moments, replicated stats/step) via with_sharding_constraint so
    nothing — donation, partitioner cost models — can reshard the train
    state between steps. Works for pure DP, DP×TP (param_rules shard
    weights over `model`; pass the same rules used in
    shard_train_state), ZeRO (zero=True), and SP meshes.

    accum_steps>1 adds gradient accumulation: the global batch is split
    into microbatches scanned sequentially with ONE weight update.
    """
    def constrain(new_state: TrainState) -> TrainState:
        sh = train_state_shardings(new_state, mesh, param_rules, zero)
        return jax.tree.map(jax.lax.with_sharding_constraint,
                            new_state, sh)

    return make_train_step(
        model, loss_fn, optimizer, metrics_fn=metrics_fn, donate=donate,
        remat=remat, accum_steps=accum_steps,
        constrain_state_fn=constrain,
    )


def aot_compile_train_step(step, state, rng, inputs, labels):
    """Ahead-of-time lower+compile a train step (make_train_step /
    make_sharded_train_step both return jax.jit objects) against
    example arguments, WITHOUT executing a step.

    Why a fleet cares (ROADMAP item 3): the first `step(...)` call of
    a fresh trainer process pays trace+lower+compile mid-"training" —
    after data pipelines spun up, inside the resilience layer's
    watchdog window. This front-loads the whole cost to one explicit
    boot-time point; with the persistent compile cache enabled
    (paddle_tpu.compilation_cache — the CLI default) the XLA compile
    inside is itself a disk hit on a warm restart, so the restarted
    trainer reaches its first real step nearly compile-free.

    Returns the compiled executable — call it exactly like the step
    (same donation semantics; arguments must match the example
    shapes/dtypes/shardings). The example args are only shape/dtype
    templates here: lowering never runs the computation."""
    return step.lower(state, rng, inputs, labels).compile()


# ---------------------------------------------------------------------------
# ZeRO: automatic cross-replica sharding of the weight update
# (PAPERS.md arXiv 2004.13336). Unlike `zero=True` above — which only
# PLACES the moment buffers sharded and lets GSPMD figure out the rest —
# this is the explicit shard_map formulation: reduce-scatter the
# gradients, run the optimizer update on each replica's 1/N slice only,
# all-gather the params afterward. Optimizer state is stored flat
# (1-D per leaf, zero-padded to a multiple of the data-axis size) so ANY
# parameter shape shards evenly and a checkpoint reshards N→M by
# re-padding, never by re-partitioning tensor dims.
# ---------------------------------------------------------------------------


def zero_pad(size: int, shards: int) -> int:
    """Length of a `size`-element buffer once zero-padded to shard evenly
    over `shards` replicas."""
    return size + (-size) % shards


def _flatten_pad(x, shards: int):
    flat = jnp.ravel(x)
    extra = (-flat.shape[0]) % shards
    if extra:
        flat = jnp.pad(flat, (0, extra))
    return flat


def zero_leaf_spec(leaf, shards: int) -> P:
    """PartitionSpec of one ZeRO-layout optimizer-state leaf: flat
    buffers shard over `data` on axis 0, scalars (and anything that
    cannot split evenly, e.g. an L-BFGS history slot count) replicate."""
    shape = tuple(getattr(leaf, "shape", ()))
    if shape and shape[0] and shape[0] % shards == 0:
        return P(DATA_AXIS)
    return P()


def zero_opt_shardings(opt_state, mesh: Mesh):
    n = int(mesh.shape[DATA_AXIS])
    return jax.tree.map(
        lambda x: NamedSharding(mesh, zero_leaf_spec(x, n)), opt_state)


def zero_init_opt_state(optimizer, params, mesh: Mesh):
    """Build optimizer state in the ZeRO layout: `optimizer.init` runs on
    the flattened+padded view of every parameter, and the resulting
    moment buffers are placed sharded over the data axis. Each replica
    then holds ~1/N of the optimizer state (the memory win the ZeRO
    paper is about), and `make_zero_train_step` updates only that slice."""
    n = int(mesh.shape[DATA_AXIS])
    opt = jax.jit(
        lambda p: optimizer.init(
            jax.tree.map(lambda x: _flatten_pad(x, n), p)))(params)
    return jax.tree.map(jax.device_put, opt, zero_opt_shardings(opt, mesh))


def zero_state_shardings(state: TrainState, mesh: Mesh) -> TrainState:
    """Canonical shardings of a ZeRO-layout TrainState: params, model
    statistics and the step counter replicated; flat optimizer moments
    sharded over `data`."""
    repl = shard_lib.replicated(mesh)
    return TrainState(
        params=jax.tree.map(lambda _: repl, state.params),
        model_state=jax.tree.map(lambda _: repl, state.model_state),
        opt_state=zero_opt_shardings(state.opt_state, mesh),
        step=repl,
    )


def zero_true_sizes(params, opt_state):
    """Unpadded element count of every ZeRO optimizer-state leaf, in the
    leaf's own tree position: moment trees that structurally match
    `params` carry their parameter's true size (the flat buffer is padded
    past it); anything else (scalars, replicated extras) carries its own.
    This is the piece of layout info a topology manifest must record —
    padded lengths depend on the shard count, true sizes do not."""
    params_def = jax.tree.structure(params)
    sizes = jax.tree.map(lambda p: int(np.size(p)), params)

    def align(node):
        if jax.tree.structure(node) == params_def:
            return sizes
        return jax.tree.map(lambda x: int(np.size(x)), node)

    if isinstance(opt_state, dict):
        return {k: align(v) for k, v in opt_state.items()}
    return jax.tree.map(lambda x: int(np.size(x)), opt_state)


def reshard_zero_leaf(full, true_size: int, mesh: Mesh):
    """Re-pad one saved flat optimizer-state buffer (padded for its OLD
    data-axis size) for THIS mesh and place it sharded. `full` is the
    fully-gathered saved value as a host array."""
    m = int(mesh.shape[DATA_AXIS])
    flat = np.asarray(full).reshape(-1)[:true_size]
    out = np.zeros((zero_pad(true_size, m),), flat.dtype)
    out[:true_size] = flat
    sh = NamedSharding(mesh, P(DATA_AXIS))
    return jax.make_array_from_callback(out.shape, sh,
                                        lambda idx: out[idx])


def opt_state_bytes_per_replica(opt_state) -> int:
    """Worst-case optimizer-state bytes RESIDENT on one device — the
    quantity ZeRO shrinks ~1/N. Computed from the arrays' addressable
    shards, so a replicated buffer counts once per device and a sharded
    one counts its slice; this is what the memory-win assertions measure
    (asserted, not claimed)."""
    per_device: dict = {}
    for leaf in jax.tree.leaves(opt_state):
        if not hasattr(leaf, "addressable_shards"):
            continue
        for s in leaf.addressable_shards:
            per_device[s.device] = (per_device.get(s.device, 0)
                                    + s.data.nbytes)
    return max(per_device.values()) if per_device else 0


def make_zero_train_step(
    model: Layer,
    loss_fn: Callable,
    optimizer: Optimizer,
    mesh: Mesh,
    *,
    metrics_fn: Optional[Callable] = None,
    donate: bool = True,
    remat: bool = False,
    zero_update: bool = True,
    aux_loss_weight: float = 0.0,
):
    """Jitted ZeRO train step over a pure data-parallel mesh.

    Per arXiv 2004.13336: forward/backward run batch-sharded as usual,
    but the gradient all-reduce is replaced by a reduce-scatter
    (`psum_scatter` — same wire bytes as the all-reduce's scatter half),
    the optimizer update runs ONLY on each replica's 1/N flat slice of
    params+moments, and the updated params are all-gathered (the other
    half of the all-reduce's bytes). Net: full-model throughput at ~1/N
    optimizer-state memory per replica.

    zero_update=False is the bit-exactness oracle arm: the SAME
    shard_map body and the SAME psum_scatter reduction, but the full
    gradient is re-gathered and the whole (flat, padded) update runs
    replicated. Because our optimizer updates are elementwise over the
    flat layout, the two arms are bit-identical — this is what the
    parity tests pin. (Non-elementwise optimizer state — lbfgs/owlqn
    history dot products, chain(clip_global_norm=...)'s cross-leaf
    norm — would see per-shard values under zero_update=True; use the
    elementwise FirstOrder family here.)

    Expects `state.opt_state` in the ZeRO layout (`zero_init_opt_state`)
    when zero_update=True; inputs/labels arrive batch-sharded over
    `data` and the batch must divide the data-axis size.
    """
    n = int(mesh.shape[DATA_AXIS])
    for ax, size in dict(mesh.shape).items():
        if ax != DATA_AXIS and size != 1:
            raise ValueError(
                f"make_zero_train_step is data-parallel only, but mesh "
                f"axis {ax!r} has size {size}; use make_sharded_train_step"
                f"(zero=True) for DP×TP meshes")
    axis = DATA_AXIS

    def apply_model(params, mstate, rng, *inputs):
        return model.apply(params, mstate, *inputs, training=True, rng=rng)

    if remat:
        apply_model = jax.checkpoint(apply_model)

    def _pmean_floats(tree):
        return jax.tree.map(
            lambda x: lax.pmean(x, axis)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact) else x,
            tree)

    def body(params, mstate, opt_state, step_ct, rng, inputs, labels):
        def compute_loss(p):
            out, new_mstate = apply_model(p, mstate, rng, *inputs)
            loss = loss_fn(out, *labels)
            if aux_loss_weight:
                for path, leaf in jax.tree_util.tree_leaves_with_path(
                        new_mstate):
                    key = getattr(path[-1], "key", None) if path else None
                    if key == "aux_loss":
                        loss = loss + aux_loss_weight * leaf
            return loss, (out, new_mstate)

        (loss, (out, new_mstate)), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(params)
        metrics = metrics_fn(out, *labels) if metrics_fn else {}

        # Reduce-scatter of the global-MEAN gradient: each replica
        # leaves this with only its own contiguous 1/n slice of every
        # (flat, padded) gradient.
        gshard = jax.tree.map(
            lambda g: lax.psum_scatter(
                _flatten_pad(g, n), axis,
                scatter_dimension=0, tiled=True) / n,
            grads)

        if zero_update:
            idx = lax.axis_index(axis)

            def my_slice(p):
                flat = _flatten_pad(p, n)
                k = flat.shape[0] // n
                return lax.dynamic_slice_in_dim(flat, idx * k, k)

            pshard = jax.tree.map(my_slice, params)
            new_pshard, new_opt = optimizer.update(
                gshard, opt_state, pshard, step_ct)
            pfull = jax.tree.map(
                lambda s: lax.all_gather(s, axis, axis=0, tiled=True),
                new_pshard)
        else:
            # Oracle arm: regather the identical reduced gradient and
            # run the whole flat update on every replica.
            gfull = jax.tree.map(
                lambda s: lax.all_gather(s, axis, axis=0, tiled=True),
                gshard)
            pflat = jax.tree.map(lambda p: _flatten_pad(p, n), params)
            pfull, new_opt = optimizer.update(
                gfull, opt_state, pflat, step_ct)

        new_params = jax.tree.map(
            lambda f, p: f[:p.size].reshape(p.shape), pfull, params)
        loss = lax.pmean(loss, axis)
        metrics = _pmean_floats(metrics)
        new_mstate = _pmean_floats(new_mstate)
        return new_params, new_mstate, new_opt, loss, metrics

    def step(state: TrainState, rng, inputs, labels):
        inputs = inputs if isinstance(inputs, tuple) else (inputs,)
        labels = labels if isinstance(labels, tuple) else (labels,)
        opt_specs = jax.tree.map(
            lambda x: zero_leaf_spec(x, n) if zero_update else P(),
            state.opt_state)
        sharded = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), opt_specs, P(), P(),
                      jax.tree.map(lambda _: P(axis), inputs),
                      jax.tree.map(lambda _: P(axis), labels)),
            out_specs=(P(), P(), opt_specs, P(), P()),
            check_vma=False)
        new_params, new_mstate, new_opt, loss, metrics = sharded(
            state.params, state.model_state, state.opt_state, state.step,
            rng, inputs, labels)
        new_state = TrainState(
            params=new_params,
            model_state=merge_state(state.model_state, new_mstate),
            opt_state=new_opt,
            step=state.step + 1,
        )
        return new_state, loss, metrics

    return jax.jit(step, donate_argnums=(0,) if donate else ())
