"""Mixture-of-experts with expert parallelism over the mesh.

No reference counterpart (acmol/Paddle predates MoE); this extends the
framework's "EP" story beyond sparse embeddings (parallel/sparse.py) to
sparsely-activated FFNs, the modern TPU workload the mesh design exists
for. Design follows the GShard/Switch dispatch shape — chosen because
it is the MXU-native formulation:

- top-k softmax router with an auxiliary load-balancing loss;
- FIXED expert capacity C (static shapes — XLA requirement), tokens
  over capacity are dropped (their combine weight is zero, the
  residual stream carries them through unchanged);
- dispatch/combine are one-hot einsums — big batched matmuls instead
  of scatter/gather, which is exactly what the MXU wants;
- expert parallelism: experts sharded over the mesh `model` axis, the
  dispatched [E, C, D] block exchanged with ONE tiled all_to_all each
  way over ICI (the same exchange shape as sparse.alltoall_lookup).

Parity of intent: the reference scaled sparse models by sharding
embedding rows across pservers; this shards expert FFNs across chips.

Which path drops and which does not. `moe_ffn`, `expert_choice_ffn` and
`make_expert_parallel_ffn` are **capacity-factor** paths: every expert
gets a fixed [C, D] buffer, and an assignment past its expert's
capacity is **dropped** (or, under expert choice, a token may be picked
by no expert). `dropless_ffn` is the **dropless** path: softmax over all
experts, top-k renormalised, every (position, choice) row computed
whatever the imbalance, as grouped matrix products over rows ordered by
expert (`ops.moe_grouped_matmul`); it builds no [T, E, C] or [E, C, D]
buffer, and it may hold a share of the experts (an expert-parallel
chip's): it then routes over all of them and computes the terms of its
own.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from paddle_tpu.core.dtypes import default_policy
from paddle_tpu.core.mesh import MODEL_AXIS
from paddle_tpu.nn import initializers
from paddle_tpu.ops import pallas_util
from paddle_tpu.ops.moe_grouped_matmul import grouped_matmul
from paddle_tpu.ops.moe_rows import moe_sum_held_rows, moe_take_held_rows


class MoEOutput(NamedTuple):
    y: jnp.ndarray          # [T, D] combined expert outputs
    aux_loss: jnp.ndarray   # scalar load-balancing loss
    dropped: jnp.ndarray    # scalar fraction of tokens over capacity


def init_moe_params(rng, n_experts: int, d_model: int, d_ff: int,
                    dtype=jnp.float32):
    """Stacked expert FFNs + router. Expert weights are [E, ...] so one
    einsum runs every expert; shard axis 0 over the mesh for EP."""
    k_r, k_1, k_2 = jax.random.split(rng, 3)
    smart = initializers.smart_uniform()
    w1 = jnp.stack([smart(k, (d_model, d_ff))
                    for k in jax.random.split(k_1, n_experts)]).astype(dtype)
    w2 = jnp.stack([smart(k, (d_ff, d_model))
                    for k in jax.random.split(k_2, n_experts)]).astype(dtype)
    return {
        "router": {"kernel": initializers.normal(0.02)(
            k_r, (d_model, n_experts)).astype(dtype)},
        "w1": w1, "b1": jnp.zeros((n_experts, d_ff), dtype),
        "w2": w2, "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def shard_moe_params(params, mesh: Mesh, *, axis: str = MODEL_AXIS):
    """Expert-shard the stacked weights over `axis` (router replicated)."""
    e = params["w1"].shape[0]
    if e % mesh.shape[axis] != 0:
        raise ValueError(f"{e} experts not divisible by {axis} axis size "
                         f"{mesh.shape[axis]}")
    def put(x, s):
        return jax.device_put(x, NamedSharding(mesh, s))

    return {
        "router": {"kernel": put(params["router"]["kernel"], P())},
        "w1": put(params["w1"], P(axis)), "b1": put(params["b1"], P(axis)),
        "w2": put(params["w2"], P(axis)), "b2": put(params["b2"], P(axis)),
    }


def capacity_for(n_tokens: int, n_experts: int,
                 capacity_factor: float = 1.25, k: int = 1, *,
                 multiple: int = 4) -> int:
    """Static per-expert capacity: factor * k * tokens/experts, rounded
    up to `multiple` (sublane-friendly). Top-k routing makes k*T
    assignments, so capacity must scale with k or even perfectly
    balanced routing drops (k-1)/k of the assignments (GShard sizes
    capacity the same way)."""
    raw = max(1, int(capacity_factor * k * n_tokens / n_experts))
    return -(-raw // multiple) * multiple


class Routing(NamedTuple):
    """Index-form routing: per round r < k and token t, token t goes to
    `expert[r, t]` slot `slot[r, t]` with weight `gate[r, t]` (0 when
    dropped). Linear in T — the dense [T, E, C] tensors are derived
    views for small shapes (top_k_gating)."""
    expert: jnp.ndarray    # [k, T] int32
    slot: jnp.ndarray      # [k, T] int32
    keep: jnp.ndarray      # [k, T] bool
    gate: jnp.ndarray      # [k, T] f32, kept-renormalized per token
    aux_loss: jnp.ndarray  # scalar
    dropped: jnp.ndarray   # scalar


def top_k_routing(router_logits, k: int, capacity: int, *,
                  rng: Optional[jax.Array] = None, jitter: float = 0.0,
                  token_mask=None) -> Routing:
    """Top-k expert assignment with fixed capacity, in index form.

    router_logits: [T, E]. token_mask: optional [T] bool — False
    positions (padding) claim NO capacity slots, contribute nothing to
    the aux loss, and don't count as dropped.

    aux_loss is the Switch/GShard load-balancing term: E * sum_e
    (token_fraction_e * mean_router_prob_e) — 1.0 at perfect balance.
    Position within each expert's capacity is assigned in token order
    (cumsum over the one-hot), over-capacity assignments get gate 0.
    """
    t, e = router_logits.shape
    if rng is not None and jitter > 0.0:
        router_logits = router_logits * jax.random.uniform(
            rng, router_logits.shape, router_logits.dtype,
            1.0 - jitter, 1.0 + jitter)
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    if token_mask is None:
        valid = jnp.ones((t,), jnp.float32)
    else:
        valid = token_mask.astype(jnp.float32)

    # claimed[e] tokens already routed to expert e by earlier choices
    claimed = jnp.zeros((e,), jnp.int32)
    masked = probs
    first_mask = None
    kept_any = jnp.zeros((t,), bool)
    experts, slots, keeps, gates = [], [], [], []
    for _ in range(k):
        gate = jnp.max(masked, axis=-1) * valid              # [T]
        choice = jnp.argmax(masked, axis=-1)                 # [T]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32) \
            * valid[:, None]                                 # pads claim 0
        if first_mask is None:
            first_mask = onehot
        # position of each token in its chosen expert's buffer
        pos = (jnp.cumsum(onehot, axis=0) - onehot) + claimed[None, :]
        pos_tok = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [T]
        keep = (pos_tok < capacity) & (valid > 0)
        kept_any = kept_any | keep
        experts.append(choice.astype(jnp.int32))
        slots.append(jnp.minimum(pos_tok, capacity - 1))
        keeps.append(keep)
        gates.append(gate * keep.astype(jnp.float32))
        claimed = claimed + jnp.sum(
            onehot * keep[:, None].astype(jnp.float32), axis=0).astype(
                jnp.int32)
        masked = masked * (1.0 - onehot)                      # next choice

    gate_kt = jnp.stack(gates)                                # [k, T]
    # renormalize over the KEPT gates so each surviving token's weights
    # sum to 1 (dropped assignments are excluded from the mass)
    denom = jnp.sum(gate_kt, axis=0, keepdims=True)
    gate_kt = jnp.where(denom > 0, gate_kt / jnp.maximum(denom, 1e-9), 0.0)

    n_valid = jnp.maximum(jnp.sum(valid), 1.0)
    frac_tokens = jnp.sum(first_mask, axis=0) / n_valid       # [E]
    mean_prob = jnp.sum(probs * valid[:, None], axis=0) / n_valid  # [E]
    aux = e * jnp.sum(frac_tokens * mean_prob)
    dropped = 1.0 - jnp.sum(kept_any.astype(jnp.float32) * valid) / n_valid
    return Routing(jnp.stack(experts), jnp.stack(slots), jnp.stack(keeps),
                   gate_kt, aux, dropped)


class ECRouting(NamedTuple):
    """Expert-choice routing (Zhou et al. 2022): each EXPERT picks its
    top-`capacity` tokens. token_idx[e, c] is the token filling expert
    e's slot c; gate[e, c] its combine weight (0 for masked padding)."""
    token_idx: jnp.ndarray  # [E, C] int32
    gate: jnp.ndarray       # [E, C] f32
    dropped: jnp.ndarray    # scalar: fraction of valid tokens no expert picked


def expert_choice_routing(router_logits, capacity: int, *,
                          token_mask=None) -> ECRouting:
    """Every expert slot fills (perfect load balance, no aux loss
    needed); a token can be picked by several experts or none (residual
    carries unpicked tokens). Dispatch is a pure gather, combine a
    scatter-add — no capacity bookkeeping at all."""
    t, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    if token_mask is not None:
        probs = probs * token_mask.astype(jnp.float32)[:, None]
    gate, token_idx = jax.lax.top_k(probs.T, capacity)    # [E, C] each
    picked = jnp.zeros((t,), bool).at[token_idx.reshape(-1)].set(
        True, mode="drop")
    valid = jnp.ones((t,), bool) if token_mask is None else token_mask
    n_valid = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    dropped = 1.0 - jnp.sum((picked & valid).astype(jnp.float32)) / n_valid
    return ECRouting(token_idx.astype(jnp.int32), gate, dropped)


def expert_choice_ffn(params, x, *, capacity_factor: float = 2.0,
                      token_mask=None,
                      activation=jax.nn.gelu) -> MoEOutput:
    """MoE FFN under expert-choice routing. x: [T, D]. Capacity per
    expert = capacity_factor * T / E (the paper's formulation; factor 2
    means each token is used twice on average)."""
    t, d = x.shape
    e = params["w1"].shape[0]
    # an expert can never take more tokens than exist — decode steps
    # (t = batch) and short prefills would otherwise ask top_k for more
    # entries than the token axis holds
    cap = min(capacity_for(t, e, capacity_factor), t)
    logits = x @ params["router"]["kernel"]
    r = expert_choice_routing(logits, cap, token_mask=token_mask)
    expert_in = jnp.take(x, r.token_idx.reshape(-1), axis=0) \
        .reshape(e, cap, d)                               # pure gather
    out = _expert_ffn(params, expert_in, activation)
    weighted = (r.gate[..., None] * out.astype(jnp.float32)) \
        .reshape(e * cap, d)
    y = jnp.zeros((t, d), jnp.float32).at[r.token_idx.reshape(-1)] \
        .add(weighted)                                    # scatter combine
    return MoEOutput(y.astype(x.dtype), jnp.zeros((), jnp.float32),
                     r.dropped)


def top_k_gating(router_logits, k: int, capacity: int, *,
                 rng: Optional[jax.Array] = None, jitter: float = 0.0,
                 token_mask=None):
    """Dense [T, E, C] dispatch/combine tensors derived from
    top_k_routing — O(T*E*C) memory, intended for small shapes and
    tests; the compute paths use the index form or the einsum dispatch
    chosen by _use_scatter. Returns (dispatch, combine, aux_loss,
    dropped_frac)."""
    t, e = router_logits.shape
    r = top_k_routing(router_logits, k, capacity, rng=rng, jitter=jitter,
                      token_mask=token_mask)
    dispatch, combine = _dense_from_routing(r, e, capacity)
    return dispatch, combine, r.aux_loss, r.dropped


def _dense_from_routing(r: Routing, e: int, capacity: int):
    eo = jax.nn.one_hot(r.expert, e, dtype=jnp.float32) \
        * r.keep[..., None]                                   # [k, T, E]
    so = jax.nn.one_hot(r.slot, capacity, dtype=jnp.float32) \
        * r.keep[..., None]                                   # [k, T, C]
    sel = eo[:, :, :, None] * so[:, :, None, :]               # [k, T, E, C]
    dispatch = jnp.sum(sel, axis=0)
    combine = jnp.sum(r.gate[:, :, None, None] * sel, axis=0)
    return dispatch, combine


# element-count ceiling for materializing the dense [T, E, C] dispatch
# tensor (einsum dispatch feeds the MXU best at small/medium shapes; at
# LM shapes C grows with T so the tensor is quadratic in T and must be
# avoided — 2^24 f32 elements = 64 MiB)
_EINSUM_DISPATCH_MAX = 1 << 24


def _use_scatter(impl: str, t: int, e: int, cap: int) -> bool:
    if impl == "auto":
        return t * e * cap > _EINSUM_DISPATCH_MAX
    if impl in ("scatter", "einsum"):
        return impl == "scatter"
    raise ValueError(f"dispatch_impl must be auto|einsum|scatter, got {impl}")


def _dispatch_expert_in(routing: Routing, x, e: int, cap: int, impl: str):
    """[E, C, D] expert inputs via the impl chosen by _use_scatter.
    Returns (expert_in, dense_combine_or_None) — the dense combine is
    reused by _combine_out when the einsum path was taken."""
    t = x.shape[0]
    if _use_scatter(impl, t, e, cap):
        return scatter_dispatch(routing, x, e, cap), None
    dispatch, combine = _dense_from_routing(routing, e, cap)
    ein = jnp.einsum("tec,td->ecd", dispatch,
                     x.astype(jnp.float32)).astype(x.dtype)
    return ein, combine


def _combine_out(routing: Routing, dense_combine, out_ecd, cap: int):
    """Per-token combine matching _dispatch_expert_in's chosen impl."""
    if dense_combine is None:
        return gather_combine(routing, out_ecd, cap)
    return jnp.einsum("tec,ecd->td", dense_combine,
                      out_ecd.astype(jnp.float32))


def scatter_dispatch(routing: Routing, x, n_experts: int, capacity: int):
    """Build [E, C, D] expert inputs by scatter-add — O(k*T + E*C*D)
    memory (the einsum dispatch materializes [T, E, C], quadratic in T
    since C grows with T; at LM shapes that tensor is GBs)."""
    k, t = routing.expert.shape
    d = x.shape[-1]
    flat = routing.expert * capacity + routing.slot           # [k, T]
    # dropped assignments -> index E*C, written into a dump row
    flat = jnp.where(routing.keep, flat, n_experts * capacity)
    buf = jnp.zeros((n_experts * capacity + 1, d), x.dtype)
    xs = jnp.broadcast_to(x, (k, t, d)).reshape(k * t, d)
    buf = buf.at[flat.reshape(-1)].add(xs)
    return buf[:-1].reshape(n_experts, capacity, d)


def gather_combine(routing: Routing, expert_out, capacity: int):
    """Combine [E, C, D] expert outputs back per token: y[t] = sum_r
    gate[r,t] * out[expert[r,t], slot[r,t]] — gates are 0 for dropped
    assignments, so any gathered row there is discarded."""
    e, c, d = expert_out.shape
    flat_out = expert_out.reshape(e * c, d).astype(jnp.float32)
    flat = routing.expert * capacity + routing.slot           # [k, T]
    picked = jnp.take(flat_out, flat.reshape(-1), axis=0)     # [k*T, D]
    picked = picked.reshape(*flat.shape, d)                   # [k, T, D]
    return jnp.sum(routing.gate[..., None] * picked, axis=0)  # [T, D]


def _expert_ffn(params, x, activation):
    """x: [E_local, C', D] -> [E_local, C', D] via the stacked weights."""
    h = jnp.einsum("ecd,edf->ecf", x, params["w1"]) + params["b1"][:, None, :]
    h = activation(h)
    return jnp.einsum("ecf,efd->ecd", h, params["w2"]) + params["b2"][:, None, :]


def moe_ffn(params, x, *, k: int = 2, capacity_factor: float = 1.25,
            rng=None, jitter: float = 0.0, token_mask=None,
            activation=jax.nn.gelu,
            dispatch_impl: str = "auto") -> MoEOutput:
    """Single-device MoE FFN. x: [T, D] (flatten [B, S, D] first).
    token_mask [T] bool: padding positions neither claim capacity nor
    bias the aux loss. dispatch_impl: "einsum" (one-hot matmuls,
    materializes [T, E, C]) vs "scatter" (linear-memory scatter/gather);
    "auto" picks by the dense tensor's size."""
    t, d = x.shape
    e = params["w1"].shape[0]
    cap = capacity_for(t, e, capacity_factor, k)
    logits = x @ params["router"]["kernel"]
    routing = top_k_routing(logits, k, cap, rng=rng, jitter=jitter,
                            token_mask=token_mask)
    expert_in, dense_combine = _dispatch_expert_in(routing, x, e, cap,
                                                   dispatch_impl)
    expert_out = _expert_ffn(params, expert_in, activation)
    y = _combine_out(routing, dense_combine, expert_out, cap)
    return MoEOutput(y.astype(x.dtype), routing.aux_loss, routing.dropped)


def make_expert_parallel_ffn(mesh: Mesh, *, axis: str = MODEL_AXIS,
                             data_axis: Optional[str] = None,
                             k: int = 2, capacity_factor: float = 1.25,
                             jitter: float = 0.0,
                             activation=jax.nn.gelu,
                             dispatch_impl: str = "auto"):
    """Build an expert-parallel MoE FFN over `mesh`.

    Tokens arrive sharded over BOTH mesh axes (or replicated when
    `data_axis` is None); experts are sharded over `axis`
    (shard_moe_params). Each shard routes its local tokens, dispatches
    into [E, C_loc, D], then ONE tiled all_to_all regroups the block so
    every shard holds its OWN experts' tokens from ALL shards; the FFN
    runs batched over local experts; the mirrored all_to_all brings
    results home for the local combine. Per-step ICI volume is
    2 * E * C_loc * D — the K*D shape of sparse.alltoall_lookup, with
    matmul dispatch instead of sorts.

    The token axis is split over (data_axis, axis) jointly: if it were
    split over data_axis alone, every `axis` peer would hold the same
    tokens, compute the same routing, and the exchange would carry
    n_model identical copies — n_model-fold redundant expert FLOPs and
    ICI traffic. With the joint split each peer's C_loc block is
    distinct tokens and the exchange volume claim above is real.

    Returns fn(params, x [T, D], rng=None) -> MoEOutput with y sharded
    like x. T must divide by data_axis_size * axis_size (static
    shapes).
    """
    n_exp_shards = mesh.shape[axis]
    dspec = P((data_axis, axis)) if data_axis else P()

    def body(params, x, rng):
        t_loc, d = x.shape
        e_loc = params["w1"].shape[0]
        e = e_loc * n_exp_shards  # global expert count
        cap = capacity_for(t_loc, e, capacity_factor, k)
        logits = x @ params["router"]["kernel"]
        if data_axis is not None:
            # distinct jitter noise per token shard (both mesh axes)
            rng = jax.random.fold_in(
                rng, lax.axis_index(data_axis) * n_exp_shards
                + lax.axis_index(axis))
        routing = top_k_routing(logits, k, cap, rng=rng, jitter=jitter)
        aux, dropped = routing.aux_loss, routing.dropped
        if data_axis is None:
            # tokens replicated: every shard computes identical routing,
            # so exchanging dispatch buffers would move (and compute on)
            # n identical copies. Run only the LOCAL experts'
            # assignments and psum the partial combines — zero
            # all-to-all, 1/n the expert FLOPs.
            shard = lax.axis_index(axis)
            local_e = routing.expert - shard * e_loc
            in_range = (local_e >= 0) & (local_e < e_loc) & routing.keep
            r_loc = routing._replace(
                expert=jnp.clip(local_e, 0, e_loc - 1),
                keep=in_range,
                gate=routing.gate * in_range.astype(jnp.float32))
            local_in, dense_c = _dispatch_expert_in(r_loc, x, e_loc, cap,
                                                    dispatch_impl)
            out = _expert_ffn(params, local_in, activation)
            y = _combine_out(r_loc, dense_c, out, cap)
            y = lax.psum(y, axis).astype(x.dtype)
            return MoEOutput(y, aux, dropped)
        # local dispatch against ALL experts: [E, C, D]
        expert_in, combine = _dispatch_expert_in(routing, x, e, cap,
                                                 dispatch_impl)
        # regroup: shard j receives its local experts' buffers from all
        # shards -> [E_loc * n, C, D] == concat over source shards
        recv = lax.all_to_all(expert_in, axis, split_axis=0, concat_axis=0,
                              tiled=True)
        # run local experts over the concatenated capacity blocks:
        # [n * E_loc, C, D] -> group to [E_loc, n * C, D]
        grouped = recv.reshape(n_exp_shards, e_loc, cap, d).swapaxes(0, 1) \
            .reshape(e_loc, n_exp_shards * cap, d)
        out = _expert_ffn(params, grouped, activation)
        # mirror the reshape + exchange to bring tokens home
        back = out.reshape(e_loc, n_exp_shards, cap, d).swapaxes(0, 1) \
            .reshape(n_exp_shards * e_loc, cap, d)
        home = lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                              tiled=True)                     # [E, C, D]
        y = _combine_out(routing, combine, home, cap).astype(x.dtype)
        aux = lax.pmean(aux, (data_axis, axis))
        dropped = lax.pmean(dropped, (data_axis, axis))
        return MoEOutput(y, aux, dropped)

    pspec = {"router": {"kernel": P()},
             "w1": P(axis), "b1": P(axis), "w2": P(axis), "b2": P(axis)}
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, dspec, P()),
        out_specs=MoEOutput(dspec, P(), P()),
        check_vma=False,
    )

    def apply(params, x, rng=None):
        if rng is None:
            rng = jax.random.key(0)
        return fn(params, x, rng)

    return apply


# -- the dropless path ------------------------------------------------------


class DroplessStats(NamedTuple):
    """Counts of one dropless layer's step, int32. `route_counts`: a
    layer that chooses by an expert bias also counts the rows routed to
    each of the router's experts, held or not ([E]; what
    `update_expert_bias` reads), and None elsewhere."""
    rows_held: jnp.ndarray          # (position, choice) rows of held experts
    rows_max_expert: jnp.ndarray    # rows of the fullest held expert
    route_counts: Optional[jnp.ndarray] = None


class DroplessOutput(NamedTuple):
    y: jnp.ndarray                  # [T, D] what the held experts add
    stats: DroplessStats


def count_dropless_stats(stats: DroplessStats, positions: int,
                         timeline=None) -> None:
    """Host side, where a training loop reads its loss: add a step's
    counts (the loss's auxiliary output, stacked over the layers) to the
    timeline's counters `moe.rows_held`, `moe.rows_max_expert` and
    `moe.positions` (positions routed, a layer each); where the layers
    count their routes over all experts, also `moe.route_rows` (rows
    routed) and `moe.route_rows_max` (rows of the fullest of all the
    router's experts, summed over the layers)."""
    from paddle_tpu.obs.trace import default_timeline

    timeline = timeline if timeline is not None else default_timeline()
    rows_held = jnp.atleast_1d(stats.rows_held)
    timeline.count("moe.rows_held", int(jnp.sum(rows_held)))
    timeline.count("moe.rows_max_expert", int(jnp.sum(stats.rows_max_expert)))
    timeline.count("moe.positions", positions * rows_held.shape[0])
    if stats.route_counts is not None:
        counts = jnp.asarray(stats.route_counts)
        timeline.count("moe.route_rows", int(jnp.sum(counts)))
        timeline.count("moe.route_rows_max",
                       int(jnp.sum(jnp.max(counts, axis=-1))))


def update_expert_bias(bias, route_counts, coeff: float):
    """Auxiliary-loss-free load balancing (Wang et al. 2024, as
    DeepSeek-V3 and Trinity train): after a step, each expert's bias
    moves `coeff` towards the mean load, up where the expert took fewer
    rows than the mean and down where it took more, and the moves are
    centred so that the biases keep their mean:

        delta_e = coeff * sign(mean(c) - c_e);  b <- b + delta - mean(delta)

    bias and route_counts [..., E] (a layer's `DroplessStats.route_counts`,
    or a stack of layers'); float32 out. No gradient reaches the bias:
    it only chooses, and the step updates it outside the optimizer."""
    with jax.named_scope("moe/bias_update"):
        c = route_counts.astype(jnp.float32)
        delta = coeff * jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)
        return (bias.astype(jnp.float32) + delta
                - jnp.mean(delta, axis=-1, keepdims=True))


def init_dropless_params(rng, n_experts: int, n_held: int, d_model: int,
                         d_ff: int, dtype=jnp.float32, d_shared=None,
                         shared_gate: bool = True):
    """Router over all `n_experts` + the `n_held` gated-SiLU experts this
    layer holds, stacked [n_held, ...], no bias anywhere. `d_shared`: a
    shared gated-SiLU expert of that width (`shared/{gate,up,down}_proj`)
    and, where `shared_gate`, the kernel [d_model, 1] of its output's
    scale (`shared_scale`)."""
    k_r, k_g, k_u, k_d = jax.random.split(rng, 4)
    smart = initializers.smart_uniform()

    def stack(key, shape):
        return jnp.stack([smart(k, shape) for k in
                          jax.random.split(key, n_held)]).astype(dtype)

    params = {
        "router": {"kernel": smart(k_r, (d_model, n_experts)).astype(dtype)},
        "w_gate": stack(k_g, (d_model, d_ff)),
        "w_up": stack(k_u, (d_model, d_ff)),
        "w_down": stack(k_d, (d_ff, d_model)),
    }
    if d_shared is not None:
        k_sg, k_su, k_sd, k_ss = jax.random.split(jax.random.fold_in(rng, 1),
                                                  4)
        kernel = lambda key, shape: {"kernel": smart(key, shape).astype(dtype)}
        params["shared"] = {"gate_proj": kernel(k_sg, (d_model, d_shared)),
                            "up_proj": kernel(k_su, (d_model, d_shared)),
                            "down_proj": kernel(k_sd, (d_shared, d_model))}
        if shared_gate:
            params["shared_scale"] = kernel(k_ss, (d_model, 1))
    return params


@jax.custom_vjp
def _take_rows(x, row_of_slot, slot_of_pair, held):
    """x [T, D] -> [R, D], slot s gets x[row_of_slot[s]]; slots past the
    held rows are not written (`ops.moe_rows`). Its gradient gathers
    too: position t collects the slots of its held choices
    (`slot_of_pair` [T, k])."""
    return moe_take_held_rows(x, row_of_slot, jnp.sum(held, dtype=jnp.int32))


def _take_rows_fwd(x, row_of_slot, slot_of_pair, held):
    return _take_rows(x, row_of_slot, slot_of_pair, held), (slot_of_pair, held)


def _take_rows_bwd(res, g):
    slot_of_pair, held = res
    dx = moe_sum_held_rows(g, slot_of_pair, held)
    return dx.astype(g.dtype), None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _combine_rows(out, weight, slot_of_pair, held, pair_of_slot):
    """y[t] = sum over t's held choices c of weight[t, c] * out[slot]:
    a gather, never a scatter, in float32. Rows of `out` that no expert
    owns are not read."""
    return moe_sum_held_rows(out, slot_of_pair, held, weight)


def _combine_rows_fwd(out, weight, slot_of_pair, held, pair_of_slot):
    return (_combine_rows(out, weight, slot_of_pair, held, pair_of_slot),
            (out, weight, slot_of_pair, held, pair_of_slot))


def _combine_rows_bwd(res, g):
    out, weight, slot_of_pair, held, pair_of_slot = res
    k = weight.shape[1]
    # slot s holds pair (t, c) = divmod(pair_of_slot[s], k): its row gets
    # weight[t, c] * g[t], and weight[t, c] gets <out[s], g[t]>; slots
    # past the held rows are not written
    d_out, dot = moe_take_held_rows(
        g, pair_of_slot // k, jnp.sum(held, dtype=jnp.int32),
        scale=weight.reshape(-1)[pair_of_slot], other=out,
        out_dtype=out.dtype)
    d_weight = jnp.where(held, jnp.take(dot, slot_of_pair), 0.0)
    return d_out, d_weight, None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def dropless_ffn(params, x, *, k: int, first_held: int = 0,
                 token_mask=None, score: str = "softmax",
                 route_scale: float = 1.0) -> DroplessOutput:
    """Dropless token-choice MoE over the experts this layer holds.
    x: [T, D]. The router scores all E = router width in float32, by a
    softmax or, with `score="sigmoid"`, an independent sigmoid an
    expert; the k largest are chosen and renormalised over the k chosen
    (held or not; a sigmoid's sum + 1e-20, as the published router),
    times `route_scale`. A layer whose params carry `expert_bias` [E]
    chooses the k largest of score + bias and weights them by the
    unbiased scores (auxiliary-loss-free balancing: the bias only
    chooses, `update_expert_bias` moves it), and counts the rows routed
    to every expert (`DroplessStats.route_counts`).
    Of the T*k (position, choice) rows, those whose expert is one
    of the `params["w_gate"].shape[0]` held, from expert `first_held`,
    are ordered by expert and computed as grouped products: gate and up,
    silu(gate) * up, down; each position then adds its rows by its
    weights. What the experts not held would add is left out: that is
    another chip's part. No row is dropped whatever the imbalance: the
    row buffer is sized for every choice (T*k rows), and the kernels
    visit only the tiles the held rows fill. token_mask [T] bool:
    positions that route nowhere.

    A layer with a shared expert (`params["shared"]`, from
    `init_dropless_params(d_shared=...)`) adds sigmoid(x . w) times that
    expert's output on every position to the routed sum (scope
    `moe/shared`), or the output alone where the layer has no
    `shared_scale`; `token_mask` does not reach it."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"score must be 'softmax' or 'sigmoid', got "
                         f"{score!r}")
    t, d = x.shape
    n_held = params["w_gate"].shape[0]
    cd = default_policy().compute_dtype
    pallas_util.note_traced("moe.expert_matmul", "pallas_grouped")
    pallas_util.note_traced("moe.row_gather", "held_rows")
    bias = params.get("expert_bias")
    pallas_util.note_traced("moe.router", score if bias is None
                            else f"{score}_bias")
    with jax.named_scope("moe/router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            params["router"]["kernel"].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        if score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
        else:
            probs = jax.nn.sigmoid(logits)
        if bias is None:
            top_p, top_e = lax.top_k(probs, k)
        else:
            _, top_e = lax.top_k(probs + bias.astype(jnp.float32), k)
            top_p = jnp.take_along_axis(probs, top_e, axis=-1)
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        weight = top_p / (total if score == "softmax" else total + 1e-20)
        if route_scale != 1.0:
            weight = weight * route_scale                         # [T, k]
    with jax.named_scope("moe/dispatch"):
        local = top_e.astype(jnp.int32) - first_held
        held = (local >= 0) & (local < n_held)
        if token_mask is not None:
            held = held & token_mask[:, None]
        # rows of held experts first, by expert; the rest behind them
        key = jnp.where(held, local, n_held).reshape(-1)          # [T*k]
        pair_of_slot = jnp.argsort(key, stable=True).astype(jnp.int32)
        slot_of_pair = jnp.zeros((t * k,), jnp.int32).at[pair_of_slot].set(
            jnp.arange(t * k, dtype=jnp.int32), unique_indices=True
        ).reshape(t, k)
        sizes = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
        rows = _take_rows(x.astype(cd), pair_of_slot // k, slot_of_pair, held)
    with jax.named_scope("moe/experts"):
        gate = grouped_matmul(rows, params["w_gate"].astype(cd), sizes)
        up = grouped_matmul(rows, params["w_up"].astype(cd), sizes)
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(cd)
        out = grouped_matmul(hidden, params["w_down"].astype(cd), sizes)
    with jax.named_scope("moe/combine"):
        y = _combine_rows(out, weight, slot_of_pair, held, pair_of_slot)
    if "shared" in params:
        gated = "shared_scale" in params
        pallas_util.note_traced("moe.shared_expert",
                                "gated" if gated else "plain")
        with jax.named_scope("moe/shared"):
            s = params["shared"]
            xc = x.astype(cd)
            mm = lambda a, w: jnp.matmul(a, w.astype(cd),
                                         preferred_element_type=jnp.float32)
            hidden = (jax.nn.silu(mm(xc, s["gate_proj"]["kernel"]))
                      * mm(xc, s["up_proj"]["kernel"])).astype(cd)
            if gated:
                scale = jax.nn.sigmoid(mm(xc, params["shared_scale"]["kernel"]))
                y = y + scale * mm(hidden, s["down_proj"]["kernel"])
            else:
                y = y + mm(hidden, s["down_proj"]["kernel"])
    route_counts = None
    if bias is not None:
        routed = (jnp.ones_like(top_e, jnp.int32) if token_mask is None
                  else jnp.broadcast_to(token_mask[:, None], top_e.shape
                                        ).astype(jnp.int32))
        route_counts = jnp.zeros((probs.shape[-1],), jnp.int32).at[
            top_e.reshape(-1)].add(routed.reshape(-1))
    stats = DroplessStats(jnp.sum(sizes, dtype=jnp.int32), jnp.max(sizes),
                          route_counts)
    return DroplessOutput(y.astype(x.dtype), stats)
