"""Optimizers as pure gradient transforms.

Parity with the reference's optimizer family (reference:
paddle/parameter/FirstOrderOptimizer.h:24-346 — Sgd, SparseMomentum,
Adagrad, AdaDelta, RMSProp, DecayedAdagrad, Adam, Adamax,
OptimizerWithGradientClipping; fluid optimizer ops
paddle/operators/{sgd,momentum,adam,adamax,adagrad,adadelta,rmsprop,
decayed_adagrad,ftrl,proximal_gd,proximal_adagrad}_op.cc).

Design: each optimizer is an `Optimizer` with
  init(params) -> opt_state (a pytree aligned with params)
  update(grads, opt_state, params, step) -> (new_params, new_opt_state)
The whole update jits and shards with the params: running it under pjit
with sharded opt state is the TPU-native replacement of pserver-side
optimization (reference: pserver/ParameterServer2.h:660 op_SGD).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.pytree import global_norm, named_leaves
from paddle_tpu.optim import schedules

OptState = Any


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable  # (grads, opt_state, params, step) -> (params, opt_state)

    def __post_init__(self):
        # every update, whoever builds it and whoever calls it, traces
        # under one scope: the device trace's op_name then says which
        # operations are the optimizer's
        object.__setattr__(
            self, "update", jax.named_scope("optimizer")(self.update))

    def with_transforms(self, *, weight_decay: float = 0.0,
                        clip_global_norm: Optional[float] = None,
                        clip_value: Optional[float] = None) -> "Optimizer":
        return chain(self, weight_decay=weight_decay,
                     clip_global_norm=clip_global_norm, clip_value=clip_value)


def _treemap(fn, *trees):
    return jax.tree.map(fn, *trees)


def sgd(learning_rate=0.01) -> Optimizer:
    """Plain SGD (reference: SgdOptimizer, operators/sgd_op.cc)."""
    lr_fn = schedules.resolve(learning_rate)

    def init(params):
        return ()

    def update(grads, opt_state, params, step):
        lr = lr_fn(step)
        new_params = _treemap(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
        return new_params, opt_state

    return Optimizer(init, update)


def momentum(learning_rate=0.01, mu: float = 0.9, nesterov: bool = False) -> Optimizer:
    """Momentum SGD (reference: momentum in SgdOptimizer + operators/momentum_op.cc)."""
    lr_fn = schedules.resolve(learning_rate)

    def init(params):
        return {"velocity": _treemap(jnp.zeros_like, params)}

    def update(grads, opt_state, params, step):
        lr = lr_fn(step)
        vel = _treemap(lambda v, g: mu * v + g.astype(v.dtype), opt_state["velocity"], grads)
        if nesterov:
            upd = _treemap(lambda v, g: g + mu * v, vel, grads)
        else:
            upd = vel
        new_params = _treemap(lambda p, u: p - lr * u.astype(p.dtype), params, upd)
        return new_params, {"velocity": vel}

    return Optimizer(init, update)


def adagrad(learning_rate=0.01, epsilon: float = 1e-6) -> Optimizer:
    """Adagrad (reference: AdagradParameterOptimizer, operators/adagrad_op.cc)."""
    lr_fn = schedules.resolve(learning_rate)

    def init(params):
        return {"accum": _treemap(jnp.zeros_like, params)}

    def update(grads, opt_state, params, step):
        lr = lr_fn(step)
        accum = _treemap(lambda a, g: a + jnp.square(g.astype(a.dtype)), opt_state["accum"], grads)
        new_params = _treemap(
            lambda p, g, a: p - lr * g.astype(p.dtype) / (jnp.sqrt(a) + epsilon),
            params, grads, accum,
        )
        return new_params, {"accum": accum}

    return Optimizer(init, update)


def decayed_adagrad(learning_rate=0.01, decay: float = 0.95, epsilon: float = 1e-6) -> Optimizer:
    """Decayed Adagrad (reference: DecayedAdagradParameterOptimizer,
    operators/decayed_adagrad_op.cc)."""
    lr_fn = schedules.resolve(learning_rate)

    def init(params):
        return {"accum": _treemap(jnp.zeros_like, params)}

    def update(grads, opt_state, params, step):
        lr = lr_fn(step)
        accum = _treemap(
            lambda a, g: decay * a + (1.0 - decay) * jnp.square(g.astype(a.dtype)),
            opt_state["accum"], grads,
        )
        new_params = _treemap(
            lambda p, g, a: p - lr * g.astype(p.dtype) / (jnp.sqrt(a) + epsilon),
            params, grads, accum,
        )
        return new_params, {"accum": accum}

    return Optimizer(init, update)


def adadelta(rho: float = 0.95, epsilon: float = 1e-6, learning_rate=1.0) -> Optimizer:
    """AdaDelta (reference: AdaDeltaParameterOptimizer, operators/adadelta_op.cc)."""
    lr_fn = schedules.resolve(learning_rate)

    def init(params):
        zeros = _treemap(jnp.zeros_like, params)
        return {"accum_g": zeros, "accum_dx": _treemap(jnp.zeros_like, params)}

    def update(grads, opt_state, params, step):
        lr = lr_fn(step)
        accum_g = _treemap(
            lambda a, g: rho * a + (1 - rho) * jnp.square(g.astype(a.dtype)),
            opt_state["accum_g"], grads,
        )

        def _delta(g, ag, adx):
            return g.astype(ag.dtype) * jnp.sqrt(adx + epsilon) / jnp.sqrt(ag + epsilon)

        deltas = _treemap(_delta, grads, accum_g, opt_state["accum_dx"])
        accum_dx = _treemap(
            lambda a, d: rho * a + (1 - rho) * jnp.square(d),
            opt_state["accum_dx"], deltas,
        )
        new_params = _treemap(lambda p, d: p - lr * d.astype(p.dtype), params, deltas)
        return new_params, {"accum_g": accum_g, "accum_dx": accum_dx}

    return Optimizer(init, update)


def rmsprop(learning_rate=0.01, rho: float = 0.95, epsilon: float = 1e-6,
            momentum_mu: float = 0.0) -> Optimizer:
    """RMSProp (reference: RMSPropParameterOptimizer, operators/rmsprop_op.cc)."""
    lr_fn = schedules.resolve(learning_rate)

    def init(params):
        st = {"ms": _treemap(jnp.zeros_like, params)}
        if momentum_mu:
            st["mom"] = _treemap(jnp.zeros_like, params)
        return st

    def update(grads, opt_state, params, step):
        lr = lr_fn(step)
        ms = _treemap(
            lambda m, g: rho * m + (1 - rho) * jnp.square(g.astype(m.dtype)),
            opt_state["ms"], grads,
        )
        scaled = _treemap(
            lambda g, m: g.astype(m.dtype) / (jnp.sqrt(m) + epsilon), grads, ms
        )
        new_state = {"ms": ms}
        if momentum_mu:
            mom = _treemap(lambda v, s: momentum_mu * v + lr * s, opt_state["mom"], scaled)
            new_params = _treemap(lambda p, v: p - v.astype(p.dtype), params, mom)
            new_state["mom"] = mom
        else:
            new_params = _treemap(lambda p, s: p - lr * s.astype(p.dtype), params, scaled)
        return new_params, new_state

    return Optimizer(init, update)


def adam(learning_rate=0.001, beta1: float = 0.9, beta2: float = 0.999,
         epsilon: float = 1e-8) -> Optimizer:
    """Adam with bias correction (reference: AdamParameterOptimizer
    FirstOrderOptimizer.h:281, operators/adam_op.cc)."""
    lr_fn = schedules.resolve(learning_rate)

    def init(params):
        return {
            "m": _treemap(jnp.zeros_like, params),
            "v": _treemap(jnp.zeros_like, params),
        }

    def update(grads, opt_state, params, step):
        t = step.astype(jnp.float32) + 1.0
        lr = lr_fn(step) * jnp.sqrt(1.0 - beta2**t) / (1.0 - beta1**t)
        m = _treemap(lambda m_, g: beta1 * m_ + (1 - beta1) * g.astype(m_.dtype),
                     opt_state["m"], grads)
        v = _treemap(lambda v_, g: beta2 * v_ + (1 - beta2) * jnp.square(g.astype(v_.dtype)),
                     opt_state["v"], grads)
        new_params = _treemap(
            lambda p, m_, v_: p - (lr * m_ / (jnp.sqrt(v_) + epsilon)).astype(p.dtype),
            params, m, v,
        )
        return new_params, {"m": m, "v": v}

    return Optimizer(init, update)


def adamax(learning_rate=0.002, beta1: float = 0.9, beta2: float = 0.999,
           epsilon: float = 1e-8) -> Optimizer:
    """Adamax (reference: AdamaxParameterOptimizer, operators/adamax_op.cc)."""
    lr_fn = schedules.resolve(learning_rate)

    def init(params):
        return {
            "m": _treemap(jnp.zeros_like, params),
            "u": _treemap(jnp.zeros_like, params),
        }

    def update(grads, opt_state, params, step):
        t = step.astype(jnp.float32) + 1.0
        lr = lr_fn(step) / (1.0 - beta1**t)
        m = _treemap(lambda m_, g: beta1 * m_ + (1 - beta1) * g.astype(m_.dtype),
                     opt_state["m"], grads)
        u = _treemap(lambda u_, g: jnp.maximum(beta2 * u_, jnp.abs(g.astype(u_.dtype))),
                     opt_state["u"], grads)
        new_params = _treemap(
            lambda p, m_, u_: p - (lr * m_ / (u_ + epsilon)).astype(p.dtype),
            params, m, u,
        )
        return new_params, {"m": m, "u": u}

    return Optimizer(init, update)


def ftrl(learning_rate=0.01, l1: float = 0.0, l2: float = 0.0,
         lr_power: float = -0.5) -> Optimizer:
    """FTRL-proximal (reference: operators/ftrl_op.cc)."""
    lr_fn = schedules.resolve(learning_rate)

    def init(params):
        return {
            "n": _treemap(jnp.zeros_like, params),
            "z": _treemap(jnp.zeros_like, params),
        }

    def update(grads, opt_state, params, step):
        lr = lr_fn(step)

        def _upd(p, g, n, z):
            g = g.astype(p.dtype)
            new_n = n + jnp.square(g)
            sigma = (jnp.power(new_n, -lr_power) - jnp.power(n, -lr_power)) / lr
            new_z = z + g - sigma * p
            new_p = jnp.where(
                jnp.abs(new_z) <= l1,
                jnp.zeros_like(p),
                (jnp.sign(new_z) * l1 - new_z)
                / (jnp.power(new_n, -lr_power) / lr + 2 * l2),
            )
            return new_p, new_n, new_z

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_n = treedef.flatten_up_to(opt_state["n"])
        flat_z = treedef.flatten_up_to(opt_state["z"])
        out = [_upd(p, g, n, z) for p, g, n, z in zip(flat_p, flat_g, flat_n, flat_z)]
        new_params = treedef.unflatten([o[0] for o in out])
        new_n = treedef.unflatten([o[1] for o in out])
        new_z = treedef.unflatten([o[2] for o in out])
        return new_params, {"n": new_n, "z": new_z}

    return Optimizer(init, update)


def _lbfgs_family(learning_rate, history: int, min_curvature: float,
                  l1: float) -> Optimizer:
    """Shared L-BFGS / OWL-QN core (see lbfgs() and owlqn())."""
    lr_fn = schedules.resolve(learning_rate)
    m = history

    def init(params):
        flat, _ = jax.tree.flatten(params)
        dim_total = sum(int(np.prod(p.shape)) for p in flat)
        return {
            "s": jnp.zeros((m, dim_total), jnp.float32),
            "y": jnp.zeros((m, dim_total), jnp.float32),
            "rho": jnp.zeros((m,), jnp.float32),  # 1/(s·y), 0 = empty
            "prev_x": jnp.zeros((dim_total,), jnp.float32),
            "prev_g": jnp.zeros((dim_total,), jnp.float32),
            "gamma": jnp.ones((), jnp.float32),
            "count": jnp.zeros((), jnp.int32),
        }

    def _flatten(tree):
        flat, _ = jax.tree.flatten(tree)
        return jnp.concatenate([jnp.ravel(a).astype(jnp.float32)
                                for a in flat])

    def _unflatten_like(vec, params):
        flat, treedef = jax.tree.flatten(params)
        out, off = [], 0
        for p in flat:
            n = int(np.prod(p.shape))
            out.append(vec[off:off + n].reshape(p.shape).astype(p.dtype))
            off += n
        return treedef.unflatten(out)

    def update(grads, opt_state, params, step):
        lr = lr_fn(step)
        x = _flatten(params)
        g = _flatten(grads)
        if l1 > 0.0:
            # op_make_steepest_desc_dir: L1 pseudo-gradient — the l1
            # subgradient chosen to point into the descent orthant;
            # coordinates pinned at 0 inside the [-l1, l1] band get 0
            pg = jnp.where(
                x < 0, g - l1,
                jnp.where(x > 0, g + l1,
                          jnp.where(g < -l1, g + l1,
                                    jnp.where(g > l1, g - l1, 0.0))))
        else:
            pg = g
        st = opt_state
        count = st["count"]

        # record the newest (s, y) pair from the PREVIOUS step
        s_new = x - st["prev_x"]
        y_new = g - st["prev_g"]
        sy = jnp.dot(s_new, y_new)
        ok = (count > 0) & (sy > min_curvature)
        slot = jnp.where(count > 0, (count - 1) % m, 0)
        s_buf = st["s"].at[slot].set(jnp.where(ok, s_new, st["s"][slot]))
        y_buf = st["y"].at[slot].set(jnp.where(ok, y_new, st["y"][slot]))
        # a rejected pair INVALIDATES the slot (rho 0) rather than
        # leaving an m-steps-old pair masquerading as the newest
        rho = st["rho"].at[slot].set(
            jnp.where(ok, 1.0 / jnp.maximum(sy, min_curvature), 0.0))

        # two-loop recursion, newest -> oldest then back; empty slots
        # carry rho == 0 so their terms vanish
        def newest_first(i):
            return (slot - i) % m

        q = pg
        alphas = []
        for i in range(m):
            j = newest_first(i)
            a = rho[j] * jnp.dot(s_buf[j], q)
            q = q - a * y_buf[j]
            alphas.append((j, a))
        # initial Hessian scale gamma = s·y / y·y of the newest ACCEPTED
        # pair (Nocedal & Wright 7.20) — a rejected step keeps the last
        # good scale rather than collapsing to I, which on an ill-
        # conditioned objective would blow the un-line-searched step up
        # by 1/gamma
        ynorm = jnp.dot(y_new, y_new)
        gamma = jnp.where(ok, sy / jnp.maximum(ynorm, 1e-12),
                          st["gamma"])
        r = gamma * q
        for j, a in reversed(alphas):
            b = rho[j] * jnp.dot(y_buf[j], r)
            r = r + (a - b) * s_buf[j]

        # first step (no history): plain (pseudo-)gradient direction
        direction = jnp.where(count > 0, r, pg)
        if l1 > 0.0:
            # op_fix_dir_signs: the quasi-Newton direction may not
            # leave the steepest-descent orthant — zero disagreeing
            # coordinates (move dir -direction vs steepest -pg)
            direction = jnp.where(direction * pg > 0, direction, 0.0)
        new_x = x - lr * direction
        if l1 > 0.0:
            # op_fix_omega_signs: a coordinate crossing zero clamps AT
            # zero (the orthant-projection that makes OWL-QN sparse)
            new_x = jnp.where(x * new_x < 0, 0.0, new_x)
        new_state = {
            "s": s_buf, "y": y_buf, "rho": rho,
            "prev_x": x, "prev_g": g, "gamma": gamma,
            "count": count + 1,
        }
        return _unflatten_like(new_x, params), new_state

    return Optimizer(init, update)


def lbfgs(learning_rate=1.0, history: int = 10,
          min_curvature: float = 1e-10) -> Optimizer:
    """Limited-memory BFGS with the standard two-loop recursion.

    Reference parity: the pserver's `doOperation` vector-op set
    (`pserver/ParameterServer2.h op_SGD … op_fix_omega_signs`,
    `op_make_steepest_desc_dir`) existed precisely to host
    (OWL-)L-BFGS-style algorithms server-side; the TPU-native answer is
    a pure-functional optimizer whose history pytree shards like any
    other optimizer state (ZeRO via shard_train_state).

    Fixed-size history (XLA static shapes): the m most recent (s, y)
    pairs live in [m, ...] buffers with a rolling write index; pairs
    with curvature s·y <= min_curvature invalidate their slot (keeps H
    positive-definite). No line search — the step is
    `learning_rate * H⁻¹g` (deterministic full-batch or large-batch
    regimes; for stochastic minibatches prefer adam). First step falls
    back to plain gradient descent.
    """
    return _lbfgs_family(learning_rate, history, min_curvature, 0.0)


def owlqn(learning_rate=1.0, l1: float = 1e-4, history: int = 10,
          min_curvature: float = 1e-10) -> Optimizer:
    """Orthant-wise L-BFGS for L1-regularized objectives (OWL-QN) —
    the exact algorithm the reference's pserver op set implements
    (`op_make_steepest_desc_dir` = the L1 pseudo-gradient,
    `op_fix_dir_signs`, `op_fix_omega_signs` = the orthant projection;
    pserver/ParameterServer2.cpp:1153-1202). Minimizes f(x) + l1*|x|_1
    with exact zeros in the solution (the sparsity L1 is for)."""
    if l1 <= 0:
        raise ValueError(f"owlqn requires l1 > 0, got {l1}")
    return _lbfgs_family(learning_rate, history, min_curvature, l1)


def proximal_gd(learning_rate=0.01, l1: float = 0.0, l2: float = 0.0) -> Optimizer:
    """Proximal gradient descent (reference: operators/proximal_gd_op.cc)."""
    lr_fn = schedules.resolve(learning_rate)

    def init(params):
        return ()

    def update(grads, opt_state, params, step):
        lr = lr_fn(step)

        def _upd(p, g):
            prox = p - lr * g.astype(p.dtype)
            return (
                jnp.sign(prox)
                * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
                / (1.0 + lr * l2)
            )

        return _treemap(_upd, params, grads), opt_state

    return Optimizer(init, update)


# ---------------------------------------------------------------------------
# transforms: clipping, weight decay (regularizers), composition
# ---------------------------------------------------------------------------


def clip_by_global_norm(grads, max_norm: float):
    """Global-norm gradient clipping (reference:
    OptimizerWithGradientClipping FirstOrderOptimizer.h:334, operators/clip_op)."""
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm


def clip_by_value(grads, limit: float):
    return jax.tree.map(lambda g: jnp.clip(g, -limit, limit), grads)


def chain(base: Optimizer, *, weight_decay: float = 0.0,
          clip_global_norm: Optional[float] = None,
          clip_value: Optional[float] = None,
          decay_mask: Optional[Callable[[str], bool]] = None) -> Optimizer:
    """Wrap an optimizer with L2 weight decay + gradient clipping
    (reference: OptimizerWithRegularizer, OptimizerWithGradientClipping)."""

    def init(params):
        return base.init(params)

    def update(grads, opt_state, params, step):
        if clip_value is not None:
            grads = clip_by_value(grads, clip_value)
        if clip_global_norm is not None:
            grads, _ = clip_by_global_norm(grads, clip_global_norm)
        if weight_decay:
            if decay_mask is None:
                grads = jax.tree.map(
                    lambda g, p: g + weight_decay * p.astype(g.dtype), grads, params
                )
            else:
                from paddle_tpu.core.pytree import tree_map_with_name

                named_params = dict(named_leaves(params))
                grads = tree_map_with_name(
                    lambda name, g: g
                    + (weight_decay * named_params[name].astype(g.dtype)
                       if decay_mask(name) else 0.0),
                    grads,
                )
        return base.update(grads, opt_state, params, step)

    return Optimizer(init, update)


def get(name: str, **kwargs) -> Optimizer:
    table = {
        "sgd": sgd,
        "momentum": momentum,
        "adagrad": adagrad,
        "decayed_adagrad": decayed_adagrad,
        "adadelta": adadelta,
        "rmsprop": rmsprop,
        "adam": adam,
        "adamax": adamax,
        "ftrl": ftrl,
        "lbfgs": lbfgs,
        "owlqn": owlqn,
        "proximal_gd": proximal_gd,
    }
    try:
        return table[name](**kwargs)
    except KeyError:
        raise ValueError(f"unknown optimizer {name!r}; known: {sorted(table)}") from None
