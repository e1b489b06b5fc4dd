"""Composite layers: residual blocks and parallel branches.

The reference expresses non-linear topologies through its config DSL
(reference: python/paddle/trainer_config_helpers/networks.py — e.g.
img_conv_group / resnet configs in benchmark/paddle/image/resnet.py:1-40,
googlenet.py inception blocks via multiple projections into one
concat_layer, gserver/layers/ConcatenateLayer.cpp and AddtoLayer.cpp).
TPU-native equivalent: composition combinators over pure layers — XLA sees
one fused graph either way.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from paddle_tpu.nn.module import Layer, ShapeSpec
from paddle_tpu.ops import activations as A


class Residual(Layer):
    """y = act(main(x) + shortcut(x)) — AddtoLayer-style skip connection
    (reference: gserver/layers/AddtoLayer.cpp; resnet config
    benchmark/paddle/image/resnet.py)."""

    def __init__(
        self,
        main: Layer,
        shortcut: Optional[Layer] = None,
        *,
        activation=None,
        name: Optional[str] = None,
    ):
        self.main = main
        self.shortcut = shortcut
        self.activation = A.get(activation)
        self.name = name

    def _init(self, rng, spec: ShapeSpec, _abstract: bool = False):
        params, state = {}, {}
        if _abstract:
            m_p, m_s, out = self.main._init(None, spec, _abstract=True)
            if self.shortcut is not None:
                self.shortcut._init(None, spec, _abstract=True)
            return {}, {}, out
        r_main, r_short = jax.random.split(rng)
        m_p, m_s, out = self.main._init(r_main, spec)
        params["main"] = m_p
        if m_s:
            state["main"] = m_s
        if self.shortcut is not None:
            s_p, s_s, _ = self.shortcut._init(r_short, spec)
            if s_p:
                params["shortcut"] = s_p
            if s_s:
                state["shortcut"] = s_s
        return params, state, out

    def _apply(self, params, state, x, *, training: bool, rng):
        r_main = r_short = None
        if rng is not None:
            r_main, r_short = jax.random.split(rng)
        y, m_s = self.main._apply(
            params.get("main", {}), state.get("main", {}), x,
            training=training, rng=r_main,
        )
        if self.shortcut is not None:
            sc, s_s = self.shortcut._apply(
                params.get("shortcut", {}), state.get("shortcut", {}), x,
                training=training, rng=r_short,
            )
        else:
            sc, s_s = x, {}
        new_state = {}
        if m_s:
            new_state["main"] = m_s
        if s_s:
            new_state["shortcut"] = s_s
        return self.activation(y + sc), new_state


class Remat(Layer):
    """Rematerialize a sub-layer's forward during the backward
    (jax.checkpoint around the wrapped apply).

    The reference had no activation checkpointing (SURVEY §5 — its
    long-sequence memory grew linearly); on TPU remat is also a
    bandwidth tool: re-computing cheap VPU ops (BN normalize, ReLU) in
    the backward instead of streaming their saved outputs trades MXU
    FLOPs for bytes (where the image cell's time goes: PERF.md
    section 5).

    policy:
      None        — save nothing inside the block; the backward re-runs
                    the whole forward from the block input.
      "conv_out"  — save only tensors tagged ``checkpoint_name
                    'conv_out'`` (every nn.Conv2D output); BN stats,
                    normalize and activations recompute from those.

    The wrapper is transparent: it adopts the inner layer's name and
    passes params/state through unchanged, so wrapping does not change
    the checkpoint/pytree layout of a model.
    """

    def __init__(self, inner: Layer, *, policy: Optional[str] = "conv_out",
                 name: Optional[str] = None):
        if policy not in (None, "conv_out"):
            raise ValueError(
                f"Remat policy must be None or 'conv_out', got {policy!r}")
        self.inner = inner
        self.policy = policy
        self.name = name if name is not None else inner.name

    def _init(self, rng, *specs, _abstract: bool = False):
        return self.inner._init(rng, *specs, _abstract=_abstract)

    def _apply(self, params, state, *inputs, training: bool, rng):
        kwargs = {}
        if self.policy == "conv_out":
            kwargs["policy"] = \
                jax.checkpoint_policies.save_only_these_names("conv_out")

        @functools.partial(jax.checkpoint, **kwargs)
        def fn(params, state, rng, *inputs):
            return self.inner._apply(params, state, *inputs,
                                     training=training, rng=rng)

        return fn(params, state, rng, *inputs)


class MultiTask(Layer):
    """Several independent sub-networks trained jointly (reference:
    gserver/gradientmachines/MultiNetwork.h — one input per sub-network,
    forward all, total cost = caller's combination of the outputs).

    init takes one ShapeSpec per sub-network (in order); apply takes one
    input per sub-network and returns a tuple of outputs.
    """

    def __init__(self, networks, name=None):
        """networks: list of (name, Layer) pairs or a dict."""
        if isinstance(networks, dict):
            networks = list(networks.items())
        self.networks = list(networks)
        self.name = name

    def _init(self, rng, *specs, _abstract: bool = False):
        from paddle_tpu.core.errors import enforce

        enforce(len(specs) == len(self.networks),
                f"{len(self.networks)} sub-networks but {len(specs)} specs")
        params, state, outs = {}, {}, []
        for (key, net), spec in zip(self.networks, specs):
            if _abstract:
                sub_p, sub_s, out = net._init(None, spec, _abstract=True)
            else:
                rng, sub = jax.random.split(rng)
                sub_p, sub_s, out = net._init(sub, spec)
            if sub_p:
                params[key] = sub_p
            if sub_s:
                state[key] = sub_s
            outs.append(out)
        return params, state, tuple(outs)

    def _apply(self, params, state, *inputs, training: bool, rng):
        from paddle_tpu.core.errors import enforce

        enforce(len(inputs) == len(self.networks),
                f"{len(self.networks)} sub-networks but {len(inputs)} inputs")
        outs, new_state = [], {}
        for (key, net), x in zip(self.networks, inputs):
            sub_rng = None
            if rng is not None:
                rng, sub_rng = jax.random.split(rng)
            out, sub_s = net._apply(params.get(key, {}), state.get(key, {}),
                                    x, training=training, rng=sub_rng)
            if sub_s:
                new_state[key] = sub_s
            outs.append(out)
        return tuple(outs), new_state


class Branches(Layer):
    """Apply N sub-layers to the same input; concatenate outputs on the
    channel (last) axis — the inception pattern (reference: concat_layer in
    config DSL, gserver/layers/ConcatenateLayer.cpp)."""

    def __init__(self, branches: Sequence[Layer], name: Optional[str] = None):
        self.branches = list(branches)
        self.name = name

    def _init(self, rng, spec: ShapeSpec, _abstract: bool = False):
        params, state = {}, {}
        out_specs: List[ShapeSpec] = []
        for i, br in enumerate(self.branches):
            key = br.name or f"branch{i}"
            if _abstract:
                _, _, out = br._init(None, spec, _abstract=True)
            else:
                rng, sub = jax.random.split(rng)
                b_p, b_s, out = br._init(sub, spec)
                if b_p:
                    params[key] = b_p
                if b_s:
                    state[key] = b_s
            out_specs.append(out)
        ch = sum(s.shape[-1] for s in out_specs)
        out_spec = ShapeSpec(out_specs[0].shape[:-1] + (ch,), out_specs[0].dtype)
        return params, state, out_spec

    def _apply(self, params, state, x, *, training: bool, rng):
        outs = []
        new_state = {}
        for i, br in enumerate(self.branches):
            key = br.name or f"branch{i}"
            sub_rng = None
            if rng is not None:
                rng, sub_rng = jax.random.split(rng)
            y, b_s = br._apply(
                params.get(key, {}), state.get(key, {}), x,
                training=training, rng=sub_rng,
            )
            if b_s:
                new_state[key] = b_s
            outs.append(y)
        return jnp.concatenate(outs, axis=-1), new_state
