"""Driver `train_lm_afmoe`: a decoder-only LM of Trinity's block (AfMoE):
sliding-window layers with a rotary embedding beside full layers without
one (NoPE), an output gate on every attention, sandwich norms, leading
dense gated-SiLU layers, then a dropless expert layer that holds a share
of its experts beside an ungated shared expert, its sigmoid router
choosing by an expert bias; trained by one jitted
`value_and_grad(T.loss_and_aux)` + `optimizer.update` +
`moe.update_expert_bias` with the state donated: `train_lm_moe`'s loop
on another block, with the expert bias carried through the step's state
beside the parameters (it is no parameter: no gradient reaches it, the
optimizer never sees it).

From the configuration: `layers_kept` names the published layers this
stage holds and `layer_types` (copied whole) their kinds;
`num_dense_layers` of them lead with the dense MLP of
`intermediate_size`; the router scores by `score_func` over
`router_width` experts, renormalised (`route_norm`) and times
`route_scale`; the shared expert is `num_shared_experts` x
`moe_intermediate_size` wide; `mup_enabled` multiplies the embedding by
sqrt(hidden_size); `load_balance_coeff` is the bias update's step.
Weights come from `weights_stacked.py`, as the other MoE cells': its
N(0, 1) table keeps the token above what every position shares in the
stream (the configuration's `assumed` says why).

The step's counts, summed in its state and read after the window: rows
routed to held experts and rows of the fullest held expert (as the
other MoE cells), rows routed over all the router's experts and rows of
the fullest of them, a layer each. `correct` also compares the expert
bias after the check steps: by layer, the norm of its change from 0.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

import weights_stacked as weights
from loading import HERE, load_module

from paddle_tpu import optim
from paddle_tpu.core import dtypes
from paddle_tpu.models import transformer as T
from paddle_tpu.parallel import moe

SLIDING, FULL = "sliding_attention", "full_attention"
DRIVERS = os.path.join(HERE, "drivers")


def layer_types(config: dict) -> list:
    return [config["layer_types"][i] for i in config["layers_kept"]]


def attention_kinds(config: dict) -> tuple:
    kinds = {SLIDING: T.AttentionKind(window=config["sliding_window"],
                                      output_gate=True),
             FULL: T.AttentionKind(output_gate=True, rotary_dim=0)}
    return tuple((name, kinds[name])
                 for name in dict.fromkeys(layer_types(config)))


def bias_names(cfg) -> list:
    """The `stats` leaf of each expert layer's bias."""
    return [f"blocks/{i}/moe/expert_bias" for i in cfg.moe_layers]


class Driver(load_module(DRIVERS, "train_lm_moe").Driver):
    def _build(self):
        c, t = self.config, self.traffic
        if len(c["layers_kept"]) != c["num_hidden_layers"]:
            raise ValueError("layers_kept names one published layer a layer")
        if (c["score_func"] != "sigmoid" or not c["route_norm"]
                or c["rope_scaling"] is not None
                or c["hidden_act"] != "silu"
                or {c["n_group"], c["topk_group"]} != {1}
                or c["intermediate_size"] % c["hidden_size"]):
            raise ValueError("the driver builds the published Trinity-Mini "
                             "router and block alone")
        if c["compute_dtype"] == "bfloat16":
            dtypes.set_default_policy(dtypes.bf16_compute_policy())
        else:
            dtypes.set_default_policy(dtypes.Policy())
        self.cfg = cfg = T.TransformerConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
            rope_base=float(c["rope_theta"]), norm="rms", bias=False,
            qk_norm=True, rms_eps=c["rms_norm_eps"], sandwich_norm=True,
            embed_scale=(math.sqrt(c["hidden_size"]) if c["mup_enabled"]
                         else None),
            layer_types=tuple(layer_types(c)),
            attention_kinds=attention_kinds(c), mlp="swiglu",
            mlp_ratio=c["intermediate_size"] // c["hidden_size"],
            moe_dense_layers=c["num_dense_layers"], moe_router="dropless",
            moe_experts=c["router_width"], moe_every=1,
            moe_k=c["num_experts_per_tok"], moe_dim=c["moe_intermediate_size"],
            moe_held=c["num_experts"], moe_held_first=c["experts_held_first"],
            moe_shared_dim=(c["num_shared_experts"]
                            * c["moe_intermediate_size"]),
            moe_shared_gate=False, moe_score=c["score_func"],
            moe_route_scale=c["route_scale"], moe_expert_bias=True,
            attn_impl=t["attn_impl"], remat=t["remat"],
            fused_ce_chunk=t["fused_ce_chunk"])
        o = c["optimizer"]
        self.opt = opt = optim.get(o["name"], **{k: v for k, v in o.items()
                                                 if k != "name"})
        self.shapes = jax.eval_shape(
            lambda: T.init_params(jax.random.key(0), cfg))
        coeff = c["load_balance_coeff"]

        def step(state, toks):
            params, opt_state, i, counts, bias = state
            (loss, stats), grads = jax.value_and_grad(
                lambda q: T.loss_and_aux(q, cfg, toks, expert_bias=bias),
                has_aux=True)(params)
            params, opt_state = opt.update(grads, opt_state, params, i)
            bias = moe.update_expert_bias(bias, stats.route_counts, coeff)
            counts = counts + jnp.stack(
                [jnp.sum(stats.rows_held), jnp.sum(stats.rows_max_expert),
                 jnp.sum(stats.route_counts),
                 jnp.sum(jnp.max(stats.route_counts, axis=-1))])
            return (params, opt_state, i + 1, counts, bias), loss

        self.step = self._jitted = jax.jit(step, donate_argnums=(0,))

    def _bias_norms(self, bias) -> dict:
        """By expert layer, the norm of the bias's change from its start
        (0)."""
        norms = jnp.sqrt(jnp.sum(jnp.square(bias), axis=-1))
        return dict(zip(bias_names(self.cfg),
                        (float(x) for x in jax.device_get(norms))))

    def setup(self):
        if self.step is None:
            self._build()
        self._make_pool()

        def initial_state(key):
            params = weights.generate(self.shapes, key)
            return (params, self.opt.init(params), jnp.zeros((), jnp.int32),
                    jnp.zeros((4,), jnp.int32), T.init_expert_bias(self.cfg))

        state = jax.jit(initial_state)(weights.seed_key(self.seed))
        beta1 = self.config["optimizer"]["beta1"]
        losses, grad1 = [], None
        for b in range(self.traffic["check_steps"]):
            state, loss = self.step(state, jax.device_put(self.pool[b]))
            losses.append(loss)
            if b == 0:      # m after one step is (1 - beta1) * gradient
                grad1 = weights.norms(state[1]["m"]) / (1.0 - beta1)
        self.program_numbers = {
            "loss": [float(x) for x in losses],
            "grad1": weights.named(self.shapes, grad1),
            "dparam": weights.named(self.shapes,
                                    self._change_norms(state[0])),
            "stats": self._bias_norms(state[4]),
        }
        self.state = state
        # read here, not inside the window
        self._before = int(state[2]), jax.device_get(state[3])

    # -- the measured window: the LM driver's loop, and the counts ---------
    def window(self, deadline, watcher, tracer, spans):
        steps_before, counts_before = self._before
        counters = load_module(DRIVERS, "train_lm").Driver.window(
            self, deadline, watcher, tracer, spans)
        rows_held, rows_max, route_rows, route_max = (
            int(x) for x in jax.device_get(self.state[3]) - counts_before)
        steps = int(self.state[2]) - steps_before
        return {**counters, "moe.rows_held": rows_held,
                "moe.rows_max_expert": rows_max,
                "moe.route_rows": route_rows, "moe.route_rows_max": route_max,
                "moe.positions": (steps * self.units_per_step
                                  * len(self.cfg.moe_layers))}

    # -- the plain reference, on the same weights and rows ------------------
    def reference_numbers(self, precision):
        c, t = self.config, self.traffic
        arch = {"n_heads": c["num_attention_heads"],
                "n_kv_heads": c["num_key_value_heads"],
                "head_dim": c["head_dim"], "rope_base": float(c["rope_theta"]),
                "rms_eps": c["rms_norm_eps"],
                "embed_scale": self.cfg.embed_scale or 1.0,
                "window": c["sliding_window"], "layer_types": layer_types(c),
                "dense_layers": c["num_dense_layers"],
                "experts_per_tok": c["num_experts_per_tok"],
                "first_held": c["experts_held_first"],
                "route_scale": c["route_scale"],
                "bias_coeff": c["load_balance_coeff"]}
        step = self.reference.make_step(arch, c["optimizer"], precision)

        def initial_state(key):
            params = weights.generate(self.shapes, key)
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            return (params, zeros(), zeros(), jnp.zeros((), jnp.float32),
                    T.init_expert_bias(self.cfg))

        state = jax.jit(initial_state)(weights.seed_key(self.seed))
        losses, grad1 = [], None
        for b in range(t["check_steps"]):
            state, loss = step(state, jnp.asarray(self.pool[b]))
            losses.append(float(loss))
            if b == 0:
                grad1 = weights.norms(state[1]) / (
                    1.0 - c["optimizer"]["beta1"])
        dparam = self._change_norms(state[0])
        stats = self._bias_norms(state[4])
        del state
        step.clear_cache()      # unload it: the next program needs the room
        return {"loss": losses, "rank": weights.ranks(self.shapes),
                "grad1": weights.named(self.shapes, grad1),
                "dparam": weights.named(self.shapes, dparam),
                "stats": stats}
