"""Driver `train_lm_moe`: a decoder-only LM whose layers differ in kind
(sliding-window and full attention, each with its own rotary embedding)
and whose FFN is a dropless expert layer that holds a share of its
experts, trained by one jitted `value_and_grad(T.loss_and_aux)` +
`optimizer.update` with the state donated: the user flow of
`examples/transformer_lm.py --layer-pattern`.

The benchmark owns the loop (`drivers/train_lm.py`'s own, one loop for
the LM cells), the token rows (uniform over the rows of the vocabulary
held) and the weights (`weights_stacked.py`). The program owns the
model, the attention kind of each layer and its flash kernels, the
dropless expert layer, the fused cross entropy and the optimizer; its
loss's auxiliary counts (rows routed to held experts, rows of the
fullest expert, a layer) are summed in the step's state and read after
the window, for the counters.

From the configuration: `layer_types[:num_hidden_layers]` names each
layer's kind, `rope_parameters[kind]` its rotary embedding (`default` or
`yarn` with its numbers), and `sliding_window` is the window of the
`sliding_attention` kind alone.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

import weights_stacked as weights
from loading import HERE, load_module

from paddle_tpu import optim
from paddle_tpu.core import dtypes
from paddle_tpu.models import transformer as T

SLIDING = "sliding_attention"


def layer_types(config: dict) -> list:
    return config["layer_types"][:config["num_hidden_layers"]]


def attention_kinds(config: dict) -> tuple:
    """((kind, T.AttentionKind), ...) for the kinds the layers use."""
    kinds = []
    for name in dict.fromkeys(layer_types(config)):
        rope = config["rope_parameters"][name]
        window = config["sliding_window"] if name == SLIDING else None
        if rope["rope_type"] == "default":
            kinds.append((name, T.AttentionKind(window=window)))
        elif rope["rope_type"] == "yarn":
            kinds.append((name, T.AttentionKind(
                window=window, rope_scaling="yarn",
                rope_factor=float(rope["factor"]),
                rope_original=rope["original_max_position_embeddings"],
                rope_beta_fast=float(rope["beta_fast"]),
                rope_beta_slow=float(rope["beta_slow"]),
                rope_attention_factor=rope["attention_factor"])))
        else:
            raise ValueError(f"no rotary embedding {rope['rope_type']!r}")
    return tuple(kinds)


def rope_base(config: dict) -> float:
    bases = {float(config["rope_parameters"][k]["rope_theta"])
             for k in layer_types(config)}
    if len(bases) != 1:
        raise ValueError(f"the program has one rope_base, got {bases}")
    return bases.pop()


class Driver(load_module(os.path.join(HERE, "drivers"), "train_lm").Driver):
    def _build(self):
        c, t = self.config, self.traffic
        if c["rms_norm_eps"] != 1e-6:
            raise ValueError("the program's RMSNorm has eps 1e-6 alone")
        if set(c["mlp_layer_types"][:c["num_hidden_layers"]]) != {"sparse"}:
            raise ValueError("every layer's FFN is the expert layer here")
        if c["compute_dtype"] == "bfloat16":
            dtypes.set_default_policy(dtypes.bf16_compute_policy())
        else:
            dtypes.set_default_policy(dtypes.Policy())
        self.cfg = cfg = T.TransformerConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
            rope_base=rope_base(c), norm="rms", bias=c["attention_bias"],
            qk_norm=True, layer_types=tuple(layer_types(c)),
            attention_kinds=attention_kinds(c), moe_router="dropless",
            moe_experts=c["router_width"], moe_every=1,
            moe_k=c["num_experts_per_tok"], moe_dim=c["moe_intermediate_size"],
            moe_held=c["num_experts"], moe_held_first=c["experts_held_first"],
            attn_impl=t["attn_impl"], remat=t["remat"],
            fused_ce_chunk=t["fused_ce_chunk"])
        o = c["optimizer"]
        self.opt = opt = optim.get(o["name"], **{k: v for k, v in o.items()
                                                 if k != "name"})
        self.shapes = jax.eval_shape(
            lambda: T.init_params(jax.random.key(0), cfg))

        def step(state, toks):
            params, opt_state, i, counts = state
            (loss, stats), grads = jax.value_and_grad(
                lambda q: T.loss_and_aux(q, cfg, toks), has_aux=True)(params)
            params, opt_state = opt.update(grads, opt_state, params, i)
            counts = counts + jnp.stack(
                [jnp.sum(stats.rows_held), jnp.sum(stats.rows_max_expert)])
            return (params, opt_state, i + 1, counts), loss

        self.step = self._jitted = jax.jit(step, donate_argnums=(0,))

    def _change_norms(self, params):
        """Per-leaf norms of `params` minus the seed's weights, which are
        made again here and not kept beside the optimizer's state."""
        return jax.jit(lambda p, k: weights.leaf_norms(jax.tree.map(
            jnp.subtract, p, weights.generate(self.shapes, k))))(
                params, weights.seed_key(self.seed))

    # -- set-up: the steps `correct` compares are the warm-up --------------
    def setup(self):
        if self.step is None:
            self._build()
        self._make_pool()

        def initial_state(key):
            params = weights.generate(self.shapes, key)
            return (params, self.opt.init(params), jnp.zeros((), jnp.int32),
                    jnp.zeros((2,), jnp.int32))

        state = jax.jit(initial_state)(weights.seed_key(self.seed))
        beta1 = self.config["optimizer"]["beta1"]
        losses, grad1 = [], None
        for b in range(self.traffic["check_steps"]):
            state, loss = self.step(state, jax.device_put(self.pool[b]))
            losses.append(loss)
            if b == 0:      # m after one step is (1 - beta1) * gradient
                grad1 = weights.norms(state[1]["m"]) / (
                    1.0 - beta1)
        self.program_numbers = {
            "loss": [float(x) for x in losses],
            "grad1": weights.named(self.shapes, grad1),
            "dparam": weights.named(self.shapes,
                                    self._change_norms(state[0])),
        }
        self.state = state
        # read here, not inside the window: nothing of the window waits
        # on a device-to-host copy before its first step
        self._before = int(state[2]), np.asarray(state[3])

    # -- the measured window: the LM driver's loop, and the counts ------
    def window(self, deadline, watcher, tracer, spans):
        steps_before, counts_before = self._before
        counters = super().window(deadline, watcher, tracer, spans)
        rows_held, rows_max = (int(x) for x in
                               np.asarray(self.state[3]) - counts_before)
        steps = int(self.state[2]) - steps_before
        return {**counters, "moe.rows_held": rows_held,
                "moe.rows_max_expert": rows_max,
                "moe.positions": (steps * self.units_per_step
                                  * self.config["num_hidden_layers"])}

    def free(self):
        """The state, and the step's loaded program with the scratch
        space it reserves: the reference needs the room (the next
        `setup()` reads the program back from the compile cache)."""
        self.state = None
        if self.step is not None:
            self._jitted.clear_cache()

    # -- the plain reference, on the same weights and rows ------------------
    def reference_numbers(self, precision):
        c, t = self.config, self.traffic
        arch = {"n_heads": c["num_attention_heads"],
                "n_kv_heads": c["num_key_value_heads"],
                "head_dim": c["head_dim"], "rope_base": rope_base(c),
                "rms_eps": c["rms_norm_eps"],
                "experts_per_tok": c["num_experts_per_tok"],
                "first_held": c["experts_held_first"],
                "window": c["sliding_window"],
                "layer_types": layer_types(c),
                "yarn": next((r for r in c["rope_parameters"].values()
                              if r["rope_type"] == "yarn"), None)}
        step = self.reference.make_step(arch, c["optimizer"], precision)

        def initial_state(key):
            params = weights.generate(self.shapes, key)
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            return params, zeros(), zeros(), jnp.zeros((), jnp.float32)

        state = jax.jit(initial_state)(weights.seed_key(self.seed))
        losses, grad1 = [], None
        for b in range(t["check_steps"]):
            state, loss = step(state, jnp.asarray(self.pool[b]))
            losses.append(float(loss))
            if b == 0:
                grad1 = weights.norms(state[1]) / (
                    1.0 - c["optimizer"]["beta1"])
        dparam = self._change_norms(state[0])
        del state
        step.clear_cache()      # unload it: the next program needs the room
        return {"loss": losses, "rank": weights.ranks(self.shapes),
                "grad1": weights.named(self.shapes, grad1),
                "dparam": weights.named(self.shapes, dparam)}
