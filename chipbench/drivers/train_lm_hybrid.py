"""Driver `train_lm_hybrid`: a decoder-only LM whose layers mix token
mixers, Gated DeltaNet layers (`linear_attention`) and gated softmax
attention layers (`full_attention`), over a dropless expert layer that
holds a share of its experts beside a gated shared expert (Qwen3-Next),
trained by one jitted `value_and_grad(T.loss_and_aux)` +
`optimizer.update` with the state donated: `train_lm_moe`'s loop, step
and counts on another block.

From the configuration: `layer_types[:num_hidden_layers]` names each
layer's kind; the `full_attention` kind gates its output and turns the
first `partial_rotary_factor * head_dim` lanes of each head at base
`rope_theta`; the `linear_attention` kind is a Gated DeltaNet layer of
the `linear_*` sizes; `shared_expert_intermediate_size` is the shared
expert's width. Weights come from `weights_hybrid.py` (A_log and
dt_bias by their own rules).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

import weights_hybrid as weights
from loading import HERE, load_module

from paddle_tpu import optim
from paddle_tpu.core import dtypes
from paddle_tpu.models import transformer as T

LINEAR, FULL = "linear_attention", "full_attention"


def layer_types(config: dict) -> list:
    return config["layer_types"][:config["num_hidden_layers"]]


def rotary_dim(config: dict) -> int:
    return int(config["head_dim"] * config["partial_rotary_factor"])


def attention_kinds(config: dict) -> tuple:
    kinds = {LINEAR: T.AttentionKind(mixer="gated_delta"),
             FULL: T.AttentionKind(output_gate=True,
                                   rotary_dim=rotary_dim(config))}
    return tuple((name, kinds[name])
                 for name in dict.fromkeys(layer_types(config)))


class Driver(load_module(os.path.join(HERE, "drivers"), "train_lm_moe").Driver):
    def _build(self):
        c, t = self.config, self.traffic
        if c["rms_norm_eps"] != 1e-6:
            raise ValueError("the program's RMSNorm has eps 1e-6 alone")
        if c["mlp_only_layers"] or c["decoder_sparse_step"] != 1:
            raise ValueError("every layer's FFN is the expert layer here")
        if c["rope_scaling"] is not None:
            raise ValueError("no rotary scaling here")
        if c["compute_dtype"] == "bfloat16":
            dtypes.set_default_policy(dtypes.bf16_compute_policy())
        else:
            dtypes.set_default_policy(dtypes.Policy())
        self.cfg = cfg = T.TransformerConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
            rope_base=float(c["rope_theta"]), norm="rms", bias=False,
            qk_norm=True, layer_types=tuple(layer_types(c)),
            attention_kinds=attention_kinds(c), moe_router="dropless",
            moe_experts=c["router_width"], moe_every=1,
            moe_k=c["num_experts_per_tok"], moe_dim=c["moe_intermediate_size"],
            moe_held=c["num_experts"], moe_held_first=c["experts_held_first"],
            moe_shared_dim=c["shared_expert_intermediate_size"],
            gdn_key_heads=c["linear_num_key_heads"],
            gdn_value_heads=c["linear_num_value_heads"],
            gdn_key_dim=c["linear_key_head_dim"],
            gdn_value_dim=c["linear_value_head_dim"],
            gdn_conv=c["linear_conv_kernel_dim"],
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

    # -- the seed's weights by this block's rules: the parent's three
    # -- methods that make them, with `weights_hybrid` ---------------------
    def _change_norms(self, params):
        return jax.jit(lambda p, k: weights.leaf_norms(jax.tree.map(
            jnp.subtract, p, weights.generate(self.shapes, k))))(
                params, weights.seed_key(self.seed))

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
                grad1 = weights.norms(state[1]["m"]) / (1.0 - beta1)
        self.program_numbers = {
            "loss": [float(x) for x in losses],
            "grad1": weights.named(self.shapes, grad1),
            "dparam": weights.named(self.shapes,
                                    self._change_norms(state[0])),
        }
        self.state = state
        # read here, not inside the window
        self._before = int(state[2]), jax.device_get(state[3])

    def reference_numbers(self, precision):
        c, t = self.config, self.traffic
        arch = {"n_heads": c["num_attention_heads"],
                "n_kv_heads": c["num_key_value_heads"],
                "head_dim": c["head_dim"], "rope_base": float(c["rope_theta"]),
                "rotary_dim": rotary_dim(c), "rms_eps": c["rms_norm_eps"],
                "experts_per_tok": c["num_experts_per_tok"],
                "first_held": c["experts_held_first"],
                "layer_types": layer_types(c),
                "key_heads": c["linear_num_key_heads"],
                "value_heads": c["linear_num_value_heads"],
                "key_dim": c["linear_key_head_dim"],
                "value_dim": c["linear_value_head_dim"],
                "conv": c["linear_conv_kernel_dim"]}
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
