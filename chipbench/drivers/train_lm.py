"""Driver `train_lm`: a decoder-only LM trained by one jitted
`value_and_grad(T.loss)` + `optimizer.update` with the state donated:
the user flow of `examples/transformer_lm.py`.

The benchmark owns the loop, the token rows (a pool of distinct batches
from the seed, made on the host and put on the device each step) and
the weights (`weights.py`). The program owns the model, its attention
dispatch and kernels, the fused cross entropy and the optimizer.
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

from paddle_tpu import optim
from paddle_tpu.core import dtypes
from paddle_tpu.models import transformer as T


class Driver:
    def __init__(self, config, traffic, seed, devices):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.batch, self.seq = traffic["batch"], traffic["seq"]
        self.units_per_step = self.batch * self.seq
        self.flops = load_module(os.path.join(HERE, "flops"), config["flops"])
        self.reference = load_module(os.path.join(HERE, "reference"),
                                     config["reference"])
        self.step = None

    def _build(self):
        c, t = self.config, self.traffic
        if c["compute_dtype"] == "bfloat16":
            dtypes.set_default_policy(dtypes.bf16_compute_policy())
        else:
            dtypes.set_default_policy(dtypes.Policy())
        self.cfg = cfg = T.TransformerConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            mlp_ratio=c["intermediate_size"] // c["hidden_size"],
            rope_base=c["rope_theta"], attn_window=c["sliding_window"],
            attn_impl=t["attn_impl"], remat=t["remat"],
            fused_ce_chunk=t["fused_ce_chunk"])
        o = c["optimizer"]
        self.opt = opt = optim.get(o["name"], **{k: v for k, v in o.items()
                                                 if k != "name"})
        self.shapes = jax.eval_shape(
            lambda: T.init_params(jax.random.key(0), cfg))

        def step(state, toks):
            params, opt_state, i = state
            loss, grads = jax.value_and_grad(
                lambda p: T.loss(p, cfg, toks))(params)
            params, opt_state = opt.update(grads, opt_state, params, i)
            return (params, opt_state, i + 1), loss

        self.step = jax.jit(step, donate_argnums=(0,))

    def _change_norms(self, params):
        """Per-leaf norms of `params` minus the seed's weights, which are
        made again here and not kept beside the optimizer's state."""
        return jax.jit(lambda p, k: weights.leaf_norms(jax.tree.map(
            jnp.subtract, p, weights.generate(self.shapes, k))))(
                params, weights.seed_key(self.seed))

    def _make_pool(self):
        rng = np.random.default_rng(self.seed)
        self.pool = rng.integers(
            0, self.config["vocab_size"],
            (self.traffic["pool_batches"], self.batch, self.seq + 1),
            dtype=np.int32)

    # -- set-up: the steps `correct` compares are the warm-up --------------
    def setup(self):
        if self.step is None:
            self._build()
        self._make_pool()
        def initial_state(key):
            params = weights.generate(self.shapes, key)
            return params, self.opt.init(params), jnp.zeros((), jnp.int32)

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

    # -- the measured window ------------------------------------------------
    def window(self, deadline, watcher, tracer, spans):
        lag = self.traffic["cost_read_lag"]
        n_pool = len(self.pool)
        pending = collections.deque()
        input_wait = 0.0
        i = self.traffic["check_steps"]
        count = 0
        state = self.state
        self.state = None
        while time.perf_counter() < deadline:
            t = time.perf_counter()
            with spans("next_batch"):
                toks = jax.device_put(self.pool[i % n_pool])
            input_wait += time.perf_counter() - t
            with spans("step_call"):
                state, loss = self.step(state, toks)
            with spans("handler"):
                read_loss = lambda loss=loss: float(loss)
                watcher.put(read_loss)
                pending.append(read_loss)
                if lag is not None and len(pending) > lag:
                    pending.popleft()()         # a logging loop
                if tracer is not None:
                    tracer.step_dispatched(count, read_loss)
            i += 1
            count += 1
        self.state = state
        return {"input_wait_s": input_wait}

    def built(self) -> bool:
        return self.step is not None

    def free(self):
        self.state = None

    def model_flops_per_step(self):
        return self.flops.train_flops_per_step(self.config, self.traffic)

    # -- the plain reference, on the same weights and rows ------------------
    def reference_numbers(self, precision):
        c = self.config
        arch = {"n_heads": c["num_attention_heads"],
                "n_kv_heads": c["num_key_value_heads"],
                "rope_base": c["rope_theta"], "window": c["sliding_window"]}
        step = self.reference.make_step(arch, c["optimizer"], precision)
        def initial_state(key):
            params = weights.generate(self.shapes, key)
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            return params, zeros(), zeros(), jnp.zeros((), jnp.float32)

        state = jax.jit(initial_state)(weights.seed_key(self.seed))
        losses, grad1 = [], None
        for b in range(self.traffic["check_steps"]):
            state, loss = step(state, jnp.asarray(self.pool[b]))
            losses.append(float(loss))
            if b == 0:
                grad1 = weights.norms(state[1]) / (
                    1.0 - c["optimizer"]["beta1"])
        return {"loss": losses, "rank": weights.ranks(self.shapes),
                "grad1": weights.named(self.shapes, grad1),
                "dparam": weights.named(self.shapes,
                                        self._change_norms(state[0]))}
