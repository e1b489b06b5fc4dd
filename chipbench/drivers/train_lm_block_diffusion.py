"""Driver `train_lm_block_diffusion`: a block-diffusion LM trained by one
jitted `value_and_grad(T.block_diffusion_loss)` + `optimizer.update`
with the state donated: the user flow of `examples/transformer_lm.py
--block-diffusion`.

The benchmark owns the loop, the batches and the weights
(`weights_stacked.py`). A batch is tokens **and noise**, drawn on the
host from the seed and handed to program and reference alike: for each
(sequence, block of `block_length` tokens) one t ~ U(0, 1), p = eps +
(1 - eps) t, each token of the block replaced by the mask id (the last
id of the vocabulary held) independently with probability p. It
travels as **one array** `int32[B, 3, L]` (x0, xt, and the float32
bits of each token's p), which the jitted step unpacks before it calls
the program's function: `tools/readings.py` and `faults.py` drive
`driver.step(state, toks)` and slice `toks[:B // 2]`.

The loop of the measured window is `drivers/train_lm.py`'s own, one
loop for both LM cells. `units_per_step` counts **data tokens** (batch
x seq), not the 2 x seq positions a sequence puts through the model.
The program owns the
model, the block-diffusion mask in its flash kernels, the dropless
expert layer, the fused cross entropy and the optimizer; its loss's
auxiliary counts (rows routed to held experts, rows of the fullest
expert, a layer) are summed in the step's state and read after the
window, for the counters.
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


def place_hot_experts(params, mask_id: int, first: int, held: int):
    """Deal the mask token's experts one to a chip: a permutation of
    each router's columns (expert labels mean nothing at random init).

    Half of the noised positions, a quarter of all, carry the one mask
    embedding, and with it nearly one router input: they choose (nearly)
    the same 8 experts in every layer. Which chip holds those is a
    property of a checkpoint and its placement; drawn anew with every
    seed it is Binomial(8, 1/8) a layer, the rows held here swing by 8%
    (s.d.) from seed to seed and the rate by 1% (PERF.md section 6,
    PR 34). An 8-way deployment that balances its one hot token places
    those eight one a chip, so here: of the experts that norm(embedding
    of the mask id) ranks top 8 under a layer's router, the one of rank
    `layer mod 8` gets a held label and the other seven labels of other
    chips; all else keeps its order. The rows held are then about one
    a position in every layer and seed, the fullest expert four to
    five times the mean: the imbalance the dropless layer is for."""
    e = params["embed"]["table"][mask_id].astype(jnp.float32)
    h = e * jax.lax.rsqrt(jnp.mean(jnp.square(e)) + 1e-6)
    blocks = []
    for layer, block in enumerate(params["blocks"]):
        router = block["moe"]["router"]["kernel"]
        n = router.shape[1]
        logits = h @ router.astype(jnp.float32)
        rank = jnp.argsort(jnp.argsort(-logits))
        # the chosen hot expert, then the cold ones, then the other hot
        group = jnp.where(rank == layer % 8, 0, jnp.where(rank >= 8, 1, 2))
        order = jnp.argsort(group * n + jnp.arange(n))
        slots = jnp.concatenate([jnp.arange(first, first + held),
                                 jnp.arange(0, first),
                                 jnp.arange(first + held, n)])
        perm = jnp.zeros((n,), jnp.int32).at[slots].set(order)
        blocks.append({**block, "moe": {**block["moe"], "router": {
            "kernel": jnp.take(router, perm, axis=1)}}})
    return {**params, "blocks": blocks}


def unpack(toks):
    """int32[B, 3, L] -> (tokens, masked, p)."""
    tokens, noised = toks[:, 0], toks[:, 1]
    return (tokens, noised != tokens,
            jax.lax.bitcast_convert_type(toks[:, 2], jnp.float32))


class Driver(load_module(os.path.join(HERE, "drivers"), "train_lm").Driver):
    def __init__(self, config, traffic, seed, devices):
        super().__init__(config, traffic, seed, devices)
        self.mask_id = config["vocab_size"] - 1

    def _build(self):
        c, t = self.config, self.traffic
        if c["rms_norm_eps"] != 1e-6:
            raise ValueError("the program's RMSNorm has eps 1e-6 alone")
        if c["compute_dtype"] == "bfloat16":
            dtypes.set_default_policy(dtypes.bf16_compute_policy())
        else:
            dtypes.set_default_policy(dtypes.Policy())
        self.cfg = cfg = T.TransformerConfig(
            vocab=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
            rope_base=float(c["rope_theta"]), norm="rms",
            bias=c["attention_bias"],
            qk_norm=True, moe_router="dropless",
            moe_experts=c["router_width"], moe_every=c["decoder_sparse_step"],
            moe_k=c["num_experts_per_tok"], moe_dim=c["moe_intermediate_size"],
            moe_held=c["num_experts"], moe_held_first=c["experts_held_first"],
            attn_impl=t["attn_impl"], remat=t["remat"],
            fused_ce_chunk=t["fused_ce_chunk"])
        o = c["optimizer"]
        self.opt = opt = optim.get(o["name"], **{k: v for k, v in o.items()
                                                 if k != "name"})
        self.shapes = jax.eval_shape(
            lambda: T.init_params(jax.random.key(0), cfg))
        block_length, mask_id = t["block_length"], self.mask_id

        def step(state, toks):
            params, opt_state, i, counts = state
            tokens, masked, p = unpack(toks)
            (loss, stats), grads = jax.value_and_grad(
                lambda q: T.block_diffusion_loss(
                    q, cfg, tokens, masked, p, block_length=block_length,
                    mask_id=mask_id), has_aux=True)(params)
            params, opt_state = opt.update(grads, opt_state, params, i)
            counts = counts + jnp.stack(
                [jnp.sum(stats.rows_held), jnp.sum(stats.rows_max_expert)])
            return (params, opt_state, i + 1, counts), loss

        self.step = self._jitted = jax.jit(step, donate_argnums=(0,))

    def _weights(self, key):
        """The seed's weights, the program's and the reference's alike."""
        return place_hot_experts(
            weights.generate(self.shapes, key), self.mask_id,
            self.config["experts_held_first"], self.config["num_experts"])

    def _change_norms(self, params):
        """Per-leaf norms of `params` minus the seed's weights, which are
        made again here and not kept beside the optimizer's state."""
        return jax.jit(lambda p, k: weights.leaf_norms(jax.tree.map(
            jnp.subtract, p, self._weights(k))))(
                params, weights.seed_key(self.seed))

    def _make_pool(self):
        """[pool, B, 3, L] int32: x0, xt and the bits of p."""
        t = self.traffic
        rng = np.random.default_rng(self.seed)
        shape = (t["pool_batches"], self.batch, self.seq)
        x0 = rng.integers(0, self.mask_id, shape, dtype=np.int32)
        blocks = shape[:2] + (self.seq // t["block_length"],)
        p = (t["noise_eps"] + (1.0 - t["noise_eps"]) * rng.random(
            blocks, dtype=np.float32)).astype(np.float32)
        p = np.repeat(p, t["block_length"], axis=-1)
        xt = np.where(rng.random(shape, dtype=np.float32) < p,
                      np.int32(self.mask_id), x0)
        self.pool = np.stack([x0, xt, p.view(np.int32)], axis=2)

    # -- set-up: the steps `correct` compares are the warm-up --------------
    def setup(self):
        if self.step is None:
            self._build()
        self._make_pool()

        def initial_state(key):
            params = self._weights(key)
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
                "moe.positions": (steps * 2 * self.units_per_step
                                  * self.config["num_hidden_layers"])}

    def free(self):
        """The state, and the step's loaded program with the 4.5 GB of
        scratch space it reserves: the reference needs the room (the
        next `setup()` reads the program back from the compile cache)."""
        self.state = None
        if self.step is not None:
            self._jitted.clear_cache()

    # -- the plain reference, on the same weights and batches ---------------
    def reference_numbers(self, precision):
        c, t = self.config, self.traffic
        arch = {"n_heads": c["num_attention_heads"],
                "n_kv_heads": c["num_key_value_heads"],
                "head_dim": c["head_dim"], "rope_base": float(c["rope_theta"]),
                "rms_eps": c["rms_norm_eps"],
                "experts_per_tok": c["num_experts_per_tok"],
                "first_held": c["experts_held_first"],
                "block_length": t["block_length"], "mask_id": self.mask_id}
        step = self.reference.make_step(arch, c["optimizer"], precision)

        def initial_state(key):
            params = self._weights(key)
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            return params, zeros(), zeros(), jnp.zeros((), jnp.float32)

        state = jax.jit(initial_state)(weights.seed_key(self.seed))
        losses, grad1 = [], None
        for b in range(t["check_steps"]):
            state, loss = step(state, unpack(jnp.asarray(self.pool[b])))
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
