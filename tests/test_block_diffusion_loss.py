"""`transformer.block_diffusion_loss` (RMSNorm, bias-free fused QKV at
heads x head_dim != dim, QK-norm, the block-diffusion mask, a dropless
MoE that holds a share of its experts) against the benchmark's plain
reference, `chipbench/reference/sdar_moe.py`, loaded by path: the one
copy. Seeded weights at dim 64, 4 heads x 32, 2 KV heads, 8 experts
with 4 held, top 3, L 32, Bd 4."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import dtypes
from paddle_tpu.models import transformer as T
from paddle_tpu.parallel.sharding import make_param_shardings

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
VOCAB, LENGTH, BD = 96, 32, 4


@pytest.fixture(scope="module")
def reference():
    sys.path.insert(0, CHIPBENCH)       # the file imports reference.quant
    try:
        spec = importlib.util.spec_from_file_location(
            "chipbench_reference_sdar_moe",
            os.path.join(CHIPBENCH, "reference", "sdar_moe.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(CHIPBENCH)
    return mod


def config(**kw):
    base = dict(vocab=VOCAB, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                head_size=32, rope_base=1e6, norm="rms", bias=False,
                qk_norm=True, moe_experts=8, moe_every=1, moe_k=3,
                moe_router="dropless", moe_dim=48, moe_held=4,
                moe_held_first=2, attn_impl="dense")
    return T.TransformerConfig(**{**base, **kw})


def arch_of(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rope_base": cfg.rope_base,
            "rms_eps": 1e-6, "experts_per_tok": cfg.moe_k,
            "first_held": cfg.moe_held_first, "block_length": BD,
            "mask_id": VOCAB - 1}


def seeded(cfg, seed=0):
    params = T.init_params(jax.random.key(seed), cfg)
    # norm weights away from one and a router with some spread, so that
    # every leaf matters and few top-k sets sit at a near tie
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, x) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            x = 1.0 + 0.2 * jax.random.normal(jax.random.key(100 + i),
                                              x.shape)
        elif "router" in name:
            x = 4.0 * x
        out.append(x)
    params = jax.tree_util.tree_unflatten(treedef, out)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, LENGTH), 0,
                                VOCAB - 1)
    masked, p = T.block_diffusion_noise(jax.random.key(seed + 2), tokens, BD)
    return params, tokens, masked, p


def both(reference, cfg, params, tokens, masked, p):
    ours = jax.jit(jax.value_and_grad(lambda q: T.block_diffusion_loss(
        q, cfg, tokens, masked, p, block_length=BD), has_aux=True))(params)
    theirs = jax.jit(jax.value_and_grad(lambda q: reference.loss_fn(
        q, (tokens, masked, p), arch_of(cfg),
        reference.rounding("float32"))))(params)
    return ours, theirs


@pytest.mark.parametrize("kw", [
    dict(),
    dict(attn_impl="flash", fused_ce_chunk=16, remat=True),
    dict(moe_held=8, moe_held_first=0),
], ids=["dense", "flash_fused_remat", "all_held"])
def test_loss_and_gradients_match_the_reference(reference, kw):
    cfg = config(**kw)
    params, tokens, masked, p = seeded(cfg)
    ((loss, stats), grads), (ref_loss, ref_grads) = both(
        reference, cfg, params, tokens, masked, p)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3,
            atol=2e-5 * float(jnp.max(jnp.abs(b)) + 1e-8) + 1e-7,
            err_msg=jax.tree_util.keystr(path))
    # the counts: per layer, rows of held experts of 2 * 2L * k choices
    assert stats.rows_held.shape == (cfg.n_layers,)
    chosen = np.asarray(reference.chosen_experts(
        params, (tokens, masked, p), arch_of(cfg)))
    held = (chosen >= cfg.moe_held_first) & (
        chosen < cfg.moe_held_first + cfg.experts_held)
    np.testing.assert_array_equal(np.asarray(stats.rows_held),
                                  held.sum(axis=(1, 2)))


def test_under_the_bf16_policy(reference):
    """bfloat16 compute against the float32 reference: operands carry 8
    bits, so the loss agrees to a few parts in a thousand and a
    gradient leaf's norm to a few percent (a near tie of the top-3
    that falls the other way moves one position's expert terms, which
    the small size makes visible); the structure, not the digits."""
    cfg = config(attn_impl="flash", fused_ce_chunk=16)
    params, tokens, masked, p = seeded(cfg, seed=3)
    prev = dtypes.default_policy()
    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    try:
        ((loss, _), grads), (ref_loss, ref_grads) = both(
            reference, cfg, params, tokens, masked, p)
    finally:
        dtypes.set_default_policy(prev)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-2)
    norm = lambda t: np.array([float(jnp.linalg.norm(x.astype(jnp.float32)))
                               for x in jax.tree.leaves(t)])
    got, want = norm(grads), norm(ref_grads)
    assert np.all(np.abs(got - want) <= 0.08 * np.maximum(
        want, np.median(want)))


def test_the_noise_is_data(reference):
    cfg = config()
    params, tokens, masked, p = seeded(cfg)
    a = T.block_diffusion_loss(params, cfg, tokens, masked, p,
                               block_length=BD)[0]
    b = T.block_diffusion_loss(params, cfg, tokens, masked, p,
                               block_length=BD)[0]
    assert float(a) == float(b)
    # p is constant over a block, inside (0, 1], and masks follow it
    assert np.all(np.asarray(p).reshape(2, -1, BD).std(axis=-1) == 0)
    assert 0 < float(p.min()) and float(p.max()) <= 1
    none = T.block_diffusion_loss(params, cfg, tokens,
                                  jnp.zeros_like(masked), p,
                                  block_length=BD)[0]
    assert float(none) == 0.0


def test_next_token_loss_runs_the_same_block():
    """The causal objective through the new descriptors: one body."""
    cfg = config()
    params, tokens, _, _ = seeded(cfg)
    value, grads = jax.jit(jax.value_and_grad(
        lambda q: T.loss(q, cfg, tokens)))(params)
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))


def test_shapes_follow_the_explicit_head_size():
    cfg = config()
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    blk = shapes["blocks"][0]
    assert blk["qkv"]["kernel"].shape == (64, (4 + 2 * 2) * 32)
    assert blk["proj"]["kernel"].shape == (4 * 32, 64)
    assert "bias" not in blk["qkv"] and "offset" not in blk["ln1"]
    assert blk["q_norm"]["scale"].shape == (32,)
    assert blk["moe"]["router"]["kernel"].shape == (64, 8)
    assert blk["moe"]["w_gate"].shape == (4, 64, 48)
    assert blk["moe"]["w_down"].shape == (4, 48, 64)
    # today's configs keep their pytree
    old = jax.eval_shape(lambda: T.init_params(
        jax.random.key(0), T.TransformerConfig(vocab=32, dim=64)))
    assert set(old["blocks"][0]) == {"ln1", "qkv", "proj", "ln2", "fc1",
                                     "fc2"}
    assert set(old["blocks"][0]["ln1"]) == {"scale", "offset"}
    assert old["blocks"][0]["qkv"]["bias"].shape == (3 * 64,)


def test_decode_refuses_a_block_it_cannot_serve():
    cfg = config()
    params, tokens, _, _ = seeded(cfg)
    with pytest.raises(NotImplementedError, match="decoding is not"):
        T.generate(params, cfg, tokens[:, :4], steps=2)
    with pytest.raises(NotImplementedError, match="decoding is not"):
        T.beam_decode(params, cfg, tokens[:1, :4], steps=2)


def test_config_refuses_a_share_outside_the_router():
    with pytest.raises(ValueError, match="dropless"):
        config(moe_held=4, moe_held_first=6)
    with pytest.raises(ValueError, match="dropless"):
        T.TransformerConfig(vocab=8, moe_experts=4, moe_held=2)


def test_tp_rules_cover_the_new_leaves():
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = config()
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    blk = make_param_shardings(shapes, mesh, T.TP_MOE_RULES)["blocks"][0]
    # the router's rule comes first: replicated, not caught by "out"
    assert blk["moe"]["router"]["kernel"].spec == P()
    assert blk["moe"]["w_gate"].spec == P("model")
    assert blk["moe"]["w_down"].spec == P("model")
    assert blk["q_norm"]["scale"].spec == P()
