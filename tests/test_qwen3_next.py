"""A Qwen3-Next block stack (three Gated DeltaNet layers and one gated
full-attention layer with partial rotary, over a dropless expert layer
with a gated shared expert) through `T.loss_and_aux`, against the
benchmark's plain reference `chipbench/reference/qwen3_next_moe.py`,
loaded by path, whose Gated DeltaNet is the per-token recurrence.
Seeded weights at dim 64: 4 query heads x 32 and 2 KV heads, 2 key heads
x 16 and 4 value heads x 16, 8 experts of width 16 with 4 held, top 2,
a shared expert of 16, a rotary over 8 of 32 lanes, 32 positions."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops import pallas_util
from paddle_tpu.parallel import moe

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
VOCAB, SEQ = 96, 32
LINEAR, FULL = "linear_attention", "full_attention"
KINDS = ((LINEAR, T.AttentionKind(mixer="gated_delta")),
         (FULL, T.AttentionKind(output_gate=True, rotary_dim=8)))


@pytest.fixture(scope="module")
def reference():
    sys.path.insert(0, CHIPBENCH)       # the file imports reference.*
    try:
        spec = importlib.util.spec_from_file_location(
            "chipbench_reference_qwen3_next_moe",
            os.path.join(CHIPBENCH, "reference", "qwen3_next_moe.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(CHIPBENCH)
    return mod


def config(**kw):
    base = dict(vocab=VOCAB, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
                head_size=32, rope_base=1e7, norm="rms", bias=False,
                qk_norm=True, moe_experts=8, moe_every=1, moe_k=2,
                moe_router="dropless", moe_dim=16, moe_held=4,
                moe_held_first=2, moe_shared_dim=16, gdn_key_heads=2,
                gdn_value_heads=4, gdn_key_dim=16, gdn_value_dim=16,
                attn_impl="dense", layer_types=(LINEAR,) * 3 + (FULL,),
                attention_kinds=KINDS)
    return T.TransformerConfig(**{**base, **kw})


def arch_of(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rope_base": cfg.rope_base,
            "rotary_dim": 8, "rms_eps": 1e-6, "experts_per_tok": cfg.moe_k,
            "first_held": cfg.moe_held_first,
            "layer_types": list(cfg.layer_types),
            "key_heads": cfg.gdn_key_heads, "value_heads": cfg.gdn_value_heads,
            "key_dim": cfg.gdn_key_dim, "value_dim": cfg.gdn_value_dim,
            "conv": cfg.gdn_conv}


def seeded(cfg, seed=0):
    params = T.init_params(jax.random.key(seed), cfg)
    # norm weights away from one, a router with some spread and an
    # embedding at unit scale, as the mixed-attention tests seed theirs
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, x) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            x = 1.0 + 0.2 * jax.random.normal(jax.random.key(100 + i),
                                              x.shape)
        elif "router" in name:
            x = 4.0 * x
        elif name.endswith("['table']"):
            x = 50.0 * x
        out.append(x)
    params = jax.tree_util.tree_unflatten(treedef, out)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, SEQ + 1), 0,
                                VOCAB)
    return params, tokens


def leaf_gaps(grads, ref_grads):
    """Per leaf: the largest |a - b| over the leaf's largest |b|."""
    return {jax.tree_util.keystr(path): float(
        jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(ref_grads))}


# Float32 on both sides; the chunked WY form and the flash kernel here,
# the per-token recurrence and dense scores there: the mixed-attention
# tests' tolerances (their measured gaps are a unit or two of float32's
# last place) hold here as well.
LOSS_RTOL, GRAD_TOL = 5e-6, 2e-5


@pytest.mark.parametrize("kw", [
    dict(),
    dict(attn_impl="flash", fused_ce_chunk=16, remat=True),
    dict(moe_held=8, moe_held_first=0),
], ids=["jnp", "kernels_fused_remat", "all_held"])
def test_loss_and_gradients_match_the_reference(reference, kw):
    cfg = config(**kw)
    params, tokens = seeded(cfg)
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        lambda q: T.loss_and_aux(q, cfg, tokens), has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda q: reference.loss_fn(q, tokens, arch_of(cfg),
                                    reference.rounding("float32"))))(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=LOSS_RTOL)
    gaps = leaf_gaps(grads, ref_grads)
    assert max(gaps.values()) <= GRAD_TOL, gaps
    assert stats.rows_held.shape == (cfg.n_layers,)


def test_partial_rotary_turns_the_first_lanes_alone():
    x = jax.random.normal(jax.random.key(0), (1, 12, 2, 32))
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    out = T._rope(x, pos, 1e7, rotary_dim=8)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_allclose(out[..., :8], T._rope(x[..., :8], pos, 1e7),
                               rtol=1e-6)
    # by hand: lane pair i of the first 8 turns at 1e7^(-2i/8)
    ang = pos[0, :, None] * 1e7 ** (-np.arange(0, 8, 2) / 8)
    x1, x2 = np.asarray(x[0, :, 0, 0:8:2]), np.asarray(x[0, :, 0, 1:8:2])
    np.testing.assert_allclose(out[0, :, 0, 0:8:2],
                               x1 * np.cos(ang) - x2 * np.sin(ang),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out[0, :, 0, 1:8:2],
                               x1 * np.sin(ang) + x2 * np.cos(ang),
                               rtol=1e-5, atol=1e-6)


def test_output_gate_by_hand():
    """The gate's columns at +30 (sigmoid 1) give the ungated layer on
    the other columns; at -30 (sigmoid 0) the mixer adds nothing."""
    kind = T.AttentionKind(output_gate=True, rotary_dim=8)
    plain = T.AttentionKind(rotary_dim=8)
    cfg = config(layer_types=(FULL,) * 4, attention_kinds=((FULL, kind),))
    cfg_plain = config(layer_types=(FULL,) * 4,
                       attention_kinds=((FULL, plain),))
    params, _ = seeded(cfg)
    p = params["blocks"][0]
    hd = cfg.n_heads * cfg.head_dim
    x = jax.random.normal(jax.random.key(3), (1, SEQ, 64))
    pos = jnp.arange(SEQ, dtype=jnp.int32)[None]
    attn = lambda c: (lambda q, k, v: T._attention(c, q, k, v, causal=True))
    # drop the gate's columns: the ungated leaf
    p_plain = {**p, "qkv": {"kernel": jnp.concatenate(
        [p["qkv"]["kernel"][:, :hd], p["qkv"]["kernel"][:, 2 * hd:]], 1)}}
    want = T._block_parts(cfg_plain, p_plain, x, pos, attn(cfg_plain),
                          kind=plain)[0]
    y = T._norm(cfg, p["ln1"], x)
    bias = lambda s: jnp.linalg.lstsq(y[0], jnp.full((SEQ, hd), s))[0].astype(
        p["qkv"]["kernel"].dtype)
    for s, expect in ((30.0, want), (-30.0, None)):
        kernel = p["qkv"]["kernel"].at[:, hd:2 * hd].set(bias(s))
        got = T._block_parts(cfg, {**p, "qkv": {"kernel": kernel}}, x, pos,
                             attn(cfg), kind=kind)[0]
        if expect is None:      # x + 0 + the expert layer of x
            expect = x + T._ffn(cfg, p, T._norm(cfg, p["ln2"], x))[0]
        np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-4)


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """8 experts in 4 shares of 2 (expert-parallel chips): the four
    shares' outputs, the shared expert (which every chip computes alike)
    counted once, add up to the reference's layer with all 8 held."""
    cfg = config(moe_held=8, moe_held_first=0)
    params, _ = seeded(cfg)
    p = params["blocks"][0]["moe"]
    x = jax.random.normal(jax.random.key(4), (SEQ, 64))
    arch, qr = arch_of(cfg), reference.rounding("float32")
    uncut = reference._experts(arch, qr, p, x) + reference._shared_expert(
        qr, p, x)
    shared = reference._shared_expert(qr, p, x)
    parts = []
    for first in range(0, 8, 2):
        share = {**p, **{w: p[w][first:first + 2]
                         for w in ("w_gate", "w_up", "w_down")}}
        parts.append(moe.dropless_ffn(share, x, k=2, first_held=first).y)
    np.testing.assert_allclose(sum(parts) - 3 * shared, uncut, rtol=1e-5,
                               atol=1e-5)
    # and each share alone is not the layer
    assert float(jnp.max(jnp.abs(parts[0] - uncut))) > 1e-2


def test_decode_and_context_parallel_refuse_the_new_kinds():
    for kinds, names in [
            (KINDS, "gated_delta mixer, output gate, partial rotary"),
            (((LINEAR, KINDS[0][1]), (FULL, T.AttentionKind())),
             "gated_delta mixer"),
            (((LINEAR, T.AttentionKind(rotary_dim=8)),
              (FULL, T.AttentionKind(output_gate=True))),
             "output gate, partial rotary")]:
        with pytest.raises(NotImplementedError, match=names):
            T.require_decodable(config(attention_kinds=kinds))
    with pytest.raises(ValueError, match="gated_delta mixer is not supported"):
        T.make_context_parallel_loss(config(), mesh=None)


def test_config_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="mixer must be"):
        config(attention_kinds=((LINEAR, T.AttentionKind(mixer="mamba")),
                                (FULL, KINDS[1][1])))
    with pytest.raises(ValueError, match="gdn_key_heads"):
        config(gdn_key_heads=3)
    with pytest.raises(ValueError, match="rotary_dim"):
        config(attention_kinds=(KINDS[0], (FULL, T.AttentionKind(
            rotary_dim=7))))
    with pytest.raises(ValueError, match="moe_shared_dim"):
        T.TransformerConfig(vocab=8, moe_shared_dim=16)


def test_the_new_pieces_are_noted_while_tracing():
    cfg = config()
    params, tokens = seeded(cfg)
    before = pallas_util.traced()
    jax.eval_shape(lambda p: T.loss_and_aux(p, cfg, tokens), params)
    noted = {k for k, n in pallas_util.traced().items()
             if n > before.get(k, 0)}
    assert {f"transformer.layer_kinds={LINEAR}:3,{FULL}:1",
            f"transformer.mixer={LINEAR}:gated_delta,{FULL}:attention",
            f"transformer.rope={FULL}:partial_8",
            "transformer.attention.gate=sigmoid", "moe.shared_expert=gated",
            "gated_delta.forward=jnp", "gated_delta.chunk=32",
            "gated_delta.heads_per_step=1"} <= noted
