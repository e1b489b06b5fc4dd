"""A Trinity (AfMoE) block stack (a leading dense gated-SiLU layer, then
expert layers with a sigmoid router that chooses by an expert bias and
an ungated shared expert; sliding-window layers with rotary beside a
full layer without it (NoPE); an output gate, sandwich norms, RMSNorm
eps 1e-5, the embedding times sqrt(dim)) through `T.loss_and_aux`,
against the benchmark's plain reference `chipbench/reference/trinity_moe.py`,
loaded by path. Seeded weights at dim 64: a dense sliding layer, then a
full and a sliding expert layer; 4 query heads x 16 and 2 KV heads, a
window of 8, a dense layer of 128, 8 experts of width 16 with 4 held, top
2, a shared expert of 16, 32 positions."""

import functools
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import optim
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import pallas_util
from paddle_tpu.parallel import moe

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
VOCAB, SEQ, DIM = 96, 32, 64
SLIDING, FULL = "sliding_attention", "full_attention"
KINDS = ((SLIDING, T.AttentionKind(window=8, output_gate=True)),
         (FULL, T.AttentionKind(output_gate=True, rotary_dim=0)))
LAYERS = (SLIDING, FULL, SLIDING)
COEFF = 0.01


@pytest.fixture(scope="module")
def reference():
    sys.path.insert(0, CHIPBENCH)       # the file imports reference.*
    try:
        spec = importlib.util.spec_from_file_location(
            "chipbench_reference_trinity_moe",
            os.path.join(CHIPBENCH, "reference", "trinity_moe.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(CHIPBENCH)
    return mod


def config(**kw):
    base = dict(vocab=VOCAB, dim=DIM, n_layers=3, n_heads=4, n_kv_heads=2,
                head_size=16, rope_base=1e4, norm="rms", bias=False,
                qk_norm=True, rms_eps=1e-5, sandwich_norm=True,
                embed_scale=math.sqrt(DIM), layer_types=LAYERS,
                attention_kinds=KINDS, mlp="swiglu", mlp_ratio=2,
                moe_dense_layers=1, moe_router="dropless", moe_experts=8,
                moe_every=1, moe_k=2, moe_dim=16, moe_held=4,
                moe_held_first=2, moe_shared_dim=16, moe_shared_gate=False,
                moe_score="sigmoid", moe_route_scale=2.5,
                moe_expert_bias=True, attn_impl="dense")
    return T.TransformerConfig(**{**base, **kw})


def arch_of(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rope_base": cfg.rope_base,
            "rms_eps": cfg.rms_eps, "embed_scale": cfg.embed_scale,
            "window": 8, "layer_types": list(cfg.layer_types),
            "dense_layers": cfg.moe_dense_layers,
            "experts_per_tok": cfg.moe_k, "first_held": cfg.moe_held_first,
            "route_scale": cfg.moe_route_scale, "bias_coeff": COEFF}


def seeded(cfg, seed=0):
    params = T.init_params(jax.random.key(seed), cfg)
    # norm weights away from one, a router with some spread, an embedding
    # whose rows are N(0, 1) after the multiplier
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
            x = jax.random.normal(jax.random.key(99), x.shape) / math.sqrt(
                DIM)
        out.append(x)
    params = jax.tree_util.tree_unflatten(treedef, out)
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, SEQ + 1), 0,
                                VOCAB)
    # a bias that changes the choice somewhere
    bias = 0.05 * jax.random.normal(jax.random.key(seed + 2),
                                    (len(cfg.moe_layers), cfg.moe_experts))
    return params, tokens, bias


def leaf_gaps(got, want):
    """Per leaf: the largest |a - b| over the leaf's largest |b|."""
    return {jax.tree_util.keystr(path): float(
        jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want))}


# Float32 on both sides, dense scores or the flash kernel here, the
# reference's blocks there: the mixed-attention tests' tolerances (a unit
# or two of float32's last place) hold here as well.
LOSS_RTOL, GRAD_TOL = 5e-6, 2e-5
# After three Adam steps: each element moves by about the learning rate
# whatever its gradient's size, so where a gradient is at round-off its
# step is too; a thousandth of the leaf's largest value, where a sign
# flipped by round-off would read several hundredths.
PARAM_TOL = 1e-3


@pytest.mark.parametrize("kw", [
    dict(), dict(attn_impl="flash", fused_ce_chunk=16, remat=True,
                 moe_held=8, moe_held_first=0)],
    ids=["jnp", "kernels_fused_remat_all_held"])
def test_logits_loss_and_gradients_match_the_reference(reference, kw):
    cfg = config(**kw)
    params, tokens, bias = seeded(cfg)
    qr = reference.rounding("float32")
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        lambda q: T.loss_and_aux(q, cfg, tokens, expert_bias=bias),
        has_aux=True))(params)
    (ref_loss, ref_counts), ref_grads = jax.jit(jax.value_and_grad(
        lambda q: reference.loss_fn(q, bias, tokens, arch_of(cfg), qr),
        has_aux=True))(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=LOSS_RTOL)
    gaps = leaf_gaps(grads, ref_grads)
    assert max(gaps.values()) <= GRAD_TOL, gaps
    # the expert layers only, counted over all 8 experts
    assert stats.rows_held.shape == (2,)
    np.testing.assert_array_equal(stats.route_counts, ref_counts)
    assert int(jnp.sum(stats.route_counts)) == 2 * 2 * SEQ * cfg.moe_k
    logits = T.apply(params, cfg, tokens[:, :-1], expert_bias=bias)
    hid, _ = reference.hidden(params, bias, tokens[:, :-1], arch_of(cfg), qr)
    want = jnp.einsum("btd,dv->btv", hid, params["lm_head"]["kernel"],
                      precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(logits, want, rtol=2e-5, atol=2e-5)


def program_step(cfg):
    opt = optim.get("adam", learning_rate=1e-3)

    def step(state, toks):
        params, opt_state, i, bias = state
        (loss, stats), grads = jax.value_and_grad(
            lambda q: T.loss_and_aux(q, cfg, toks, expert_bias=bias),
            has_aux=True)(params)
        params, opt_state = opt.update(grads, opt_state, params, i)
        bias = moe.update_expert_bias(bias, stats.route_counts, COEFF)
        return (params, opt_state, i + 1, bias), loss

    return opt, jax.jit(step)


def test_three_steps_move_parameters_and_bias_as_the_reference(reference):
    """Adam on the parameters and the bias update outside it, three steps
    of the program's path against the reference's own step."""
    cfg = config()
    params, _, _ = seeded(cfg)
    batches = jax.random.randint(jax.random.key(7), (3, 2, SEQ + 1), 0,
                                 VOCAB)
    opt, step = program_step(cfg)
    bias = T.init_expert_bias(cfg)
    state = (params, opt.init(params), jnp.zeros((), jnp.int32), bias)
    ref_step = reference.make_step(arch_of(cfg), {"learning_rate": 1e-3},
                                   "float32")
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    ref = (jax.tree.map(jnp.copy, params), zeros(), zeros(),
           jnp.zeros((), jnp.float32), jnp.copy(bias))
    for toks in batches:
        state, loss = step(state, toks)
        ref, ref_loss = ref_step(ref, toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=LOSS_RTOL)
    gaps = leaf_gaps(state[0], ref[0])
    assert max(gaps.values()) <= PARAM_TOL, gaps
    np.testing.assert_allclose(state[3], ref[4], atol=1e-7)
    # three steps of +-0.01, centred: moved, and kept at mean zero
    assert float(jnp.max(jnp.abs(state[3]))) > 0.005
    np.testing.assert_allclose(jnp.mean(state[3], axis=-1), 0.0, atol=1e-8)


def test_bias_update_sign_centring_and_coefficient():
    counts = jnp.asarray([[10, 30, 20, 20], [0, 0, 0, 80]], jnp.int32)
    bias = jnp.asarray([[0.5, 0.0, 0.0, 0.0], [0.0, 0.0, 0.0, 0.0]])
    new = moe.update_expert_bias(bias, counts, 0.001)
    # under the mean: up; over it: down; at it: not moved; then centred
    delta = np.asarray([[1.0, -1.0, 0.0, 0.0], [1.0, 1.0, 1.0, -1.0]])
    delta = 0.001 * (delta - delta.mean(axis=-1, keepdims=True))
    np.testing.assert_allclose(new, np.asarray(bias) + delta, atol=1e-8)
    np.testing.assert_allclose(jnp.mean(new - bias, axis=-1), 0.0,
                               atol=1e-8)
    assert new.dtype == jnp.float32


def test_a_dense_layer_leads_the_expert_layers():
    cfg = config()
    assert cfg.moe_layers == (1, 2)
    params = T.init_params(jax.random.key(0), cfg)
    first, second = params["blocks"][0], params["blocks"][1]
    assert "moe" not in first and "fc1" not in first
    assert first["mlp"]["gate_proj"]["kernel"].shape == (DIM, 2 * DIM)
    assert first["mlp"]["down_proj"]["kernel"].shape == (2 * DIM, DIM)
    assert "mlp" not in second and "shared_scale" not in second["moe"]
    assert T.init_expert_bias(cfg).shape == (2, 8)
    # the dense MLP by hand: down(silu(gate y) * up y)
    y = jax.random.normal(jax.random.key(3), (1, 5, DIM))
    m = first["mlp"]
    want = (jax.nn.silu(y @ m["gate_proj"]["kernel"])
            * (y @ m["up_proj"]["kernel"])) @ m["down_proj"]["kernel"]
    np.testing.assert_allclose(T._ffn(cfg, first, y)[0], want, rtol=1e-5,
                               atol=1e-6)


def test_nope_beside_rope():
    """A NoPE layer reads no positions; a rotary layer beside it does."""
    x = jax.random.normal(jax.random.key(0), (1, 12, 2, 16))
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    assert T._rope(x, pos, 1e4, rotary_dim=0) is x
    cfg = config()
    params, _, _ = seeded(cfg)
    h = jax.random.normal(jax.random.key(1), (1, SEQ, DIM))
    order = jnp.arange(SEQ, dtype=jnp.int32)[None]
    shuffled = jax.random.permutation(jax.random.key(2), order, axis=1)
    attn = lambda q, k, v: T._attention(cfg, q, k, v, causal=True)

    @functools.partial(jax.jit, static_argnums=0)
    def run(layer, pos):
        return T._block_parts(cfg, params["blocks"][layer], h, pos, attn,
                              kind=cfg.attention_kind(layer))[0]

    for layer, moves in ((1, False), (2, True)):
        gap = float(jnp.max(jnp.abs(run(layer, order)
                                    - run(layer, shuffled))))
        assert (gap > 1e-3) == moves, (layer, gap)


def test_sandwich_norms_normalise_each_branch_output():
    """The attention's output projection 10x larger changes nothing once
    it is normalised before the residual (an eps far under the output's
    mean square), and changes the block without the sandwich."""
    h = jax.random.normal(jax.random.key(1), (1, SEQ, DIM))
    pos = jnp.arange(SEQ, dtype=jnp.int32)[None]

    @functools.partial(jax.jit, static_argnums=0)
    def run(cfg, p):
        attn = lambda q, k, v: T._attention(cfg, q, k, v, causal=True)
        return T._block_parts(cfg, p, h, pos, attn,
                              kind=cfg.attention_kind(0))[0]

    for sandwich in (True, False):
        cfg = config(sandwich_norm=sandwich, rms_eps=1e-12)
        params, _, _ = seeded(cfg)
        p = params["blocks"][0]
        louder = {**p, "proj": {"kernel": 10.0 * p["proj"]["kernel"]}}
        gap = float(jnp.max(jnp.abs(run(cfg, p) - run(cfg, louder))))
        assert (gap < 1e-4) == sandwich, (sandwich, gap)
    cfg = config()
    y = jax.random.normal(jax.random.key(4), (3, DIM))
    scale = 1.0 + jax.random.normal(jax.random.key(5), (DIM,))
    want = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(T._norm(cfg, {"scale": scale}, y),
                               want * scale, rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """8 experts in 4 shares of 2 (expert-parallel chips), each choosing
    by the same bias: the four shares' outputs, the ungated shared expert
    (which every chip computes alike) counted once, add up to the
    reference's layer with all 8 held; every chip counts the same routes."""
    cfg = config(moe_held=8, moe_held_first=0)
    params, _, bias = seeded(cfg)
    p = params["blocks"][1]["moe"]
    x = jax.random.normal(jax.random.key(4), (SEQ, DIM))
    arch, qr = arch_of(cfg), reference.rounding("float32")
    routed, counts = reference._experts(arch, qr, p, bias[0], x)
    shared = reference._shared_expert(qr, p, x)
    uncut = routed + shared
    parts = []
    for first in range(0, 8, 2):
        share = {**p, "expert_bias": bias[0],
                 **{w: p[w][first:first + 2]
                    for w in ("w_gate", "w_up", "w_down")}}
        out = moe.dropless_ffn(share, x, k=2, first_held=first,
                               score="sigmoid", route_scale=2.5)
        np.testing.assert_array_equal(out.stats.route_counts, counts)
        parts.append(out.y)
    np.testing.assert_allclose(sum(parts) - 3 * shared, uncut, rtol=1e-5,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(parts[0] - uncut))) > 1e-2


LEGACY = {
    "dense": dict(vocab=VOCAB, dim=DIM, n_layers=2, n_heads=4),
    "dropless_shared": dict(
        vocab=VOCAB, dim=DIM, n_layers=2, n_heads=4, n_kv_heads=2,
        head_size=16, norm="rms", bias=False, qk_norm=True,
        moe_router="dropless", moe_experts=8, moe_every=1, moe_k=2,
        moe_dim=16, moe_held=4, moe_held_first=2, moe_shared_dim=16),
    "kinds": dict(
        vocab=VOCAB, dim=DIM, n_layers=2, n_heads=4, norm="rms", bias=False,
        layer_types=(SLIDING, FULL), attention_kinds=(
            (SLIDING, T.AttentionKind(window=8)),
            (FULL, T.AttentionKind(output_gate=True, rotary_dim=8))),
        moe_router="dropless", moe_experts=8, moe_every=2, moe_k=2,
        moe_dim=16)}
DEFAULTS = dict(rms_eps=1e-6, sandwich_norm=False, embed_scale=None,
                mlp="gelu", moe_dense_layers=0,
                moe_score="softmax", moe_route_scale=1.0,
                moe_expert_bias=False, moe_shared_gate=True)


@pytest.mark.parametrize("name", sorted(LEGACY))
def test_the_new_descriptors_at_their_defaults_change_nothing(name):
    """Every new descriptor spelled out at its default: the same
    parameters, the same program (jaxpr text) and bit-equal outputs as
    the configuration that does not name them."""
    plain = T.TransformerConfig(**LEGACY[name])
    spelled = T.TransformerConfig(**LEGACY[name], **DEFAULTS)
    params = T.init_params(jax.random.key(0), plain)
    assert jax.tree.structure(params) == jax.tree.structure(
        T.init_params(jax.random.key(0), spelled))
    tokens = jax.random.randint(jax.random.key(1), (2, 17), 0, VOCAB)
    grad = lambda cfg: jax.value_and_grad(
        lambda p: T.loss_and_aux(p, cfg, tokens), has_aux=True)
    assert str(jax.make_jaxpr(grad(plain))(params)) == str(
        jax.make_jaxpr(grad(spelled))(params))
    (a, stats), (b, _) = (jax.jit(lambda p, cfg=cfg: T.loss_and_aux(
        p, cfg, tokens))(params) for cfg in (plain, spelled))
    assert float(a) == float(b)
    # and the dropless stats carry no route counts where no bias chooses
    if plain.moe_router == "dropless":
        assert stats.route_counts is None


@pytest.mark.parametrize("kw,name", [
    (dict(moe_score="sigmoid"), "sigmoid router"),
    (dict(moe_expert_bias=True), "expert bias"),
    (dict(moe_shared_dim=16, moe_shared_gate=False),
     "ungated shared expert"),
    (dict(moe_dense_layers=1), "leading dense layers"),
    (dict(mlp="swiglu"), "gated-SiLU MLP"),
    (dict(sandwich_norm=True), "sandwich norms"),
    (dict(embed_scale=8.0), "embedding multiplier"),
    (dict(layer_types=(SLIDING, FULL), attention_kinds=(
        (SLIDING, T.AttentionKind(window=8)),
        (FULL, T.AttentionKind(rotary_dim=0)))), "NoPE"),
])
def test_decoding_refuses_each_new_descriptor_by_name(kw, name):
    base = dict(vocab=VOCAB, dim=DIM, n_layers=2, n_heads=4,
                moe_router="dropless", moe_experts=8, moe_every=2, moe_k=2,
                moe_dim=16)
    with pytest.raises(NotImplementedError, match=name):
        T.require_decodable(T.TransformerConfig(**{**base, **kw}))


def test_config_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="moe_score"):
        config(moe_score="relu")
    with pytest.raises(ValueError, match="mlp must be"):
        config(mlp="geglu")
    with pytest.raises(ValueError, match="dropless layer"):
        T.TransformerConfig(vocab=8, moe_experts=4, moe_score="sigmoid")
    cfg = config()
    params, tokens, bias = seeded(cfg)
    with pytest.raises(ValueError, match="pass expert_bias"):
        T.loss_and_aux(params, cfg, tokens)
    with pytest.raises(ValueError, match="needs a config with"):
        T.loss_and_aux(T.init_params(jax.random.key(0),
                                     config(moe_expert_bias=False)),
                       config(moe_expert_bias=False), tokens,
                       expert_bias=bias)
    with pytest.raises(ValueError, match="score must be"):
        moe.dropless_ffn(params["blocks"][1]["moe"], jnp.zeros((4, DIM)),
                         k=2, score="tanh")


def test_the_new_pieces_are_noted_while_tracing():
    cfg = config()
    params, tokens, bias = seeded(cfg)
    before = pallas_util.traced()
    jax.eval_shape(lambda p: T.loss_and_aux(p, cfg, tokens,
                                            expert_bias=bias), params)
    noted = {k for k, n in pallas_util.traced().items()
             if n > before.get(k, 0)}
    assert {f"transformer.rope={SLIDING}:none,{FULL}:nope",
            "transformer.ffn=dense_swiglu", "transformer.ffn=moe_dropless",
            "transformer.post_norm=sandwich", "moe.router=sigmoid_bias",
            "moe.shared_expert=plain",
            "transformer.attention.gate=sigmoid"} <= noted, noted
