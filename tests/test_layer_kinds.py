"""Attention kind by layer (`TransformerConfig.layer_types` +
`attention_kinds`: a window and a rotary scaling a kind), YaRN in
`_rope`, and `loss_and_aux` on a partly held dropless layer, against
the benchmark's plain reference `chipbench/reference/mellum_moe.py`,
loaded by path: the one copy. Seeded weights at dim 64, 4 heads x 16, 1
KV head, 8 experts of width 32 with 4 held, top 2, window 8 of 32, YaRN
with original 16 and factor 4, 4 layers sliding, sliding, sliding,
full."""

import dataclasses
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import optim
from paddle_tpu.core import dtypes
from paddle_tpu.models import transformer as T
from paddle_tpu.parallel import moe

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")
VOCAB, SEQ, WINDOW = 96, 32, 8
SLIDING, FULL = "sliding_attention", "full_attention"
YARN = {"factor": 4.0, "original_max_position_embeddings": 16,
        "beta_fast": 32.0, "beta_slow": 1.0,
        "attention_factor": 0.1 * math.log(4.0) + 1.0}
ORIGINAL = YARN["original_max_position_embeddings"]
KINDS = ((SLIDING, T.AttentionKind(window=WINDOW)),
         (FULL, T.AttentionKind(
             rope_scaling="yarn", rope_factor=YARN["factor"],
             rope_original=ORIGINAL,
             rope_attention_factor=YARN["attention_factor"])))


@pytest.fixture(scope="module")
def reference():
    sys.path.insert(0, CHIPBENCH)       # the file imports reference.quant
    try:
        spec = importlib.util.spec_from_file_location(
            "chipbench_reference_mellum_moe",
            os.path.join(CHIPBENCH, "reference", "mellum_moe.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(CHIPBENCH)
    return mod


def config(**kw):
    base = dict(vocab=VOCAB, dim=64, n_layers=4, n_heads=4, n_kv_heads=1,
                head_size=16, rope_base=500000.0, norm="rms", bias=False,
                qk_norm=True, moe_experts=8, moe_every=1, moe_k=2,
                moe_router="dropless", moe_dim=32, moe_held=4,
                moe_held_first=2, attn_impl="dense",
                layer_types=(SLIDING,) * 3 + (FULL,), attention_kinds=KINDS)
    return T.TransformerConfig(**{**base, **kw})


def arch_of(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "rope_base": cfg.rope_base,
            "rms_eps": 1e-6, "experts_per_tok": cfg.moe_k,
            "first_held": cfg.moe_held_first, "window": WINDOW,
            "layer_types": list(cfg.layer_types), "yarn": YARN}


def seeded(cfg, seed=0):
    params = T.init_params(jax.random.key(seed), cfg)
    # norm weights away from one, a router with some spread and an
    # embedding at unit scale, so that every leaf matters, the token
    # decides the route and few top-k sets sit at a near tie
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


def both(reference, cfg, params, tokens):
    ours = jax.jit(jax.value_and_grad(
        lambda q: T.loss_and_aux(q, cfg, tokens), has_aux=True))(params)
    theirs = jax.jit(jax.value_and_grad(lambda q: reference.loss_fn(
        q, tokens, arch_of(cfg), reference.rounding("float32"))))(params)
    return ours, theirs


def leaf_gaps(grads, ref_grads):
    """Per leaf: the largest |a - b| over the leaf's largest |b|."""
    return {jax.tree_util.keystr(path): float(
        jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(ref_grads))}


# Float32 on both sides, the same mathematics in another order of
# operations (grouped products and a streamed softmax here, every
# expert on every position and dense scores there): the loss agrees to
# 5e-6 (measured: 2e-7 at most, a unit or two of float32's last place),
# a gradient element to 2e-5 of its leaf's largest (measured: 1.4e-6 at
# most). bfloat16 in place of float32 reads 5e-5 and 2.6e-2 on the same
# weights and fails both (the next test).
LOSS_RTOL, GRAD_TOL = 5e-6, 2e-5


@pytest.mark.parametrize("kw", [
    dict(),
    dict(attn_impl="flash", fused_ce_chunk=16, remat=True),
    dict(moe_held=8, moe_held_first=0),
], ids=["dense", "flash_fused_remat", "all_held"])
def test_loss_and_gradients_match_the_reference(reference, kw):
    cfg = config(**kw)
    params, tokens = seeded(cfg)
    ((loss, stats), grads), (ref_loss, ref_grads) = both(
        reference, cfg, params, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=LOSS_RTOL)
    gaps = leaf_gaps(grads, ref_grads)
    assert max(gaps.values()) <= GRAD_TOL, gaps
    assert stats.rows_held.shape == (cfg.n_layers,)


def test_bfloat16_in_place_of_float32_fails_those_tolerances(reference):
    cfg = config()
    params, tokens = seeded(cfg)
    prev = dtypes.default_policy()
    dtypes.set_default_policy(dtypes.bf16_compute_policy())
    try:
        ((loss, _), grads), (ref_loss, ref_grads) = both(
            reference, cfg, params, tokens)
    finally:
        dtypes.set_default_policy(prev)
    assert abs(float(loss) - float(ref_loss)) > LOSS_RTOL * float(ref_loss)
    assert max(leaf_gaps(grads, ref_grads).values()) > 100 * GRAD_TOL
    # and still the same model: the structure, not the digits
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-2)


def test_three_adam_steps_match_the_reference(reference):
    """Adam's first steps are sign steps: an element moves lr a step
    whatever its gradient's size, so the two sides agree wherever the
    gradient's sign is not rounding. Per leaf, the norm of the change
    after three steps agrees to 1e-3 (what `chipbench/check.py` compares
    on the chip), and an element to a hundredth of the 3 lr it moved,
    but for the few whose gradient is rounding on both sides (under
    0.1% of a leaf)."""
    cfg = config(attn_impl="flash", fused_ce_chunk=16, remat=True)
    params, _ = seeded(cfg)
    batches = [jax.random.randint(jax.random.key(20 + i), (2, SEQ + 1), 0,
                                  VOCAB) for i in range(3)]
    lr = 1e-3
    opt = optim.adam(lr)

    @jax.jit
    def ours_step(state, toks):
        p, s, i = state
        value, grads = jax.value_and_grad(
            lambda q: T.loss(q, cfg, toks))(p)
        p, s = opt.update(grads, s, p, i)
        return (p, s, i + 1), value

    theirs_step = reference.make_step(
        arch_of(cfg), {"learning_rate": lr}, "float32")
    ours = (params, opt.init(params), jnp.zeros((), jnp.int32))
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    theirs = (jax.tree.map(jnp.copy, params), zeros(), zeros(),
              jnp.zeros((), jnp.float32))
    for toks in batches:
        ours, a = ours_step(ours, toks)
        theirs, b = theirs_step(theirs, toks)
        # the second and third losses are of weights that differ in
        # their last places already
        np.testing.assert_allclose(float(a), float(b), rtol=2e-5)
    for (path, p0), a, b in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree.leaves(ours[0]), jax.tree.leaves(theirs[0])):
        name = jax.tree_util.keystr(path)
        da, db = np.asarray(a - p0), np.asarray(b - p0)
        np.testing.assert_allclose(np.linalg.norm(da), np.linalg.norm(db),
                                   rtol=1e-3, err_msg=name)
        off = np.abs(da - db) > 0.01 * 3 * lr
        assert off.mean() <= 1e-3, (name, off.mean())


def test_loss_stats_are_the_dropless_layers_own(reference):
    """`loss_and_aux` hands back each layer's `DroplessStats`: the rows
    the reference's router sends to held experts, and what
    `dropless_ffn` itself counts on that layer's input."""
    cfg = config()
    params, tokens = seeded(cfg)
    value, stats = jax.jit(lambda q: T.loss_and_aux(q, cfg, tokens))(params)
    arch, qr = arch_of(cfg), reference.rounding("float32")
    positions = jnp.arange(SEQ, dtype=jnp.int32)

    @jax.jit
    def by_layer(params):
        x = jnp.take(params["embed"]["table"], tokens[:, :-1], axis=0)
        out = []
        for kind, p in zip(cfg.layer_types, params["blocks"]):
            y = reference._attention_part(arch, qr, p, x, positions, kind)
            h = reference._rms_norm(y, p["ln2"], 1e-6).reshape(-1, cfg.dim)
            out.append((reference.route(arch, qr, p["moe"], h)[1],
                        moe.dropless_ffn(p["moe"], h, k=cfg.moe_k,
                                         first_held=2).stats))
            x = reference._block(arch, qr, kind, p, x, positions)
        return out

    for i, (chosen, own) in enumerate(by_layer(params)):
        chosen = np.asarray(chosen)
        assert int(stats.rows_held[i]) == ((chosen >= 2) & (chosen < 6)).sum()
        assert int(stats.rows_held[i]) == int(own.rows_held)
        assert int(stats.rows_max_expert[i]) == int(own.rows_max_expert)
    # and `loss` is its first half, for the callers it had
    assert float(jax.jit(lambda q: T.loss(q, cfg, tokens))(params)) == float(
        value)


# -- kinds by layer against one kind at a time ------------------------------


def test_kinds_by_layer_equal_the_layers_run_one_kind_at_a_time():
    """The same model, each layer run by a config that has no kinds and
    says that layer's window and rotary scaling of every layer."""
    cfg = config(moe_experts=0, moe_router="topk", moe_dim=None,
                 moe_held=None, moe_held_first=0)
    params, tokens = seeded(cfg)
    got = T.apply(params, cfg, tokens)
    plain = dataclasses.replace(cfg, layer_types=None, attention_kinds=None)
    per_kind = {
        SLIDING: dataclasses.replace(plain, attn_window=WINDOW),
        FULL: dataclasses.replace(
            plain, rope_scaling="yarn", rope_factor=YARN["factor"],
            rope_original=ORIGINAL,
            rope_attention_factor=YARN["attention_factor"])}
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
    for kind, p in zip(cfg.layer_types, params["blocks"]):
        x, _ = T._block(per_kind[kind], p, x, positions)
    want = T._norm(cfg, params["ln_f"], x) @ params["lm_head"]["kernel"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # and the kinds matter: one kind for every layer is another model
    other = T.apply(params, per_kind[SLIDING], tokens)
    assert float(jnp.max(jnp.abs(other - got))) > 1e-3


def test_flash_and_dense_agree_by_kind():
    cfg = config()
    params, tokens = seeded(cfg)
    dense, g_dense = jax.jit(jax.value_and_grad(
        lambda q: T.loss(q, cfg, tokens)))(params)
    flash_cfg = dataclasses.replace(cfg, attn_impl="flash", remat=True)
    flash, g_flash = jax.jit(jax.value_and_grad(
        lambda q: T.loss(q, flash_cfg, tokens)))(params)
    np.testing.assert_allclose(float(flash), float(dense), rtol=1e-5)
    assert max(leaf_gaps(g_flash, g_dense).values()) <= GRAD_TOL


def test_the_kinds_add_no_leaf():
    """A config with kinds by layer has the pytree of the same config
    without them, and today's configs (the two LM cells' descriptors)
    keep theirs: `weights.py`, `weights_stacked.py` and the cells'
    limits read leaf paths."""
    def paths(cfg):
        shapes = jax.eval_shape(
            lambda: T.init_params(jax.random.key(0), cfg))
        return {jax.tree_util.keystr(p): x.shape for p, x in
                jax.tree_util.tree_leaves_with_path(shapes)}

    cfg = config()
    assert paths(cfg) == paths(dataclasses.replace(
        cfg, layer_types=None, attention_kinds=None))
    dense = T.TransformerConfig(vocab=64, dim=48, n_layers=2, n_heads=4,
                                n_kv_heads=2, attn_window=16)
    assert set(paths(dense)) == {
        f"['blocks'][{i}]['{m}']['{leaf}']" for i in range(2)
        for m, leaves in (("ln1", ("scale", "offset")),
                          ("ln2", ("scale", "offset")),
                          ("qkv", ("kernel", "bias")),
                          ("proj", ("kernel", "bias")),
                          ("fc1", ("kernel", "bias")),
                          ("fc2", ("kernel", "bias")))
        for leaf in leaves} | {
        "['embed']['table']", "['ln_f']['scale']", "['ln_f']['offset']",
        "['lm_head']['kernel']"}
    block = {k for k in paths(cfg) if k.startswith("['blocks'][0]")}
    assert block == {"['blocks'][0]" + k for k in (
        "['ln1']['scale']", "['ln2']['scale']", "['qkv']['kernel']",
        "['proj']['kernel']", "['q_norm']['scale']", "['k_norm']['scale']",
        "['moe']['router']['kernel']", "['moe']['w_gate']",
        "['moe']['w_up']", "['moe']['w_down']")}


# -- the rotary embedding ---------------------------------------------------


def _rope_numpy(x, positions, inv, scale=1.0):
    ang = positions[..., None].astype(np.float64) * inv
    cos, sin = np.cos(ang)[:, :, None, :] * scale, \
        np.sin(ang)[:, :, None, :] * scale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return np.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                    axis=-1).reshape(x.shape)


def test_yarn_against_a_numpy_transcription_at_the_published_numbers(
        reference):
    """head_dim 128, base 500000, factor 16, original 8192, beta 32 / 1:
    the ramp runs from lane pair 18 to 35 and the attention factor is
    0.1 ln 16 + 1 = 1.27726."""
    dh, base, factor, original = 128, 500000.0, 16.0, 8192
    lane = lambda n: dh * math.log(original / (2 * math.pi * n)) / (
        2 * math.log(base))
    low, high = math.floor(lane(32.0)), math.ceil(lane(1.0))
    assert (low, high) == (18, 35)
    attention_factor = 0.1 * math.log(factor) + 1.0
    assert attention_factor == pytest.approx(1.2772588722239782)
    i = np.arange(dh // 2)
    inv = base ** (-2.0 * i / dh)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    blended = inv / factor * ramp + inv * (1.0 - ramp)
    assert np.all(blended[:19] == inv[:19])             # kept
    np.testing.assert_allclose(blended[35:], inv[35:] / 16)     # slowed
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 7, 3, dh)).astype(np.float32)
    positions = rng.integers(0, 8192, (2, 7))
    want = _rope_numpy(x.astype(np.float64), positions, blended,
                       attention_factor)
    for given in (1.2772588722239782, None):    # None: 0.1 ln(factor) + 1
        got = T._rope(jnp.asarray(x), jnp.asarray(positions), base, "yarn",
                      factor, original=original, beta_fast=32.0,
                      beta_slow=1.0, attention_factor=given)
        # float32 angles up to 8191 radians carry 5e-4 of absolute error
        np.testing.assert_allclose(np.asarray(got), want, atol=4e-3)
    # the reference's transcription is a third, written apart
    r_low, r_high, r_inv = reference.yarn_frequencies(dh, base, {
        "factor": factor, "original_max_position_embeddings": original,
        "beta_fast": 32, "beta_slow": 1})
    assert (r_low, r_high) == (18, 35)
    np.testing.assert_allclose(np.asarray(r_inv), blended, rtol=1e-6)
    # scores between rotated q and k carry the square of the factor
    q = T._rope(jnp.asarray(x), jnp.asarray(positions), base, "yarn",
                factor, original=original)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(q), axis=-1),
        attention_factor * np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_yarn_keeps_its_attention_factor_under_bfloat16():
    """bfloat16 has no 1.2773: cos and sin cast to it would read 1.2734
    wherever cos is 1 (every position of the slow lanes), and the
    rotated q and k come out 0.3% short there, their squared norm 0.9954
    of the float32 rotation's. The rotation runs in float32 and only
    its result is rounded."""
    x = jax.random.normal(jax.random.key(0), (1, 4096, 2, 128),
                          jnp.bfloat16)
    positions = jnp.arange(4096, dtype=jnp.int32)[None]
    kw = dict(original=8192, attention_factor=1.2772588722239782)
    got = T._rope(x, positions, 500000.0, "yarn", 16.0, **kw)
    want = T._rope(x.astype(jnp.float32), positions, 500000.0, "yarn", 16.0,
                   **kw)
    assert got.dtype == jnp.bfloat16 and want.dtype == jnp.float32
    slow = slice(70, 128)       # lane pairs 35 and up: the slowed ones
    ratio = float(jnp.sum(jnp.square(got[..., slow].astype(jnp.float32)))
                  / jnp.sum(jnp.square(want[..., slow])))
    assert abs(ratio - 1.0) < 5e-4, ratio


def _rope_before(x, positions, base, scaling="none", factor=1.0):
    """`_rope` as it was before "yarn", line for line."""
    dh = x.shape[-1]
    if scaling == "linear" and factor != 1.0:
        positions = positions / factor
    elif scaling == "ntk" and factor != 1.0:
        base = base * factor ** (dh / max(dh - 2, 1))
    freqs = base ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


@pytest.mark.parametrize("scaling,factor", [
    ("none", 1.0), ("linear", 4.0), ("ntk", 4.0), ("linear", 1.0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_other_scalings_keep_their_results_bit_for_bit(scaling, factor,
                                                           dtype):
    x = jax.random.normal(jax.random.key(0), (2, 9, 3, 16), dtype)
    positions = jax.random.randint(jax.random.key(1), (2, 9), 0, 4096)
    got = T._rope(x, positions, 10000.0, scaling, factor)
    want = _rope_before(x, positions, 10000.0, scaling, factor)
    assert got.dtype == want.dtype and bool(jnp.array_equal(got, want))


def test_rope_refuses_what_it_cannot_do():
    x, pos = jnp.ones((1, 2, 1, 8)), jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="none|linear|ntk|yarn"):
        T._rope(x, pos, 1e4, "dynamic")
    with pytest.raises(ValueError, match="rope_original"):
        T._rope(x, pos, 1e4, "yarn", 4.0)


# -- what the config and the other paths refuse -----------------------------


@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=(SLIDING,) * 3), "one entry a layer"),
    (dict(layer_types=(SLIDING,) * 3 + ("linear",)), "one entry a layer"),
    (dict(attention_kinds=None), "go together"),
    (dict(attn_window=8), "leave attn_window"),
    (dict(rope_scaling="ntk", rope_factor=2.0), "leave attn_window"),
    (dict(attention_kinds=((SLIDING, 8), (FULL, None))), "one entry a layer"),
])
def test_config_refuses_kinds_it_cannot_read(kw, match):
    with pytest.raises(ValueError, match=match):
        config(**kw)


def _decodable(**kw):
    return T.TransformerConfig(vocab=32, dim=32, n_layers=2, n_heads=2, **kw)


@pytest.mark.parametrize("kw", [
    dict(layer_types=(SLIDING, FULL), attention_kinds=KINDS),
    dict(rope_scaling="yarn", rope_factor=4.0, rope_original=16),
], ids=["kinds_by_layer", "yarn"])
def test_decode_refuses_kinds_by_layer_and_yarn(kw):
    cfg = _decodable(**kw)
    params = T.init_params(jax.random.key(0), cfg)
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="kinds by layer"):
        T.require_decodable(cfg)
    with pytest.raises(NotImplementedError, match="decoding is not"):
        T.generate(params, cfg, prompt, steps=2)
    # it trains all the same
    assert np.isfinite(float(T.loss(params, cfg, jnp.zeros((1, 9),
                                                           jnp.int32))))
    T.require_decodable(_decodable(attn_window=4, rope_scaling="ntk",
                                   rope_factor=2.0))


def test_context_parallel_refuses_a_windowed_kind():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    with pytest.raises(ValueError, match="attn_window"):
        T.make_context_parallel_loss(
            _decodable(layer_types=(SLIDING, FULL), attention_kinds=KINDS),
            mesh)
    with pytest.raises(ValueError, match="attn_window"):
        T.make_context_parallel_loss(_decodable(attn_window=4), mesh)


def test_the_kinds_are_noted_while_tracing():
    from paddle_tpu.ops import pallas_util

    cfg = config(attn_impl="flash")
    params, tokens = seeded(cfg)
    before = dict(pallas_util.traced())
    jax.jit(lambda q: T.loss(q, cfg, tokens)).lower(params)
    new = {k for k, v in pallas_util.traced().items()
           if v > before.get(k, 0)}
    assert {f"transformer.layer_kinds={SLIDING}:3,{FULL}:1",
            f"transformer.rope={SLIDING}:none,{FULL}:yarn",
            "flash_attention.mask=window",
            "transformer.ffn=moe_dropless"} <= new
    assert any(k.startswith("flash_attention.fwd_block_kinds=") for k in new)
