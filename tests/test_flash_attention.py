"""Flash attention (Pallas, interpret mode on CPU) vs dense reference —
the cross-backend equivalence strategy of the reference's
test_NetworkCompare.cpp applied to the TPU kernel."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.flash_attention import flash_attention
from paddle_tpu.parallel.ring_attention import dense_attention


def _qkv(np_rng, b=2, t=48, h=2, d=16, t_kv=None):
    t_kv = t if t_kv is None else t_kv
    q = jnp.asarray(np_rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(np_rng.randn(b, t_kv, h, d), jnp.float32)
    v = jnp.asarray(np_rng.randn(b, t_kv, h, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense(np_rng, causal):
    q, k, v = _qkv(np_rng)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kw", [
    dict(causal=True), dict(causal=True, window=20),
    dict(block_diffusion=(24, 4)),
], ids=["causal", "window", "block_diffusion"])
def test_names_for_a_checkpoint_are_the_identity_outside_one(
        np_rng, monkeypatch, kw):
    """A differentiated call names the forward kernel's output and
    log-sum-exp (`REMAT_SAVED`) for a checkpoint's policy; with no
    checkpoint around it the value and the gradients are those of a
    call that names nothing, bit for bit, and an undifferentiated call
    carries no name."""
    from paddle_tpu.ops import flash_attention as fa

    q, k, v = _qkv(np_rng)
    w = jnp.asarray(np_rng.randn(*q.shape), jnp.float32)
    f = jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block_q=16, block_k=16, bwd_block_q=16, bwd_block_k=16,
        **kw) * w), (0, 1, 2))

    def names(jaxpr):
        return [e.params["name"] for e in jaxpr.eqns
                if e.primitive.name == "name"] + [
            n for e in jaxpr.eqns
            for sub in jax.core.jaxprs_in_params(e.params)
            for n in names(sub)]

    named = f(q, k, v)
    assert sorted(names(jax.make_jaxpr(f)(q, k, v).jaxpr)) == sorted(
        fa.REMAT_SAVED)
    assert names(jax.make_jaxpr(lambda *a: flash_attention(*a, **kw))(
        q, k, v).jaxpr) == []
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    jax.clear_caches()      # jax caches the traced forward rule
    try:
        assert names(jax.make_jaxpr(f)(q, k, v).jaxpr) == []
        nameless = f(q, k, v)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for a, b in zip(jax.tree.leaves(named), jax.tree.leaves(nameless)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_non_divisible_lengths(np_rng):
    # T not a multiple of the block: tail masking must be exact
    q, k, v = _qkv(np_rng, t=37, t_kv=53)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_cross_attention_shapes(np_rng):
    q, k, v = _qkv(np_rng, t=8, t_kv=24)
    out = flash_attention(q, k, v, block_q=8, block_k=8)
    assert out.shape == q.shape


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_dense(np_rng, causal):
    q, k, v = _qkv(np_rng, b=1, t=24, h=2, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=8, block_k=8,
                                       bwd_block_q=8, bwd_block_k=8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_jit_composes(np_rng):
    q, k, v = _qkv(np_rng, b=1, t=16, h=1, d=8)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, block_q=8,
                                                block_k=8))
    out = f(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bad_rank_raises(np_rng):
    with pytest.raises(ValueError, match="B, T, H, D"):
        flash_attention(jnp.zeros((4, 8, 3)), jnp.zeros((4, 8, 3)),
                        jnp.zeros((4, 8, 3)))


def _masked_dense(q, k, v, lens, causal):
    """key_lens as a dense mask, via the ONE canonical dense impl."""
    b, tq, tk = q.shape[0], q.shape[1], k.shape[1]
    key_ok = jnp.arange(tk)[None, :] < lens[:, None]
    return dense_attention(
        q, k, v, causal=causal,
        mask=jnp.broadcast_to(key_ok[:, None, :], (b, tq, tk)))


@pytest.mark.parametrize("causal", [False, True])
def test_key_lens_matches_masked_dense(np_rng, causal):
    """Per-row key-length bound (variable-length right-padded prefill)
    vs a key-masked dense reference — rows attend only [0, lens[b])."""
    q, k, v = _qkv(np_rng, b=3, t=24, h=2, d=8)
    lens = jnp.asarray([24, 13, 5], jnp.int32)  # incl. non-block-aligned
    out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                          key_lens=lens)
    ref = _masked_dense(q, k, v, lens, causal)
    # rows past their length see garbage queries attending real keys —
    # only positions with at least one valid key are meaningful; here
    # every QUERY row is compared (the mask bounds keys, not queries)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_key_lens_grads_match_masked_dense(np_rng):
    q, k, v = _qkv(np_rng, b=2, t=16, h=1, d=8)
    lens = jnp.asarray([16, 7], jnp.int32)

    gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=8, block_k=8, key_lens=lens,
        bwd_block_q=8, bwd_block_k=8) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(
        _masked_dense(q, k, v, lens, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_key_lens_zero_and_overlong_rows(np_rng):
    """lens=0 rows must output exactly 0 (not the mean of v — NEG_INF
    is finite so an unmasked p would be exp(0)=1 everywhere), matching
    the backward's zero grads; lens>Tkv clamps to the no-mask result."""
    q, k, v = _qkv(np_rng, b=3, t=8, h=1, d=8)
    lens = jnp.asarray([0, 8, 100], jnp.int32)
    out = flash_attention(q, k, v, block_q=8, block_k=8, key_lens=lens)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    ref = dense_attention(q[1:], k[1:], v[1:])
    np.testing.assert_allclose(np.asarray(out[1:]), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


class TestSlidingWindow:
    def _windowed_dense(self, q, k, v, window):
        tq, tk = q.shape[1], k.shape[1]
        qpos = jnp.arange(tq)[:, None] + (tk - tq)
        mask = (qpos >= jnp.arange(tk)[None, :]) & \
            (qpos - jnp.arange(tk)[None, :] < window)
        return dense_attention(
            q, k, v,
            mask=jnp.broadcast_to(mask, (q.shape[0], tq, tk)))

    @pytest.mark.parametrize("window", [1, 5, 16])
    def test_matches_windowed_dense(self, np_rng, window):
        q, k, v = _qkv(np_rng, b=2, t=40, h=2, d=8)
        out = flash_attention(q, k, v, causal=True, block_q=8,
                              block_k=8, window=window)
        ref = self._windowed_dense(q, k, v, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_huge_window_equals_full_causal(self, np_rng):
        q, k, v = _qkv(np_rng, b=1, t=24, h=2, d=8)
        out = flash_attention(q, k, v, causal=True, block_q=8,
                              block_k=8, window=10_000)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_windowed_dense(self, np_rng):
        q, k, v = _qkv(np_rng, b=1, t=24, h=1, d=8)
        gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=8, block_k=8, window=6,
            bwd_block_q=8, bwd_block_k=8) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda q, k, v: jnp.sum(
            self._windowed_dense(q, k, v, 6) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("window,blocks", [
        (40, (16, 16)),     # a band that cuts several blocks a row
        (40, (16, 32)),
        (33, (32, 16)),
    ])
    def test_a_band_over_several_blocks_matches_the_models_dense_path(
            self, np_rng, window, blocks):
        """Forward and gradients against `transformer._dense_attention`
        (what `attn_impl="dense"` runs for a windowed layer), at T 112:
        rows whose band starts inside one block and ends inside
        another, with whole blocks between and skipped ones before."""
        from paddle_tpu.models.transformer import _dense_attention

        q, k, v = _qkv(np_rng, b=1, t=112, h=2, d=8)
        w = jnp.asarray(np_rng.randn(*q.shape), jnp.float32)
        flash = lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, block_q=blocks[0],
            block_k=blocks[1], bwd_block_q=blocks[0], bwd_block_k=blocks[1])
        dense = lambda q, k, v: _dense_attention(q, k, v, True, None, window)
        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(dense(q, k, v)),
                                   rtol=2e-5, atol=2e-5)
        got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("window,suffix", [(40, "_window"), (112, ""),
                                               (None, "")])
    def test_a_window_that_cuts_names_its_kernels_apart(self, window,
                                                        suffix):
        """A trace of a model that mixes band and full layers tells
        their events by name; an inert window keeps today's names."""
        from paddle_tpu.ops import pallas_util

        x = jax.ShapeDtypeStruct((1, 112, 2, 8), jnp.float32)
        before = pallas_util.traced().get("flash_attention.mask=window", 0)
        text = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window)), (0, 1, 2))).trace(
                x, x, x).jaxpr.pretty_print(use_color=False)
        # the kernels' names, not those the output and the log-sum-exp
        # take for a checkpoint (`REMAT_SAVED`)
        assert re.findall(r"name=(flash_attention_(?:fwd|bwd)\w*)",
                          text) == [
            kernel + suffix for kernel in (
                "flash_attention_fwd", "flash_attention_bwd_dkv",
                "flash_attention_bwd_dq")]
        after = pallas_util.traced().get("flash_attention.mask=window", 0)
        assert (after > before) == bool(suffix)

    def test_validation(self, np_rng):
        q, k, v = _qkv(np_rng, b=1, t=8, h=1, d=8)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, window=4)
        with pytest.raises(ValueError, match="window"):
            flash_attention(q, k, v, causal=True, window=0)

    def test_window_composes_with_key_lens(self, np_rng):
        """All three kernel masks at once — per-row length bound,
        causal, band — including a short row whose band lies entirely
        past its length for late queries."""
        q, k, v = _qkv(np_rng, b=2, t=24, h=1, d=8)
        lens = jnp.asarray([24, 7], jnp.int32)
        window = 5
        out = flash_attention(q, k, v, causal=True, block_q=8,
                              block_k=8, key_lens=lens, window=window)
        qpos = jnp.arange(24)[:, None]
        kpos = jnp.arange(24)[None, :]
        mask = (qpos >= kpos) & (qpos - kpos < window)
        ref = dense_attention(
            q, k, v,
            mask=jnp.broadcast_to(mask, (2, 24, 24))
            & (kpos < lens[:, None, None]))
        # rows/queries with at least one in-band valid key must match;
        # row 1 queries from pos len+window-1 = 11 on have NO valid key
        # (band (q-5, q] ∩ kpos<7 empty) -> kernel returns 0 by contract
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out[1, :11]),
                                   np.asarray(ref[1, :11]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(out[1, 11:]), 0.0)


def test_key_lens_shape_validated(np_rng):
    q, k, v = _qkv(np_rng, b=2, t=8, h=1, d=8)
    with pytest.raises(ValueError, match="key_lens"):
        flash_attention(q, k, v, key_lens=jnp.asarray([8, 8, 8]))


# -- the backward kernels (PR 32) against dense attention's autodiff ------

def _pair_mask_dense(t, t_kv, causal, window, lens):
    """[B, Tq, Tkv] bool: the three masks of the kernels, densely."""
    qpos = jnp.arange(t, dtype=jnp.int32)[:, None]
    kpos = jnp.arange(t_kv, dtype=jnp.int32)[None, :]
    mask = jnp.ones((t, t_kv), bool)
    if causal:
        mask = mask & (qpos >= kpos)
    if window is not None:
        mask = mask & (qpos - kpos < window)
    return mask[None] & (kpos[None] < jnp.asarray(lens)[:, None, None])


_BWD_CASES = [
    # t, t_kv, causal, window, key_lens, bwd blocks (q, k)
    (21, 37, False, None, None, (8, 16)),
    (37, 37, True, None, None, (8, 16)),
    (37, 37, True, None, None, (16, 8)),
    (29, 29, False, None, (29, 13), (8, 8)),
    (29, 29, True, None, (0, 13), (8, 16)),     # a row with no key at all
    (29, 29, False, None, (0, 29), (16, 8)),
    (40, 40, True, 1, None, (8, 8)),
    (40, 40, True, 5, None, (8, 16)),
    (40, 40, True, 16, None, (16, 8)),
    (27, 27, True, 6, (20, 7), (8, 8)),         # queries past len + window
    (27, 27, True, 100, (27, 7), (8, 16)),      # a window wider than t
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,t_kv,causal,window,lens,blocks", _BWD_CASES)
def test_backward_kernels_match_dense_autodiff(np_rng, t, t_kv, causal,
                                               window, lens, blocks, dtype):
    """dq, dk, dv of the Pallas backward over several blocks on both
    axes, non-divisible lengths, each mask and their combinations,
    against autodiff through dense attention on the same operands (in
    float32). A query with no valid key outputs 0 by the kernel's
    contract (dense softmax would average v), so its cotangent is left
    out of the dense loss; a row with no key has zero gradients."""
    q, k, v = (x.astype(dtype) for x in _qkv(np_rng, b=2, t=t, t_kv=t_kv,
                                             h=1, d=8))
    w = jnp.asarray(np_rng.randn(*q.shape), jnp.float32)
    key_lens = None if lens is None else jnp.asarray(lens, jnp.int32)
    mask = _pair_mask_dense(t, t_kv, causal, window,
                            (t_kv, t_kv) if lens is None else lens)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, window=window,
                            key_lens=key_lens, block_q=8, block_k=8,
                            bwd_block_q=blocks[0], bwd_block_k=blocks[1])
        return jnp.sum(o.astype(jnp.float32) * w)

    def loss_dense(q, k, v):
        o = dense_attention(q, k, v, mask=mask)
        return jnp.sum(o * w * jnp.any(mask, -1)[:, :, None, None])

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    # bf16: p and ds are rounded to the operands' dtype for their
    # matmuls and the gradients leave in it (2^-9 a rounding)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == dtype, name
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=tol, atol=tol, err_msg=name)
    if lens is not None and 0 in lens:
        row = lens.index(0)
        for a in got:
            np.testing.assert_array_equal(np.asarray(a[row], np.float32), 0.0)


def test_backward_is_traced_as_the_pallas_kernels(np_rng):
    from paddle_tpu.ops import pallas_util

    before = pallas_util.traced().get("flash_attention.backward=pallas", 0)
    q, k, v = _qkv(np_rng, b=1, t=16, h=1, d=8)
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=8, block_k=8)))(q)
    assert pallas_util.traced()["flash_attention.backward=pallas"] > before


@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 1), (True, 5), (True, 20)])
@pytest.mark.parametrize("block_q,block_k", [(8, 8), (8, 16), (16, 8)])
def test_needed_block_ranges_are_the_block_predicate(causal, window,
                                                     block_q, block_k):
    """The closed forms the backward's `index_map`s clamp to admit
    exactly the blocks `_block_needed` admits, for every row length."""
    from paddle_tpu.ops import flash_attention as FA

    t = 48
    nq, nk = t // block_q, t // block_k
    masks = dict(block_q=block_q, block_k=block_k, causal=causal,
                 window=window)
    for n_keys in (0, 1, 7, 8, 9, 30, 48):
        needed = np.array([[bool(FA._block_needed(i, j, n_keys, **masks))
                            for j in range(nk)] for i in range(nq)])
        for i in range(nq):
            first, last = FA._needed_k_blocks(i, n_keys, **masks)
            want = [int(first) <= j <= int(last) and n_keys > 0
                    for j in range(nk)]
            assert want == list(needed[i]), (n_keys, i)
        for j in range(nk):
            first, last = FA._needed_q_blocks(j, n_keys, nq, **masks)
            want = [int(first) <= i <= int(last) and j * block_k < n_keys
                    for i in range(nq)]
            assert want == list(needed[:, j]), (n_keys, j)


# -- the forward's three kinds of block and its blocks (PR 35) ------------

_CLASSIFIER_CASES = [
    # causal, window, block_q, block_k
    (False, None, 8, 8), (False, None, 8, 16), (False, None, 16, 8),
    (True, None, 8, 8), (True, None, 8, 16), (True, None, 16, 8),
    (True, 1, 8, 8), (True, 5, 8, 16),          # narrower than a block
    (True, 8, 8, 8), (True, 16, 16, 8),         # a block wide
    (True, 20, 8, 8), (True, 30, 8, 16),        # wider than a block
    (True, 100, 16, 8),                         # wider than the sequence
]


@pytest.mark.parametrize("causal,window,block_q,block_k", _CLASSIFIER_CASES)
def test_block_classifier_is_the_pair_mask(causal, window, block_q, block_k):
    """`_block_interior` is true exactly where `_pair_mask` admits every
    pair of the block, and a block `_block_needed` skips holds no
    admitted pair: for every block of a 48 x 48 grid and every row
    length (none, inside a block, on a block's edge, all)."""
    from paddle_tpu.ops import flash_attention as FA

    t = 48
    masks = dict(block_q=block_q, block_k=block_k, causal=causal,
                 window=window)
    kinds = set()
    for n_keys in (0, 1, 7, 8, 9, 16, 30, 32, 47, 48):
        for qi in range(t // block_q):
            for j in range(t // block_k):
                mask = np.broadcast_to(np.asarray(FA._pair_mask(
                    qi, j, n_keys, **masks)), (block_q, block_k))
                interior = bool(FA._block_interior(qi, j, n_keys, **masks))
                needed = bool(FA._block_needed(qi, j, n_keys, **masks))
                assert interior == mask.all(), (n_keys, qi, j)
                assert needed or not mask.any(), (n_keys, qi, j)
                assert needed or not interior
                kinds.add("interior" if interior else
                          "cut" if needed else "skipped")
    # a band narrower than a q block beside a k block cuts every block
    assert kinds == ({"interior", "cut", "skipped"}
                     if window is None or window >= block_q + block_k
                     else {"cut", "skipped"})


@pytest.mark.parametrize("predicate", ["_block_needed", "_block_interior"])
@pytest.mark.parametrize("masks", [
    dict(block_q=8, block_k=8, causal=True, window=11),
    dict(block_q=8, block_k=8, causal=False, window=None,
         block_diffusion=(24, 4)),
    dict(block_q=4, block_k=4, causal=False, window=None,
         block_diffusion=(12, 8)),
], ids=["causal_window", "block_diffusion", "inside_a_bd_block"])
def test_block_classifier_under_a_trace_is_the_same_arithmetic(predicate,
                                                               masks):
    """The predicates take numpy's path for Python integers (the counts,
    the tests above) and jax.numpy's for traced ones (the kernel): one
    answer, over every block of a grid."""
    from paddle_tpu.ops import flash_attention as FA

    fn = getattr(FA, predicate)
    qi, j = np.meshgrid(np.arange(6), np.arange(6), indexing="ij")
    traced = jax.jit(lambda qi, j, n: fn(qi, j, n, **masks))(
        jnp.asarray(qi, jnp.int32), jnp.asarray(j, jnp.int32), jnp.int32(48))
    np.testing.assert_array_equal(np.asarray(traced), fn(qi, j, 48, **masks))


_THREE_KIND_CASES = [
    # t, causal, window, key_lens, forward blocks (q, k), and each row's
    # (interior, cut, diagonal, skipped) grid steps
    (40, True, None, None, (8, 8), [(10, 5, 0, 10)]),
    (48, True, None, None, (8, 16), [(6, 6, 0, 6)]),
    (48, True, None, None, (16, 8), [(6, 6, 0, 6)]),
    (48, True, 20, None, (8, 8), [(5, 13, 0, 18)]),  # interior in the band
    (48, True, 5, None, (8, 8), [(0, 11, 0, 25)]),   # a band with none
    (48, True, 30, (48, 19), (8, 16), [(2, 10, 0, 6), (1, 9, 0, 8)]),
    # not causal: only the lengths cut, inside a block and on its edge
    (40, False, None, (40, 21, 0), (8, 8),
     [(25, 0, 0, 0), (10, 5, 0, 10), (0, 0, 0, 25)]),
    (40, False, None, (16, 24, 40), (8, 8),
     [(10, 0, 0, 15), (15, 0, 0, 10), (25, 0, 0, 0)]),
    (37, True, None, (37, 20), (8, 8),                # tail
     [(10, 5, 0, 10), (7, 5, 0, 13)]),
]


@pytest.mark.parametrize("t,causal,window,lens,blocks,kinds",
                         _THREE_KIND_CASES)
def test_three_kinds_of_block_match_dense(np_rng, t, causal, window, lens,
                                          blocks, kinds):
    """Forward and gradients against dense attention on grids that hold
    interior, cut and skipped blocks at once: the unmasked body and the
    masked one meet in one row's streaming softmax."""
    from paddle_tpu.ops import flash_attention as FA

    b = 2 if lens is None else len(lens)
    q, k, v = _qkv(np_rng, b=b, t=t, h=2, d=8)
    w = jnp.asarray(np_rng.randn(*q.shape), jnp.float32)
    key_lens = None if lens is None else jnp.asarray(lens, jnp.int32)
    mask = _pair_mask_dense(t, t, causal, window,
                            (t,) * b if lens is None else lens)
    nq, nk = -(-t // blocks[0]), -(-t // blocks[1])
    assert kinds == [FA._block_kinds(
        nq, nk, n_keys, block_q=blocks[0], block_k=blocks[1], causal=causal,
        window=window) for n_keys in (lens or (t,))]

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               key_lens=key_lens, block_q=blocks[0],
                               block_k=blocks[1], bwd_block_q=8,
                               bwd_block_k=8)

    some = jnp.any(mask, -1)[:, :, None, None]
    out = flash(q, k, v)
    ref = dense_attention(q, k, v, mask=mask) * some
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(
        dense_attention(*a, mask=mask) * w * some), argnums=(0, 1, 2))(
            q, k, v)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape,kw,kinds", [
    # starcoder2_3b_l4.train_seq4k and sdar_30b_a3b_ep8.train_bd4_seq4k
    ((2, 4096, 24, 128), dict(causal=True), "interior:6,cut:4,skipped:6"),
    ((2, 8192, 32, 128), dict(block_diffusion=(4096, 4)),
     "interior:12,cut:8,diagonal:4,skipped:40"),
    # the dense cell's own call: 4095 positions, its window inert
    ((2, 4095, 24, 128), dict(causal=True, window=4096),
     "interior:6,cut:4,skipped:6"),
    # mellum2_12b_a2p5b_ep4.train_seq8k: a band of 1024 (every needed
    # block is cut at 1024 x 1024, two a row) and its full layer
    ((2, 8192, 32, 128), dict(causal=True, window=1024),
     "interior:0,cut:15,skipped:49"),
    ((2, 8192, 32, 128), dict(causal=True), "interior:28,cut:8,skipped:28"),
    # trinity_mini_26b_a3b_ep8.train_seq8k: a band of 2048
    ((2, 8192, 32, 128), dict(causal=True, window=2048),
     "interior:7,cut:14,skipped:43"),
])
def test_forward_counters_at_the_cells_shapes(shape, kw, kinds, direction):
    """Traced abstractly, the forward and the backward each say how many
    grid steps of a (batch x head) row run unmasked, masked and not at
    all, on their own blocks (1024 x 1024 in both at these shapes); the
    forward also names its blocks."""
    from paddle_tpu.ops import pallas_util

    before = pallas_util.traced()
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    fn = lambda q, k, v: jnp.sum(flash_attention(q, k, v, **kw))
    if direction == "bwd":
        fn = jax.grad(fn, argnums=(0, 1, 2))
    jax.eval_shape(fn, x, x, x)
    after = pallas_util.traced()
    keys = [f"flash_attention.{direction}_block_kinds={kinds}"]
    if direction == "fwd":
        keys.append("flash_attention.fwd_blocks=1024x1024")
    for key in keys:
        assert after.get(key, 0) == before.get(key, 0) + 1, (key, after)


# -- the backward's interior kind: bit for bit the masked body ------------

_INTERIOR_BWD_CASES = [
    # t, causal, window, key_lens, block_diffusion, backward blocks,
    # a row's interior steps (the last row's)
    (40, True, None, None, None, (16, 8), 6),     # a tail of padded queries
    (48, True, 20, None, None, (8, 8), 5),        # a band with interior
    (48, True, 5, None, None, (8, 8), 0),         # a band with none
    (48, True, None, (48, 19), None, (8, 8), 9),  # stops at a row's length
    (40, False, None, (40, 21), None, (8, 8), 10),
    (1024, False, None, None, (512, 4), (256, 256), 2),   # diagonal steps
    (96, False, None, None, (48, 3), (16, 16), 4),         # Bd 3
]


@pytest.mark.parametrize("t,causal,window,lens,block_diffusion,blocks,"
                         "n_interior", _INTERIOR_BWD_CASES)
def test_interior_backward_steps_are_the_masked_body_bit_for_bit(
        np_rng, monkeypatch, t, causal, window, lens, block_diffusion,
        blocks, n_interior):
    """dq, dk and dv of the backward kernels with interior steps equal,
    bit for bit, those of the same kernels with every computed step
    masked (`_block_interior` forced false), on one fixed o, lse and
    cotangent: an interior block's admitted pairs go through the same
    float32 expression, and it has no refused pair."""
    from paddle_tpu.ops import flash_attention as FA
    from paddle_tpu.ops import pallas_util

    bh, d = 2, 16
    q, k, v, g = (jnp.asarray(np_rng.randn(bh, t, d), jnp.float32)
                  for _ in range(4))
    lens = jnp.asarray((t, t) if lens is None else lens, jnp.int32)
    masks = dict(causal=causal, window=window,
                 block_diffusion=block_diffusion)
    o, lse = FA._flash_forward(q, k, v, lens, block_q=8, block_k=8, **masks)
    nq, nk = -(-t // blocks[0]), -(-t // blocks[1])

    def backward():
        before = pallas_util.traced()
        grads = FA._flash_backward(q, k, v, lens, o, lse, g,
                                   block_q=blocks[0], block_k=blocks[1],
                                   **masks)
        noted = [key for key, n in pallas_util.traced().items()
                 if n > before.get(key, 0) and "bwd_block_kinds" in key]
        return grads, noted

    kinds = FA._block_kinds(nq, nk, int(lens[-1]), block_q=blocks[0],
                            block_k=blocks[1], **masks)
    assert kinds[0] == n_interior
    got, noted = backward()
    assert len(noted) == 1 and noted[0].startswith(
        "flash_attention.bwd_block_kinds=interior:"), noted
    monkeypatch.setattr(FA, "_block_interior",
                        lambda qi, j, n_keys, **_: j < 0)
    want, noted = backward()
    assert noted[0].startswith("flash_attention.bwd_block_kinds=interior:0,")
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def _padded(t, block):
    block = min(block, max(t, 1))
    return -(-t // block) * block


def test_forward_blocks_come_from_the_shape():
    from paddle_tpu.ops import flash_attention as FA
    from paddle_tpu.ops import pallas_util

    bf16 = jnp.bfloat16
    # the two cells, and the dense cell's 4095 positions
    assert FA._forward_blocks(4096, 4096, 128, bf16) == (1024, 1024)
    assert FA._forward_blocks(8192, 8192, 128, bf16) == (1024, 1024)
    assert FA._forward_blocks(4095, 4095, 128, bf16) == (1024, 1024)
    # no sequence is padded further than 256 x 512 blocks padded it
    for t in range(1, 4101):
        bq, bk = FA._forward_blocks(t, t, 128, bf16)
        assert _padded(t, bq) <= _padded(t, 256), (t, bq)
        assert _padded(t, bk) <= _padded(t, 512), (t, bk)
        # whole lane tiles, or the whole (short) sequence in one block
        assert bq % 128 == 0 or (bq == t and t <= 256), (t, bq)
        assert bk % 128 == 0 or (bk == t and t <= 512), (t, bk)
        assert bq <= 1024 and bk <= 1024
    assert FA._forward_blocks(1280, 1280, 128, bf16) == (640, 768)
    assert _padded(1280, 640) == 1280          # not 2048
    for t in (1, 37, 100, 128, 256):           # short: one block
        assert FA._forward_blocks(t, t, 64, bf16) == (t, t)
    assert FA._forward_blocks(8, 2048, 64, bf16) == (8, 1024)   # Tq != Tkv
    # a head so wide that 1024 x 1024 would pass the VMEM budget
    bq, bk = FA._forward_blocks(4096, 4096, 2048, jnp.float32)
    assert (bq, bk) == (512, 512)
    assert FA._forward_vmem_bytes(bq, bk, 2048, 4) \
        <= pallas_util.VMEM_BUDGET_BYTES \
        < FA._forward_vmem_bytes(1024, 1024, 2048, 4)
    assert FA._forward_vmem_bytes(1024, 1024, 128, 2) \
        <= pallas_util.VMEM_BUDGET_BYTES


def test_default_blocks_are_the_choosers(np_rng):
    """A call that names no blocks runs on the chooser's: a sequence of
    several blocks of its choice (640 x 768 at T 1280) against dense."""
    from paddle_tpu.ops import pallas_util

    q, k, v = _qkv(np_rng, b=1, t=1280, h=1, d=8)
    before = pallas_util.traced().get("flash_attention.fwd_blocks=640x768", 0)
    out = flash_attention(q, k, v, causal=True)
    assert pallas_util.traced()["flash_attention.fwd_blocks=640x768"] \
        == before + 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_attention(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)
