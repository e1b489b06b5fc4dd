"""The block-diffusion mask of the flash kernels (`block_diffusion=(L,
Bd)`): the element mask, the block predicate and the closed-form runs
against a brute-force mask written from the table, and the three
kernels (interpret mode) against dense attention under that mask.

A sequence of 2L positions, the noised copy and then the clean copy,
blk(i) = (i mod L) // Bd:

    noised query, noised key   blk(k) == blk(q)
    noised query, clean key    blk(k) <  blk(q)
    clean query,  clean key    blk(k) <= blk(q)
    clean query,  noised key   never
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops import flash_attention as FA


def brute_mask(length, bd):
    """[2L, 2L] bool, rows queries, from the table above."""
    pos = np.arange(2 * length)
    noised, blk = pos < length, (pos % length) // bd
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return np.where(qn & kn, kb == qb,
                    np.where(qn & ~kn, kb < qb,
                             np.where(~qn & ~kn, kb <= qb, False)))


SHAPES = [  # (L, Bd, block_q, block_k)
    (32, 4, 8, 16), (32, 4, 16, 8), (32, 8, 8, 8), (24, 4, 16, 16),
    (32, 4, 24, 40),            # blocks that straddle the two halves
    (16, 4, 64, 128),           # blocks larger than the sequence
    (16, 1, 8, 8), (32, 32, 16, 16), (30, 3, 8, 16),
]


def _grid(length, block_q, block_k):
    return -(-2 * length // block_q), -(-2 * length // block_k)


@pytest.mark.parametrize("length,bd,block_q,block_k", SHAPES)
@pytest.mark.parametrize("k_major", [False, True])
def test_pair_mask_is_the_table(length, bd, block_q, block_k, k_major):
    want = brute_mask(length, bd)
    nq, nk = _grid(length, block_q, block_k)
    padded = np.zeros((nq * block_q, nk * block_k), bool)
    padded[:2 * length, :2 * length] = want
    for qi in range(nq):
        for j in range(nk):
            got = np.asarray(FA._pair_mask(
                qi, j, 2 * length, block_q=block_q, block_k=block_k,
                causal=False, window=None, block_diffusion=(length, bd),
                k_major=k_major))
            got = np.broadcast_to(got, (block_k, block_q) if k_major
                                  else (block_q, block_k))
            got = got.T if k_major else got
            rows = min(block_q, 2 * length - qi * block_q)  # real queries
            np.testing.assert_array_equal(
                got[:rows], padded[qi * block_q:qi * block_q + rows,
                                   j * block_k:(j + 1) * block_k])


@pytest.mark.parametrize("length,bd,block_q,block_k", SHAPES)
def test_block_predicate_and_runs_are_the_table(length, bd, block_q,
                                                block_k):
    want = brute_mask(length, bd)
    nq, nk = _grid(length, block_q, block_k)
    any_pair = np.array([[want[qi * block_q:(qi + 1) * block_q,
                               j * block_k:(j + 1) * block_k].any()
                          for j in range(nk)] for qi in range(nq)])
    kw = dict(block_q=block_q, block_k=block_k,
              block_diffusion=(length, bd))
    got = np.array([[bool(FA._block_needed(
        qi, j, 2 * length, causal=False, window=None, **kw))
        for j in range(nk)] for qi in range(nq)])
    np.testing.assert_array_equal(got, any_pair)

    def in_runs(i, runs):
        a0, a1, b0, b1 = (int(x) for x in runs)
        return a0 <= i <= a1 or b0 <= i <= b1

    for qi in range(nq):
        runs = FA._bd_needed_k_runs(qi, **kw)
        assert [in_runs(j, runs) for j in range(nk)] == list(any_pair[qi])
        # a clamped step always names a needed block
        for j in range(nk):
            assert any_pair[qi, int(FA._clamp_to_runs(j, *runs))]
    for j in range(nk):
        runs = FA._bd_needed_q_runs(j, **kw)
        assert [in_runs(qi, runs) for qi in range(nq)] == list(any_pair[:, j])
        for qi in range(nq):
            assert any_pair[int(FA._clamp_to_runs(qi, *runs)), j]


@pytest.mark.parametrize("length,bd,block_q,block_k", SHAPES + [
    (32, 8, 4, 4), (32, 16, 8, 4),      # blocks smaller than a Bd-block
    (32, 8, 8, 16), (64, 4, 16, 16),    # equal to one; much larger
    (12, 4, 4, 8),                      # a k block across the two halves
    # blocks of two sub-squares: diagonal steps, and Bd 3, which
    # straddles a sub-square's edge, with none
    (512, 4, 256, 256), (512, 8, 256, 256), (512, 128, 256, 256),
    (384, 3, 256, 256),
])
def test_block_classifier_is_the_pair_mask(length, bd, block_q, block_k):
    """The forward's four kinds of block: one `_block_interior` names
    holds only admitted pairs (it runs unmasked), one `_block_needed`
    skips holds none, one `_block_diagonal` names holds them all inside
    the S x S sub-squares on its diagonal (it runs masked on those
    alone); where no block lies across the two halves,
    `_block_interior` names every block whose mask is all true."""
    nq, nk = _grid(length, block_q, block_k)
    kw = dict(block_q=block_q, block_k=block_k, causal=False, window=None,
              block_diffusion=(length, bd))
    whole = length % block_q == 0 and length % block_k == 0
    sub = FA._diagonal_sub(block_q, block_k, (length, bd))
    kinds = {"interior": 0, "cut": 0, "diagonal": 0, "skipped": 0}
    for qi in range(nq):
        for j in range(nk):
            mask = np.broadcast_to(np.asarray(FA._pair_mask(
                qi, j, 2 * length, **kw)), (block_q, block_k))
            interior = bool(FA._block_interior(qi, j, 2 * length, **kw))
            needed = bool(FA._block_needed(qi, j, 2 * length, **kw))
            diagonal = bool(FA._block_diagonal(qi, j, 2 * length, **kw))
            rows = min(block_q, 2 * length - qi * block_q)  # real queries
            assert not interior or mask.all(), (qi, j)
            assert needed or not mask[:rows].any(), (qi, j)
            assert needed or not interior
            if whole:
                assert interior == mask.all(), (qi, j)
            if diagonal:
                assert needed and not interior, (qi, j)
                squares = np.kron(np.eye(block_q // sub, dtype=bool),
                                  np.ones((sub, sub), bool))
                assert not (mask & ~squares).any(), (qi, j)
            kinds["interior" if interior else "diagonal" if diagonal
                  else "cut" if needed else "skipped"] += 1
    assert tuple(kinds.values()) == FA._block_kinds(nq, nk, 2 * length, **kw)
    if whole and bd < block_q < length:      # the mask cuts some block
        assert min(kinds["interior"], kinds["cut"], kinds["skipped"]) > 0, \
            kinds
    assert (kinds["diagonal"] > 0) == (
        sub is not None and block_q <= length), kinds


def test_forward_blocks_under_the_mask_are_the_choosers():
    """No blocks of the mask's own: at the cell's shape the chooser
    gives what PR 34 timed for it, and a call that names none runs on
    the chooser's."""
    from paddle_tpu.ops import pallas_util

    assert FA._forward_blocks(8192, 8192, 128, jnp.bfloat16) == (1024, 1024)
    x = jax.ShapeDtypeStruct((1, 3072, 2, 128), jnp.bfloat16)
    key = "flash_attention.fwd_blocks=%dx%d" % FA._forward_blocks(
        3072, 3072, 128, jnp.bfloat16)
    before = pallas_util.traced().get(key, 0)
    jax.eval_shape(lambda q, k, v: FA.flash_attention(
        q, k, v, block_diffusion=(1536, 3)), x, x, x)
    assert pallas_util.traced()[key] == before + 1


def _dense(q, k, v, length, bd):
    return T._dense_attention(q, k, v, causal=False,
                              block_diffusion=(length, bd))


def test_dense_path_uses_the_table(np_rng):
    length, bd = 16, 4
    q, k, v = (jnp.asarray(np_rng.randn(1, 2 * length, 2, 8), jnp.float32)
               for _ in range(3))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8.0)
    w = jax.nn.softmax(jnp.where(brute_mask(length, bd), s, -1e30), axis=-1)
    np.testing.assert_allclose(
        np.asarray(_dense(q, k, v, length, bd)),
        np.asarray(jnp.einsum("bhqk,bkhd->bqhd", w, v)), rtol=1e-5,
        atol=1e-5)


@pytest.mark.parametrize("length,bd,bq,bk,bwd,kinds", [
    # kinds: forward grid steps of a row (interior, cut, diagonal,
    # skipped)
    (32, 4, 16, 16, (16, 16), (2, 6, 0, 8)),
    (32, 4, 8, 32, (32, 8), (0, 12, 0, 4)),
    (24, 4, 16, 16, (128, 128), (0, 7, 0, 2)),  # one padded backward block
    (64, 8, 32, 16, (32, 64), (4, 12, 0, 16)),
    (30, 3, 16, 16, (16, 16), (1, 11, 0, 4)),   # Bd no power of two, ragged
    (32, 16, 8, 8, (16, 16), (24, 0, 0, 40)),   # blocks inside a Bd-block
    (32, 4, 8, 8, (32, 32), (12, 12, 0, 40)),   # the cell's counts, in small
    # blocks of two sub-squares of 128: the noised diagonal's blocks
    # run on their sub-squares alone, in all three kernels
    (512, 4, 256, 256, (256, 256), (2, 4, 2, 8)),
    (512, 8, 256, 256, (256, 256), (2, 4, 2, 8)),
    (512, 128, 256, 256, (256, 256), (2, 4, 2, 8)),   # Bd equal to S
    (384, 3, 256, 256, (256, 256), (0, 8, 0, 1)),     # Bd 3: none
])
def test_flash_kernels_match_dense(np_rng, length, bd, bq, bk, bwd, kinds):
    q, k, v = (jnp.asarray(np_rng.randn(2, 2 * length, 2, 16), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(np_rng.randn(2, 2 * length, 2, 16), jnp.float32)

    def flash(q, k, v):
        return FA.flash_attention(
            q, k, v, block_q=bq, block_k=bk, bwd_block_q=bwd[0],
            bwd_block_k=bwd[1], block_diffusion=(length, bd))

    assert kinds == FA._block_kinds(
        *_grid(length, bq, bk), 2 * length, block_q=bq, block_k=bk,
        causal=False, window=None, block_diffusion=(length, bd))
    # the backward kernels classify their own blocks the same way
    assert kinds[2] == FA._block_kinds(
        *_grid(length, *bwd), 2 * length, block_q=bwd[0], block_k=bwd[1],
        causal=False, window=None, block_diffusion=(length, bd))[2]

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(_dense(q, k, v, length, bd)),
                               rtol=2e-5, atol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    gd = jax.grad(lambda *a: jnp.sum(_dense(*a, length, bd) * w),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_refuses_what_the_mask_cannot_be(np_rng):
    x = jnp.zeros((1, 16, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="mask of its own"):
        FA.flash_attention(x, x, x, causal=True, block_diffusion=(8, 4))
    with pytest.raises(ValueError, match="2L positions"):
        FA.flash_attention(x, x, x, block_diffusion=(16, 4))
    with pytest.raises(ValueError, match="Bd | L"):
        FA.flash_attention(x, x, x, block_diffusion=(8, 3))
