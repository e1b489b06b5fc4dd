"""The chunked gated delta rule (`ops.gated_delta`) against the rule
position by position (`gated_delta.recurrence`): the Pallas kernels in
interpret mode and the `jnp` path that runs the same chunk functions
under `lax.scan`, forward and the gradients of q, k, v, the decays and
beta, in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import gated_delta as GD
from paddle_tpu.ops import pallas_util

IMPLS = ["jnp", "pallas"]


def _inputs(seed, b=2, t=40, hk=2, hv=4, dk=16, dv=8, decay=1.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, t, hk, dk)))
    v = jax.random.normal(ks[2], (b, t, hv, dv))
    g = -decay * jax.random.uniform(ks[3], (b, t, hv))
    beta = jax.random.uniform(ks[4], (b, t, hv))
    return q, k, v, g, beta


def _grads(fn, args, w):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=range(5))(*args)


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("t,chunk", [
    (16, 16),       # one chunk
    (64, 16),       # several chunks
    (50, 16),       # not a multiple of the chunk: padded
])
def test_forward_and_gradients_match_the_recurrence(impl, t, chunk):
    args = _inputs(t, t=t)
    want = GD.recurrence(*args)
    got = GD.gated_delta_rule(*args, chunk=chunk, impl=impl)
    assert got.shape == want.shape == (2, t, 4, 8)
    _close(got, want)
    w = jax.random.normal(jax.random.key(1), want.shape)
    for d_got, d_want in zip(
            _grads(lambda *a: GD.gated_delta_rule(*a, chunk=chunk, impl=impl),
                   args, w),
            _grads(GD.recurrence, args, w)):
        _close(d_got, d_want, 1e-4)


def _tiled(monkeypatch, chunk, hb):
    """`gated_delta_rule` with its tiling forced to (chunk, hb): in
    interpret mode any Hb that divides BH runs, whatever the lanes."""
    monkeypatch.setattr(GD, "_tiling", lambda *a, **k: (chunk, hb))
    return lambda *a: GD.gated_delta_rule(*a, impl="pallas")


@pytest.mark.parametrize("chunk", [8, 16])
@pytest.mark.parametrize("hb", [1, 2, 4])
def test_heads_a_grid_step_match_the_recurrence(monkeypatch, hb, chunk):
    """Hb of the 2 x 4 value heads a grid step, over several chunks and
    a padded tail: each head still runs its own recurrence, forward and
    the five gradients."""
    args = _inputs(7, t=40)
    rule = _tiled(monkeypatch, chunk, hb)
    want = GD.recurrence(*args)
    _close(rule(*args), want)
    w = jax.random.normal(jax.random.key(3), want.shape)
    for d_got, d_want in zip(_grads(rule, args, w),
                             _grads(GD.recurrence, args, w)):
        _close(d_got, d_want, 1e-4)


def test_more_heads_a_step_give_one_heads_results(monkeypatch):
    """Batching the heads changes no head's arithmetic: Hb 2 and 4 give
    what one head a step gives, outputs and gradients, to 1e-6."""
    args = _inputs(8, t=40)
    w = jax.random.normal(jax.random.key(4), (2, 40, 4, 8))
    one = _tiled(monkeypatch, 16, 1)
    want = (one(*args), _grads(one, args, w))
    for hb in (2, 4):
        rule = _tiled(monkeypatch, 16, hb)
        _close(rule(*args), want[0], 1e-6)
        for d_got, d_want in zip(_grads(rule, args, w), want[1]):
            _close(d_got, d_want, 1e-6)


def test_the_tiling_follows_the_shapes():
    """`_tiling`: the cell's shape takes CHUNK_MAX and HEADS_MAX; Hb
    divides BH and the backward step's VMEM stays in budget; a chunk
    the caller gives is kept; a short sequence takes a short chunk, and
    a chunk narrower than the lanes one head a step."""
    budget = pallas_util.VMEM_BUDGET_BYTES
    assert GD._tiling(64, 8192, 128, 128, jnp.bfloat16) == (
        GD.CHUNK_MAX, GD.HEADS_MAX) == (128, 4)
    for bh, t, d in [(64, 8192, 128), (6, 8192, 128), (3, 1000, 64),
                     (8, 257, 64), (512, 4096, 256), (4, 200, 128)]:
        c, hb = GD._tiling(bh, t, d, d, jnp.float32)
        assert bh % hb == 0 and hb & (hb - 1) == 0
        assert GD._bwd_vmem_bytes(c, hb, d, d, 4) <= budget
    assert GD._tiling(6, 8192, 128, 128, jnp.bfloat16) == (128, 2)
    assert GD._tiling(64, 8192, 128, 128, jnp.bfloat16, 16) == (16, 1)
    assert GD._tiling(64, 8192, 128, 128, jnp.bfloat16, 256)[0] == 256
    assert GD._tiling(8, 32, 16, 16, jnp.float32) == (32, 1)
    assert GD._tiling(8, 5, 16, 16, jnp.float32) == (16, 1)
    assert GD._tiling(8, 257, 64, 64, jnp.bfloat16) == (128, 4)
    # too wide for the budget at HEADS_MAX: fewer heads a step
    c, hb = GD._tiling(64, 8192, 1024, 1024, jnp.bfloat16)
    assert hb < GD.HEADS_MAX
    assert GD._bwd_vmem_bytes(c, hb, 1024, 1024, 2) <= budget


@pytest.mark.parametrize("impl", IMPLS)
def test_no_decay_is_the_plain_delta_rule(impl):
    """g = 0 (gamma = 1): nothing fades; the chunks' decays are all ones
    and the state carries every write across the chunk boundary."""
    q, k, v, g, beta = _inputs(3, t=32)
    g = jnp.zeros_like(g)
    want = GD.recurrence(q, k, v, g, beta)
    _close(GD.gated_delta_rule(q, k, v, g, beta, chunk=8, impl=impl), want)
    w = jax.random.normal(jax.random.key(2), want.shape)
    for d_got, d_want in zip(
            _grads(lambda *a: GD.gated_delta_rule(*a, chunk=8, impl=impl),
                   (q, k, v, g, beta), w),
            _grads(GD.recurrence, (q, k, v, g, beta), w)):
        _close(d_got, d_want, 1e-4)


@pytest.mark.parametrize("impl", IMPLS)
def test_beta_zero_leaves_the_state_untouched(impl):
    """beta = 0 writes nothing: the state stays 0 and so does every
    output; a head whose beta is 0 is untouched by the others'."""
    q, k, v, g, beta = _inputs(4, t=24)
    zero = GD.gated_delta_rule(q, k, v, g, jnp.zeros_like(beta), chunk=8,
                               impl=impl)
    assert float(jnp.max(jnp.abs(zero))) == 0.0
    beta = beta.at[:, :, 1].set(0.0)
    out = GD.gated_delta_rule(q, k, v, g, beta, chunk=8, impl=impl)
    assert float(jnp.max(jnp.abs(out[:, :, 1]))) == 0.0
    _close(out, GD.recurrence(q, k, v, g, beta))


@pytest.mark.parametrize("impl", IMPLS)
def test_value_heads_share_key_heads_two_to_one(impl):
    """Value heads 2j and 2j + 1 read key head j: the same as handing
    every value head its own copy of that key head."""
    q, k, v, g, beta = _inputs(5, t=20, hk=2, hv=4)
    shared = GD.gated_delta_rule(q, k, v, g, beta, chunk=8, impl=impl)
    copied = GD.gated_delta_rule(jnp.repeat(q, 2, axis=2),
                                 jnp.repeat(k, 2, axis=2), v, g, beta,
                                 chunk=8, impl=impl)
    _close(shared, copied, 1e-6)
    # head 1 reads key head 0: swapping key heads 0 and 1 moves it
    swapped = GD.gated_delta_rule(q[:, :, ::-1], k[:, :, ::-1], v, g, beta,
                                  chunk=8, impl=impl)
    assert float(jnp.max(jnp.abs(swapped[:, :, 1] - shared[:, :, 1]))) > 1e-3


@pytest.mark.parametrize("c", [64, 128])
def test_the_unit_lower_inverse_is_exact_where_powers_grow(c):
    """(I + A)^-1 by doubling at C = 64 and 128 with every entry of A =
    0.9: the powers of A reach ~1e17 (C 64) and cancel, which a product
    of (I + A^2^i) factors loses in float32; block substitution keeps
    the inverse."""
    i, j = GD._iota(c)
    a = jnp.where(j < i, 0.9, 0.0).astype(jnp.float32)
    x = GD._unit_lower_inverse(a, i, j)
    eye = jnp.eye(c, dtype=jnp.float32)
    _close(jnp.matmul(eye + a, x, precision="highest"), eye, 1e-5)


def test_kernels_and_scan_note_what_ran():
    before = pallas_util.traced()
    args = _inputs(6, t=16)
    jax.grad(lambda q: jnp.sum(GD.gated_delta_rule(
        q, *args[1:], chunk=8, impl="jnp")))(args[0])
    after = pallas_util.traced()
    for name in ("gated_delta.forward=jnp", "gated_delta.backward=jnp",
                 "gated_delta.chunk=8", "gated_delta.heads_per_step=1"):
        assert after.get(name, 0) > before.get(name, 0), name
    with pytest.raises(ValueError, match="power of two"):
        GD.gated_delta_rule(*args, chunk=48)
    with pytest.raises(ValueError, match="divide"):
        GD.gated_delta_rule(args[0][:, :, :1].repeat(3, 2),
                            args[1][:, :, :1].repeat(3, 2), *args[2:])
