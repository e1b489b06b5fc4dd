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


def test_the_unit_lower_inverse_is_exact_where_powers_grow():
    """(I + A)^-1 by doubling at C = 64 with every entry of A = 0.9: the
    powers of A reach ~1e17 and cancel, which a product of (I + A^2^i)
    factors loses in float32; block substitution keeps the inverse."""
    c = 64
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
                 "gated_delta.chunk=8"):
        assert after.get(name, 0) > before.get(name, 0), name
    with pytest.raises(ValueError, match="power of two"):
        GD.gated_delta_rule(*args, chunk=48)
    with pytest.raises(ValueError, match="divide"):
        GD.gated_delta_rule(args[0][:, :, :1].repeat(3, 2),
                            args[1][:, :, :1].repeat(3, 2), *args[2:])
