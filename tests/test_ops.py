"""Op-level tests: shapes, known values, gradient checks.

Mirrors the reference's per-op test style (reference:
python/paddle/v2/fluid/tests/op_test.py check_output/check_grad).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import activations as A
from paddle_tpu.ops import conv as C
from paddle_tpu.ops import losses as L
from paddle_tpu.ops import metrics as M
from paddle_tpu.ops import norm as N

from gradcheck import directional_grad_check


class TestActivations:
    @pytest.mark.parametrize(
        "name",
        ["sigmoid", "tanh", "relu", "brelu", "softrelu", "stanh", "abs",
         "square", "exponential", "softmax", "swish", "leaky_relu",
         "hard_sigmoid", "soft_shrink"],
    )
    def test_finite_and_shape(self, name, np_rng):
        x = jnp.asarray(np_rng.randn(4, 7), jnp.float32)
        y = A.get(name)(x)
        assert y.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(y)))

    def test_registry_unknown(self):
        with pytest.raises(ValueError):
            A.get("nope")

    def test_brelu_clips(self):
        x = jnp.asarray([-5.0, 3.0, 30.0])
        np.testing.assert_allclose(A.brelu(x), [0.0, 3.0, 24.0])

    def test_softmax_sums_to_one(self, np_rng):
        x = jnp.asarray(np_rng.randn(3, 9), jnp.float32)
        np.testing.assert_allclose(jnp.sum(A.softmax(x), -1), np.ones(3), rtol=1e-5)


class TestConv:
    def test_conv2d_shape_same(self, np_rng):
        x = jnp.asarray(np_rng.randn(2, 8, 8, 3), jnp.float32)
        k = jnp.asarray(np_rng.randn(3, 3, 3, 16) * 0.1, jnp.float32)
        y = C.conv2d(x, k, stride=2, padding="SAME")
        assert y.shape == (2, 4, 4, 16)

    def test_conv2d_identity_kernel(self):
        x = jnp.arange(16.0).reshape(1, 4, 4, 1)
        k = jnp.zeros((3, 3, 1, 1)).at[1, 1, 0, 0].set(1.0)
        y = C.conv2d(x, k, padding="SAME")
        np.testing.assert_allclose(y, x, rtol=1e-6)

    def test_depthwise(self, np_rng):
        x = jnp.asarray(np_rng.randn(2, 8, 8, 4), jnp.float32)
        k = jnp.asarray(np_rng.randn(3, 3, 1, 4) * 0.1, jnp.float32)
        y = C.depthwise_conv2d(x, k)
        assert y.shape == (2, 8, 8, 4)

    def test_max_pool(self):
        x = jnp.arange(16.0).reshape(1, 4, 4, 1)
        y = C.max_pool2d(x, 2)
        np.testing.assert_allclose(y[0, :, :, 0], [[5.0, 7.0], [13.0, 15.0]])

    def test_avg_pool(self):
        x = jnp.ones((1, 4, 4, 1))
        y = C.avg_pool2d(x, 2)
        np.testing.assert_allclose(y, np.ones((1, 2, 2, 1)))

    def test_conv_grad(self, np_rng):
        x = jnp.asarray(np_rng.randn(1, 5, 5, 2), jnp.float32)
        k = jnp.asarray(np_rng.randn(3, 3, 2, 3) * 0.3, jnp.float32)
        directional_grad_check(
            lambda p: jnp.sum(jnp.square(C.conv2d(x, p["k"]))), {"k": k}
        )

    @pytest.mark.parametrize(
        "window,stride,padding",
        [(2, 2, "VALID"), (3, 2, "SAME"), (3, 1, "SAME"), ((2, 3), (2, 1), "VALID"),
         (3, 2, 1)],
    )
    def test_max_pool_tie_split_matches_native(self, window, stride, padding,
                                               np_rng):
        # away from ties the custom VJP must equal select-and-scatter's
        x = jnp.asarray(np_rng.randn(2, 9, 11, 3), jnp.float32)
        w = jnp.asarray(np_rng.randn(
            *C.max_pool2d(x, window, stride=stride, padding=padding).shape),
            jnp.float32)

        def f(x, tie_split):
            y = C.max_pool2d(x, window, stride=stride, padding=padding,
                             tie_split=tie_split)
            return jnp.sum(y * w)

        np.testing.assert_allclose(f(x, True), f(x, False), rtol=1e-6)
        g_ts = jax.grad(lambda x: f(x, True))(x)
        g_raw = jax.grad(lambda x: f(x, False))(x)
        np.testing.assert_allclose(g_ts, g_raw, rtol=1e-5, atol=1e-6)

    def test_max_pool_tie_split_shares_gradient(self):
        # a 4-way tie gets dy/4 each (XLA native would give one element
        # 1); explicit opt-in — the DEFAULT is the native formulation
        # (the custom VJP measured slower on the chip, ROADMAP C5)
        x = jnp.ones((1, 2, 2, 1), jnp.float32)
        g = jax.grad(lambda x: jnp.sum(
            C.max_pool2d(x, 2, tie_split=True)))(x)
        np.testing.assert_allclose(g, np.full((1, 2, 2, 1), 0.25))
        # gradient mass is conserved either way
        assert float(jnp.sum(g)) == pytest.approx(1.0)

    def test_max_pool_nan_window_stays_finite_elsewhere(self):
        # a NaN window max means cnt==0 (NaN != NaN); the guard drops
        # that window's grad instead of spreading inf/NaN around it
        x = np.random.RandomState(0).randn(1, 8, 8, 1).astype(np.float32)
        x[0, 2, 2, 0] = np.nan
        g = jax.grad(lambda x: jnp.nansum(C.max_pool2d(x, 2)))(jnp.asarray(x))
        # positions outside the NaN window keep finite gradients
        mask = np.ones((1, 8, 8, 1), bool)
        mask[0, 2:4, 2:4, 0] = False
        assert bool(jnp.all(jnp.isfinite(g[mask])))

    def test_max_pool_jvp_via_tie_split_off(self, np_rng):
        # forward-mode needs the native path (custom_vjp rejects jvp)
        x = jnp.asarray(np_rng.randn(1, 4, 4, 2), jnp.float32)
        _, t = jax.jvp(
            lambda x: C.max_pool2d(x, 2, tie_split=False), (x,), (x,))
        assert t.shape == (1, 2, 2, 2)

    def test_out_hw_explicit_asymmetric_padding(self):
        assert C.out_hw(8, 8, 3, 2, ((1, 2), (0, 1))) == (5, 4)
        # and the s2d conv accepts the nested form end-to-end
        x = jnp.asarray(np.random.RandomState(0).randn(1, 8, 8, 3),
                        jnp.float32)
        k = jnp.asarray(np.random.RandomState(1).randn(4, 4, 3, 4) * 0.2,
                        jnp.float32)
        y0 = C.conv2d(x, k, stride=2, padding=((2, 2), (2, 2)))
        y1 = C.conv2d_space_to_depth(x, k, stride=2, padding=((2, 2), (2, 2)))
        np.testing.assert_allclose(y0, y1, rtol=1e-4, atol=1e-4)

    def test_space_to_depth_roundtrip(self, np_rng):
        x = jnp.asarray(np_rng.randn(2, 6, 8, 5), jnp.float32)
        np.testing.assert_array_equal(
            C.depth_to_space(C.space_to_depth(x, (3, 2)), (3, 2)), x)

    @pytest.mark.parametrize(
        "hw,kernel,stride,padding",
        [(16, 7, 2, "SAME"),     # the ResNet stem shape (pad 2/3 -> blocks)
         (16, 4, 2, "VALID"),
         (15, 5, 3, "VALID"),    # block 3, kernel padded 5->6
         (16, 4, 2, "SAME"),     # pad (1,1): odd low pad -> fallback path
         (18, 3, 3, "SAME")],
    )
    def test_conv_space_to_depth_equivalence(self, hw, kernel, stride,
                                             padding, np_rng):
        x = jnp.asarray(np_rng.randn(2, hw, hw, 3), jnp.float32)
        k = jnp.asarray(np_rng.randn(kernel, kernel, 3, 8) * 0.2, jnp.float32)
        y0 = C.conv2d(x, k, stride=stride, padding=padding)
        y1 = C.conv2d_space_to_depth(x, k, stride=stride, padding=padding)
        assert y0.shape == y1.shape
        np.testing.assert_allclose(y0, y1, rtol=1e-4, atol=1e-4)
        # gradients agree too (wrt input and kernel)
        g0 = jax.grad(lambda x, k: jnp.sum(jnp.square(
            C.conv2d(x, k, stride=stride, padding=padding))), (0, 1))(x, k)
        g1 = jax.grad(lambda x, k: jnp.sum(jnp.square(
            C.conv2d_space_to_depth(x, k, stride=stride, padding=padding))),
            (0, 1))(x, k)
        np.testing.assert_allclose(g0[0], g1[0], rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(g0[1], g1[1], rtol=1e-3, atol=1e-3)

    def test_im2col_shape(self, np_rng):
        x = jnp.asarray(np_rng.randn(2, 6, 6, 3), jnp.float32)
        p = C.im2col(x, 3, stride=1, padding="VALID")
        assert p.shape == (2, 4, 4, 27)

    def test_roi_pool_shape(self, np_rng):
        x = jnp.asarray(np_rng.randn(2, 8, 8, 3), jnp.float32)
        rois = jnp.asarray([[0, 0, 0, 4, 4], [1, 2, 2, 7, 7]], jnp.float32)
        y = C.roi_pool(x, rois, (2, 2))
        assert y.shape == (2, 2, 2, 3)


class TestNorm:
    def test_batch_norm_train_normalizes(self, np_rng):
        x = jnp.asarray(np_rng.randn(64, 5) * 3 + 2, jnp.float32)
        y, m, v = N.batch_norm(
            x, jnp.ones(5), jnp.zeros(5), jnp.zeros(5), jnp.ones(5),
            training=True,
        )
        np.testing.assert_allclose(np.mean(np.asarray(y), 0), np.zeros(5), atol=1e-4)
        np.testing.assert_allclose(np.std(np.asarray(y), 0), np.ones(5), atol=1e-2)

    def test_batch_norm_eval_uses_running(self, np_rng):
        x = jnp.asarray(np_rng.randn(8, 3), jnp.float32)
        y, m, v = N.batch_norm(
            x, jnp.ones(3), jnp.zeros(3), jnp.zeros(3), jnp.ones(3),
            training=False, epsilon=0.0,
        )
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-5)

    def test_lrn_shape(self, np_rng):
        x = jnp.asarray(np_rng.randn(2, 4, 4, 8), jnp.float32)
        y = N.lrn(x)
        assert y.shape == x.shape

    def test_layer_norm(self, np_rng):
        x = jnp.asarray(np_rng.randn(4, 6) * 5, jnp.float32)
        y = N.layer_norm(x, jnp.ones(6), jnp.zeros(6))
        np.testing.assert_allclose(np.mean(np.asarray(y), -1), np.zeros(4), atol=1e-4)


class TestLosses:
    def test_softmax_ce_matches_manual(self, np_rng):
        logits = jnp.asarray(np_rng.randn(6, 4), jnp.float32)
        labels = jnp.asarray([0, 1, 2, 3, 0, 1])
        got = L.softmax_cross_entropy(logits, labels)
        logp = np.log(np.asarray(A.softmax(logits)))
        want = -logp[np.arange(6), np.asarray(labels)]
        np.testing.assert_allclose(got, want, rtol=1e-3)

    def test_sigmoid_ce_stable(self):
        logits = jnp.asarray([1000.0, -1000.0])
        labels = jnp.asarray([1.0, 0.0])
        got = L.sigmoid_cross_entropy(logits, labels)
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(got, [0.0, 0.0], atol=1e-5)

    def test_squared_error(self):
        pred = jnp.asarray([[1.0, 2.0]])
        tgt = jnp.asarray([[0.0, 0.0]])
        np.testing.assert_allclose(L.squared_error(pred, tgt), [2.5])

    def test_huber_regression_regions(self):
        pred = jnp.asarray([[0.5], [3.0]])
        tgt = jnp.zeros((2, 1))
        got = L.huber_regression(pred, tgt, delta=1.0)
        np.testing.assert_allclose(got, [0.125, 2.5])

    def test_rank_cost_symmetry(self):
        a, b = jnp.asarray([1.0]), jnp.asarray([0.0])
        # label 1 => prefers left higher => lower cost when left > right
        c_hi = float(L.rank_cost(a, b, jnp.asarray([1.0]))[0])
        c_lo = float(L.rank_cost(b, a, jnp.asarray([1.0]))[0])
        assert c_hi < c_lo

    def test_ce_grad(self, np_rng):
        logits = jnp.asarray(np_rng.randn(5, 7), jnp.float32)
        labels = jnp.asarray(np_rng.randint(0, 7, 5))
        directional_grad_check(
            lambda p: jnp.mean(L.softmax_cross_entropy(p["x"], labels)),
            {"x": logits},
        )

    def test_cos_sim(self):
        a = jnp.asarray([[1.0, 0.0]])
        np.testing.assert_allclose(L.cos_sim(a, a), [1.0], rtol=1e-5)

    def test_lambda_rank_runs(self, np_rng):
        scores = jnp.asarray(np_rng.randn(8), jnp.float32)
        rel = jnp.asarray(np_rng.randint(0, 3, 8), jnp.float32)
        val = L.lambda_rank_segment(scores, rel)
        assert np.isfinite(float(val))


class TestMetrics:
    def test_accuracy(self):
        logits = jnp.asarray([[0.9, 0.1], [0.2, 0.8], [0.7, 0.3]])
        labels = jnp.asarray([0, 1, 1])
        np.testing.assert_allclose(M.accuracy(logits, labels), 2.0 / 3.0, rtol=1e-6)

    def test_top_k(self):
        logits = jnp.asarray([[0.5, 0.3, 0.2], [0.1, 0.2, 0.7]])
        labels = jnp.asarray([1, 0])
        np.testing.assert_allclose(M.top_k_accuracy(logits, labels, k=2), 0.5)


class TestRound3LossGaps:
    """modified_huber / squared_l2 family (reference:
    operators/modified_huber_loss_op.cc, squared_l2_distance_op.cc,
    l1_norm_op.cc, squared_l2_norm_op.cc)."""

    def test_modified_huber_regions(self):
        from paddle_tpu.ops import losses

        logits = jnp.asarray([2.0, 0.5, -0.5, -2.0])
        labels = jnp.asarray([1, 1, 1, 1])
        out = np.asarray(losses.modified_huber_loss(logits, labels))
        # z = [2, .5, -.5, -2]: quadratic branch for z>=-1, linear else
        np.testing.assert_allclose(out, [0.0, 0.25, 2.25, 8.0], rtol=1e-6)
        # label 0 mirrors
        out0 = np.asarray(losses.modified_huber_loss(-logits,
                                                     jnp.zeros(4, jnp.int32)))
        np.testing.assert_allclose(out0, out, rtol=1e-6)

    def test_modified_huber_grad(self, np_rng):
        from gradcheck import directional_grad_check
        from paddle_tpu.ops import losses

        x = jnp.asarray(np_rng.randn(6), jnp.float32)
        labels = jnp.asarray(np_rng.randint(0, 2, 6))
        directional_grad_check(
            lambda p: jnp.sum(losses.modified_huber_loss(p, labels)), x)

    def test_squared_l2_family(self, np_rng):
        from paddle_tpu.ops import losses

        x = jnp.asarray(np_rng.randn(3, 4), jnp.float32)
        y = jnp.asarray(np_rng.randn(3, 4), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(losses.squared_l2_distance(x, y)),
            ((np.asarray(x) - np.asarray(y)) ** 2).sum(1), rtol=1e-5)
        np.testing.assert_allclose(
            float(losses.l1_norm(x)), np.abs(np.asarray(x)).sum(),
            rtol=1e-5)
        np.testing.assert_allclose(
            float(losses.squared_l2_norm(x)),
            (np.asarray(x) ** 2).sum(), rtol=1e-5)


class TestTokenSampling:
    """Distribution-shape invariants for the ops.sampling per-row
    sampler (the serving engine's sampler) and the speculative
    acceptance rule."""

    def _logits(self, np_rng, n=5, v=17):
        return jnp.asarray(np_rng.randn(n, v), jnp.float32)

    def test_top_k_masks_exactly_k(self, np_rng):
        from paddle_tpu.ops import sampling as S

        lg = self._logits(np_rng)
        n, v = lg.shape
        for k in (1, 3, v, v + 5):
            out = S.per_row_filter_logits(
                lg, jnp.ones((n,)), jnp.full((n,), k, jnp.int32),
                jnp.ones((n,)))
            kept = np.isfinite(np.asarray(out)).sum(axis=-1)
            # gaussian logits: ties measure-zero, so exactly min(k, V)
            np.testing.assert_array_equal(kept, min(k, v))

    def test_per_row_k_varies_by_row(self, np_rng):
        from paddle_tpu.ops import sampling as S

        lg = self._logits(np_rng, n=4)
        ks = jnp.asarray([1, 2, 5, 17], jnp.int32)
        out = S.per_row_filter_logits(
            lg, jnp.ones((4,)), ks, jnp.ones((4,)))
        np.testing.assert_array_equal(
            np.isfinite(np.asarray(out)).sum(axis=-1), np.asarray(ks))

    def test_temperature_zero_is_greedy(self, np_rng):
        from paddle_tpu.ops import sampling as S

        lg = self._logits(np_rng)
        n = lg.shape[0]
        keys = jax.random.split(jax.random.key(0), n)
        toks = S.per_row_sample(lg, jnp.zeros((n,)),
                                jnp.full((n,), 17, jnp.int32),
                                jnp.ones((n,)), keys)
        np.testing.assert_array_equal(
            np.asarray(toks), np.asarray(jnp.argmax(lg, axis=-1)))

    def test_temperature_to_zero_converges_to_greedy(self, np_rng):
        from paddle_tpu.ops import sampling as S

        lg = self._logits(np_rng)
        n = lg.shape[0]
        keys = jax.random.split(jax.random.key(1), n)
        greedy = np.asarray(jnp.argmax(lg, axis=-1))
        for temp in (1e-2, 1e-4):
            toks = S.per_row_sample(
                lg, jnp.full((n,), temp),
                jnp.full((n,), 17, jnp.int32), jnp.ones((n,)), keys)
            np.testing.assert_array_equal(np.asarray(toks), greedy)

    def test_nucleus_keeps_argmax_and_masks_tail(self, np_rng):
        from paddle_tpu.ops import sampling as S

        lg = self._logits(np_rng)
        n, v = lg.shape
        out = S.per_row_filter_logits(
            lg, jnp.ones((n,)), jnp.full((n,), v, jnp.int32),
            jnp.full((n,), 1e-6, jnp.float32))
        kept = np.isfinite(np.asarray(out))
        np.testing.assert_array_equal(kept.sum(axis=-1), 1)
        assert kept[np.arange(n), np.asarray(jnp.argmax(lg, -1))].all()

    def test_seeded_determinism_and_row_independence(self, np_rng):
        from paddle_tpu.ops import sampling as S

        lg = self._logits(np_rng)
        n = lg.shape[0]
        keys = jax.random.split(jax.random.key(7), n)
        args = (jnp.ones((n,)), jnp.full((n,), 17, jnp.int32),
                jnp.ones((n,)))
        a = S.per_row_sample(lg, *args, keys)
        b = S.per_row_sample(lg, *args, keys)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # a row's draw depends only on its own key: perturbing row 0's
        # logits and key must not move the other rows
        lg2 = lg.at[0].set(-lg[0])
        keys2 = keys.at[0].set(jax.random.key(99))
        c = S.per_row_sample(lg2, *args, keys2)
        np.testing.assert_array_equal(np.asarray(a)[1:],
                                      np.asarray(c)[1:])

    def test_matches_models_filter_when_uniform(self, np_rng):
        from paddle_tpu.ops import sampling as S
        from paddle_tpu.models import transformer as T

        lg = self._logits(np_rng)
        n = lg.shape[0]
        ref = T._filter_logits(T.at_least_f32(lg), 0.7, 3, 0.9)
        out = S.per_row_filter_logits(
            lg, jnp.full((n,), 0.7, jnp.float32),
            jnp.full((n,), 3, jnp.int32),
            jnp.full((n,), 0.9, jnp.float32))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


class TestSpecVerifyRule:
    """ngram_spec_verify: the rejection-sampling acceptance rule for
    deterministic drafts."""

    def _setup(self, np_rng, s=3, k=4, v=13):
        lg = jnp.asarray(np_rng.randn(s, k + 1, v), jnp.float32)
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        window = jnp.concatenate(
            [jnp.full((s, 1), 5, jnp.int32), greedy[:, :k]], axis=1)
        return lg, greedy, window

    def test_greedy_accepts_agreeing_prefix(self, np_rng):
        from paddle_tpu.ops import sampling as S

        lg, greedy, window = self._setup(np_rng)
        s, k = 3, 4
        v = lg.shape[-1]
        # row 1 disagrees at j=2; row 2 budget-capped at 2
        window = window.at[1, 3].set(
            (int(greedy[1, 2]) + 1) % v)
        dl = jnp.asarray([4, 4, 2], jnp.int32)
        keys = jax.random.split(jax.random.key(0), s)
        nt, na, lpd, lpn = S.ngram_spec_verify(
            lg, window, dl, jnp.zeros((s,)),
            jnp.full((s,), v, jnp.int32), jnp.ones((s,)), keys)
        np.testing.assert_array_equal(np.asarray(na), [4, 2, 2])
        # next token is the target argmax at the break position
        expect = np.asarray(jnp.take_along_axis(
            greedy, na[:, None], axis=1)[:, 0])
        np.testing.assert_array_equal(np.asarray(nt), expect)
        # logprobs follow the full-softmax rescoring convention
        full = jax.nn.log_softmax(lg, axis=-1)
        want = np.asarray(jnp.take_along_axis(
            full[:, :k], window[:, 1:, None], axis=-1)[:, :, 0])
        np.testing.assert_allclose(np.asarray(lpd), want, rtol=1e-6)

    def test_zero_draft_len_is_plain_decode(self, np_rng):
        from paddle_tpu.ops import sampling as S

        lg, greedy, window = self._setup(np_rng)
        s = 3
        v = lg.shape[-1]
        dl = jnp.zeros((s,), jnp.int32)
        keys = jax.random.split(jax.random.key(1), s)
        nt, na, _, _ = S.ngram_spec_verify(
            lg, window, dl, jnp.zeros((s,)),
            jnp.full((s,), v, jnp.int32), jnp.ones((s,)), keys)
        np.testing.assert_array_equal(np.asarray(na), 0)
        np.testing.assert_array_equal(
            np.asarray(nt), np.asarray(greedy[:, 0]))

    def test_sampled_rows_preserve_target_distribution(self, np_rng):
        """Empirical check of the Leviathan guarantee for a delta
        proposer: over many seeded trials the emitted first token's
        frequencies match the target softmax whether or not the draft
        agrees, within statistical error."""
        from paddle_tpu.ops import sampling as S

        v = 5
        lg = jnp.asarray(np_rng.randn(1, 2, v), jnp.float32)
        p = np.asarray(jax.nn.softmax(lg[0, 0] / 0.8))
        trials = 4000
        draft = int(np.argsort(p)[-2])  # a likely-but-not-top draft
        window = jnp.asarray([[3, draft]], jnp.int32)

        def one(key):
            nt, na, _, _ = S.ngram_spec_verify(
                lg, window, jnp.ones((1,), jnp.int32),
                jnp.full((1,), 0.8, jnp.float32),
                jnp.full((1,), v, jnp.int32),
                jnp.ones((1,)), key[None])
            # the round's first emitted token: the draft if accepted,
            # else the residual redraw
            return jnp.where(na[0] > 0, window[0, 1], nt[0])

        keys = jax.random.split(jax.random.key(2), trials)
        toks = np.asarray(jax.jit(jax.vmap(one))(keys))
        freq = np.bincount(toks, minlength=v) / trials
        # 4k trials: se ~ sqrt(p(1-p)/n) <= 0.008; allow 4 sigma
        np.testing.assert_allclose(freq, p, atol=0.035)

    def test_greedy_never_accepts_beyond_disagreement(self, np_rng):
        from paddle_tpu.ops import sampling as S

        lg, greedy, window = self._setup(np_rng)
        s, k = 3, 4
        v = lg.shape[-1]
        window = window.at[:, 1].set((greedy[:, 0] + 1) % v)
        keys = jax.random.split(jax.random.key(3), s)
        _, na, _, _ = S.ngram_spec_verify(
            lg, window, jnp.full((s,), k, jnp.int32), jnp.zeros((s,)),
            jnp.full((s,), v, jnp.int32), jnp.ones((s,)), keys)
        np.testing.assert_array_equal(np.asarray(na), 0)
