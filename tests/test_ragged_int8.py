"""Int8 dequant-fused ragged attention: parity + serving contract.

The quantized `(s8, scale)` pair arenas are half the HBM of a float
pool — the 2x-concurrency lever — and with PR12 they take the SAME
one-launch fused walk as float arenas: per-page dequant runs on the
VMEM scratch block right after its DMA lands, before the shared
attention body. The contract mirrors tests/test_ragged_attention.py
exactly: the kernel must match the jnp dequant-gather oracle
BIT-FOR-BIT under jit in interpret mode (`_walk_kernel_int8`'s
per-block `(s8 -> f32) * scale -> q.dtype` is the same element
sequence as `kv_dequantize`, so equality is exact, not approximate),
and an int8-pool ENGINE forced through the kernel must serve the
identical tokens + logprobs as the jnp path through oversubscription
and speculative verify rounds.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops import paged_attention as PA
from paddle_tpu.ops import ragged_paged_attention as RPA
from paddle_tpu.serve.engine import DecodeEngine

pytestmark = pytest.mark.pallas

PAGE, HKV, DH = 4, 2, 8


def _arena8(np_rng, num_pages):
    """Quantized `(s8, scale)` K and V arenas with non-trivial scales
    (standard-normal data -> per-(position, kv-head) absmax varies)."""
    shape = (num_pages, PAGE, HKV, DH)
    ka = jnp.asarray(np_rng.standard_normal(shape), jnp.float32)
    va = jnp.asarray(np_rng.standard_normal(shape), jnp.float32)
    return PA.kv_quantize(ka), PA.kv_quantize(va)


def _jit(fn, **static):
    return jax.jit(functools.partial(fn, **static))


def assert_kernel_matches_oracle(q, ka8, va8, pt, pos0, active, *,
                                 page_size, max_len):
    kw = dict(page_size=page_size, max_len=max_len)
    ref = _jit(RPA.ragged_reference, **kw)(q, ka8, va8, pt, pos0,
                                           active)
    ker = _jit(RPA.ragged_pallas, **kw)(q, ka8, va8, pt, pos0, active)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    return ref


class TestInt8RaggedParity:
    """Bit-identity of the dequant-fused walk across the same shape
    zoo the float suite pins."""

    def test_single_token_decode(self, np_rng):
        ka8, va8 = _arena8(np_rng, 9)
        pt = jnp.asarray(np_rng.randint(0, 9, (5, 4)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((5, 1, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([0, 3, 7, 13, 5], jnp.int32)
        active = jnp.ones((5,), bool)
        assert_kernel_matches_oracle(q, ka8, va8, pt, pos0, active,
                                     page_size=PAGE, max_len=14)

    def test_page_boundary_crossing_window(self, np_rng):
        # TQ=3 prefill-chunk windows straddling page boundaries — the
        # dequant runs per scratch BLOCK, so a window reading both
        # sides of a block edge reads two independently-scaled dequants
        ka8, va8 = _arena8(np_rng, 8)
        pt = jnp.asarray(np_rng.randint(0, 8, (4, 4)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((4, 3, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([PAGE - 1, PAGE - 2, 2 * PAGE - 1, 0],
                           jnp.int32)
        active = jnp.ones((4,), bool)
        assert_kernel_matches_oracle(q, ka8, va8, pt, pos0, active,
                                     page_size=PAGE, max_len=16)

    def test_mixed_chunk_decode_verify_batch(self, np_rng):
        # one launch, ragged mix: prefill chunk mid-prompt, fresh
        # prompt at 0, deep decode row, inactive row — decode, chunk
        # and speculative verify windows are all this one grid
        ka8, va8 = _arena8(np_rng, 12)
        pt = jnp.asarray(np_rng.randint(0, 12, (4, 5)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((4, 4, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([6, 0, 15, 19], jnp.int32)
        active = jnp.asarray([True, True, True, False])
        assert_kernel_matches_oracle(q, ka8, va8, pt, pos0, active,
                                     page_size=PAGE, max_len=19)

    def test_sentinel_and_inactive_rows(self, np_rng):
        # sentinel table entries (= num_pages) clip to the last real
        # page in BOTH the data and the scale-plane DMA — a mismatch
        # would dequantize real bytes with a garbage scale
        ka8, va8 = _arena8(np_rng, 6)
        pt = jnp.asarray(np_rng.randint(0, 6, (3, 4)), jnp.int32)
        pt = pt.at[0, 2:].set(6).at[2, :].set(6)
        q = jnp.asarray(np_rng.standard_normal((3, 1, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([5, 9, 21], jnp.int32)
        active = jnp.asarray([True, True, False])
        assert_kernel_matches_oracle(q, ka8, va8, pt, pos0, active,
                                     page_size=PAGE, max_len=12)

    def test_bf16_compute_dtype(self, np_rng):
        # dequant lands on q.dtype scratch: with a bf16 q the kernel's
        # f32-multiply-then-round must equal kv_dequantize(..., bf16)
        ka8, va8 = _arena8(np_rng, 6)
        pt = jnp.asarray(np_rng.randint(0, 6, (3, 3)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((3, 2, 4, DH)),
                        jnp.bfloat16)
        pos0 = jnp.asarray([0, 4, 8], jnp.int32)
        active = jnp.ones((3,), bool)
        assert_kernel_matches_oracle(q, ka8, va8, pt, pos0, active,
                                     page_size=PAGE, max_len=11)

    def test_max_len_not_page_multiple(self, np_rng):
        ka8, va8 = _arena8(np_rng, 7)
        pt = jnp.asarray(np_rng.randint(0, 7, (3, 3)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((3, 1, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([0, 5, 9], jnp.int32)
        active = jnp.ones((3,), bool)
        assert_kernel_matches_oracle(q, ka8, va8, pt, pos0, active,
                                     page_size=PAGE, max_len=10)

    @pytest.mark.slow
    def test_int8_shape_sweep(self, np_rng):
        # randomized geometry sweep (each trial is a fresh compile —
        # the count is a tier-1 budget lever, same as the float sweep)
        for trial in range(5):
            num_pages = int(np_rng.randint(4, 14))
            mp = int(np_rng.randint(2, 6))
            r = int(np_rng.randint(1, 7))
            tq = int(np_rng.randint(1, 6))
            max_len = int(np_rng.randint(tq, mp * PAGE + 1))
            ka8, va8 = _arena8(np_rng, num_pages)
            pt = jnp.asarray(
                np_rng.randint(0, num_pages + 1, (r, mp)), jnp.int32)
            q = jnp.asarray(
                np_rng.standard_normal((r, tq, 2 * HKV, DH)),
                jnp.float32)
            pos0 = jnp.asarray(
                np_rng.randint(0, max(1, max_len - tq + 1), (r,)),
                jnp.int32)
            active = jnp.asarray(np_rng.randint(0, 2, (r,)) > 0)
            assert_kernel_matches_oracle(
                q, ka8, va8, pt, pos0, active, page_size=PAGE,
                max_len=max_len)


class TestInt8Dispatch:
    def test_verify_tq1_is_decode_int8(self, np_rng):
        """The spec path's K=0 degenerate is a plain decode step on
        int8 arenas too — through the forced kernel on both sides."""
        ka8, va8 = _arena8(np_rng, 9)
        pt = jnp.asarray(np_rng.randint(0, 9, (4, 4)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((4, 1, 4, DH)),
                        jnp.float32)
        k = jnp.asarray(np_rng.standard_normal((4, 1, HKV, DH)),
                        jnp.float32)
        v = jnp.asarray(np_rng.standard_normal((4, 1, HKV, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([0, 5, 9, 30], jnp.int32)
        active = jnp.asarray([True, True, True, False])
        kw = dict(page_size=PAGE, max_len=14, impl="pallas")
        out_d, ka_d, va_d = _jit(PA.paged_decode_attention, **kw)(
            q, k, v, ka8, va8, pt, pos0, active)
        out_v, ka_v, va_v = _jit(PA.paged_verify_attention, **kw)(
            q, k, v, ka8, va8, pt, pos0, active)
        np.testing.assert_array_equal(np.asarray(out_d),
                                      np.asarray(out_v))
        for a, b in zip(ka_d + va_d, ka_v + va_v):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


CFG8 = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                           attn_impl="dense", kv_cache_dtype="int8")


@pytest.fixture(scope="module")
def params8():
    return T.init_params(jax.random.key(0), CFG8)


def _mk_eng(params, impl, **kw):
    return DecodeEngine(params, CFG8, slots=2, max_len=48,
                        page_size=8, ragged_impl=impl, **kw)


def _prompts(seed=0):
    """Oversubscribed traffic (6 requests through 2 slots) with the
    repetitive shapes the n-gram proposer bites on."""
    r = np.random.RandomState(seed)
    base = r.randint(0, 61, (6,)).astype(np.int32)
    return [np.concatenate([base, base, base[:3]]).astype(np.int32),
            r.randint(0, 61, (7,)).astype(np.int32),
            np.concatenate([base, base]).astype(np.int32),
            r.randint(0, 61, (5,)).astype(np.int32),
            np.concatenate([base[:4], base]).astype(np.int32),
            r.randint(0, 61, (4,)).astype(np.int32)]


class TestInt8EngineParity:
    """ISSUE acceptance: greedy serving parity (tokens + logprobs) for
    an int8-pool engine with the kernel forced, through
    oversubscription and speculative rounds — the engine-level proof
    that dropping the int8-excludes-kernel special case is safe."""

    @pytest.mark.slow
    def test_oversubscribed_greedy_parity(self, params8):
        ps = _prompts()
        want, want_lp = _mk_eng(params8, "jnp").serve(
            [p.copy() for p in ps], max_new=8, return_logprobs=True)
        eng = _mk_eng(params8, "pallas")
        got, got_lp = eng.serve([p.copy() for p in ps], max_new=8,
                                return_logprobs=True)
        assert got == want
        for a, b in zip(got_lp, want_lp):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert eng.artifact_manifest()["ragged_impl"] == "pallas"

    @pytest.mark.slow
    def test_speculative_rounds_parity(self, params8):
        ps = _prompts(seed=2)[:4]
        want = _mk_eng(params8, "jnp").serve(
            [p.copy() for p in ps], max_new=10, speculative=True)
        eng = _mk_eng(params8, "pallas")
        got = eng.serve([p.copy() for p in ps], max_new=10,
                        speculative=True)
        assert got == want
        st = eng.last_stats
        # the verify windows must actually exercise TQ>1 kernel
        # launches (real acceptance), not degenerate to decode
        assert st.draft_proposed > 0
        assert 0 < st.draft_accepted <= st.draft_proposed
