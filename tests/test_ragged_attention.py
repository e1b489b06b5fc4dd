"""Interpret-mode parity suite for the fused ragged paged-attention
kernel: the Pallas page-table walk must match the jnp oracle
BIT-FOR-BIT, under jit on both sides — jit is what the engine runs,
and eager-vs-jit XLA fusion differs by ulps, so comparing compiled
against compiled is the honest contract (the kernel and the jitted
oracle agree exactly; see test_jit_is_the_contract for the pin).

Interpret mode executes the kernel's primitive sequence (DMA walk per
page-table entry, shared attention body over VMEM scratch) on the same
XLA CPU backend as the oracle. The TPU compiler refuses this kernel
(docs/KERNELS.md), so auto dispatch never selects it; these tests keep
the walk's addressing honest for the kernel that replaces it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_attention as PA
from paddle_tpu.ops import ragged_paged_attention as RPA

pytestmark = pytest.mark.pallas

PAGE, HKV, DH = 4, 2, 8


def _arena(np_rng, num_pages):
    shape = (num_pages, PAGE, HKV, DH)
    return (jnp.asarray(np_rng.standard_normal(shape), jnp.float32),
            jnp.asarray(np_rng.standard_normal(shape), jnp.float32))


def _jit(fn, **static):
    return jax.jit(functools.partial(fn, **static))


def assert_kernel_matches_oracle(q, ka, va, pt, pos0, active, *,
                                 page_size, max_len):
    kw = dict(page_size=page_size, max_len=max_len)
    ref = _jit(RPA.ragged_reference, **kw)(q, ka, va, pt, pos0, active)
    ker = _jit(RPA.ragged_pallas, **kw)(q, ka, va, pt, pos0, active)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    return ref


class TestRaggedParity:
    """Bit-identity across the ragged shape zoo."""

    def test_single_token_decode(self, np_rng):
        ka, va = _arena(np_rng, 9)
        pt = jnp.asarray(np_rng.randint(0, 9, (5, 4)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((5, 1, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([0, 3, 7, 13, 5], jnp.int32)
        active = jnp.ones((5,), bool)
        assert_kernel_matches_oracle(q, ka, va, pt, pos0, active,
                                     page_size=PAGE, max_len=14)

    def test_page_boundary_crossing_window(self, np_rng):
        # TQ=3 windows straddling page boundaries: pos0 = PAGE-1 puts
        # queries on both sides of a block edge; pos0 = PAGE-2 ends
        # exactly ON the edge
        ka, va = _arena(np_rng, 8)
        pt = jnp.asarray(np_rng.randint(0, 8, (4, 4)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((4, 3, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([PAGE - 1, PAGE - 2, 2 * PAGE - 1, 0],
                           jnp.int32)
        active = jnp.ones((4,), bool)
        assert_kernel_matches_oracle(q, ka, va, pt, pos0, active,
                                     page_size=PAGE, max_len=16)

    def test_full_page_prompt_and_max_len_edge(self, np_rng):
        # rows at exactly-full pages, and the last query landing on
        # max_len - 1 (the static slice edge)
        ka, va = _arena(np_rng, 8)
        pt = jnp.asarray(np_rng.randint(0, 8, (3, 4)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((3, 2, 4, DH)),
                        jnp.float32)
        max_len = 4 * PAGE
        pos0 = jnp.asarray([PAGE, 2 * PAGE, max_len - 2], jnp.int32)
        active = jnp.ones((3,), bool)
        assert_kernel_matches_oracle(q, ka, va, pt, pos0, active,
                                     page_size=PAGE, max_len=max_len)

    def test_mixed_chunk_and_decode_batch(self, np_rng):
        # one launch, ragged mix: a prefill chunk mid-prompt (TQ real
        # queries), a fresh prompt at position 0, a deep decode row
        # (TQ padding beyond its single real query), an inactive row
        ka, va = _arena(np_rng, 12)
        pt = jnp.asarray(np_rng.randint(0, 12, (4, 5)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((4, 4, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([6, 0, 15, 19], jnp.int32)
        active = jnp.asarray([True, True, True, False])
        assert_kernel_matches_oracle(q, ka, va, pt, pos0, active,
                                     page_size=PAGE, max_len=19)

    def test_sentinel_and_inactive_rows(self, np_rng):
        # unmapped table entries carry the sentinel id (= num_pages):
        # the kernel's min-clip must read the same clipped page the
        # oracle's mode="clip" gather reads, and inactive rows must
        # reproduce the oracle's all-masked softmax exactly
        ka, va = _arena(np_rng, 6)
        pt = jnp.asarray(np_rng.randint(0, 6, (3, 4)), jnp.int32)
        pt = pt.at[0, 2:].set(6).at[2, :].set(6)
        q = jnp.asarray(np_rng.standard_normal((3, 1, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([5, 9, 21], jnp.int32)
        active = jnp.asarray([True, True, False])
        assert_kernel_matches_oracle(q, ka, va, pt, pos0, active,
                                     page_size=PAGE, max_len=12)

    def test_mha_no_grouping(self, np_rng):
        # H == Hkv (group size 1): the grouped path degenerates to MHA
        ka, va = _arena(np_rng, 6)
        pt = jnp.asarray(np_rng.randint(0, 6, (2, 3)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((2, 2, HKV, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([1, 6], jnp.int32)
        active = jnp.ones((2,), bool)
        assert_kernel_matches_oracle(q, ka, va, pt, pos0, active,
                                     page_size=PAGE, max_len=10)

    def test_max_len_not_page_multiple(self, np_rng):
        # the static slice cuts mid-page: the walk's last block is
        # partially exposed
        ka, va = _arena(np_rng, 7)
        pt = jnp.asarray(np_rng.randint(0, 7, (3, 3)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((3, 1, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([0, 5, 9], jnp.int32)
        active = jnp.ones((3,), bool)
        assert_kernel_matches_oracle(q, ka, va, pt, pos0, active,
                                     page_size=PAGE, max_len=10)

    @pytest.mark.slow
    def test_ragged_shape_sweep(self, np_rng):
        # randomized sweep over (rows, TQ, pages-per-slot, max_len,
        # positions): the wide net behind the targeted cases above.
        # 6 trials: every trial is a fresh compile (distinct shapes),
        # so the count is a direct tier-1 budget lever — the targeted
        # cases above carry the known-tricky geometries
        for trial in range(6):
            num_pages = int(np_rng.randint(4, 14))
            mp = int(np_rng.randint(2, 6))
            r = int(np_rng.randint(1, 7))
            tq = int(np_rng.randint(1, 6))
            max_len = int(np_rng.randint(tq, mp * PAGE + 1))
            ka, va = _arena(np_rng, num_pages)
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
                q, ka, va, pt, pos0, active, page_size=PAGE,
                max_len=max_len)


class TestDispatchAndIntegration:
    def test_jit_is_the_contract(self, np_rng):
        """Pin WHY the suite compares under jit: the eager oracle and
        the jitted oracle differ by ulps (XLA fusion), while the
        kernel matches the jitted oracle exactly. If this ever starts
        failing because eager == jit, the comment in the module header
        is stale — not a bug."""
        ka, va = _arena(np_rng, 9)
        pt = jnp.asarray(np_rng.randint(0, 9, (5, 4)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((5, 1, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([0, 3, 7, 13, 5], jnp.int32)
        active = jnp.ones((5,), bool)
        kw = dict(page_size=PAGE, max_len=14)
        ref_j = _jit(RPA.ragged_reference, **kw)(q, ka, va, pt, pos0,
                                                 active)
        ker_e = RPA.ragged_pallas(q, ka, va, pt, pos0, active, **kw)
        np.testing.assert_array_equal(np.asarray(ref_j),
                                      np.asarray(ker_e))

    @pytest.mark.parametrize("on_tpu", [False, True])
    def test_auto_dispatch_is_jnp_everywhere(self, np_rng, monkeypatch,
                                             on_tpu):
        # the kernel is deselected statically: even where the backend
        # says TPU, impl=None must not reach pallas_call
        from paddle_tpu.ops import pallas_util

        monkeypatch.setattr(pallas_util, "on_tpu", lambda: on_tpu)
        monkeypatch.setattr(
            RPA, "ragged_pallas",
            lambda *a, **k: pytest.fail("auto dispatch took the kernel"))
        ka, va = _arena(np_rng, 6)
        pt = jnp.asarray(np_rng.randint(0, 6, (2, 3)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((2, 1, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([2, 7], jnp.int32)
        active = jnp.ones((2,), bool)
        kw = dict(page_size=PAGE, max_len=9)
        auto = RPA.ragged_attention(q, ka, va, pt, pos0, active, **kw)
        ref = RPA.ragged_reference(q, ka, va, pt, pos0, active, **kw)
        # auto must be the EAGER jnp path, byte-for-byte
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))
        with pytest.raises(ValueError, match="impl must be"):
            RPA.ragged_attention(q, ka, va, pt, pos0, active,
                                 impl="fused", **kw)

    def test_int8_arena_dispatches_through_kernel(self, np_rng):
        """int8 `(s8, scale)` pair arenas no longer exclude the kernel:
        forced pallas runs the dequant-fused walk and must match the
        jnp dequant-gather oracle bit-for-bit (the deep parity zoo
        lives in tests/test_ragged_int8.py; this pins the DISPATCH
        contract flip from the pre-fusion fallback behaviour)."""
        ka, va = _arena(np_rng, 6)
        ka8 = PA.kv_quantize(ka)
        va8 = PA.kv_quantize(va)
        pt = jnp.asarray(np_rng.randint(0, 6, (2, 3)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((2, 1, 4, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([2, 7], jnp.int32)
        active = jnp.ones((2,), bool)
        kw = dict(page_size=PAGE, max_len=9)
        forced = _jit(RPA.ragged_attention, impl="pallas", **kw)(
            q, ka8, va8, pt, pos0, active)
        ref = _jit(RPA.ragged_reference, **kw)(q, ka8, va8, pt, pos0,
                                               active)
        np.testing.assert_array_equal(np.asarray(forced),
                                      np.asarray(ref))

    def test_verify_tq1_is_decode(self, np_rng):
        """paged_verify_attention with a one-token window must be
        paged_decode_attention, bit-for-bit — the spec path's K=0
        degenerate IS a plain decode step."""
        ka, va = _arena(np_rng, 9)
        pt = jnp.asarray(np_rng.randint(0, 9, (4, 4)), jnp.int32)
        q = jnp.asarray(np_rng.standard_normal((4, 1, 4, DH)),
                        jnp.float32)
        k = jnp.asarray(np_rng.standard_normal((4, 1, HKV, DH)),
                        jnp.float32)
        v = jnp.asarray(np_rng.standard_normal((4, 1, HKV, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([0, 5, 9, 30], jnp.int32)
        active = jnp.asarray([True, True, True, False])
        kw = dict(page_size=PAGE, max_len=14)
        out_d, ka_d, va_d = _jit(PA.paged_decode_attention, **kw)(
            q, k, v, ka, va, pt, pos0, active)
        out_v, ka_v, va_v = _jit(PA.paged_verify_attention, **kw)(
            q, k, v, ka, va, pt, pos0, active)
        for a, b in ((out_d, out_v), (ka_d, ka_v), (va_d, va_v)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_verify_window_matches_sequential_decode(self, np_rng):
        """A TQ=3 verify window must equal three sequential decode
        steps' attention reads: same writes, same causal exposure —
        the property that makes verify-in-one-launch sound. Page
        tables are DISJOINT across rows — the pool invariant (slots
        never share a writable page; shared prefix pages are read-only
        because decode writes land beyond them) that makes the
        one-launch write sound."""
        ka, va = _arena(np_rng, 9)
        pt = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
        tq = 3
        q = jnp.asarray(np_rng.standard_normal((2, tq, 4, DH)),
                        jnp.float32)
        k = jnp.asarray(np_rng.standard_normal((2, tq, HKV, DH)),
                        jnp.float32)
        v = jnp.asarray(np_rng.standard_normal((2, tq, HKV, DH)),
                        jnp.float32)
        pos0 = jnp.asarray([2, PAGE - 1], jnp.int32)
        active = jnp.ones((2,), bool)
        kw = dict(page_size=PAGE, max_len=14)
        out_v, ka_v, va_v = _jit(PA.paged_verify_attention, **kw)(
            q, k, v, ka, va, pt, pos0, active)
        ka_s, va_s = ka, va
        outs = []
        step = _jit(PA.paged_decode_attention, **kw)
        for i in range(tq):
            o, ka_s, va_s = step(q[:, i:i + 1], k[:, i:i + 1],
                                 v[:, i:i + 1], ka_s, va_s, pt,
                                 pos0 + i, active)
            outs.append(o)
        np.testing.assert_array_equal(np.asarray(ka_v),
                                      np.asarray(ka_s))
        np.testing.assert_array_equal(np.asarray(va_v),
                                      np.asarray(va_s))
        np.testing.assert_array_equal(
            np.asarray(out_v), np.asarray(jnp.concatenate(outs, 1)))

    def test_chunk_attention_unchanged_through_dispatch(self, np_rng):
        """paged_chunk_attention now routes its read through the
        ragged dispatcher — on CPU that must still be the identical
        jnp gather (the engine's golden transcripts depend on it)."""
        ka, va = _arena(np_rng, 9)
        row = jnp.asarray(np_rng.randint(0, 9, (4,)), jnp.int32)
        c = 5
        q = jnp.asarray(np_rng.standard_normal((1, c, 4, DH)),
                        jnp.float32)
        k = jnp.asarray(np_rng.standard_normal((1, c, HKV, DH)),
                        jnp.float32)
        v = jnp.asarray(np_rng.standard_normal((1, c, HKV, DH)),
                        jnp.float32)
        kw = dict(page_size=PAGE, max_len=14)
        out, ka2, va2 = _jit(PA.paged_chunk_attention, **kw)(
            q, k, v, ka, va, row, jnp.int32(3))
        ap = 3 + jnp.arange(c, dtype=jnp.int32)
        pg, off = PA.page_addresses(row, ap, page_size=PAGE)
        ka_ref = PA.write_kv(ka, k[0], pg, off)
        va_ref = PA.write_kv(va, v[0], pg, off)
        k_read = PA.gather_kv(ka_ref, row[None], 14, q.dtype)
        v_read = PA.gather_kv(va_ref, row[None], 14, q.dtype)
        valid = jnp.arange(14, dtype=jnp.int32)[None, :] <= ap[:, None]
        ref = jax.jit(PA.grouped_masked_attention)(
            q, k_read, v_read, valid[None, None])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6)
