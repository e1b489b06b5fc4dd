"""Chip-independent compiled-cost counts.

These tests assert *compiled-program* properties — residual-set bytes,
while-loop state dtypes, scan-body FLOP scaling — on the CPU backend:
counts computed from shapes, which a CPU run can give. They say what a
change moves and by how many bytes; whether that shows as time is a
chip question (ROADMAP A1), and none of these is a device metric.

Three claims:

  (a) ResNet remat shrinks the fwd->bwd residual set (the HBM-resident
      activations the backward reads) — measured abstractly via
      eval_shape of the vjp closure, which is
      exact at any batch size without materializing anything.
  (b) The int8 decode loop STREAMS s8 weights: the compiled while
      state carries s8 tensors (dequant traced inside the body, pinned
      by a loop-varying optimization_barrier). The negative control —
      dequant outside generate() — shows XLA hoisting the convert,
      which is exactly the failure docs/PARITY.md asked about.
  (c) Sliding-window attention cost scales with the window, not T^2:
      the backward's scan-body FLOPs are CONSTANT as T doubles (trip
      count is linear in T => linear total), where the full-attention
      backward's body FLOPs are linear in T (=> quadratic total).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import losses


def _residual_bytes(model, mstate, params, rng, x_shape):
    """Bytes of the fwd->bwd residual pytree — jax.vjp's returned
    closure IS a pytree of the saved tensors, and eval_shape walks it
    abstractly, so this is exact at any batch size at zero cost."""
    x = jax.ShapeDtypeStruct(x_shape, jnp.float32)
    y = jax.ShapeDtypeStruct((x_shape[0],), jnp.int32)

    def loss_fn(p, x, y):
        logits, _ = model.apply(p, mstate, x, training=True, rng=rng)
        return jnp.mean(losses.softmax_cross_entropy(logits, y))

    vjp_shape = jax.eval_shape(
        lambda p, x, y: jax.vjp(loss_fn, p, x, y)[1], params, x, y)
    return sum(l.size * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(vjp_shape))


class TestRematResiduals:
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_remat_shrinks_residual_set(self):
        """Measured AT the headline bench config (bs 256, 224px —
        eval_shape makes the big shape free): 42.16 GiB of residuals
        baseline -> 18.69 (conv_out, -56%) -> 8.86 (full, -79%). Small
        batches would dilute the ratio with the batch-independent
        parameter residuals, so the assertion runs at the real shape."""
        from paddle_tpu import models
        from paddle_tpu.nn.module import ShapeSpec

        rng = jax.random.key(0)
        sizes = {}
        for remat in (None, "conv_out", "full"):
            model = models.resnet.resnet(50, num_classes=1000,
                                         remat=remat)
            params, mstate = model.init(rng, ShapeSpec((2, 224, 224, 3)))
            sizes[remat] = _residual_bytes(model, mstate, params, rng,
                                           (256, 224, 224, 3))
        assert sizes[None] > 40 * 2**30, sizes   # the roofline's scale
        assert sizes["conv_out"] < 0.5 * sizes[None], sizes
        assert sizes["full"] < 0.25 * sizes[None], sizes

    def test_remat_survives_lowering(self):
        """The recompute must reach XLA: jax.checkpoint lowers its saved
        residuals through optimization_barrier ops, so their presence in
        the StableHLO is the signature that the remat was not traced
        away before the compiler ever saw it."""
        from paddle_tpu import models
        from paddle_tpu.nn.module import ShapeSpec

        rng = jax.random.key(0)

        def lowered_text(remat):
            model = models.resnet.resnet(18, num_classes=10, remat=remat)
            params, mstate = model.init(rng, ShapeSpec((2, 64, 64, 3)))

            def loss_fn(p, x, y):
                logits, _ = model.apply(p, mstate, x, training=True,
                                        rng=rng)
                return jnp.mean(losses.softmax_cross_entropy(logits, y))

            x = jnp.zeros((2, 64, 64, 3), jnp.float32)
            y = jnp.zeros((2,), jnp.int32)
            return jax.jit(jax.grad(loss_fn)).lower(params, x, y).as_text()

        assert "optimization_barrier" not in lowered_text(None)
        assert lowered_text("full").count("optimization_barrier") >= 8


def _while_lines(compiled_text):
    return [l for l in compiled_text.splitlines() if " while(" in l]


class TestInt8DecodeLoop:
    @pytest.fixture(scope="class")
    def setup(self):
        from paddle_tpu.models import transformer as T
        from paddle_tpu.serve import quant

        cfg = T.TransformerConfig(vocab=128, dim=64, n_layers=2,
                                  n_heads=2, attn_impl="dense")
        params = T.init_params(jax.random.key(0), cfg)
        qp = quant.quantize_params(params)
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(0, 128, (1, 8)), jnp.int32)
        return T, quant, cfg, params, qp, prompt

    def test_decode_loop_carries_s8(self, setup):
        """The PARITY.md hoisting question, answered in the affirmative
        direction: pass qparams to generate() and the compiled decode
        while-loop's carried state includes the s8 weights — each step
        streams 1/4 the weight bytes of a hoisted-f32 loop."""
        T, quant, cfg, params, qp, prompt = setup
        txt = jax.jit(
            lambda qp, p: T.generate(qp, cfg, p, steps=4)
        ).lower(qp, prompt).compile().as_text()
        wl = _while_lines(txt)
        assert wl, "decode did not compile to a while loop"
        assert any("s8[" in l for l in wl), (
            "int8 decode loop state carries no s8 tensors — the dequant "
            "was hoisted and the loop streams full-precision weights")

    def test_hoisted_control_has_no_s8_loop(self, setup):
        """Negative control: dequantizing OUTSIDE generate() leaves the
        f32 weights as loop invariants (this was the only int8 path
        before r5) — documents why the in-loop placement matters."""
        T, quant, cfg, params, qp, prompt = setup
        txt = jax.jit(
            lambda qp, p: T.generate(quant.dequantize_params(qp), cfg, p,
                                     steps=4)
        ).lower(qp, prompt).compile().as_text()
        wl = _while_lines(txt)
        assert wl and not any("s8[" in l for l in wl)

    def test_streaming_matches_hoisted_tokens(self, setup):
        """Placement must not change math: in-loop dequant decodes the
        exact same tokens as the hoisted path."""
        T, quant, cfg, params, qp, prompt = setup
        a = T.generate(qp, cfg, prompt, steps=6)
        b = T.generate(quant.dequantize_params(qp), cfg, prompt, steps=6)
        assert jnp.array_equal(a, b)


class TestRollingSWACache:
    def test_decode_loop_state_is_window_sized(self):
        """Sliding-window decode must CARRY a window-slot ring cache,
        not a full-length masked buffer — the full buffer would stream
        O(total) cache bytes every step (the einsum reads the whole
        buffer; masking happens after). total=80 and window=8 are
        chosen to be unambiguous in the HLO shape strings."""
        from paddle_tpu.models import transformer as T

        cfg = T.TransformerConfig(vocab=48, dim=16, n_layers=2,
                                  n_heads=2, attn_impl="dense",
                                  attn_window=8)
        params = T.init_params(jax.random.key(0), cfg)
        prompt = jnp.zeros((1, 16), jnp.int32)  # + 64 steps = total 80
        txt = jax.jit(
            lambda p, toks: T.generate(p, cfg, toks, steps=64)
        ).lower(params, prompt).compile().as_text()
        wl = _while_lines(txt)
        assert wl, "decode did not compile to a while loop"
        assert any("[1,8," in l for l in wl), (
            "no window-sized (8-slot) cache in the decode loop state")
        assert not any("[1,80," in l for l in wl), (
            "decode loop still carries a full-length (80-slot) buffer")

    @pytest.mark.slow

    def test_rolling_matches_full_buffer_band_mask(self):
        """The ring layout must not change math: same tokens as the
        band-masked full buffer, which still serves beam_decode (its
        greedy-equality is tested in test_transformer, but assert the
        cross-impl equality here where the ring is the subject)."""
        from paddle_tpu.models import transformer as T

        cfg = T.TransformerConfig(vocab=32, dim=16, n_layers=2,
                                  n_heads=2, mlp_ratio=2,
                                  attn_impl="dense", attn_window=4)
        params = T.init_params(jax.random.key(1), cfg)
        prompt = jnp.asarray(
            np.random.RandomState(1).randint(1, 32, (2, 6)), jnp.int32)
        rolled = T.generate(params, cfg, prompt, steps=7)   # ring path
        seqs, _ = T.beam_decode(params, cfg, prompt, steps=7,
                                beam_size=1)                # full buffer
        np.testing.assert_array_equal(np.asarray(seqs[:, 0]),
                                      np.asarray(rolled))


class TestSWAFlopScaling:
    @staticmethod
    def _bwd_computed_steps(T, window, block=128):
        """Grid steps of one head that compute in each backward kernel:
        the (q block, k block) pairs the kernels' one predicate admits
        (the rest are skipped and, their `index_map`s clamped, fetch
        nothing). Each costs the same five-plus matmuls of a block, so
        the count is the scaling law of the backward's work. Until PR
        32 this read the FLOPs of the `jnp` scan's body from XLA's cost
        analysis, which cannot see inside a Mosaic call."""
        from paddle_tpu.ops import flash_attention as FA

        n = T // block
        qi, j = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
        needed = FA._block_needed(qi, j, T, block_q=block, block_k=block,
                                  causal=True, window=window)
        # the ranges the index maps clamp to admit the same steps
        for i in (0, n // 2, n - 1):
            first, last = FA._needed_k_blocks(
                i, T, block_q=block, block_k=block, causal=True,
                window=window)
            assert int(needed[i].sum()) == int(last) - int(first) + 1
        return int(needed.sum())

    def test_swa_backward_linear_in_t(self):
        """Counted: full causal backward 528 -> 2080 computed steps a
        kernel as T doubles 4096 -> 8192 (ratio 3.94: quadratic);
        windowed (w=256) 93 -> 189 (ratio 2.03: linear, and 11x fewer
        at T=8192)."""
        full = [self._bwd_computed_steps(t, None) for t in (4096, 8192)]
        sw = [self._bwd_computed_steps(t, 256) for t in (4096, 8192)]
        assert full == [528, 2080] and sw == [93, 189]
        assert full[1] / full[0] > 3.5, full     # quadratic in T
        assert sw[1] / sw[0] < 2.2, sw           # linear in T
        assert sw[1] < full[1] / 4, (sw, full)   # and much cheaper

    @staticmethod
    def _fwd_kinds(T, window):
        """(interior, cut, diagonal, skipped) grid steps of one head in
        the forward, on the blocks it takes from the shape (1024 x 1024 at
        these lengths), from the forward's own classifier."""
        from paddle_tpu.ops import flash_attention as FA

        bq, bk = FA._forward_blocks(T, T, 128, jnp.bfloat16)
        assert (bq, bk) == (1024, 1024)
        return FA._block_kinds(T // bq, T // bk, T, block_q=bq, block_k=bk,
                               causal=True, window=window)

    def test_swa_forward_linear_in_t(self):
        """Counted from `_block_kinds`: the full causal forward computes
        10 -> 36 steps a head as T doubles 4096 -> 8192 (6 -> 28 of them
        unmasked); under a window of 256 every computed step is cut by
        the band or the diagonal, 7 -> 15 (linear), and the rest fetch
        nothing."""
        assert [self._fwd_kinds(t, None) for t in (4096, 8192)] == [
            (6, 4, 0, 6), (28, 8, 0, 28)]
        assert [self._fwd_kinds(t, 256) for t in (4096, 8192)] == [
            (0, 7, 0, 9), (0, 15, 0, 49)]
        # a band of two blocks and a half leaves whole blocks unmasked
        assert self._fwd_kinds(8192, 2560) == (7, 19, 0, 38)


class TestFusedCEResiduals:
    """Claim (d), r5: fused chunked cross-entropy removes the [N, vocab]
    logits tensor from the fwd->bwd residual set of the flagship LM.

    Counted at batch 4 x 8192 tokens, dim 512, 8 layers, vocab 32000
    (flash attention + per-block remat): 4.81 GiB of residuals plain
    -> 0.91 GiB fused (-81%); the f32 logits (4*8191*32000*4 B = 4.19
    GiB) were 87% of the set. Since PR 38 a checkpointed block also
    keeps the flash kernel's output and log-sum-exp (0.51 GiB here, in
    both sets: 8 layers x 4 x 8191 positions x (512 + 8) float32); the
    shares are of the set without them. eval_shape makes the big shape
    free on CPU."""

    def test_fused_ce_drops_logits_residual(self):
        import dataclasses

        from paddle_tpu.models import transformer as T

        cfg = T.TransformerConfig(vocab=32000, dim=512, n_layers=8,
                                  n_heads=8, attn_impl="flash",
                                  remat=True)
        params = T.init_params(jax.random.key(0), cfg)
        toks = jax.ShapeDtypeStruct((4, 8192), jnp.int32)

        def residual_bytes(c):
            vjp_shape = jax.eval_shape(
                lambda p, t: jax.vjp(lambda p: T.loss(p, c, t), p)[1],
                params, toks)
            return sum(l.size * jnp.dtype(l.dtype).itemsize
                       for l in jax.tree.leaves(vjp_shape))

        base = residual_bytes(cfg)
        fused = residual_bytes(
            dataclasses.replace(cfg, fused_ce_chunk=2048))
        logits_bytes = 4 * 8191 * 32000 * 4
        assert base > logits_bytes, (base, logits_bytes)
        # the drop IS the logits tensor: what the fused path stops
        # saving is (to within 10%) exactly the [N, V] f32 logits
        assert base - fused > 0.9 * logits_bytes, (base, fused)
        # what the checkpoints keep by the kernel's names is in both sets
        named = cfg.n_layers * 4 * 8191 * (cfg.dim + cfg.n_heads) * 4
        assert fused - named < 0.35 * (base - named), (fused, base, named)


class TestGQACacheState:
    def test_decode_loop_cache_shrinks_with_kv_heads(self):
        """Claim (e): GQA's win is decode bandwidth — the KV cache the
        while loop CARRIES (and re-reads every step, the decode
        bottleneck) must shrink by n_heads/n_kv_heads, and the compact
        cache must never be expanded back to n_heads inside the loop.
        H=4 heads, head_dim 8, total length 24: the MHA loop state
        carries [1,24,4,8] K/V buffers; with n_kv_heads=1 it must carry
        [1,24,1,8] and no [1,24,4,8] tensor may appear in the loop."""
        import dataclasses

        from paddle_tpu.models import transformer as T

        base = T.TransformerConfig(vocab=48, dim=32, n_layers=1,
                                   n_heads=4, attn_impl="dense")
        prompt = jnp.zeros((1, 8), jnp.int32)  # + 16 steps = total 24

        def while_text(cfg):
            params = T.init_params(jax.random.key(0), cfg)
            txt = jax.jit(
                lambda p, toks: T.generate(p, cfg, toks, steps=16)
            ).lower(params, prompt).compile().as_text()
            wl = _while_lines(txt)
            assert wl, "decode did not compile to a while loop"
            return "\n".join(wl)

        mha = while_text(base)
        gqa = while_text(dataclasses.replace(base, n_kv_heads=1))
        assert "[1,24,4,8]" in mha, mha[:400]
        assert "[1,24,1,8]" in gqa, gqa[:400]
        assert "[1,24,4,8]" not in gqa, (
            "GQA decode loop materializes a full-head cache — the "
            "4x bandwidth win is lost")


class TestInt8KVCacheState:
    def test_decode_loop_carries_s8_kv(self):
        """Claim (f), r5: with kv_cache_dtype="int8" the decode while
        loop's carried state holds the KV cache as s8 (+ small scale
        tensors), and no full-size fp KV buffer remains in the loop —
        the per-step cache read (the bandwidth term that GROWS with
        context) drops to ~half the bf16 bytes at head_dim-64 serving shapes (+1 scale per vector), 4x vs f32. Shapes chosen unambiguous: total=24 slots,
        2 kv-heads, head_dim 16."""
        import dataclasses

        from paddle_tpu.models import transformer as T

        cfg = T.TransformerConfig(vocab=48, dim=32, n_layers=1,
                                  n_heads=2, attn_impl="dense")
        prompt = jnp.zeros((1, 8), jnp.int32)  # + 16 steps = total 24

        def while_text(c):
            params = T.init_params(jax.random.key(0), c)
            txt = jax.jit(
                lambda p, toks: T.generate(p, c, toks, steps=16)
            ).lower(params, prompt).compile().as_text()
            wl = _while_lines(txt)
            assert wl, "decode did not compile to a while loop"
            return "\n".join(wl)

        fp = while_text(cfg)
        q8 = while_text(dataclasses.replace(cfg, kv_cache_dtype="int8"))
        assert "s8[1,24,2,16]" in q8, q8[:500]
        assert "s8[" not in fp
        # the fp-size cache must not ALSO ride the loop (that would be
        # dequant-hoisting — the cache analog of the weights failure)
        for fp_kind in ("f32[1,24,2,16]", "bf16[1,24,2,16]",
                        "f64[1,24,2,16]"):
            assert fp_kind not in q8, fp_kind


class TestEngineCompiledStep:
    def test_int8_pool_step_reads_s8(self):
        """Claim (g): the serving engine's jitted decode step takes the
        int8 pool as s8 arguments and returns s8 — no fp-size cache
        tensor appears anywhere in the compiled step, so per-step pool
        traffic is s8 for the engine exactly as the while-loop state is
        for generate(). The pool is the block-paged ARENA now
        ([num_pages, page_size, Hkv, Dh]): 3 slots x 24 max_len at
        page_size 8 -> 9 pages x 8 x 2 kv-heads x 16."""
        import dataclasses

        from paddle_tpu.models import transformer as T
        from paddle_tpu.serve.engine import DecodeEngine

        cfg = T.TransformerConfig(vocab=48, dim=32, n_layers=1,
                                  n_heads=2, attn_impl="dense",
                                  kv_cache_dtype="int8")
        params = T.init_params(jax.random.key(0), cfg)
        eng = DecodeEngine(params, cfg, slots=3, max_len=24,
                           page_size=8)
        assert eng.num_pages == 9 and eng.page_size == 8
        state = eng.init_state()
        txt = eng._step_jit.lower(state).compile().as_text()
        # the ARENA STATE crosses the step boundary as s8: parameters
        # and the root result carry s8 pool tensors, and no fp-size
        # arena tensor appears in the entry signature (the per-step
        # dequant is a transient inside the gathered attention reads)
        sig = [l for l in txt.splitlines()
               if "ENTRY" in l or "ROOT" in l or " parameter(" in l]
        sig = "\n".join(sig)
        assert "s8[9,8,2,16]" in sig, sig[:500]
        for fp_kind in ("f32[9,8,2,16]", "bf16[9,8,2,16]",
                        "f64[9,8,2,16]"):
            assert fp_kind not in sig, (fp_kind, sig[:500])
