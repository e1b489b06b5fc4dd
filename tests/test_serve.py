"""Compiled inference artifacts + C ABI (reference: paddle/capi,
merge_model single-file deployment)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import models, nn
from paddle_tpu.nn.module import ShapeSpec
from paddle_tpu.serve import (export_compiled_model, load_compiled_model)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _export_mlp(path, batch=4, din=16, dout=3):
    model = nn.Sequential([nn.Dense(8, activation="relu"), nn.Dense(dout)])
    params, mstate = model.init(jax.random.key(0), ShapeSpec((batch, din)))

    def forward(x):
        out, _ = model.apply(params, mstate, x, training=False)
        return out

    x = np.random.RandomState(0).rand(batch, din).astype(np.float32)
    export_compiled_model(forward, [x], path, name="mlp")
    return forward, x


def test_artifact_roundtrip(tmp_path):
    path = str(tmp_path / "mlp.ptc")
    forward, x = _export_mlp(path)
    m = load_compiled_model(path)
    assert m.meta["name"] == "mlp"
    assert m.input_signature[0]["shape"] == [4, 16]
    got = m.predict(x)
    want = forward(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


class TestDecoderArtifact:
    """The decode LOOP (prefill + scan) as a serving artifact — the
    reference's SequenceGenerator serving surface (api/PaddleAPI.h:1025)
    compiled to one weights-folded program."""

    def _cfg(self):
        from paddle_tpu.models import transformer as T
        return T.TransformerConfig(vocab=32, dim=16, n_layers=2,
                                   n_heads=2, mlp_ratio=2,
                                   attn_impl="dense")

    def test_greedy_roundtrip_matches_generate(self, tmp_path):
        from paddle_tpu.models import transformer as T
        from paddle_tpu.serve import export_decoder
        cfg = self._cfg()
        params = T.init_params(jax.random.key(0), cfg)
        path = str(tmp_path / "dec.ptc")
        export_decoder(params, cfg, path, batch=2, prompt_len=5, steps=4)
        m = load_compiled_model(path)
        assert m.meta["kind"] == "decoder"
        prompt = np.random.RandomState(0).randint(
            1, 32, (2, 5)).astype(np.int32)
        got = np.asarray(m.predict(prompt))
        want = np.asarray(T.generate(params, cfg, jnp.asarray(prompt),
                                     steps=4))
        np.testing.assert_array_equal(got, want)

    def test_varlen_sampled_roundtrip(self, tmp_path):
        from paddle_tpu.models import transformer as T
        from paddle_tpu.serve import export_decoder
        cfg = self._cfg()
        params = T.init_params(jax.random.key(1), cfg)
        path = str(tmp_path / "dec.ptc")
        export_decoder(params, cfg, path, batch=2, prompt_len=6, steps=3,
                       variable_lengths=True, temperature=0.8, top_k=8)
        m = load_compiled_model(path)
        assert m.meta["sampled"] and m.meta["variable_lengths"]
        prompt = np.zeros((2, 6), np.int32)
        prompt[0] = np.random.RandomState(1).randint(1, 32, 6)
        prompt[1, :4] = np.random.RandomState(2).randint(1, 32, 4)
        lens = np.asarray([6, 4], np.int32)
        seed = np.asarray(
            jax.random.key_data(jax.random.key(7)), np.uint32)
        got = np.asarray(m.predict(prompt, lens, seed))
        want = np.asarray(T.sample(
            params, cfg, jnp.asarray(prompt), steps=3,
            rng=jax.random.key(7), temperature=0.8, top_k=8,
            prompt_lens=jnp.asarray(lens)))
        np.testing.assert_array_equal(got, want)

    def test_decoder_artifact_needs_no_model_code(self, tmp_path):
        """The decode loop must run from the artifact alone in a fresh
        process that never imports the transformer."""
        from paddle_tpu.models import transformer as T
        from paddle_tpu.serve import export_decoder
        cfg = self._cfg()
        params = T.init_params(jax.random.key(2), cfg)
        path = str(tmp_path / "dec.ptc")
        export_decoder(params, cfg, path, batch=1, prompt_len=4, steps=3)
        code = f"""
import sys
sys.path.insert(0, {REPO!r})
import numpy as np
from paddle_tpu.serve.artifact import load_compiled_model
m = load_compiled_model({path!r})
out = m.predict(np.ones((1, 4), np.int32))
assert np.asarray(out).shape == (1, 7), out.shape
print("ok")
"""
        r = subprocess.run([sys.executable, "-c", code],
                           env={**os.environ, "JAX_PLATFORMS": "cpu"},
                           capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "ok" in r.stdout


class TestInt8Quant:
    """Weight-only int8 serving: per-channel quantization accuracy,
    in-jit dequant decode parity, and the shrunk decoder artifact."""

    def test_roundtrip_error_small(self):
        from paddle_tpu.serve import quant
        w = np.random.RandomState(0).randn(64, 32).astype(np.float32)
        # per-channel scales must survive wildly different column norms
        w[:, 0] *= 100.0
        qt = quant.quantize_tensor(jnp.asarray(w))
        assert qt.q.dtype == jnp.int8 and qt.scale.shape == (32,)
        d = np.asarray(quant.dequantize_tensor(qt))
        rel = np.linalg.norm(d - w) / np.linalg.norm(w)
        assert rel < 0.01, rel
        # int8 range actually used (not crushed to a few levels)
        assert int(jnp.max(jnp.abs(qt.q))) == 127

    def test_vectors_ints_and_unmatched_pass_through(self):
        from paddle_tpu.serve import quant
        tree = {"proj": {"kernel": jnp.ones((4, 4)),
                         "bias": jnp.ones((4,))},
                "embed": {"table": jnp.ones((8, 4))},
                "ids": jnp.arange(6, dtype=jnp.int32)}
        qt = quant.quantize_params(tree)  # DEFAULT_MATCH
        assert isinstance(qt["proj"]["kernel"], quant.QuantizedTensor)
        assert qt["proj"]["bias"].shape == (4,)       # vector: untouched
        assert not isinstance(qt["embed"]["table"],
                              quant.QuantizedTensor)  # excluded by match
        assert jnp.issubdtype(qt["ids"].dtype, jnp.integer)
        back = quant.dequantize_params(qt)
        np.testing.assert_allclose(np.asarray(back["proj"]["kernel"]),
                                   np.ones((4, 4)), atol=0.02)

    def test_per_expert_scales_on_stacked_kernels(self):
        from paddle_tpu.serve import quant
        # one expert 100x larger must not crush the others' resolution
        w = np.random.RandomState(0).randn(4, 16, 8).astype(np.float32)
        w[3] *= 100.0
        qt = quant.quantize_tensor(jnp.asarray(w))
        assert qt.scale.shape == (4, 8)  # per expert, per out channel
        d = np.asarray(quant.dequantize_tensor(qt))
        for e in range(4):
            rel = np.linalg.norm(d[e] - w[e]) / np.linalg.norm(w[e])
            assert rel < 0.01, (e, rel)

    def test_quantized_decode_close_to_full_precision(self):
        from paddle_tpu.models import transformer as T
        from paddle_tpu.serve import quant
        cfg = T.TransformerConfig(vocab=32, dim=32, n_layers=2,
                                  n_heads=4, mlp_ratio=2,
                                  attn_impl="dense")
        params = T.init_params(jax.random.key(0), cfg)
        qp = quant.quantize_params(params)  # DEFAULT_MATCH
        assert quant.quantization_error(params, qp) < 0.02
        toks = jnp.asarray(
            np.random.RandomState(0).randint(1, 32, (2, 8)), jnp.int32)
        full = np.asarray(T.apply(params, cfg, toks))
        q = np.asarray(T.apply(quant.dequantize_params(qp), cfg, toks))
        # logits track closely; argmax agrees on a large majority
        agree = (full.argmax(-1) == q.argmax(-1)).mean()
        assert agree >= 0.8, agree

    def test_int8_decoder_artifact_shrinks_and_runs(self, tmp_path):
        from paddle_tpu.models import transformer as T
        from paddle_tpu.serve import export_decoder
        cfg = T.TransformerConfig(vocab=64, dim=64, n_layers=2,
                                  n_heads=4, mlp_ratio=4,
                                  attn_impl="dense")
        params = T.init_params(jax.random.key(1), cfg)
        p32 = str(tmp_path / "dec32.ptc")
        p8 = str(tmp_path / "dec8.ptc")
        export_decoder(params, cfg, p32, batch=1, prompt_len=4, steps=3)
        export_decoder(params, cfg, p8, batch=1, prompt_len=4, steps=3,
                       int8_weights=True)
        # matmul weights dominate this model: int8 must cut the
        # artifact to well under half the f32 size
        assert os.path.getsize(p8) < 0.5 * os.path.getsize(p32), (
            os.path.getsize(p8), os.path.getsize(p32))
        m = load_compiled_model(p8)
        assert m.meta["int8_weights"] is True
        out = np.asarray(m.predict(np.ones((1, 4), np.int32)))
        assert out.shape == (1, 7)
        assert (out >= 0).all() and (out < 64).all()


def test_artifact_input_validation(tmp_path):
    path = str(tmp_path / "mlp.ptc")
    _export_mlp(path)
    m = load_compiled_model(path)
    with pytest.raises(ValueError, match="takes 1 inputs"):
        m.predict(np.zeros((4, 16), np.float32), np.zeros(3))
    with pytest.raises(ValueError, match="input shape"):
        m.predict(np.zeros((2, 16), np.float32))


def test_artifact_needs_no_model_code(tmp_path):
    """Loading runs in a fresh process that never builds the model."""
    path = str(tmp_path / "mlp.ptc")
    _, x = _export_mlp(path)
    code = f"""
import numpy as np
from paddle_tpu.serve import load_compiled_model
m = load_compiled_model({path!r})
x = np.random.RandomState(0).rand(4, 16).astype(np.float32)
out = np.asarray(m.predict(x))
assert out.shape == (4, 3), out.shape
assert np.isfinite(out).all()
print("STANDALONE_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert "STANDALONE_OK" in r.stdout, r.stderr[-2000:]


def test_capi_end_to_end(tmp_path):
    """Real C program drives the embedded-interpreter inference ABI."""
    from paddle_tpu.native.build import ensure_capi_built

    capi = ensure_capi_built()
    artifact = str(tmp_path / "mlp.ptc")
    forward, x = _export_mlp(artifact)
    want = np.asarray(forward(np.full((4, 16), 0.5, np.float32)))

    driver_src = os.path.join(REPO, "tests", "capi_driver.c")
    driver = str(tmp_path / "capi_driver")
    subprocess.run(["gcc", "-O1", "-o", driver, driver_src, "-ldl", "-lm"],
                   check=True, capture_output=True, text=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PADDLE_TPU_PLATFORM="cpu",
               PYTHONPATH=REPO)
    r = subprocess.run(
        [driver, capi, REPO, artifact, str(4 * 16), str(4 * 3)],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
    assert "CAPI_OK" in r.stdout
    out0 = float([l for l in r.stdout.splitlines()
                  if l.startswith("OUT0")][0].split()[1])
    assert out0 == pytest.approx(float(want[0, 0]), rel=1e-4)


def test_artifact_carries_raw_mlir(tmp_path):
    """The artifact now embeds program.mlir — the raw StableHLO text the
    Python-free PJRT-C server (native/src/pjrt_serve.cc) compiles via
    PJRT_Client_Compile(format="mlir")."""
    import tarfile

    from paddle_tpu.serve.artifact import extract_mlir

    path = str(tmp_path / "mlp.ptc")
    _export_mlp(path)
    with tarfile.open(path) as tar:
        names = tar.getnames()
    assert "program.mlir" in names
    mlir_path = str(tmp_path / "program.mlir")
    meta = extract_mlir(path, mlir_path)
    text = open(mlir_path, "rb").read()
    assert b"stablehlo" in text and b"func.func public @main" in text
    assert meta["name"] == "mlp"


@pytest.mark.slow


def test_pjrt_serve_library_builds():
    """The PJRT-C serving library must compile and expose its ABI.
    (Running it needs a PJRT plugin device — covered by the gated test
    below on TPU hosts.)"""
    import ctypes

    pytest.importorskip(
        "tensorflow", reason="pjrt_c_api.h ships in the tensorflow wheel")

    from paddle_tpu.native.build import ensure_pjrt_built

    lib = ctypes.CDLL(ensure_pjrt_built())
    for sym in ("pts_load", "pts_forward", "pts_free", "pts_last_error"):
        assert hasattr(lib, sym)


@pytest.mark.skipif(
    os.environ.get("PADDLE_TPU_RUN_PJRT_TEST") != "1",
    reason="needs a live PJRT plugin device; set "
           "PADDLE_TPU_RUN_PJRT_TEST=1 on a TPU host (one process per "
           "chip: nothing else may hold it)")
def test_pjrt_serve_end_to_end(tmp_path):
    """Full Python-free TPU serving: export artifact, extract raw
    StableHLO, compile+run it through libtpu's PJRT C API from C."""
    import ctypes

    from paddle_tpu.native.build import ensure_pjrt_built
    from paddle_tpu.serve.artifact import extract_mlir

    path = str(tmp_path / "mlp.ptc")
    forward, x = _export_mlp(path)
    want = np.asarray(forward(x))
    mlir_path = str(tmp_path / "program.mlir")
    extract_mlir(path, mlir_path)

    import libtpu

    plugin = os.path.join(os.path.dirname(libtpu.__file__), "libtpu.so")
    lib = ctypes.CDLL(ensure_pjrt_built())
    lib.pts_load.restype = ctypes.c_void_p
    lib.pts_load.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.pts_last_error.restype = ctypes.c_char_p
    h = lib.pts_load(plugin.encode(), mlir_path.encode())
    assert h, lib.pts_last_error().decode()
    dims = (ctypes.c_int64 * 2)(*x.shape)
    out = np.zeros(want.shape, np.float32)
    rc = lib.pts_forward(
        ctypes.c_void_p(h), x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        dims, 2, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size)
    assert rc == 0, lib.pts_last_error().decode()
    np.testing.assert_allclose(out, want, rtol=1e-3, atol=1e-3)
    lib.pts_free(ctypes.c_void_p(h))
