"""AOT serving artifacts + persistent compile cache (ROADMAP item 3).

The fleet cold-start contract, proven at test size:

- an engine served THROUGH the exported artifact bundle is
  bit-identical to the jit path (greedy, including speculative) —
  an artifact may be slower to build, never different;
- a manifest mismatch (bucket shape, jax version) degrades to the
  jit path with `artifact_fallbacks` counted and a flight event,
  never a wrong answer and never a failed boot;
- a corrupt persistent-cache entry is a MISS (recompile), not an
  error;
- a fresh process against a warm cache dir reaches steady-state
  serving with zero RecompileGuard compile events after its warmup
  round and zero cache misses — the restart the cache exists for.

Cold-start times are not measured on the chip (ROADMAP A7); this file
is the correctness side and the counts (hits, misses, loads,
fallbacks). Everything here is CPU-fast and runs IN tier-1; `-m aot`
runs the lane alone.
"""

import json
import os
import subprocess
import sys
import tarfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import compilation_cache
from paddle_tpu.models import transformer as T
from paddle_tpu.obs.flight import FlightRecorder
from paddle_tpu.serve.artifact import (ArtifactMismatchError,
                                       load_engine_artifact,
                                       save_engine_artifact)
from paddle_tpu.serve.engine import DecodeEngine
from paddle_tpu.serve.server import ServingServer

pytestmark = pytest.mark.aot

ROOT = Path(__file__).resolve().parents[1]

CFG = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                          attn_impl="dense")
GEOM = dict(slots=2, max_len=64, page_size=16, num_pages=8)
BUCKETS = (32,)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.key(0), CFG)


def mk_engine(params):
    return DecodeEngine(params, CFG, **GEOM)


@pytest.fixture(scope="module")
def bundle(params, tmp_path_factory):
    """One exported engine bundle shared by the whole module — the
    export itself (trace + serialize, no compile) is the slow part."""
    path = str(tmp_path_factory.mktemp("art") / "engine.tar")
    save_engine_artifact(mk_engine(params), path, buckets=BUCKETS)
    return path


@pytest.fixture(scope="module")
def eng_art(params, bundle):
    """One artifact-adopted engine shared by the parity tests (same
    amortization as test_serve_server's module-scoped engines)."""
    return mk_engine(params)


def _prompts(seed, lens):
    r = np.random.RandomState(seed)
    return [r.randint(0, 61, (l,)).astype(np.int32) for l in lens]


def _serve(srv, prompts, max_new, **submit_kw):
    ids = [srv.submit(p, max_new=max_new, **submit_kw) for p in prompts]
    res = srv.run()
    for rid in ids:
        assert res[rid].outcome == "completed"
    return [res[rid].tokens for rid in ids]


# -- round-trip parity -----------------------------------------------------

def test_roundtrip_greedy_parity(params, bundle, eng_art):
    """Greedy serve through the bound artifact programs is
    bit-identical to the jit path, with the adoption counters
    proving the artifact actually served (loads=1, fallbacks=0 —
    any bound program that failed would have been dropped and
    counted)."""
    srv_jit = ServingServer(mk_engine(params), max_queue=8,
                            buckets=BUCKETS)
    srv_art = ServingServer(eng_art, max_queue=8, buckets=BUCKETS,
                            artifact_path=bundle)
    assert eng_art.artifact_loads == 1
    assert eng_art.artifact_fallbacks == 0
    assert eng_art._artifact is not None

    # 3 < page_size exercises the sub-page path; 20 pads into the 32
    # bucket; two requests overlap in flight across the 2 slots
    prompts = _prompts(seed=1, lens=[3, 20, 9])
    toks_jit = _serve(srv_jit, prompts, max_new=8)
    toks_art = _serve(srv_art, prompts, max_new=8)
    assert toks_jit == toks_art
    assert eng_art.artifact_fallbacks == 0
    c = srv_art.counters()
    assert c["artifact_loads"] == 1
    assert c["artifact_fallbacks"] == 0


@pytest.mark.slow


def test_roundtrip_speculative_parity(params, bundle, eng_art):
    """Speculative serving (draft + one-launch verify via the
    exported spec program) stays greedy-bit-identical to the plain
    jit path on the n-gram proposer's win case: repetitive prompts
    whose drafts actually land."""
    assert "spec" in eng_art._artifact
    srv_jit = ServingServer(mk_engine(params), max_queue=8,
                            buckets=BUCKETS, speculative=True)
    srv_art = ServingServer(eng_art, max_queue=8, buckets=BUCKETS,
                            speculative=True, artifact_path=bundle)
    base = _prompts(seed=2, lens=[6])[0]
    prompts = [np.concatenate([base] * 4)[:l] for l in (20, 24)]
    toks_jit = _serve(srv_jit, prompts, max_new=10)
    toks_art = _serve(srv_art, prompts, max_new=10)
    assert toks_jit == toks_art
    assert eng_art.artifact_fallbacks == 0


# -- manifest-mismatch fallback --------------------------------------------

def _ref_tokens(params, prompt, max_new):
    out = T.generate(params, CFG, jnp.asarray(prompt)[None, :],
                     steps=max_new)
    return [int(t) for t in np.asarray(out[0, len(prompt):])]


def _fallback_events(flight):
    return [e for e in flight.events()
            if e["kind"] == "artifact" and e["name"] == "fallback"]


def test_bucket_mismatch_falls_back_to_jit(params, bundle):
    """A bundle exported for different prefill buckets must NOT be
    adopted: the padded-prefill shapes it contains are wrong for this
    server. Boot succeeds on the jit path with the fallback counted
    and flight-recorded, and the served tokens are still correct."""
    flight = FlightRecorder()
    eng = mk_engine(params)
    srv = ServingServer(eng, max_queue=8, buckets=(16, 32),
                        flight=flight, artifact_path=bundle)
    assert eng.artifact_loads == 0
    assert eng.artifact_fallbacks == 1
    assert eng._artifact is None
    evs = _fallback_events(flight)
    assert len(evs) == 1
    assert evs[0]["member"] == "load"
    assert "bucket" in evs[0]["error"]
    c = srv.counters()
    assert c["artifact_fallbacks"] == 1

    prompt = _prompts(seed=3, lens=[5])[0]
    toks = _serve(srv, [prompt], max_new=6)
    assert toks[0] == _ref_tokens(params, prompt, 6)


def test_jax_version_mismatch_falls_back(params, bundle, tmp_path):
    """A bundle whose manifest names a different jax version is
    refused (ArtifactMismatchError on direct load; counted fallback
    through the server boot path) — versioned artifacts are never
    trusted across the toolchain that produced them."""
    tampered = str(tmp_path / "tampered.tar")
    with tarfile.open(bundle) as tf:
        members = {m.name: tf.extractfile(m).read()
                   for m in tf.getmembers() if m.isfile()}
    man = json.loads(members["manifest.json"])
    man["jax_version"] = "0.0.0-bogus"
    members["manifest.json"] = json.dumps(man).encode()
    with tarfile.open(tampered, "w") as tf:
        for name, data in members.items():
            info = tarfile.TarInfo(name)
            info.size = len(data)
            import io
            tf.addfile(info, io.BytesIO(data))

    eng = mk_engine(params)
    with pytest.raises(ArtifactMismatchError, match="jax_version"):
        load_engine_artifact(eng, tampered, expect_buckets=BUCKETS)

    flight = FlightRecorder()
    ServingServer(eng, max_queue=8, buckets=BUCKETS, flight=flight,
                  artifact_path=tampered)
    assert eng.artifact_loads == 0
    assert eng.artifact_fallbacks == 1
    evs = _fallback_events(flight)
    assert len(evs) == 1
    assert "jax_version" in evs[0]["error"]


# -- persistent compile cache ----------------------------------------------

def test_corrupt_cache_entry_degrades_to_miss(tmp_path):
    """Garbage bytes where a cache entry should be cost ONE recompile
    and produce the right answer — `enable()` pins
    jax_raise_persistent_cache_errors=False so a truncated write from
    a killed process can never take a replica down."""
    try:
        d = compilation_cache.enable(str(tmp_path / "xla"))
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        x = jnp.arange(17.0, dtype=jnp.float32)
        expect = np.asarray(jax.device_get(f(x)))
        entries = [p for p in Path(d).rglob("*") if p.is_file()]
        assert entries, "compile produced no persistent-cache entry"
        for p in entries:
            p.write_bytes(b"\x00garbage\xff" * 7)
        jax.clear_caches()
        compilation_cache.reset_counters()
        got = np.asarray(jax.device_get(f(x)))   # must not raise
        np.testing.assert_array_equal(got, expect)
        c = compilation_cache.counters()
        assert c["hits"] == 0
        assert c["misses"] >= 1
    finally:
        compilation_cache.disable()
        compilation_cache.reset_counters()


def test_restarted_engine_serves_from_a_warm_cache_all_hits(
        params, tmp_path):
    """The restart the cache exists for, inside one process: a first
    engine serves against an empty cache directory (every compile a
    miss, written back); with jax's in-memory caches dropped, a second
    engine of the same geometry serves the same request with cache
    hits only, zero misses, and the same tokens."""
    prompt = _prompts(seed=3, lens=[7])

    def restart_and_serve():
        jax.clear_caches()
        compilation_cache.reset_counters()
        srv = ServingServer(mk_engine(params), max_queue=8,
                            buckets=BUCKETS)
        return _serve(srv, prompt, max_new=3), compilation_cache.counters()

    try:
        compilation_cache.enable(str(tmp_path / "xla"))
        cold, first = restart_and_serve()
        assert first["misses"] > 0 and first["hits"] == 0
        warm, second = restart_and_serve()
        assert second["hits"] > 0 and second["misses"] == 0, second
        assert warm == cold
    finally:
        compilation_cache.disable()
        compilation_cache.reset_counters()


_PLACEMENT_CHILD = """
import json, os
import jax
from jax._src import xla_bridge
from paddle_tpu import compilation_cache

written = []
real_update = jax.config.update
def spy(name, value):
    written.append(name)
    return real_update(name, value)
jax.config.update = spy
path = compilation_cache.enable()
print(json.dumps({
    "path": path, "config": jax.config.jax_compilation_cache_dir,
    "wrote_dir": "jax_compilation_cache_dir" in written,
    "backend_up": xla_bridge.backends_are_initialized()}))
"""


def _run_placement_child(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PLACEMENT_CHILD],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax has already read it:
    enable() writes no directory of its own (only thresholds and
    listeners), and it never initialises the backend — a fleet parent
    calls it and must stay off the chip its children need."""
    d = str(tmp_path / "from-env")
    got = _run_placement_child(d)
    assert got["path"] == d and got["config"] == d
    assert not got["wrote_dir"]
    assert not got["backend_up"]


def test_cache_dir_defaults_into_the_checkout():
    """Unset, the directory is the fixed, gitignored
    <checkout>/.jax_cache (the path is part of jax's cache key, so it
    must not move), again with the backend untouched."""
    got = _run_placement_child(None)
    want = str(ROOT / ".jax_cache")
    assert got["path"] == want and got["config"] == want
    assert got["wrote_dir"]
    assert not got["backend_up"]
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_environment_wins_over_an_explicit_dir(tmp_path, monkeypatch):
    """An explicit directory that disagrees with the environment is
    ignored with a warning, not quietly honoured."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    try:
        with pytest.warns(UserWarning, match="ignoring the requested"):
            d = compilation_cache.enable(str(tmp_path / "explicit"))
        assert d == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "explicit").exists()
    finally:
        compilation_cache.disable()
        compilation_cache.reset_counters()


_WARM_CHILD = """
import json, sys
import jax
import numpy as np
from paddle_tpu import compilation_cache
from paddle_tpu.analysis.guards import RecompileGuard
from paddle_tpu.models import transformer as T
from paddle_tpu.serve.engine import DecodeEngine
from paddle_tpu.serve.server import ServingServer

compilation_cache.enable(sys.argv[1])
cfg = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                          attn_impl="dense")
params = T.init_params(jax.random.key(0), cfg)
eng = DecodeEngine(params, cfg, slots=2, max_len=64, page_size=16,
                   num_pages=8)
srv = ServingServer(eng, max_queue=8, buckets=(32,))
p1, p2 = (np.random.RandomState(s).randint(0, 61, (7,)).astype(np.int32)
          for s in (3, 4))
srv.submit(p1, max_new=3)
srv.run()                       # warmup: every compile happens here
with RecompileGuard(name="warm serve steady state") as g:
    rid = srv.submit(p2, max_new=3)   # fresh prompt, same bucket
    res = srv.run()
print(json.dumps({"guard_compiles": g.compiles,
                  "tokens": list(res[rid].tokens),
                  **compilation_cache.counters()}))
"""


def _run_warm_child(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _WARM_CHILD, cache_dir],
                         capture_output=True, text=True, timeout=240,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines()
            if l.strip().startswith("{")][-1]
    return json.loads(line)


@pytest.mark.slow


def test_subprocess_cache_warm_zero_recompiles(tmp_path):
    """The restart the cache exists for: a SECOND fresh process
    against the same cache dir serves with zero cache misses, and
    both processes are compile-free after their warmup round (the
    RecompileGuard would make the child exit nonzero on any
    steady-state compile)."""
    d = str(tmp_path / "xla")
    first = _run_warm_child(d)
    second = _run_warm_child(d)
    assert first["guard_compiles"] == 0
    assert second["guard_compiles"] == 0
    assert first["misses"] > 0           # cold run populated the cache
    assert second["hits"] > 0            # warm run read it back
    assert second["misses"] == 0
    assert first["tokens"] == second["tokens"]


# -- train-step AOT --------------------------------------------------------

def test_aot_compile_train_step_matches_jit(params):
    """`aot_compile_train_step` front-loads the compile and the
    resulting executable takes one numerically-identical step."""
    from paddle_tpu import models, optim, parallel
    from paddle_tpu.nn.module import ShapeSpec
    from paddle_tpu.ops import losses
    from paddle_tpu.train.state import TrainState
    from paddle_tpu.train.trainer import make_train_step

    model = models.lenet.mlp(10, hidden=(16,))
    opt = optim.sgd(0.1)
    rng = jax.random.key(0)
    p, mstate = model.init(rng, ShapeSpec((4, 28, 28, 1)))

    def loss_fn(logits, labels):
        return jnp.mean(losses.softmax_cross_entropy(logits, labels))

    x = jnp.asarray(np.random.RandomState(0)
                    .rand(4, 28, 28, 1).astype(np.float32))
    y = jnp.asarray(np.random.RandomState(1).randint(0, 10, 4))

    step = make_train_step(model, loss_fn, opt, donate=False)
    state = TrainState.create(p, mstate, opt)
    compiled = parallel.aot_compile_train_step(
        step, state, rng, (x,), (y,))
    s_aot, loss_aot, _ = compiled(state, rng, (x,), (y,))
    s_jit, loss_jit, _ = step(state, rng, (x,), (y,))
    np.testing.assert_array_equal(float(loss_aot), float(loss_jit))
    w_aot = np.asarray(jax.device_get(s_aot.params["fc1"]["kernel"]))
    w_jit = np.asarray(jax.device_get(s_jit.params["fc1"]["kernel"]))
    np.testing.assert_array_equal(w_aot, w_jit)
