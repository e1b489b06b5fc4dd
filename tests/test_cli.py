"""CLI driver (reference: `paddle train|merge_model|dump_config|version`,
scripts/submit_local.sh.in:3-14). Runs in-process via cli.main."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.cli import main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG = """
import jax.numpy as jnp
import numpy as np
from paddle_tpu import nn, optim
from paddle_tpu.ops import losses


def _reader():
    rng = np.random.RandomState(0)
    for _ in range(128):
        x = rng.rand(16).astype(np.float32)
        yield x, int(x.sum() > 8)


def get_config():
    return {
        "name": "toy_mlp",
        "model": nn.Sequential(
            [nn.Dense(32, name="fc1", activation="relu"),
             nn.Dense(2, name="logits")]),
        "input_spec": (32, 16),
        "optimizer": optim.adam(1e-2),
        "loss_fn": lambda lo, la: jnp.mean(
            losses.softmax_cross_entropy(lo, la)),
        "reader": _reader,
        "num_passes": 2,
    }
"""


@pytest.fixture
def config_file(tmp_path):
    p = tmp_path / "config.py"
    p.write_text(CONFIG)
    return str(p)


def test_version(capsys):
    assert main(["version"]) == 0
    out = capsys.readouterr().out
    assert "paddle_tpu" in out and "jax" in out


def test_dump_config(config_file, capsys):
    assert main(["dump-config", "--config", config_file]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["input_shape"] == [32, 16]
    # fc1: 16*32 + 32; logits: 32*2 + 2
    assert d["num_parameters"] == 16 * 32 + 32 + 32 * 2 + 2
    assert any("kernel" in k for k in d["parameters"])


def test_train_save_merge_infer(config_file, tmp_path, capsys):
    save_dir = str(tmp_path / "out")
    assert main(["train", "--config", config_file, "--batch-size", "32",
                 "--save-dir", save_dir]) == 0
    out = capsys.readouterr().out
    assert "pass 0 batch 0" in out
    params_tar = os.path.join(save_dir, "params.tar")
    assert os.path.exists(params_tar)

    artifact = str(tmp_path / "model.ptc")
    assert main(["merge-model", "--config", config_file,
                 "--params", params_tar, "--output", artifact]) == 0
    capsys.readouterr()

    x = np.random.RandomState(0).rand(32, 16).astype(np.float32)
    xnpy = str(tmp_path / "x.npy")
    np.save(xnpy, x)
    prefix = str(tmp_path / "y")
    assert main(["infer", "--artifact", artifact,
                 "--output-prefix", prefix, xnpy]) == 0
    y = np.load(prefix + "0.npy")
    assert y.shape == (32, 2)
    assert np.isfinite(y).all()


@pytest.mark.elastic
def test_train_zero_resilient_resume(config_file, tmp_path, capsys):
    """`train --zero --checkpoint-dir`: the ZeRO-layout state rides the
    resilient path (ElasticCheckpointManager + the zero step_builder),
    and a second invocation resumes past the finished pass instead of
    retraining it."""
    ck = str(tmp_path / "ck")
    base = ["train", "--config", config_file, "--batch-size", "32",
            "--zero", "--checkpoint-dir", ck, "--checkpoint-every", "2"]
    assert main(base + ["--num-passes", "1"]) == 0
    out = capsys.readouterr().out
    assert "pass 0 batch 0" in out
    assert main(base + ["--num-passes", "2"]) == 0
    out = capsys.readouterr().out
    # pass 0 was restored from the checkpoint, not re-run
    assert "pass 1 batch 0" in out
    assert "pass 0 batch 0" not in out


@pytest.mark.elastic
def test_gang_job_from_config_builder(config_file):
    """The `--elastic` builder every (re)formed gang member calls: a
    config script becomes the parallel.launch job contract, and the
    batch sequence is deterministic across rebuilds — the property the
    exactly-once resume accounting rests on."""
    from paddle_tpu.cli import _gang_job_from_config

    job = _gang_job_from_config(config=config_file, batch_size=32)
    assert set(job) >= {"model", "loss_fn", "optimizer",
                        "input_specs", "batches"}
    # the config's reader yields 128 samples -> 4 full batches; asking
    # for 5 must cycle the reader, not starve
    bs = job["batches"](5)
    assert len(bs) == 5
    x, y = bs[0]
    assert x.shape == (32, 16) and y.shape == (32,)
    job2 = _gang_job_from_config(config=config_file, batch_size=32)
    bs2 = job2["batches"](5)
    np.testing.assert_array_equal(bs[4][0], bs2[4][0])
    np.testing.assert_array_equal(bs[4][1], bs2[4][1])


def test_cli_subprocess_entry():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-m", "paddle_tpu", "version"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "paddle_tpu" in r.stdout


def test_launch_dry_run(capsys):
    from paddle_tpu.cli import main

    rc = main(["launch", "--hosts", "hostA,hostB", "--dry-run",
               "--workdir", "/tmp/w", "--",
               "train", "--config", "cfg.py"])
    assert rc == 0
    out = capsys.readouterr().out
    lines = [l for l in out.strip().splitlines() if l]
    assert len(lines) == 2
    assert "hostA" in lines[0] and "hostB" in lines[1]
    assert "--coordinator hostA:1234" in lines[0].replace("'", "")
    assert "--process-id 0" in lines[0].replace("'", "")
    assert "--process-id 1" in lines[1].replace("'", "")
    assert "--num-processes 2" in lines[1].replace("'", "")
    assert "cd /tmp/w" in lines[0]


def test_launch_emit_jobset(capsys):
    from paddle_tpu.cli import main

    rc = main(["launch", "--emit-jobset", "myjob", "--image", "img:1",
               "--num-hosts", "4", "--tpu-topology", "4x4", "--",
               "train", "--config", "cfg.py"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "kind: JobSet" in out
    assert "name: myjob" in out
    assert "parallelism: 4" in out
    assert '"train", "--config", "cfg.py"' in out
    import yaml

    doc = yaml.safe_load(out)
    assert doc["spec"]["replicatedJobs"][0]["template"]["spec"][
        "parallelism"] == 4


def test_launch_requires_command():
    from paddle_tpu.cli import main

    with pytest.raises(SystemExit):
        main(["launch", "--hosts", "a,b"])


def test_make_diagram(config_file, tmp_path, capsys):
    rc = main(["make-diagram", "--config", config_file])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.startswith("digraph")
    dot_file = str(tmp_path / "m.dot")
    rc = main(["make-diagram", "--config", config_file,
               "--output", dot_file])
    assert rc == 0
    assert open(dot_file).read().startswith("digraph")


def test_export_native_and_serve(config_file, tmp_path, capsys):
    """export-native writes a .ptni the Python-free engine loads and
    whose output matches the jax forward."""
    import ctypes

    import jax
    import jax.numpy as jnp

    out = str(tmp_path / "toy.ptni")
    assert main(["export-native", "--config", config_file,
                 "--output", out]) == 0
    assert os.path.exists(out)

    from paddle_tpu.native import build

    lib = ctypes.CDLL(build.ensure_infer_built())
    lib.ptn_load.restype = ctypes.c_void_p
    lib.ptn_load.argtypes = [ctypes.c_char_p]
    lib.ptn_output_dim.restype = ctypes.c_longlong
    lib.ptn_output_dim.argtypes = [ctypes.c_void_p]
    lib.ptn_forward.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
        ctypes.c_longlong, ctypes.POINTER(ctypes.c_float)]
    m = lib.ptn_load(out.encode())
    assert m
    x = np.random.RandomState(0).rand(4, 16).astype(np.float32)
    got = np.zeros((4, 2), np.float32)
    assert lib.ptn_forward(
        m, x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), 4,
        got.ctypes.data_as(ctypes.POINTER(ctypes.c_float))) == 0
    lib.ptn_free(ctypes.c_void_p(m))

    # same weights (seed 0 init, no --params) through the jax forward
    import importlib.util

    spec = importlib.util.spec_from_file_location("cfg", config_file)
    cfg_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cfg_mod)
    cfg = cfg_mod.get_config()
    from paddle_tpu.nn.module import ShapeSpec

    model = cfg["model"]
    params, mstate = model.init(jax.random.key(0), ShapeSpec((4, 16)))
    want, _ = model.apply(params, mstate, jnp.asarray(x), training=False)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_serve_verb(tmp_path, capsys):
    """`paddle_tpu serve`: config script -> engine pool -> id-in/id-out
    completions matching generate() (greedy default)."""
    cfg_src = """
import jax


def get_serve_config():
    from paddle_tpu.models import transformer as T
    cfg = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                              attn_impl="dense")
    return {"cfg": cfg,
            "params": T.init_params(jax.random.key(0), cfg),
            "slots": 2, "max_len": 24}
"""
    cfg_file = tmp_path / "serve_cfg.py"
    cfg_file.write_text(cfg_src)
    prompts = tmp_path / "prompts.txt"
    prompts.write_text("1 2 3 4 5\n7 8 9\n")
    out = tmp_path / "out.txt"
    assert main(["serve", "--config", str(cfg_file),
                 "--prompts", str(prompts), "--max-new", "6",
                 "--logprobs", "--output", str(out)]) == 0
    lines = out.read_text().strip().splitlines()
    assert len(lines) == 4  # 2 completions + 2 logprob comments
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import transformer as T
    cfg = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                              attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)
    for line, p in zip(lines[::2], ([1, 2, 3, 4, 5], [7, 8, 9])):
        got = [int(t) for t in line.split()]
        ref = T.generate(params, cfg,
                         jnp.asarray(p, jnp.int32)[None, :], steps=6)
        assert got == [int(t) for t in np.asarray(ref[0, len(p):])]
    assert lines[1].startswith("# logprobs ")

    # --transfer-guard: the same run under jax.transfer_guard
    # ("disallow") — the decode loop must not implicitly re-stage
    # anything (docs/ANALYSIS.md), and the output must be identical
    out2 = tmp_path / "out_guarded.txt"
    assert main(["serve", "--config", str(cfg_file),
                 "--prompts", str(prompts), "--max-new", "6",
                 "--transfer-guard", "--output", str(out2)]) == 0
    assert out2.read_text().strip().splitlines() == lines[::2]


SERVE_CFG = """
import jax


def get_serve_config():
    from paddle_tpu.models import transformer as T
    cfg = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                              attn_impl="dense")
    return {"cfg": cfg,
            "params": T.init_params(jax.random.key(0), cfg),
            "slots": 2, "max_len": 24}
"""


def _wait_addr(addr_file, alive, timeout_s=120.0):
    import time

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if os.path.exists(addr_file):
            host, port = open(addr_file).read().split()
            return host, int(port)
        assert alive(), "serve --http exited before binding"
        time.sleep(0.1)
    raise AssertionError("serve --http never published its address")


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
@pytest.mark.edge
def test_serve_http_verb(tmp_path):
    """`serve --http 0`: the network mode — main() drives the edge
    while a raw-socket client streams completions matching the solo
    greedy decode; --http-max-requests drains the run to rc 0."""
    import threading

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T
    from paddle_tpu.testing.traffic import stream_generate

    cfg_file = tmp_path / "serve_cfg.py"
    cfg_file.write_text(SERVE_CFG)
    addr_file = tmp_path / "addr.txt"
    rc = {}

    def run():
        rc["v"] = main(["serve", "--config", str(cfg_file),
                        "--http", "0",
                        "--http-addr-file", str(addr_file),
                        "--http-max-requests", "2",
                        "--max-queue", "8", "--buckets", "16"])

    t = threading.Thread(target=run, daemon=True)
    t.start()
    addr = _wait_addr(str(addr_file), t.is_alive)
    cfg = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                              attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)
    for prompt in ([1, 2, 3, 4, 5], [7, 8, 9]):
        r = stream_generate(addr, prompt, 6)
        assert r.status == 200 and r.outcome == "completed"
        ref = T.generate(params, cfg,
                         jnp.asarray(prompt, jnp.int32)[None, :],
                         steps=6)
        assert r.tokens == [int(x) for x in
                            np.asarray(ref[0, len(prompt):])]
    t.join(timeout=60.0)
    assert rc.get("v") == 0


@pytest.mark.slow  # real process boot + SIGTERM, slow lane
@pytest.mark.edge
def test_serve_http_sigterm_drains_fleet(tmp_path):
    """The SIGTERM sequence on a real process, composed with
    --replicas: edge drain (newcomers shed 503) -> fleet drain ->
    the drain report and metrics snapshot land, exit code 0."""
    import signal
    import time

    from paddle_tpu.testing.traffic import stream_generate

    cfg_file = tmp_path / "serve_cfg.py"
    cfg_file.write_text(SERVE_CFG)
    addr_file = tmp_path / "addr.txt"
    report = tmp_path / "drain.json"
    metrics = tmp_path / "metrics.prom"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.cli", "serve",
         "--config", str(cfg_file), "--http", "0",
         "--http-addr-file", str(addr_file), "--replicas", "2",
         "--max-queue", "8", "--buckets", "16",
         "--drain-report", str(report),
         "--metrics-out", str(metrics)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        addr = _wait_addr(str(addr_file),
                          lambda: proc.poll() is None)
        r = stream_generate(addr, [1, 2, 3], 4)
        assert r.status == 200 and r.outcome == "completed"
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60.0)
    finally:
        if proc.poll() is None:
            proc.kill()
            out, _ = proc.communicate(timeout=10.0)
    assert proc.returncode == 0, out
    payload = json.loads(report.read_text())
    assert payload["kind"] == "edge_drain_report"
    assert payload["reason"].startswith("signal")
    assert payload["edge"]["requests"] == 1
    assert payload["fleet"]["completed"] >= 1
    assert "edge_requests" in metrics.read_text()


def test_fleet_procs_parent_does_not_build_the_model(tmp_path, monkeypatch):
    """One process per chip: `serve --fleet-procs` must not call
    get_serve_config() in the parent — building params there would
    initialise the backend and hold the chip the replica children need.
    The children run the config themselves."""
    from paddle_tpu import cli

    cfg = tmp_path / "cfg.py"
    cfg.write_text(
        "def get_serve_config():\n"
        "    raise AssertionError('parent built the model')\n")
    prompts = tmp_path / "prompts.txt"
    prompts.write_text("1 2 3\n")
    seen = {}

    def fake_fleet(args, prompts, sampling, buckets, sink):
        seen["prompts"] = [p.tolist() for p in prompts]
        return 0

    monkeypatch.setattr(cli, "_serve_fleet_procs", fake_fleet)
    assert main(["serve", "--config", str(cfg), "--fleet-procs", "1",
                 "--prompts", str(prompts), "--no-compile-cache"]) == 0
    assert seen["prompts"] == [[1, 2, 3]]
    # without the flag the same config IS called, here
    with pytest.raises(AssertionError, match="parent built the model"):
        main(["serve", "--config", str(cfg), "--prompts", str(prompts),
              "--no-compile-cache"])
