"""Keep `chip_smoke.py` from rotting, without a chip.

`--tiny` runs the phases at toy size on the CPU backend — on two
virtual devices, so the sharded train branch is covered. The real
contract — exit 0 and a result line only on a TPU — is checked from the
other side: a plain run under JAX_PLATFORMS=cpu must fail, name the
missing chip, and print no result. Both runs get their compile cache
from the environment, so neither fills the checkout's `.jax_cache`."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _run(tmp_path, *args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path)


def test_tiny_runs_every_phase(tmp_path):
    out = _run(tmp_path, "--tiny", devices=2)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    assert lines[0]["compile_cache_dir"] == str(tmp_path / "cache")
    phases = {l["phase"]: l for l in lines if "phase" in l}
    assert list(phases) == ["train_resnet50", "train_transformer",
                            "train_layer_kinds", "train_hybrid",
                            "train_afmoe", "serve_http"]
    kinds = phases["train_layer_kinds"]
    assert kinds["traced"]["transformer.layer_kinds=sliding:3,full:1"] > 0
    assert kinds["traced"]["transformer.rope=sliding:none,full:yarn"] > 0
    assert kinds["traced"]["transformer.ffn=moe_dropless"] > 0
    assert kinds["counters"]["moe.positions"] == 4 * 2 * 64
    hybrid = phases["train_hybrid"]["traced"]
    for name in ("transformer.layer_kinds=linear:3,full:1",
                 "transformer.mixer=linear:gated_delta,full:attention",
                 "transformer.rope=full:partial_4",
                 "transformer.attention.gate=sigmoid",
                 "moe.shared_expert=gated", "gated_delta.forward=jnp",
                 "gated_delta.backward=jnp", "gated_delta.chunk=64",
                 "gated_delta.heads_per_step=1"):
        assert hybrid[name] > 0, name
    afmoe = phases["train_afmoe"]
    for name in ("transformer.layer_kinds=sliding:4,full:1",
                 "transformer.rope=sliding:none,full:nope",
                 "transformer.ffn=dense_swiglu", "transformer.ffn=moe_dropless",
                 "transformer.post_norm=sandwich", "moe.router=sigmoid_bias",
                 "moe.shared_expert=plain"):
        assert afmoe["traced"][name] > 0, name
    assert afmoe["counters"]["moe.positions"] == 4 * 2 * 64
    assert afmoe["counters"]["moe.route_rows"] == 2 * 4 * 2 * 64
    assert phases["train_resnet50"]["sharded_over"] == 2
    serve = phases["serve_http"]
    assert serve["traced"]["ragged_attention=jnp"] > 0
    assert serve["edge"]["completed"] == serve["requests"] == 8
    assert lines[-1] == {"ok": True, "chip": False,
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 2}}


def test_without_a_chip_it_fails_and_prints_no_result(tmp_path):
    out = _run(tmp_path)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
