"""`run.py` end to end at toy size on the CPU for the `train_lm_hybrid`
driver (`tiny/BENCHMARK_qn.json`: dim 64, three Gated DeltaNet layers of
2 key heads x 16 and 4 value heads x 16 and one gated attention layer of
4 heads x 32 with 2 KV heads and a rotary over 8 lanes, 8 experts with 4
held, top 2, a shared expert of 16), and the faults planted under it."""

import json
import os

import pytest

from conftest import CHIPBENCH

TINY_QN = os.path.join(CHIPBENCH, "tiny", "BENCHMARK_qn.json")
CELL = "qwen3_next_d64_l4.train_seq32_qn"


@pytest.fixture
def run_qn(capsys):
    import run

    def go(seed, trace=0, seconds=1.0):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      benchmark_file=TINY_QN, require_chip=False)
        out, err = capsys.readouterr()
        assert rc == 0
        return json.loads(out.strip().splitlines()[-1]), err

    return go


def test_untraced_line(run_qn):
    res, err = run_qn(seed=3000000019)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 3
    assert set(res["metrics"]) >= {"train_tokens_per_s", "setup_s"}
    for name, (value, limit) in res["checks"].items():
        assert value <= limit
        assert f"check {name}:" in err


def test_traced_line_reports_what_it_read(run_qn):
    res, _ = run_qn(seed=7, trace=1, seconds=2.0)
    assert res["correct"] is True
    declared = {m["name"] for m in json.load(open(TINY_QN))["per_layer"]}
    assert set(res["metrics"]) <= declared
    # no device plane in a CPU trace: the trace's readers are left out
    assert not any("roofline" in k or k.startswith(("device_", "step_mfu"))
                   for k in res["metrics"])
    assert res["metrics"]["window_compiles.qn"]["value"] == 0
    assert res["metrics"]["moe_load_max_over_mean.qn"]["value"] >= 1.0


def test_the_driver_reads_kinds_and_sizes_from_the_files():
    import loading

    cell, config, traffic, _ = loading.load_cell(TINY_QN, CELL)
    mod = loading.load_module(os.path.join(CHIPBENCH, "drivers"),
                              traffic["driver"])
    d = mod.Driver(config, traffic, 5, None)
    assert d.units_per_step == 2 * 32
    d._build()
    cfg = d.cfg
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert cfg.attention_kind(0).mixer == "gated_delta"
    assert cfg.attention_kind(3).rotary_dim == 8
    assert cfg.attention_kind(3).output_gate
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads) == (2, 4)
    assert cfg.moe_shared_dim == 16 and cfg.moe_held_first == 2
    # A_log and dt_bias by their own rules, the rest as the other MoE cells
    import jax
    import weights_hybrid

    params = jax.jit(weights_hybrid.generate)(d.shapes,
                                              weights_hybrid.seed_key(5))
    a = jax.numpy.exp(params["blocks"][0]["A_log"])
    assert float(a.min()) >= 1.0 and float(a.max()) <= 16.0
    dt = jax.nn.softplus(params["blocks"][0]["dt_bias"])
    assert float(dt.min()) >= 1e-3 - 1e-7 and float(dt.max()) <= 0.1 + 1e-7


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_planted_fault_is_not_correct(fault):
    import check
    import faults
    import loading

    cell, config, traffic, limits = loading.load_cell(TINY_QN, CELL)
    mod = loading.load_module(os.path.join(CHIPBENCH, "drivers"),
                              traffic["driver"])
    d = mod.Driver(config, traffic, 11, None)
    d._build()
    faults.plant(d, fault)
    d.setup()
    compared = check.compare(d.program_numbers,
                             d.reference_numbers("float32"), limits)
    assert not all(c["ok"] for c in compared.values())
