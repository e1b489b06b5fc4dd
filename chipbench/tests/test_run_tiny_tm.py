"""`run.py` end to end at toy size on the CPU for the `train_lm_afmoe`
driver (`tiny/BENCHMARK_tm.json`: dim 64, a dense layer of 128, then
sliding, full (NoPE), sliding, sliding expert layers of 4 heads x 16
with 2 KV heads and a window of 8, 8 experts with 4 held, top 2, a
sigmoid router choosing by an expert bias, an ungated shared expert of
16), and the faults planted under it."""

import json
import os

import pytest

from conftest import CHIPBENCH

TINY_TM = os.path.join(CHIPBENCH, "tiny", "BENCHMARK_tm.json")
CELL = "trinity_d64_l5.train_seq32_tm"


@pytest.fixture
def run_tm(capsys):
    import run

    def go(seed, trace=0, seconds=1.0):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      benchmark_file=TINY_TM, require_chip=False)
        out, err = capsys.readouterr()
        assert rc == 0
        return json.loads(out.strip().splitlines()[-1]), err

    return go


def test_untraced_line(run_tm):
    res, err = run_tm(seed=3000000019)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 3
    assert set(res["metrics"]) >= {"train_tokens_per_s", "setup_s"}
    assert "stats_worst_leaf_gap" in res["checks"]
    for name, (value, limit) in res["checks"].items():
        assert value <= limit
        assert f"check {name}:" in err


def test_traced_line_reports_what_it_read(run_tm):
    res, _ = run_tm(seed=7, trace=1, seconds=2.0)
    assert res["correct"] is True
    declared = {m["name"] for m in json.load(open(TINY_TM))["per_layer"]}
    assert set(res["metrics"]) <= declared
    # no device plane in a CPU trace: the trace's readers are left out
    assert not any("roofline" in k or k.startswith(("device_", "step_mfu"))
                   for k in res["metrics"])
    assert res["metrics"]["window_compiles.tm"]["value"] == 0
    assert res["metrics"]["moe_load_max_over_mean.tm"]["value"] >= 1.0
    assert res["metrics"]["moe_route_max_over_mean.tm"]["value"] >= 1.0


def test_the_step_carries_the_bias_and_counts_all_routes():
    import jax
    import loading
    import numpy as np

    cell, config, traffic, _ = loading.load_cell(TINY_TM, CELL)
    mod = loading.load_module(os.path.join(CHIPBENCH, "drivers"),
                              traffic["driver"])
    d = mod.Driver(config, traffic, 5, None)
    d.setup()
    cfg = d.cfg
    assert cfg.layer_types == ("sliding_attention",) * 2 + (
        "full_attention",) + ("sliding_attention",) * 2
    assert cfg.moe_layers == (1, 2, 3, 4) and cfg.moe_held_first == 2
    bias = np.asarray(d.state[4])
    assert bias.shape == (4, 8)
    # three updates of +-0.01, centred
    assert 0 < np.abs(bias).max() <= 3 * 2 * config["load_balance_coeff"]
    np.testing.assert_allclose(bias.mean(axis=-1), 0.0, atol=1e-7)
    assert set(d.program_numbers["stats"]) == {
        f"blocks/{i}/moe/expert_bias" for i in (1, 2, 3, 4)}
    counts = np.asarray(d.state[3])
    # rows routed: 3 steps x 4 layers x 64 positions x top 2
    assert counts[2] == 3 * 4 * 64 * 2 and counts[3] <= counts[2]
    # the embedding: the other MoE cells' N(0, 1) table, times sqrt(dim)
    # on the way in
    import weights_stacked

    params = jax.jit(weights_stacked.generate)(d.shapes,
                                               weights_stacked.seed_key(5))
    assert 0.8 < float(params["embed"]["table"].std()) < 1.2
    assert cfg.embed_scale == 8.0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_planted_fault_is_not_correct(fault):
    import check
    import faults
    import loading

    cell, config, traffic, limits = loading.load_cell(TINY_TM, CELL)
    mod = loading.load_module(os.path.join(CHIPBENCH, "drivers"),
                              traffic["driver"])
    d = mod.Driver(config, traffic, 11, None)
    d._build()
    faults.plant(d, fault)
    d.setup()
    compared = check.compare(d.program_numbers,
                             d.reference_numbers("float32"), limits)
    assert not all(c["ok"] for c in compared.values())
