"""`run.py` end to end at toy size on the CPU for the `train_lm_moe`
driver (`tiny/BENCHMARK_mel.json`: dim 64, 4 heads x 16, 1 KV head, 8
experts with 4 held, top 2, 4 layers sliding, sliding, sliding, full,
window 8 of 32, YaRN with original 16), and the faults planted under
it."""

import json
import os

import pytest

from conftest import CHIPBENCH

TINY_MEL = os.path.join(CHIPBENCH, "tiny", "BENCHMARK_mel.json")
CELL = "mellum_d64_l4.train_seq32"


@pytest.fixture
def run_mel(capsys):
    import run

    def go(seed, trace=0, seconds=1.0):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      benchmark_file=TINY_MEL, require_chip=False)
        out, err = capsys.readouterr()
        assert rc == 0
        return json.loads(out.strip().splitlines()[-1]), err

    return go


def test_untraced_line(run_mel):
    res, err = run_mel(seed=3000000019)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 3
    assert set(res["metrics"]) >= {"train_tokens_per_s", "setup_s"}
    for name, (value, limit) in res["checks"].items():
        assert value <= limit
        assert f"check {name}:" in err


def test_traced_line_reports_what_it_read(run_mel):
    res, _ = run_mel(seed=7, trace=1, seconds=2.0)
    assert res["correct"] is True
    declared = {m["name"] for m in json.load(open(TINY_MEL))["per_layer"]}
    assert set(res["metrics"]) <= declared
    # no device plane in a CPU trace: the trace's readers are left out
    assert not any("roofline" in k or k.startswith(("device_", "step_mfu"))
                   for k in res["metrics"])
    assert res["metrics"]["window_compiles.mel"]["value"] == 0
    # the counters come from `loss_and_aux`, so they are here too: the
    # fullest of 4 held experts has at least the mean's rows
    assert res["metrics"]["moe_load_max_over_mean.mel"]["value"] >= 1.0


def test_the_driver_reads_kinds_and_rows_from_the_files():
    import loading

    cell, config, traffic, _ = loading.load_cell(TINY_MEL, CELL)
    mod = loading.load_module(os.path.join(CHIPBENCH, "drivers"),
                              traffic["driver"])
    d = mod.Driver(config, traffic, 5, None)
    assert d.units_per_step == 2 * 32
    d._make_pool()
    assert d.pool.shape == (4, 2, 33) and d.pool.dtype.name == "int32"
    assert 0 <= d.pool.min() and d.pool.max() < 128
    d._build()
    # layer_types has one entry more than the layers kept: the first
    # num_hidden_layers of them are read
    assert d.cfg.layer_types == ("sliding_attention",) * 3 + (
        "full_attention",)
    assert d.cfg.attention_kind(0).window == 8
    assert d.cfg.attention_kind(3).rope_scaling == "yarn"
    assert d.cfg.moe_held == 4 and d.cfg.moe_held_first == 2
    assert mod.rope_base({**config, "rope_parameters": {
        **config["rope_parameters"]}}) == 500000.0
    with pytest.raises(ValueError, match="one rope_base"):
        mod.rope_base({**config, "rope_parameters": {
            "full_attention": {"rope_theta": 1e6},
            "sliding_attention": {"rope_theta": 5e5}}})


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_planted_fault_is_not_correct(fault):
    import check
    import faults
    import loading

    cell, config, traffic, limits = loading.load_cell(TINY_MEL, CELL)
    mod = loading.load_module(os.path.join(CHIPBENCH, "drivers"),
                              traffic["driver"])
    d = mod.Driver(config, traffic, 11, None)
    d._build()
    faults.plant(d, fault)
    d.setup()
    compared = check.compare(d.program_numbers,
                             d.reference_numbers("float32"), limits)
    assert not all(c["ok"] for c in compared.values())
