"""The third LM configuration's FLOP, pair and parameter functions
against brute-force and hand counts, its parameter count against the
program's own pytree at the cell's configuration, and the configuration
file against the catalog's row."""

import json
import os

import numpy as np
import pytest

from conftest import CHIPBENCH

CELL = "mellum2_12b_a2p5b_ep4.train_seq8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(kind, name):
    import loading

    return loading.load_module(os.path.join(CHIPBENCH, kind), name)


def _cell():
    import loading

    return loading.load_cell(os.path.join(os.path.dirname(CHIPBENCH),
                                          "BENCHMARK.json"), CELL)


@pytest.mark.parametrize("seq,window", [(32, 8), (32, None), (24, 24),
                                        (16, 100), (40, 1)])
def test_admitted_pairs_against_a_brute_force_count(seq, window):
    f = _load("flops", "mellum_moe")
    ref = _load("reference", "mellum_moe")
    pos = np.arange(seq)
    count = int(np.asarray(ref.attends(pos, pos, window)).sum())
    assert f.pairs(seq, window) == count
    # the kernels' own counts are of the same pairs
    flash, _ = _load("flops", "flash").forward(
        batch=1, heads=1, seq=seq, head_dim=1, window=window,
        bytes_per_value=2)
    assert flash == 4 * count


def test_pairs_by_layer_kind_at_the_cells_shapes():
    _, config, _, _ = _cell()
    f = _load("flops", "mellum_moe")
    band = 1024 * 1025 // 2 + (8192 - 1024) * 1024
    full = 8192 * 8193 // 2
    assert (band, full) == (7_864_832, 33_558_528)      # 23%
    assert f.layer_pairs(config, 8192) == [band, band, band, full]


def test_parameters_are_the_programs_pytree():
    """595.2M: the count the issue sized the cell by, and what the
    program's `init_params` really holds at the cell's configuration."""
    import jax

    _, config, traffic, _ = _cell()
    f = _load("flops", "mellum_moe")
    p = f.parameters(config)
    assert p["attention"] == 2304 * 5120 + 4096 * 2304      # 21.234M
    assert p["router"] == 2304 * 64 and p["expert"] == 3 * 2304 * 896
    assert p["layer"] == 120_476_416
    assert p["total"] == 595_154_176
    driver = _load("drivers", traffic["driver"]).Driver(config, traffic, 0,
                                                         None)
    driver._build()
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(driver.shapes))
    assert held == p["total"]
    # the kinds by layer, as the driver hands them to the program
    cfg = driver.cfg
    assert cfg.layer_types == ("sliding_attention",) * 3 + (
        "full_attention",)
    assert [cfg.attention_kind(i).window for i in range(4)] == [
        1024, 1024, 1024, None]
    full = cfg.attention_kind(3)
    assert (full.rope_scaling, full.rope_factor, full.rope_original,
            full.rope_attention_factor) == ("yarn", 16.0, 8192,
                                            1.2772588722239782)
    assert cfg.attention_kind(0).rope_scaling == "none"
    assert cfg.rope_base == 500000.0 and cfg.moe_held == 16


def test_step_flops_are_24_5_tflop():
    _, config, traffic, _ = _cell()
    f = _load("flops", "mellum_moe")
    step = f.train_flops_per_step(config, traffic)
    # two rows a position on average: top 8 x 16 held / 64
    blocks = 6 * 16384 * 4 * (21_233_664 + 147_456 + 2 * 6_193_152)
    head = 6 * 16384 * 2304 * 24576
    attention = 12 * 128 * 32 * 2 * (3 * 7_864_832 + 33_558_528)
    assert step == blocks + head + attention
    assert abs(step / 1e12 - 24.46) < 0.01


def test_config_keeps_every_published_number():
    """Every key of the catalog's row under the same key and with the
    same value (nested groups whole), but for the three keys `reduced`
    lists."""
    _, config, _, _ = _cell()
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = {r["name"]: r for r in rows}["Mellum2-12B-A2.5B-Instruct"]
    bench = json.load(open(os.path.join(os.path.dirname(CHIPBENCH),
                                        "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["mellum2_12b_a2p5b_ep4"]
    assert entry["source"] == row["source_url"] == config["source"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 24576)
    assert config["router_width"] == 64
    assert config["vocab_size"] * 4 == config["published"]["vocab_size"]
