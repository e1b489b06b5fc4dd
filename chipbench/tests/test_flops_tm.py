"""The Trinity-Mini configuration's FLOP and parameter functions against
hand counts at the cell's size and at the tiny twin's, its parameter
count against the program's own pytree, and the configuration file
against the catalog's row."""

import json
import os

import numpy as np
import pytest

from conftest import CHIPBENCH

CELL = "trinity_mini_26b_a3b_ep8.train_seq8k"
TINY_TM = os.path.join(CHIPBENCH, "tiny", "BENCHMARK_tm.json")
# a JSON-lines catalog of published configurations, one row per model
CATALOG = os.environ.get("MODEL_CATALOG", "")


def _load(kind, name):
    import loading

    return loading.load_module(os.path.join(CHIPBENCH, kind), name)


def _cell(bench=None, name=CELL):
    import loading

    return loading.load_cell(bench or os.path.join(
        os.path.dirname(CHIPBENCH), "BENCHMARK.json"), name)


def test_parameters_are_the_programs_pytree():
    """705,473,792: the count the cell was sized by (11.29 GB at 16 bytes
    a parameter), and what the program's `init_params` really holds at
    the cell's configuration; the expert bias is no parameter."""
    import jax

    _, config, traffic, _ = _cell()
    p = _load("flops", "trinity_moe").parameters(config)
    assert p["attention"] == 2048 * (64 + 8) * 128 + 4096 * 2048
    assert p["dense_layer"] == 65_020_160
    assert p["expert_layer"] == 134_488_320
    assert p["total"] == 705_473_792
    driver = _load("drivers", traffic["driver"]).Driver(config, traffic, 0,
                                                        None)
    driver._build()
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(driver.shapes))
    assert held == p["total"]
    cfg = driver.cfg
    assert cfg.layer_types == ("sliding_attention",) * 2 + (
        "full_attention",) + ("sliding_attention",) * 2
    assert cfg.moe_layers == (1, 2, 3, 4)
    sliding, full = cfg.attention_kind(0), cfg.attention_kind(2)
    assert (sliding.window, sliding.output_gate, sliding.rotary_dim) == (
        2048, True, None)
    assert (full.window, full.output_gate, full.rotary_dim) == (None, True, 0)
    assert (cfg.rms_eps, cfg.sandwich_norm, cfg.mlp, cfg.mlp_ratio) == (
        1e-5, True, "swiglu", 3)
    assert cfg.embed_scale == pytest.approx(2048 ** 0.5)
    assert (cfg.moe_experts, cfg.moe_held, cfg.moe_k, cfg.moe_dim,
            cfg.moe_shared_dim, cfg.moe_shared_gate) == (
                128, 16, 8, 1024, 1024, False)
    assert (cfg.moe_score, cfg.moe_route_scale, cfg.moe_expert_bias) == (
        "sigmoid", 2.826, True)


def test_step_flops_at_the_cell_and_the_tiny_twin():
    f = _load("flops", "trinity_moe")
    _, config, traffic, _ = _cell()
    attn = 27_262_976
    # one row a position: top 8 x 16 held / 128
    expert_layer = 262_144 + 6_291_456 + 1.0 * 6_291_456
    blocks = 6 * 16384 * (5 * attn + 37_748_736 + 4 * expert_layer)
    head = 6 * 16384 * 2048 * 25024
    band = 2048 * 2049 // 2 + (8192 - 2048) * 2048
    attention = 12 * 128 * 32 * 2 * (4 * band + 8192 * 8193 // 2)
    step = f.train_flops_per_step(config, traffic)
    assert step == blocks + head + attention
    assert abs(step / 1e12 - 36.27) < 0.01
    _, tiny, tiny_traffic, _ = _cell(TINY_TM, "trinity_d64_l5.train_seq32_tm")
    # dim 64: attention 64 x (8 + 4) x 16 + 64 x 64; dense 3 x 64 x 128;
    # expert layer 64 x 8 + 3 x 64 x 16 + one row of 3 x 64 x 16
    attn = 64 * 12 * 16 + 64 * 64
    expert_layer = 512 + 3072 + 1.0 * 3072
    blocks = 6 * 64 * (5 * attn + 3 * 64 * 128 + 4 * expert_layer)
    band = 8 * 9 // 2 + 24 * 8
    want = (blocks + 6 * 64 * 64 * 128
            + 12 * 16 * 4 * 2 * (4 * band + 32 * 33 // 2))
    assert f.train_flops_per_step(tiny, tiny_traffic) == want


def test_config_keeps_every_published_number():
    """Every key of the catalog's row under the same key and with the
    same value, but for the four keys `reduced` lists."""
    _, config, _, _ = _cell()
    bench = json.load(open(os.path.join(os.path.dirname(CHIPBENCH),
                                        "BENCHMARK.json")))
    entry = {c["name"]: c
             for c in bench["configs"]}["trinity_mini_26b_a3b_ep8"]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "num_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 16, 25024)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["router_width"] == config["published"]["num_experts"]
    # the dense layer and the period after it: sliding, sliding, full,
    # sliding, sliding
    assert [config["layer_types"][i] for i in config["layers_kept"]] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    if not os.path.exists(CATALOG):
        pytest.skip("no model catalog: set MODEL_CATALOG to its path")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = {r["name"]: r for r in rows}["Trinity-Mini"]
    assert entry["source"] == row["source_url"] == config["source"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
