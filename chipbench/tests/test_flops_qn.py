"""The Qwen3-Next configuration's FLOP, byte and parameter functions
against hand counts at the tiny twin's size and at the cell's, its
parameter count against the program's own pytree, and the configuration
file against the catalog's row."""

import json
import os

import numpy as np
import pytest

from conftest import CHIPBENCH

CELL = "qwen3_next_80b_a3b_ep16.train_seq8k"
TINY_QN = os.path.join(CHIPBENCH, "tiny", "BENCHMARK_qn.json")
# a JSON-lines catalog of published configurations, one row per model
CATALOG = os.environ.get("MODEL_CATALOG", "")


def _load(kind, name):
    import loading

    return loading.load_module(os.path.join(CHIPBENCH, kind), name)


def _cell(bench=None, name=CELL):
    import loading

    return loading.load_cell(bench or os.path.join(
        os.path.dirname(CHIPBENCH), "BENCHMARK.json"), name)


def test_the_recurrence_counts_by_hand():
    gd = _load("flops", "gated_delta")
    # a position of a head: S^T k, k u^T and S^T q, 2 dk dv each
    assert gd.forward(rows=1, seq=1, dk=3, dv=5, bytes_per_value=2)[0] == 90
    # the cell's call: 2 sequences x 32 value heads x 8192 positions
    flops, nbytes = gd.forward(rows=64, seq=8192, dk=128, dv=128,
                               bytes_per_value=2)
    assert flops == 6 * 128 * 128 * 64 * 8192 == 51_539_607_552
    assert nbytes == 64 * 8192 * (2 * 4 * 128 + 8) == 541_065_216
    bflops, bbytes = gd.backward(rows=64, seq=8192, dk=128, dv=128,
                                 bytes_per_value=2)
    assert bflops == 2 * flops
    assert bbytes == 64 * 8192 * (2 * 4 * 128 + 8 + 2 * 3 * 128 + 8)
    # at 95 and 109 FLOPs a byte both sit under the v5e's ridge (197
    # TFLOP/s over 819 GB/s, 240): their bytes bound them, 0.66 and 1.16 ms
    assert flops / 197e12 < nbytes / 819e9 and bflops / 197e12 < bbytes / 819e9
    assert round(nbytes / 819e9 * 1e3, 2) == 0.66


def test_parameters_are_the_programs_pytree():
    """625,667,136: the count the cell was sized by, and what the
    program's `init_params` really holds at the cell's configuration."""
    import jax

    _, config, traffic, _ = _cell()
    f = _load("flops", "qwen3_next_moe")
    p = f.parameters(config)
    assert p["gated_delta"] == 2048 * 12288 + 2048 * 64 + 4096 * 2048
    assert p["attention"] == 2048 * 36 * 256 + 4096 * 2048       # 27.26M
    assert p["layer"] == {"linear_attention": 138_582_208,
                          "full_attention": 132_127_232}
    assert p["total"] == 625_667_136
    driver = _load("drivers", traffic["driver"]).Driver(config, traffic, 0,
                                                         None)
    driver._build()
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(driver.shapes))
    assert held == p["total"]
    cfg = driver.cfg
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert cfg.attention_kind(0).mixer == "gated_delta"
    full = cfg.attention_kind(3)
    assert (full.mixer, full.output_gate, full.rotary_dim) == (
        "attention", True, 64)
    assert (cfg.rope_base, cfg.head_dim, cfg.n_heads, cfg.kv_heads) == (
        1e7, 256, 16, 2)
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.gdn_conv) == (16, 32, 128, 128, 4)
    assert (cfg.moe_experts, cfg.moe_held, cfg.moe_k, cfg.moe_dim,
            cfg.moe_shared_dim) == (512, 32, 10, 512, 512)


def test_step_flops_at_the_cell_and_the_tiny_twin():
    f = _load("flops", "qwen3_next_moe")
    _, config, traffic, _ = _cell()
    # 0.625 rows a position: top 10 x 32 held / 512
    ffn = 1_048_576 + 0.625 * 3_145_728 + 3_147_776
    blocks = 6 * 16384 * (3 * (33_685_504 + ffn) + 27_262_976 + ffn)
    head = 6 * 16384 * 2048 * 18992
    attention = 12 * 256 * 16 * 2 * (8192 * 8193 // 2)
    recurrence = 3 * 3 * 51_539_607_552
    step = f.train_flops_per_step(config, traffic)
    assert step == blocks + head + attention + recurrence
    assert abs(step / 1e12 - 22.62) < 0.01
    _, tiny, tiny_traffic, _ = _cell(TINY_QN, "qwen3_next_d64_l4.train_seq32_qn")
    # dim 64: GDN 64 x (2 x 32 + 2 x 64) + 64 x 8 + 64 x 64; attention
    # 64 x (8 + 4) x 32 + 128 x 64; ffn 64 x 8 + 1 x 3 x 64 x 16 +
    # 3 x 64 x 16 + 64
    ffn = 512 + 1.0 * 3072 + 3136     # top 2 x 4 held / 8: one row
    blocks = 6 * 64 * (3 * (12288 + 512 + 4096 + ffn) + 24576 + 8192 + ffn)
    want = (blocks + 6 * 64 * 64 * 128 + 12 * 32 * 4 * 2 * (32 * 33 // 2)
            + 3 * 3 * 6 * 16 * 16 * 8 * 32)
    assert f.train_flops_per_step(tiny, tiny_traffic) == want


def test_config_keeps_every_published_number():
    """Every key of the catalog's row under the same key and with the
    same value, but for the three keys `reduced` lists."""
    _, config, _, _ = _cell()
    if not os.path.exists(CATALOG):
        pytest.skip("no model catalog: set MODEL_CATALOG to its path")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = {r["name"]: r for r in rows}["Qwen3-Next-80B-A3B-Instruct"]
    bench = json.load(open(os.path.join(os.path.dirname(CHIPBENCH),
                                        "BENCHMARK.json")))
    entry = {c["name"]: c
             for c in bench["configs"]}["qwen3_next_80b_a3b_ep16"]
    assert entry["source"] == row["source_url"] == config["source"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 32, 18992)
    assert config["router_width"] == 512
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    interval = config["full_attention_interval"]
    assert config["layer_types"] == [
        "full_attention" if (i + 1) % interval == 0 else "linear_attention"
        for i in range(config["num_hidden_layers"])]
