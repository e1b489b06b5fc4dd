"""The new configuration's FLOP, pair and parameter functions against
brute-force and hand counts, and its parameter count against the
program's own pytree at the cell's configuration."""

import json
import os

import numpy as np
import pytest

from conftest import CHIPBENCH

CELL = "sdar_30b_a3b_ep8.train_bd4_seq4k"


def _load(kind, name):
    import loading

    return loading.load_module(os.path.join(CHIPBENCH, kind), name)


def _cell():
    import loading

    return loading.load_cell(os.path.join(os.path.dirname(CHIPBENCH),
                                          "BENCHMARK.json"), CELL)


@pytest.mark.parametrize("length,bd", [(32, 4), (24, 3), (16, 1), (16, 16)])
def test_admitted_pairs_against_a_brute_force_count(length, bd):
    f = _load("flops", "flash_block_diffusion")
    ref = _load("reference", "sdar_moe")
    pos = np.arange(2 * length)
    assert f.pairs(length, bd) == int(np.asarray(
        ref.attends(pos, pos, length, bd)).sum())
    assert _load("flops", "sdar_moe").pairs(length, bd) == f.pairs(length, bd)


def test_flash_block_diffusion_counts():
    f = _load("flops", "flash_block_diffusion")
    pairs = 4096 * 4096 + 4096 * 4
    assert pairs == 16_793_600              # against a causal 8,390,656
    fwd, fwd_bytes = f.forward(batch=1, heads=64, positions=8192,
                               head_dim=128, block_length=4,
                               bytes_per_value=2)
    bwd, bwd_bytes = f.backward(batch=1, heads=64, positions=8192,
                                head_dim=128, block_length=4,
                                bytes_per_value=2)
    assert fwd == 64 * pairs * 4 * 128 and bwd == 64 * pairs * 10 * 128
    assert fwd_bytes == 4 * 64 * 8192 * 128 * 2
    assert bwd_bytes == 2 * fwd_bytes


def test_grouped_product_counts():
    f = _load("flops", "moe_grouped")
    flops, nbytes = f.product(rows=16384, k=2048, n=768, groups=16,
                              bytes_per_value=2)
    assert flops == 2 * 16384 * 2048 * 768
    assert nbytes == (16 * 2048 * 768 + 16384 * (2048 + 768)) * 2
    assert f.layer_forward(rows=16384, dim=2048, expert_dim=768) == 3 * flops


def test_parameters_are_the_programs_pytree():
    """645.6M: the count the issue sized the cell by, and what the
    program's `init_params` really holds at the cell's configuration."""
    import jax

    _, config, traffic, _ = _cell()
    f = _load("flops", "sdar_moe")
    p = f.parameters(config)
    assert p["attention"] == 2048 * 5120 + 4096 * 2048      # 18.874M
    assert p["router"] == 2048 * 128 and p["expert"] == 3 * 2048 * 768
    assert p["total"] == 645_623_296
    driver = _load("drivers", traffic["driver"]).Driver(config, traffic, 0,
                                                         None)
    driver._build()
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(driver.shapes))
    assert held == p["total"]


def test_step_flops_are_25_9_tflop():
    _, config, traffic, _ = _cell()
    f = _load("flops", "sdar_moe")
    step = f.train_flops_per_step(config, traffic)
    blocks = 6 * 16384 * 6 * (18_874_368 + 262_144 + 4_718_592)
    head = 6 * 8192 * 2048 * 18992
    attention = 12 * 128 * 32 * 2 * 16_793_600 * 6
    assert step == blocks + head + attention
    assert abs(step / 1e12 - 25.9) < 0.05


def test_config_keeps_every_published_number():
    """Every number of the catalog's row under the same key, but for the
    three keys `reduced` lists."""
    _, config, _, _ = _cell()
    published = {"attention_bias": False, "decoder_sparse_step": 1,
                 "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 6144, "max_position_embeddings": 32768,
                 "max_window_layers": 48, "mlp_only_layers": [],
                 "model_type": "sdar_moe", "moe_intermediate_size": 768,
                 "norm_topk_prob": True, "num_attention_heads": 32,
                 "num_experts": 128, "num_experts_per_tok": 8,
                 "num_hidden_layers": 48, "num_key_value_heads": 4,
                 "rms_norm_eps": 1e-06, "rope_scaling": None,
                 "rope_theta": 1000000, "sliding_window": None,
                 "tie_word_embeddings": False, "use_sliding_window": False,
                 "vocab_size": 151936}
    bench = json.load(open(os.path.join(os.path.dirname(CHIPBENCH),
                                        "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["sdar_30b_a3b_ep8"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 18992)
    assert config["router_width"] == 128
