"""The FLOP and parameter functions against hand counts."""

import json
import os

from conftest import CHIPBENCH


def _load(kind, name):
    import loading

    return loading.load_module(os.path.join(CHIPBENCH, kind), name)


def test_starcoder2_3b_l4_counts():
    f = _load("flops", "transformer")
    cfg = json.load(open(os.path.join(CHIPBENCH, "configs",
                                      "starcoder2_3b_l4.json")))
    # per layer: qkv 3072x3584, proj 3072^2, fc1 + fc2 2x3072x12288
    layer = 3072 * 3584 + 3072 * 3072 + 2 * 3072 * 12288
    assert f.parameters(cfg)["matmul"] == 4 * layer + 3072 * 49152
    assert f.parameters(cfg)["total"] == 685_912_064
    per_token = f.train_flops_per_token(cfg, 4096)
    assert abs(per_token / 1e9 - 3.51) < 0.005
    step = f.train_flops_per_step(cfg, {"batch": 2, "seq": 4096})
    assert abs(step / 1e12 - 28.76) < 0.005


def test_window_shortens_attention():
    f = _load("flops", "transformer")
    assert f.mean_keys(4096, 4096) == (4096 + 1) / 2
    assert f.mean_keys(8192, None) == (8192 + 1) / 2
    # 8192 positions under a window of 4096: the first 4096 see t+1 keys
    assert f.mean_keys(8192, 4096) == (4096 * 4097 / 2 + 4096 * 4096) / 8192


def test_resnet50_forward_is_8_2_gflop_an_image():
    f = _load("flops", "resnet")
    fwd = 2 * f.forward_macs_per_image(50, 224, 1000)
    assert abs(fwd / 1e9 - 8.2) < 0.05
    cfg = json.load(open(os.path.join(CHIPBENCH, "configs", "resnet50.json")))
    assert f.train_flops_per_step(cfg, {"batch": 256}) == 3 * fwd * 256


def test_flash_forward_counts():
    f = _load("flops", "flash")
    # batch 2, 24 heads, 4096 x 4096 causal, head 128: QK^T and PV are
    # 2 x 2 x 128 FLOPs a (query, key) pair, over T(T+1)/2 pairs
    flops, nbytes = f.forward(batch=2, heads=24, seq=4096, head_dim=128,
                              window=4096, bytes_per_value=2)
    assert flops == 2 * 24 * (4096 * 4097 // 2) * 4 * 128
    assert nbytes == 4 * 2 * 24 * 4096 * 128 * 2
