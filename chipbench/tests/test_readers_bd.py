"""The three new per-layer readers on a synthetic `ctx`: a share where
the trace and the counters hold what they read, `None` where they do
not (the parent's program has no such kernel or counter)."""

import os

import pytest

from conftest import CHIPBENCH

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
FWD = ("%jvp_flash_attention_fwd_.7 = (bf16[64,8192,128]{2,1,0:T(8,128)(2,1)}, "
       "f32[64,8192,128]{2,1,0}) custom-call(s32[64]{0}, bf16[64,8192,128]"
       "{2,1,0}, bf16[64,8192,128]{2,1,0}, bf16[64,8192,128]{2,1,0}), "
       "custom_call_target=\"tpu_custom_call\"")
DKV = ("%flash_attention_bwd_dkv.3 = (bf16[64,8192,128]{2,1,0}, bf16[64,8192,"
       "128]{2,1,0}) custom-call(s32[64]{0}, bf16[64,8192,128]{2,1,0}, "
       "bf16[64,8192,128]{2,1,0}), custom_call_target=\"tpu_custom_call\"")
DQ = DKV.replace("bwd_dkv.3", "bwd_dq.4")
GMM = ("%moe_grouped_matmul.9 = bf16[131072,768]{1,0:T(8,128)(2,1)} "
       "custom-call(s32[17]{0}, s32[527]{0}, s32[527]{0}, bf16[131072,2048]"
       "{1,0}, bf16[16,2048,768]{2,1,0}), custom_call_target=\"tpu_custom_call\"")
GMM_DW = ("%moe_grouped_matmul_dw.2 = bf16[16,2048,768]{2,1,0} custom-call("
          "s32[17]{0}, s32[527]{0}, s32[527]{0}, bf16[131072,2048]{1,0}, "
          "bf16[131072,768]{1,0}), custom_call_target=\"tpu_custom_call\"")


def _reader(name):
    import loading

    return loading.load_module(os.path.join(CHIPBENCH, "layer_metrics"), name)


def _ctx(ops, counters=None, steps=10):
    return {"trace": {"ops": ops}, "peaks": PEAKS,
            "window": {"steps": steps},
            "counters": counters if counters is not None else {},
            "config": {"num_hidden_layers": 6, "num_experts": 16},
            "traffic": {"block_length": 4}}


def test_flash_forward_share():
    pairs = 64 * (4096 * 4096 + 4096 * 4)
    least = pairs * 4 * 128 / 197e12             # 2.79 ms a call
    got = _reader("flash_bd_fwd_roofline.bd").read(
        _ctx({FWD: [0.030, 2], "%fusion.1 = f32[8] fusion()": [1.0, 5]}))
    assert got == pytest.approx(100 * 2 * least / 0.030)
    assert 0 < got < 100


def test_flash_backward_share_counts_calls_by_dkv_and_time_by_both():
    pairs = 64 * (4096 * 4096 + 4096 * 4)
    least = pairs * 10 * 128 / 197e12
    got = _reader("flash_bd_bwd_roofline.bd").read(
        _ctx({DKV: [0.012, 1], DQ: [0.008, 1]}))
    assert got == pytest.approx(100 * least / 0.020)


def test_grouped_products_share_reads_rows_from_the_counters():
    # 10 steps x 6 layers at 16,000 rows a layer
    counters = {"moe.rows_held": 16000 * 60, "moe.rows_max_expert": 1100 * 60}
    flops = 2 * 16000 * 2048 * 768
    got = _reader("moe_expert_matmul_roofline.bd").read(
        _ctx({GMM: [0.004, 4], GMM_DW: [0.002, 1]}, counters))
    assert got == pytest.approx(100 * 5 * flops / 197e12 / 0.006)
    load = _reader("moe_load_max_over_mean.bd").read(_ctx({}, counters))
    assert load == pytest.approx(1100 / 1000)


@pytest.mark.parametrize("name", [
    "flash_bd_fwd_roofline.bd", "flash_bd_bwd_roofline.bd",
    "moe_expert_matmul_roofline.bd", "moe_load_max_over_mean.bd"])
def test_nothing_to_read_is_none_never_zero(name):
    read = _reader(name).read
    other = {"%fusion.1 = f32[8] fusion()": [1.0, 5]}
    assert read(_ctx(other)) is None                 # no kernel, no counter
    assert read({**_ctx(other), "trace": None}) is None
    assert read({**_ctx(other), "peaks": None}) is None
    # a program with the kernels but without the counters (or the
    # reverse) gives the grouped products' share nothing to read
    assert _reader("moe_expert_matmul_roofline.bd").read(
        _ctx({GMM: [0.004, 4]})) is None
    assert _reader("moe_expert_matmul_roofline.bd").read(
        _ctx(other, {"moe.rows_held": 5})) is None
    # the causal LM cell's traffic has no block length
    assert _reader("flash_bd_fwd_roofline.bd").read(
        {**_ctx({FWD: [0.03, 2]}), "traffic": {}}) is None
