"""The two new per-layer readers on a synthetic `ctx` whose trace mixes
band calls and full-causal calls: a share over the band calls alone,
`None` where there is nothing to read (the parent's program names no
such kernel), and the shared readers under the new suffix."""

import os

import pytest

from conftest import CHIPBENCH

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CALL = ("(bf16[64,8192,128]{2,1,0:T(8,128)(2,1)}, f32[64,8192,128]{2,1,0}) "
        "custom-call(s32[64]{0}, bf16[64,8192,128]{2,1,0}, bf16[64,8192,128]"
        "{2,1,0}, bf16[64,8192,128]{2,1,0}), "
        "custom_call_target=\"tpu_custom_call\"")
FWD_BAND = "%flash_attention_fwd_window.2 = " + CALL
FWD_BAND_JVP = "%jvp_flash_attention_fwd_window_.7 = " + CALL
FWD_FULL = "%flash_attention_fwd.3 = " + CALL
FWD_FULL_JVP = "%jvp_flash_attention_fwd_.9 = " + CALL
DKV_BAND = "%flash_attention_bwd_dkv_window.3 = " + CALL
DQ_BAND = "%flash_attention_bwd_dq_window.4 = " + CALL
DKV_FULL = "%flash_attention_bwd_dkv.5 = " + CALL
DQ_FULL = "%flash_attention_bwd_dq.6 = " + CALL
GMM = ("%moe_grouped_matmul.9 = bf16[131072,896]{1,0:T(8,128)(2,1)} "
       "custom-call(s32[17]{0}, s32[527]{0}, s32[527]{0}, bf16[131072,2304]"
       "{1,0}, bf16[16,2304,896]{2,1,0}), custom_call_target=\"tpu_custom_call\"")
BAND_PAIRS = 1024 * 1025 // 2 + (8192 - 1024) * 1024


def _reader(name):
    import loading

    return loading.load_module(os.path.join(CHIPBENCH, "layer_metrics"), name)


def _ctx(ops, counters=None, steps=10, window=1024):
    return {"trace": {"ops": ops}, "peaks": PEAKS,
            "window": {"steps": steps},
            "counters": counters if counters is not None else {},
            "config": {"num_hidden_layers": 4, "num_experts": 16,
                       "sliding_window": window},
            "traffic": {}}


def test_forward_share_reads_the_band_calls_alone():
    least = 64 * BAND_PAIRS * 4 * 128 / 197e12          # 1.31 ms a call
    ops = {FWD_BAND: [0.021, 3], FWD_BAND_JVP: [0.021, 3],
           FWD_FULL: [0.5, 1], FWD_FULL_JVP: [0.5, 1],
           "%fusion.1 = f32[8] fusion()": [1.0, 5]}
    got = _reader("flash_window_fwd_roofline.mel").read(_ctx(ops))
    assert got == pytest.approx(100 * 6 * least / 0.042)
    assert 0 < got < 100
    # the full-causal calls' time is not in it
    assert got == _reader("flash_window_fwd_roofline.mel").read(
        _ctx({FWD_BAND: [0.021, 3], FWD_BAND_JVP: [0.021, 3]}))


def test_backward_share_counts_calls_by_dkv_and_time_by_both():
    least = 64 * BAND_PAIRS * 10 * 128 / 197e12
    ops = {DKV_BAND: [0.024, 3], DQ_BAND: [0.018, 3],
           DKV_FULL: [0.5, 1], DQ_FULL: [0.5, 1]}
    got = _reader("flash_window_bwd_roofline.mel").read(_ctx(ops))
    assert got == pytest.approx(100 * 3 * least / 0.042)
    assert 0 < got < 100


def test_the_older_readers_would_misread_a_mixed_model():
    """`flash_fwd_roofline` gives every call the configuration's one
    window: over band and full calls together it counts the full
    layer's pairs as a band's. That is why the cell does not list it."""
    ops = {FWD_BAND: [0.021, 3], FWD_FULL: [0.012, 1]}
    mixed = _reader("flash_fwd_roofline.lm").read(_ctx(ops))
    band = _reader("flash_window_fwd_roofline.mel").read(_ctx(ops))
    assert mixed is not None and mixed != pytest.approx(band)


@pytest.mark.parametrize("name", ["flash_window_fwd_roofline.mel",
                                  "flash_window_bwd_roofline.mel"])
def test_nothing_to_read_is_none_never_zero(name):
    read = _reader(name).read
    # the parent's program: the same calls, no `_window` in any name
    parent = {FWD_FULL: [0.02, 4], FWD_FULL_JVP: [0.02, 4],
              DKV_FULL: [0.02, 4], DQ_FULL: [0.02, 4]}
    assert read(_ctx(parent)) is None
    assert read({**_ctx(parent), "trace": None}) is None
    band = {FWD_BAND: [0.02, 3], DKV_BAND: [0.02, 3], DQ_BAND: [0.02, 3]}
    assert read({**_ctx(band), "peaks": None}) is None
    assert read(_ctx(band, window=None)) is None    # a config with no window
    assert read(_ctx(band)) is not None


def test_the_shared_readers_serve_the_new_suffix():
    # 10 steps x 4 layers at 32,768 rows a layer
    counters = {"moe.rows_held": 32768 * 40, "moe.rows_max_expert": 2300 * 40}
    flops = 2 * 32768 * 2304 * 896
    got = _reader("moe_expert_matmul_roofline.mel").read(
        _ctx({GMM: [0.008, 4]}, counters))
    assert got == pytest.approx(100 * 4 * flops / 197e12 / 0.008)
    load = _reader("moe_load_max_over_mean.mel").read(_ctx({}, counters))
    assert load == pytest.approx(2300 / 2048)
