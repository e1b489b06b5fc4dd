"""The two gated-delta readers on synthetic events (a share from the
operand shapes in each event's text, `None` where the program has no
such kernel), and the `flash_*_roofline` readers on the Qwen3-Next
configuration: they read head_dim 256 and 16 query heads (2 KV heads
expanded before the kernel) from the events, and no window from the
configuration, so its one full-attention layer is counted causal."""

import os

import pytest

from conftest import CHIPBENCH

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ROWS = "bf16[64,8192,128]{2,1,0}"
CHUNKS = "f32[64,128,1,64]{3,2,1,0}"
STATES = "f32[64,128,128,128]{3,2,1,0}"
FWD = (f"%gated_delta_fwd.3 = {ROWS} custom-call({ROWS}, {ROWS}, {ROWS}, "
       f"{CHUNKS}, {CHUNKS}), custom_call_target=\"tpu_custom_call\"")
FWD_JVP = (f"%jvp_gated_delta_fwd_.5 = ({ROWS}, {STATES}) custom-call({ROWS}, "
           f"{ROWS}, {ROWS}, {CHUNKS}, {CHUNKS}), "
           "custom_call_target=\"tpu_custom_call\"")
BWD = (f"%gated_delta_bwd.7 = ({ROWS}, {ROWS}, {ROWS}, {CHUNKS}, {CHUNKS}) "
       f"custom-call({ROWS}, {ROWS}, {ROWS}, {CHUNKS}, {CHUNKS}, {STATES}, "
       f"{ROWS}), custom_call_target=\"tpu_custom_call\"")
FLASH = ("(bf16[32,8192,256]{2,1,0}, f32[32,8192,128]{2,1,0}) custom-call("
         "s32[32]{0}, bf16[32,8192,256]{2,1,0}, bf16[32,8192,256]{2,1,0}, "
         "bf16[32,8192,256]{2,1,0}), custom_call_target=\"tpu_custom_call\"")
FLASH_FWD = "%jvp_flash_attention_fwd_.9 = " + FLASH
FLASH_DKV = "%flash_attention_bwd_dkv.5 = " + FLASH
FLASH_DQ = "%flash_attention_bwd_dq.6 = " + FLASH


def _reader(name):
    import loading

    return loading.load_module(os.path.join(CHIPBENCH, "layer_metrics"), name)


def _config():
    import loading

    return loading.load_cell(os.path.join(os.path.dirname(CHIPBENCH),
                                          "BENCHMARK.json"),
                             "qwen3_next_80b_a3b_ep16.train_seq8k")[1]


def _ctx(ops):
    return {"trace": {"ops": ops}, "peaks": PEAKS, "window": {"steps": 5},
            "counters": {}, "config": _config(), "traffic": {}}


def test_forward_share_from_the_operands():
    # 64 rows of 8192 positions, 128 x 128: bytes bound the call
    least = 64 * 8192 * (2 * 4 * 128 + 8) / 819e9
    ops = {FWD: [0.02, 15], FWD_JVP: [0.03, 15],
           "%fusion.1 = f32[8] fusion()": [1.0, 5]}
    got = _reader("gated_delta_fwd_roofline.qn").read(_ctx(ops))
    assert got == pytest.approx(100 * 30 * least / 0.05)
    assert 0 < got < 100
    fwd = _reader("gated_delta_fwd_roofline")
    assert fwd.operands(FWD) == ("bf16", 64, 8192, 128, 128)
    assert fwd.operands(BWD) == ("bf16", 64, 8192, 128, 128)


def test_backward_share_counts_its_own_events():
    least = 64 * 8192 * (2 * 4 * 128 + 8 + 2 * 3 * 128 + 8) / 819e9
    ops = {BWD: [0.04, 15], FWD: [0.02, 15]}
    got = _reader("gated_delta_bwd_roofline.qn").read(_ctx(ops))
    assert got == pytest.approx(100 * 15 * least / 0.04)
    assert 0 < got < 100


@pytest.mark.parametrize("name", ["gated_delta_fwd_roofline.qn",
                                  "gated_delta_bwd_roofline.qn"])
def test_nothing_to_read_is_none_never_zero(name):
    read = _reader(name).read
    # the parent's program: attention kernels, no gated delta kernel
    parent = {FLASH_FWD: [0.02, 4], FLASH_DKV: [0.02, 4]}
    assert read(_ctx(parent)) is None
    assert read({**_ctx(parent), "trace": None}) is None
    assert read({**_ctx({FWD: [0.02, 1], BWD: [0.02, 1]}),
                 "peaks": None}) is None
    assert read(_ctx({FWD: [0.02, 1], BWD: [0.02, 1]})) is not None


def test_flash_readers_read_this_configurations_full_layer():
    """The events of the full layer's kernels at 2 x 16 heads of 256:
    the readers take head_dim 256 and 32 rows from the text and, with no
    `sliding_window` in the configuration, the causal pairs."""
    pairs = 8192 * 8193 // 2
    ctx = _ctx({FLASH_FWD: [0.06, 5], FLASH_DKV: [0.09, 5],
                FLASH_DQ: [0.07, 5]})
    assert ctx["config"].get("sliding_window") is None
    fwd = _reader("flash_fwd_roofline.qn").read(ctx)
    assert fwd == pytest.approx(100 * 5 * 32 * pairs * 4 * 256 / 197e12 / 0.06)
    bwd = _reader("flash_bwd_roofline.qn").read(ctx)
    assert bwd == pytest.approx(100 * 5 * 32 * pairs * 10 * 256 / 197e12 / 0.16)
    assert 0 < fwd < 100 and 0 < bwd < 100
