"""`flops/flash_bwd.py` against a hand count, and the reader
`layer_metrics/flash_bwd_roofline.py` on event names as the v5e's trace
writes them (the kernels' names from `ops/flash_attention.py`, PR 32)."""

import json
import os

import pytest

from conftest import CHIPBENCH


def _load(kind, name):
    import loading

    return loading.load_module(os.path.join(CHIPBENCH, kind), name)


def test_flash_backward_counts():
    f = _load("flops", "flash_bwd")
    # batch 2, 24 heads, 4096 x 4096 causal, head 128: five matmul terms
    # of 2 x 128 FLOPs a (query, key) pair, over T(T+1)/2 pairs; q, k, v,
    # o, g read and dq, dk, dv written
    flops, nbytes = f.backward(batch=2, heads=24, seq=4096, head_dim=128,
                               window=4096, bytes_per_value=2)
    assert flops == 2 * 24 * (4096 * 4097 // 2) * 5 * 2 * 128
    assert abs(flops / 1e12 - 0.5155) < 5e-5
    assert nbytes == 8 * 2 * 24 * 4096 * 128 * 2
    # five matmul terms for the forward's two on the same pairs, under a
    # window too
    fwd, _ = _load("flops", "flash").forward(
        batch=1, heads=8, seq=8192, head_dim=64, window=1024,
        bytes_per_value=2)
    bwd, _ = f.backward(batch=1, heads=8, seq=8192, head_dim=64,
                        window=1024, bytes_per_value=2)
    assert 2 * bwd == 5 * fwd


def _event(name, operand="bf16[48,4096,128]{2,1,0:T(8,128)(2,1)}"):
    return (f"%{name} = ({operand}, {operand}) custom-call(s32[48]{{0}} %a, "
            f"{operand} %b, {operand} %c, f32[48,1,4096]{{2,1,0}} %d)")


def test_flash_bwd_roofline_reader():
    import peaks

    reader = _load("layer_metrics", "flash_bwd_roofline.lm")
    cfg = json.load(open(os.path.join(CHIPBENCH, "configs",
                                      "starcoder2_3b_l4.json")))
    pk = peaks.lookup("TPU v5 lite")
    ops = {
        _event("flash_attention_bwd_dkv.3"): [4 * 3.2e-3, 4],
        _event("flash_attention_bwd_dq.5"): [4 * 2.9e-3, 4],
        _event("flash_attention_fwd.2"): [8 * 5.5e-3, 8],
        "%fusion.7 = bf16[48,4607,512]{2,1,0} fusion(f32[8]{0} %x)":
            [0.2, 32],
    }
    share = reader.read({"trace": {"ops": ops}, "peaks": pk, "config": cfg})
    # 0.5155 TFLOP at 197 TFLOP/s is 2.617 ms of the 6.1 ms a call took
    assert share == pytest.approx(100 * 0.5155e12 / 197e12 / 6.1e-3, rel=1e-3)
    # float32 operands: the same FLOPs bound it
    f32 = {_event("flash_attention_bwd_dkv", "f32[48,4096,128]{2,1,0}"):
           [3.2e-3, 1]}
    assert reader.read({"trace": {"ops": f32}, "peaks": pk,
                        "config": cfg}) == pytest.approx(81.8, rel=1e-2)
    # a program without the kernels (the parent's scans, whose scope is
    # `flash_attention_bwd` with no kernel under it) gives no reading
    del ops[_event("flash_attention_bwd_dkv.3")]
    del ops[_event("flash_attention_bwd_dq.5")]
    assert reader.read({"trace": {"ops": ops}, "peaks": pk,
                        "config": cfg}) is None
    assert reader.read({"trace": None, "peaks": None, "config": cfg}) is None


def test_the_recorded_toy_trace_has_no_backward_kernel():
    import peaks
    import trace_reduce

    reader = _load("layer_metrics", "flash_bwd_roofline.lm")
    r = trace_reduce.reduce_file(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "toy_lm_tpu.xplane.pb"))
    assert reader.read({"trace": r, "peaks": peaks.lookup("TPU v5 lite"),
                        "config": {}}) is None
