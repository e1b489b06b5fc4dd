"""`trace_reduce.py` against one small recorded trace: three steps of
the toy LM cell, recorded on the TPU v5e (my chip run, PR 25) and kept
beside this file."""

import os

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "toy_lm_tpu.xplane.pb")
LAST_STEP_END_NS = 48_094_334       # end of the third `jit_step` execution


def test_device_numbers():
    r = trace_reduce.reduce_file(TRACE)
    assert r["devices"] == 1
    assert r["step_module"].startswith("jit_step(")
    # three executions of the step program: two whole periods between
    # the first start (44.953425 ms) and the last start (47.993171 ms)
    assert r["steps"] == 2
    assert r["window_s"] == pytest.approx(3.039746e-3, rel=1e-6)
    assert r["busy_s"] == pytest.approx(1.94432e-4, rel=1e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    # innermost operations add up to the busy time: nothing counted twice
    assert sum(s for s, _ in r["ops"].values()) == pytest.approx(
        r["busy_s"], rel=1e-2)
    families = dict(r["device_ops"])
    assert "flash_attention_fwd" in families
    assert "jvp_flash_attention_fwd_" in families
    assert r["device_ops"] == sorted(r["device_ops"], key=lambda x: -x[1])
    # no spans given: every gap is unattributed
    assert [k for k, _ in r["idle_gaps"]] == ["no_benchmark_span"]
    assert r["idle_gaps"][0][1] == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    # the host saw the last step end at host time 1 s; it sat in
    # "handler" from 3.0 to 1.5 ms before that and in "next_batch" since
    end = 1_000_000_000
    spans = [("handler", end - 3_000_000, end - 1_500_000),
             ("next_batch", end - 1_500_000, end)]
    r = trace_reduce.reduce_file(TRACE, spans, end)
    gaps = dict(r["idle_gaps"])
    # step 1 ends at 45.05 ms, step 2 runs 46.60-46.70, step 3 starts 47.99
    assert gaps["handler"] == pytest.approx(1.5e-3, abs=0.1e-3)
    assert gaps["next_batch"] == pytest.approx(1.3e-3, abs=0.1e-3)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)


def test_names():
    name = ("%fusion.3 = (f32[8]{0:T(8)}, bf16[2,4]{1,0:T(8,128)(2,1)}) "
            "fusion(f32[8]{0} %p), kind=kLoop")
    assert trace_reduce.short_name(name) == "fusion.3 fusion (f32[8], bf16[2,4])"
    assert trace_reduce.family(name) == "fusion.3 fusion (f32[8], bf16[2,4])"
    assert trace_reduce.family("%copy-start.12 = f32[4]{0} copy-start(x)") \
        == "copy-start"
    assert trace_reduce.family(
        "%jvp_flash_attention_fwd_.7 = (f32[48,4096,128]{2,1,0}) "
        "custom-call(s32[48]{0} %a)") == "jvp_flash_attention_fwd_"


def test_flash_roofline_reader_counts_what_ran():
    import json

    import loading
    import peaks

    reader = loading.load_module(os.path.join(loading.HERE, "layer_metrics"),
                             "flash_fwd_roofline.lm")
    cfg = json.load(open(os.path.join(loading.HERE, "tiny", "configs",
                                      "lm_d64_l2.json")))
    r = trace_reduce.reduce_file(TRACE)
    share = reader.read({"trace": r, "peaks": peaks.lookup("TPU v5 lite"),
                         "config": cfg})
    assert 0 < share < 100          # toy shapes: far from the roofline
    assert reader.read({"trace": None, "peaks": None, "config": cfg}) is None
