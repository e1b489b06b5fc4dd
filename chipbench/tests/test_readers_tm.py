"""The Trinity-Mini cell's readers on hand-made counters and events:
`moe_route_max_over_mean` over all the router's experts, the expert
products' roofline over the expert layers alone (the leading dense
layer holds no expert), and `None` where the program counts nothing."""

import os

import pytest

from conftest import CHIPBENCH

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GROUPED = ("%moe_grouped_matmul.3 = bf16[131072,1024]{1,0} custom-call("
           "bf16[131072,2048]{1,0}, bf16[16,2048,1024]{2,1,0}, s32[16]{0}), "
           "custom_call_target=\"tpu_custom_call\"")


def _reader(name):
    import loading

    return loading.load_module(os.path.join(CHIPBENCH, "layer_metrics"), name)


def _config():
    import loading

    return loading.load_cell(os.path.join(os.path.dirname(CHIPBENCH),
                                          "BENCHMARK.json"),
                             "trinity_mini_26b_a3b_ep8.train_seq8k")[1]


def _ctx(counters, ops=None, steps=40):
    return {"trace": {"ops": ops or {}}, "peaks": PEAKS,
            "window": {"steps": steps}, "counters": counters,
            "config": _config(), "traffic": {}}


def test_route_max_over_mean_over_all_experts():
    # 40 steps x 4 layers x 16,384 positions x 8 choices over 128 experts:
    # the mean expert 1,024 rows a step and layer, the fullest 1,300
    counters = {"moe.route_rows": 40 * 4 * 16384 * 8,
                "moe.route_rows_max": 40 * 4 * 1300,
                "moe.rows_held": 40 * 4 * 16000,
                "moe.rows_max_expert": 40 * 4 * 1100}
    got = _reader("moe_route_max_over_mean.tm").read(_ctx(counters))
    assert got == pytest.approx(1300 / 1024)
    held = _reader("moe_load_max_over_mean.tm").read(_ctx(counters))
    assert held == pytest.approx(1100 / 1000)
    # perfectly even: 1.0
    even = {"moe.route_rows": 128 * 50, "moe.route_rows_max": 50}
    assert _reader("moe_route_max_over_mean.tm").read(_ctx(even)) == 1.0


def test_route_reader_is_none_where_nothing_is_counted():
    read = _reader("moe_route_max_over_mean.tm").read
    # the parent's program: the held experts' counts, no route counts
    assert read(_ctx({"moe.rows_held": 10, "moe.rows_max_expert": 2})) is None
    assert read({**_ctx({"moe.route_rows": 8, "moe.route_rows_max": 1}),
                 "config": {}}) is None


def test_expert_products_read_over_the_expert_layers_alone():
    """The rows of a product are the window's counted rows over its
    steps and the four expert layers, not the five layers."""
    counters = {"moe.rows_held": 40 * 4 * 16384}
    ctx = _ctx(counters, {GROUPED: [0.5, 40 * 4 * 3]})
    got = _reader("moe_expert_matmul_roofline.tm").read(ctx)
    rows = 16384
    flops = 2.0 * rows * 2048 * 1024
    nbytes = (16 * 2048 * 1024 + rows * (2048 + 1024)) * 2
    least = 40 * 4 * 3 * max(flops / 197e12, nbytes / 819e9)
    assert got == pytest.approx(100 * least / 0.5)
    shared = _reader("moe_expert_matmul_roofline").read(ctx)
    assert shared == pytest.approx(got * 4 / 5, rel=0.01)
    assert _reader("moe_expert_matmul_roofline.tm").read(_ctx({})) is None
