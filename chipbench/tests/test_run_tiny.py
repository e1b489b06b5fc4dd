"""`run.py` end to end at toy size on the CPU, for both drivers: the
last line keeps to the contract, and `correct` is true on sound code."""

import json

import pytest

from conftest import CELLS, TINY

RATE = {"resnet18_w8.train_bs8": "train_imgs_per_s",
        "lm_d64_l2.train_seq128": "train_tokens_per_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_line(run_cell, cell):
    res, err = run_cell(cell, seed=3000000019)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"        # the numbers compared come last
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 3
    assert set(res["metrics"]) >= {RATE[cell], "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    # every number compared stands beside its limit, on stderr too
    for name, (value, limit) in res["checks"].items():
        assert value <= limit
        assert f"check {name}:" in err
    assert err.strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_reports_only_what_it_read(run_cell, cell):
    res, _ = run_cell(cell, seed=7, trace=1, seconds=2.0)
    assert res["correct"] is True
    declared = {m["name"] for m in json.load(open(TINY))["per_layer"]
                if cell in m["workloads"]}
    assert set(res["metrics"]) <= declared
    # no device plane in a CPU trace: the readers of the device trace
    # and of the peaks return nothing and are left out, never 0
    assert not any(k.startswith(("device_idle_share", "step_mfu",
                                 "device_step_ms")) for k in res["metrics"])
    suffix = ".img" if "resnet" in cell else ".lm"
    assert res["metrics"]["window_compiles" + suffix]["value"] == 0
    if suffix == ".img":        # read from the host clock, so present here
        assert res["metrics"]["step_ms_p95.img"]["value"] > 0
    assert "busy_s" not in res["device"]


def test_command_line_needs_a_chip():
    import run

    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"], benchmark_file=TINY) == 1
