"""The six `trainer / input` metrics read from the program's own step
timeline (`layer_metrics/program_timeline.py`), at toy size on the CPU:
`tiny/BENCHMARK_timeline.json` is the toy file plus the six entries."""

import json
import os

import pytest

from conftest import CELLS, CHIPBENCH

TIMELINE = os.path.join(CHIPBENCH, "tiny", "BENCHMARK_timeline.json")
SIX = {"feeder_queue_wait_share.img", "feeder_put_share.img",
       "dispatch_share.img", "feeder_read_ms.img", "feeder_convert_ms.img",
       "h2d_bytes_per_step.img"}


@pytest.fixture
def traced(capsys):
    import run

    def go(workload, seed):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "2.0", "--trace", "1"],
                      benchmark_file=TIMELINE, require_chip=False)
        out, _ = capsys.readouterr()
        assert rc == 0
        return json.loads(out.strip().splitlines()[-1])

    return go


def test_the_file_is_the_toy_file_plus_the_six():
    toy = json.load(open(os.path.join(CHIPBENCH, "tiny", "BENCHMARK.json")))
    mine = json.load(open(TIMELINE))
    added = mine["per_layer"][len(toy["per_layer"]):]
    assert {m["name"] for m in added} == SIX
    assert all(m["workloads"] == [CELLS[0]] for m in added)
    mine["per_layer"] = mine["per_layer"][:len(toy["per_layer"])]
    assert mine == toy


def test_image_cell_reports_all_six(traced):
    res = traced(CELLS[0], seed=3000000019)
    assert res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert SIX <= set(m)
    # the two waits lie inside the benchmark's span around next(batch)
    assert (m["feeder_queue_wait_share.img"] + m["feeder_put_share.img"]
            <= m["input_wait_share.img"] + 1)
    for name in SIX:
        assert m[name] > 0
    for name in ("feeder_queue_wait_share.img", "feeder_put_share.img",
                 "dispatch_share.img"):
        assert m[name] < 100
    traffic = json.load(open(os.path.join(
        CHIPBENCH, "tiny", "traffic", "train_bs8.json")))
    config = json.load(open(os.path.join(
        CHIPBENCH, "tiny", "configs", "resnet18_w8.json")))
    b, hw = traffic["batch"], config["model"]["image_hw"]
    assert m["h2d_bytes_per_step.img"] == b * hw * hw * 3 * 4 + b * 8


def test_lm_cell_reports_none_of_the_six(traced, tmp_path, capsys):
    """It runs neither `Trainer` nor `DataFeeder`: the readers find
    nothing and the line leaves them out, even when the six are listed
    for the cell and an image cell of this process left rows and
    counters behind."""
    import run

    traced(CELLS[0], seed=11)
    bench = json.load(open(TIMELINE))
    for m in bench["per_layer"]:
        if m["name"] in SIX:
            m["workloads"].append(CELLS[1])
    for c in bench["configs"]:
        c["file"] = os.path.join(os.path.dirname(TIMELINE), c["file"])
    everywhere = tmp_path / "BENCHMARK.json"
    everywhere.write_text(json.dumps(bench))
    rc = run.main(["--workload", CELLS[1], "--seed", "12", "--seconds",
                   "2.0", "--trace", "1"], benchmark_file=str(everywhere),
                  require_chip=False)
    out, _ = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert "window_compiles.lm" in res["metrics"]
    assert not SIX & set(res["metrics"])
