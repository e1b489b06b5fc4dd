"""The eight metrics read from the program's compile recorder
(`layer_metrics/setup_timeline.py`): six that move `setup_s` and the two
`window_compile_ms.*`, at toy size on the CPU. `tiny/BENCHMARK_setup.json`
is the toy file plus the eight entries. A run is a process of its own
here, as on the chip: the recorder's rows are the process's."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import CELLS, CHIPBENCH

SETUP = os.path.join(CHIPBENCH, "tiny", "BENCHMARK_setup.json")
SECONDS = ("setup_import_s", "setup_trace_s", "setup_lower_s",
           "setup_cache_read_s", "setup_backend_compile_s")
SIX = set(SECONDS) | {"setup_cache_misses"}
WINDOW = {CELLS[0]: "window_compile_ms.img",
          CELLS[1]: "window_compile_ms.tokens"}
OUTSIDE = {CELLS[0]: "window_compiles.img", CELLS[1]: "window_compiles.lm"}
READERS = sorted(SIX) + ["window_compile_ms"]

RUN = """
import sys
sys.path.insert(0, {chipbench!r})
import run
sys.exit(run.main(["--workload", {cell!r}, "--seed", {seed!r}, "--seconds",
                   "2.0", "--trace", "1"], benchmark_file={bench!r},
                  require_chip=False))
"""


def traced(cell, seed, cache_dir):
    """One traced run in a fresh process with its compile cache in
    `cache_dir`: the metrics by name, and the seconds from process
    start at which `run.py` noted the end of set-up."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    done = subprocess.run(
        [sys.executable, "-c", RUN.format(chipbench=CHIPBENCH, cell=cell,
                                          seed=str(seed), bench=SETUP)],
        env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    res = json.loads(done.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    setup_s = float(re.search(r"chipbench\s+([0-9.]+)s set-up done",
                              done.stderr).group(1))
    return {k: v["value"] for k, v in res["metrics"].items()}, setup_s


def test_the_file_is_the_toy_file_plus_the_eight():
    toy = json.load(open(os.path.join(CHIPBENCH, "tiny", "BENCHMARK.json")))
    mine = json.load(open(SETUP))
    added = mine["per_layer"][len(toy["per_layer"]):]
    assert [m["name"] for m in added] == [
        "setup_import_s", "setup_trace_s", "setup_lower_s",
        "setup_cache_read_s", "setup_backend_compile_s",
        "setup_cache_misses", WINDOW[CELLS[0]], WINDOW[CELLS[1]]]
    for m in added:
        if m["name"] in SIX:
            assert (m["moves"], m["workloads"]) == ("setup_s", list(CELLS))
        else:
            assert m["workloads"] == [c for c in CELLS
                                      if WINDOW[c] == m["name"]]
    mine["per_layer"] = mine["per_layer"][:len(toy["per_layer"])]
    assert mine == toy
    # and the entries are the real file's, but for the cells' names
    real = json.load(open(os.path.join(os.path.dirname(CHIPBENCH),
                                       "BENCHMARK.json")))
    strip = lambda ms: [{k: v for k, v in m.items() if k != "workloads"}
                        for m in ms]
    assert strip(real["per_layer"][-8:]) == strip(added)


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reports_all_eight_cold_then_warm(cell, tmp_path):
    cold, cold_setup_s = traced(cell, 3900000011, tmp_path)
    warm, warm_setup_s = traced(cell, 3900000017, tmp_path)
    for m, setup_s in ((cold, cold_setup_s), (warm, warm_setup_s)):
        assert SIX | {WINDOW[cell]} <= set(m)
        assert not (set(WINDOW.values()) - {WINDOW[cell]}) & set(m)
        assert all(m[name] >= 0 for name in SECONDS)
        assert m["setup_import_s"] > 0 and m["setup_trace_s"] > 0 \
            and m["setup_lower_s"] > 0
        assert sum(m[name] for name in SECONDS) < setup_s
        # nothing compiles in the window, seen from both sides
        assert m[WINDOW[cell]] == m[OUTSIDE[cell]] == 0
    assert cold["setup_cache_misses"] > 0 and cold["setup_cache_read_s"] == 0
    assert warm["setup_cache_misses"] == 0 and warm["setup_cache_read_s"] > 0


# -- the readers' arithmetic, on rows made by hand ---------------------------

@pytest.fixture
def readers(monkeypatch):
    """Every reader, and a way to stand a timeline of the test's own in
    the program's place."""
    from loading import load_module

    from paddle_tpu.obs.trace import Timeline

    metrics = os.path.join(CHIPBENCH, "layer_metrics")
    setup = load_module(metrics, "setup_timeline")
    mods = {name: load_module(metrics, name) for name in READERS}

    def stand_in(rows=(), counters=(), keep=64):
        tl = Timeline(keep=keep)
        for row in rows:
            tl.add(*row)
        for name, n in counters:
            tl.count(name, n)
        monkeypatch.setattr(setup, "_timeline", lambda: (tl, keep))
        return mods

    return stand_in


MS = 1_000_000
CTX = {"window": {"t0": 1.0, "span_s": 2.0}}       # t0 at 1000 ms


def test_every_reader_returns_none_without_a_recorder(readers):
    mods = readers(rows=[("trainer.step", 10 * MS, 20 * MS, 0),
                         ("feeder.read", 1100 * MS, 1200 * MS, 1)],
                   counters=[("trainer.steps", 2)])
    assert {name: m.read(CTX) for name, m in mods.items()} \
        == dict.fromkeys(READERS)


def test_every_reader_returns_none_when_the_ring_is_full(readers):
    rows = [(f"compile.trace:f{i}", i * MS, (i + 1) * MS) for i in range(8)]
    mods = readers(rows=[("import.paddle_tpu", 0, MS)] + rows,
                   counters=[("compile.cache_misses", 3)], keep=8)
    assert {name: m.read(CTX) for name, m in mods.items()} \
        == dict.fromkeys(READERS)


def test_phases_are_unions_cut_at_the_windows_start(readers):
    rows = [
        ("import.paddle_tpu", 0, 100 * MS),
        # an inner trace fires inside the outer's interval: 200..300 once
        ("compile.trace:inner", 220 * MS, 240 * MS),
        ("compile.trace:outer", 200 * MS, 300 * MS),
        ("compile.trace:other_thread", 290 * MS, 310 * MS),
        ("compile.lower:jit(outer)", 300 * MS, 350 * MS),
        ("compile.cache_read", 360 * MS, 390 * MS),
        ("compile.backend:jit(outer)", 355 * MS, 400 * MS),
        ("compile.backend:jit(cold)", 400 * MS, 900 * MS),
        # closes after t0: the window's, not set-up's
        ("compile.trace:late", 990 * MS, 1010 * MS),
        ("compile.lower:jit(late)", 1010 * MS, 1013 * MS),
        ("trainer.step", 1000 * MS, 3000 * MS, 0),
        # starts in the window and outlasts it
        ("compile.backend:jit(late)", 2990 * MS, 3100 * MS),
    ]
    mods = readers(rows=rows, counters=[("compile.cache_misses", 1),
                                        ("compile.cache_hits", 1)])
    got = {name: m.read(CTX) for name, m in mods.items()}
    assert got == pytest.approx({
        "setup_import_s": 0.1, "setup_trace_s": 0.11, "setup_lower_s": 0.05,
        "setup_cache_read_s": 0.03, "setup_backend_compile_s": 0.545 - 0.03,
        "setup_cache_misses": 1, "window_compile_ms": 10 + 3 + 10})


def test_a_warm_recorder_reads_zero_not_none(readers):
    mods = readers(rows=[("compile.trace:f", 10 * MS, 20 * MS)],
                   counters=[("compile.cache_hits", 1)])
    assert mods["setup_cache_misses"].read(CTX) == 0
    assert mods["setup_cache_read_s"].read(CTX) == 0
    assert mods["window_compile_ms"].read(CTX) == 0
    assert mods["setup_import_s"].read(CTX) is None     # no such row
