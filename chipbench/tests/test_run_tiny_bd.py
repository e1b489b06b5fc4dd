"""`run.py` end to end at toy size on the CPU for the block-diffusion
driver (`tiny/BENCHMARK_bd.json`: dim 64, 4 heads x 32, 8 experts with
4 held, L 32, Bd 4), the faults planted under it, and the readings
tool on it."""

import json
import os

import pytest

from conftest import CHIPBENCH

TINY_BD = os.path.join(CHIPBENCH, "tiny", "BENCHMARK_bd.json")
CELL = "sdar_d64_l2.train_bd4_seq32"


@pytest.fixture
def run_bd(capsys):
    import run

    def go(seed, trace=0, seconds=1.0):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      benchmark_file=TINY_BD, require_chip=False)
        out, err = capsys.readouterr()
        assert rc == 0
        return json.loads(out.strip().splitlines()[-1]), err

    return go


def test_untraced_line(run_bd):
    res, err = run_bd(seed=3000000019)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 3
    assert set(res["metrics"]) >= {"train_tokens_per_s", "setup_s"}
    for name, (value, limit) in res["checks"].items():
        assert value <= limit
        assert f"check {name}:" in err


def test_traced_line_reports_what_it_read(run_bd):
    res, _ = run_bd(seed=7, trace=1, seconds=2.0)
    assert res["correct"] is True
    declared = {m["name"] for m in json.load(open(TINY_BD))["per_layer"]}
    assert set(res["metrics"]) <= declared
    # no device plane in a CPU trace: the trace's readers are left out
    assert not any("roofline" in k or k.startswith(("device_", "step_mfu"))
                   for k in res["metrics"])
    assert res["metrics"]["window_compiles.bd"]["value"] == 0
    # the counters come from the program's loss, so they are here too:
    # the fullest of 4 held experts has at least the mean's rows
    assert res["metrics"]["moe_load_max_over_mean.bd"]["value"] >= 1.0


def test_units_are_data_tokens_and_the_batch_is_one_array():
    import loading

    cell, config, traffic, _ = loading.load_cell(TINY_BD, CELL)
    mod = loading.load_module(os.path.join(CHIPBENCH, "drivers"),
                              traffic["driver"])
    d = mod.Driver(config, traffic, 5, None)
    assert d.units_per_step == 2 * 32       # not the 2 x 64 positions
    d._make_pool()
    assert d.pool.shape == (4, 2, 3, 32) and d.pool.dtype.name == "int32"
    x0, xt, p = d.pool[:, :, 0], d.pool[:, :, 1], d.pool[:, :, 2].view("f4")
    assert x0.max() < 127 and set(xt[xt != x0]) == {127}
    assert (p > 0).all() and (p <= 1).all()
    assert (p.reshape(4, 2, 8, 4).std(axis=-1) == 0).all()   # p of a block
    d2 = mod.Driver(config, traffic, 5, None)
    d2._make_pool()
    assert (d.pool == d2.pool).all()        # the seed decides the batches


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_planted_fault_is_not_correct(fault):
    import check
    import faults
    import loading

    cell, config, traffic, limits = loading.load_cell(TINY_BD, CELL)
    mod = loading.load_module(os.path.join(CHIPBENCH, "drivers"),
                              traffic["driver"])
    d = mod.Driver(config, traffic, 11, None)
    d._build()
    faults.plant(d, fault)
    d.setup()
    compared = check.compare(d.program_numbers,
                             d.reference_numbers("float32"), limits)
    assert not all(c["ok"] for c in compared.values())


def test_the_mask_tokens_experts_are_dealt_one_to_this_chip():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import loading
    import weights_stacked

    cell, config, traffic, _ = loading.load_cell(TINY_BD, CELL)
    mod = loading.load_module(os.path.join(CHIPBENCH, "drivers"),
                              traffic["driver"])
    config = {**config, "router_width": 128, "num_experts": 16,
              "experts_held_first": 32, "num_experts_per_tok": 8}
    d = mod.Driver(config, traffic, 5, None)
    d._build()
    raw = weights_stacked.generate(d.shapes, weights_stacked.seed_key(5))
    placed = d._weights(weights_stacked.seed_key(5))
    e = np.asarray(raw["embed"]["table"][d.mask_id])
    h = e / np.sqrt((e ** 2).mean() + 1e-6)
    for layer, (a, b) in enumerate(zip(raw["blocks"], placed["blocks"])):
        old = np.asarray(a["moe"]["router"]["kernel"])
        new = np.asarray(b["moe"]["router"]["kernel"])
        # a permutation of the columns, nothing else touched
        assert sorted(map(tuple, old.T)) == sorted(map(tuple, new.T))
        top8 = np.argsort(-(h @ new))[:8]
        held = [x for x in top8 if 32 <= x < 48]
        assert len(held) == 1
        assert held[0] == top8[layer % 8]
    for path, x in jax.tree_util.tree_leaves_with_path(raw):
        if "router" not in jax.tree_util.keystr(path):
            y = placed
            for k in path:
                y = y[getattr(k, "key", getattr(k, "idx", None))]
            assert jnp.array_equal(x, y)
