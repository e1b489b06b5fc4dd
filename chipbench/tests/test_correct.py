"""`correct` comes out false for the control (the reference one
precision down, in the program's place) and for each fault a training
cell can have, planted under the timed path of a whole run."""

import os

import pytest

from conftest import CELLS, CHIPBENCH, TINY


def _driver(cell, seed):
    import loading

    _, config, traffic, limits = loading.load_cell(TINY, cell)
    mod = loading.load_module(os.path.join(CHIPBENCH, "drivers"),
                          traffic["driver"])
    return mod, mod.Driver(config, traffic, seed, None), limits


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    import check

    _, d, limits = _driver(cell, seed=1)
    d._build()
    d._make_pool()
    reference = d.reference_numbers("float32")
    assert all(c["ok"] for c in check.compare(reference, reference,
                                              limits).values())
    control = check.compare(d.reference_numbers("fp8"), reference, limits)
    assert not all(c["ok"] for c in control.values()), control


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_under_the_timed_path_is_not_correct(run_cell, monkeypatch,
                                                   cell, fault):
    import faults

    mod, _, _ = _driver(cell, seed=1)
    build = mod.Driver._build

    def broken_build(self):
        build(self)
        faults.plant(self, fault)

    monkeypatch.setattr(mod.Driver, "_build", broken_build)
    res, err = run_cell(cell, seed=2)
    assert res["correct"] is False
    assert "FAILED" in err
    assert any(v > lim for v, lim in res["checks"].values())
