"""Toy cells on the CPU. Run by hand: `python -m pytest chipbench/tests -q`
(tier-1 does not collect this directory)."""

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIPBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(CHIPBENCH), CHIPBENCH,
          os.path.join(CHIPBENCH, "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = os.path.join(CHIPBENCH, "tiny", "BENCHMARK.json")
CELLS = ("resnet18_w8.train_bs8", "lm_d64_l2.train_seq128")


@pytest.fixture
def run_cell(capsys):
    """Drives `run.main` on a toy cell and returns its result line."""
    import run

    def go(workload, seed, trace=0, seconds=1.0):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      benchmark_file=TINY, require_chip=False)
        out, err = capsys.readouterr()
        assert rc == 0
        return json.loads(out.strip().splitlines()[-1]), err

    return go
