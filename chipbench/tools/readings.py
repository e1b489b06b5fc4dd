#!/usr/bin/env python3
"""Readings for the limits of `correct`, on the chip, at a cell's own size.

    python3 chipbench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        [--control fp8] [--faults half_batch,unchanged] [--out file.jsonl]

For each seed, in one process: the program's first steps (its lower
reading) against the float32 reference; with `--control`, the reference
in that lower precision put in the program's place; with `--faults`, the
program with each fault planted (`faults.py`). Training's readings need
no measured window. One JSON line a seed: every number of `check.py`
for each of these, with the leaf it was read on.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), HERE]


def main(argv=None, *, benchmark_file=None, require_chip=True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import check
    import faults
    import loading

    bench_path = benchmark_file or os.path.join(os.path.dirname(HERE), "BENCHMARK.json")
    cell, config, traffic, _ = loading.load_cell(bench_path, args.workload)
    import jax
    from paddle_tpu import compilation_cache

    if require_chip and jax.default_backend() != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 1
    compilation_cache.enable()
    devices = jax.devices()[:cell["chips"]]
    driver_mod = loading.load_module(os.path.join(HERE, "drivers"),
                                 traffic["driver"])
    plain = driver_mod.Driver(config, traffic, 0, devices)
    broken = {}
    for fault in filter(None, args.faults.split(",")):
        broken[fault] = driver_mod.Driver(config, traffic, 0, devices)
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        line = {"workload": args.workload, "seed": seed}
        plain.seed = seed
        plain.setup()
        program = plain.program_numbers
        plain.free()
        reference = plain.reference_numbers("float32")
        line["program"] = check.numbers(program, reference)
        if args.control:
            line["control_" + args.control] = check.numbers(
                plain.reference_numbers(args.control), reference)
        for fault, d in broken.items():
            d.seed = seed
            if not d.built():
                d._build()
                faults.plant(d, fault)
            d.setup()
            line[fault] = check.numbers(d.program_numbers, reference)
            d.free()
        line["seconds"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
