"""Finding the benchmark's files by the names in `BENCHMARK.json`."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


_MODULES: dict = {}


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(directory: str, name: str):
    """`<directory>/<name>.py` as a module, loaded once; for a dotted
    metric name such as `step_mfu.img` the file of the part before the
    last dot serves every suffix unless the full name has its own file."""
    for stem in (name, name.rsplit(".", 1)[0]):
        path = os.path.join(directory, stem + ".py")
        if path in _MODULES:
            return _MODULES[path]
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "chipbench_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _MODULES[path] = mod
            return mod
    raise FileNotFoundError(f"no {name}.py under {directory}")


def load_cell(bench_path: str, name: str):
    """(cell, configuration, traffic, limits) of workload `name`: the
    entry of `BENCHMARK.json` and the three data files it leads to.
    `traffic/` and `limits/` sit beside the directory of the
    configuration's file."""
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {bench_path} "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    file = os.path.join(os.path.dirname(os.path.abspath(bench_path)),
                        entry["file"])
    base = os.path.dirname(os.path.dirname(file))
    return (cell, load_json(file),
            load_json(os.path.join(base, "traffic",
                                   cell["traffic"] + ".json")),
            load_json(os.path.join(base, "limits", cell["name"] + ".json")))
