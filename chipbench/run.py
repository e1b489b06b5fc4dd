#!/usr/bin/env python3
"""The one command of the chip benchmark.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It reads `BENCHMARK.json` at the root of the checkout, finds the cell's
configuration file, its traffic file and, through the traffic file, its
driver (`drivers/<kind>.py`), and knows no cell, configuration or metric
by name. A run is: set-up (weights from the seed, warm-up, the first
steps whose numbers `correct` compares), a measured window of
`--seconds`, the reading of device memory, then the plain reference on
the freed chip and the comparison. The last line of standard output is
one JSON object (the builder's contract); the numbers compared and
their limits are its last key and the last lines of standard error.

No TPU, fewer chips than the cell asks for, a `device_kind` without a
row in `peaks.json`, or a directory without the program: exit code 1 and
no result line.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse
import contextlib
import gc
import json
import os
import queue
import shutil
import sys
import tempfile
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from loading import load_cell, load_json, load_module  # noqa: E402


def note(what: str) -> None:
    """Progress on standard error, with the seconds since process start."""
    print(f"chipbench {time.perf_counter() - T_PROCESS_START:8.2f}s {what}",
          file=sys.stderr, flush=True)


# -- statistics over the window's completions -----------------------------
# A traffic file maps each end-to-end metric it reports to one of these.


def _rate(w):
    return w["units"] / w["span_s"]


STATISTICS = {"rate": _rate}


class Watcher(threading.Thread):
    """Stamps the completion of every step off the training thread: the
    driver puts a function here as soon as a step is dispatched, which
    blocks until the step's loss is on the host and returns it; this
    thread calls it and notes the host clock."""

    def __init__(self):
        super().__init__(daemon=True)
        self.q: queue.Queue = queue.Queue()
        self.completions: list = []
        self.losses: list = []
        self.error = None

    def put(self, read_loss) -> None:
        self.q.put(read_loss)

    def run(self) -> None:
        try:
            while True:
                read_loss = self.q.get()
                if read_loss is None:
                    return
                loss = float(read_loss())
                self.completions.append(time.perf_counter())
                self.losses.append(loss)
        except BaseException as e:      # surfaced by close()
            self.error = e

    def close(self) -> None:
        self.q.put(None)
        self.join(120.0)
        if self.is_alive():
            raise RuntimeError("watcher thread did not finish")
        if self.error is not None:
            raise self.error


class CompileCounter:
    """Counts jax's compile events (tracing, lowering, backend compile)
    while `counting` is set: there should be none inside the window."""

    def __init__(self, jax):
        self.n = 0
        self.counting = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.counting and event.startswith("/jax/core/compile/"):
            self.n += 1


class Spans:
    """The benchmark's own host spans around the calls into the program,
    kept in memory: (name, start, end) on `time.perf_counter_ns`. A
    traced run lays them over the device trace to say what the host was
    doing in each idle gap."""

    def __init__(self):
        self.rows: list = []
        self._open: dict = {}

    def begin(self, name: str) -> None:
        self._open[name] = time.perf_counter_ns()

    def end(self, name: str) -> None:
        self.rows.append((name, self._open.pop(name),
                          time.perf_counter_ns()))

    @contextlib.contextmanager
    def __call__(self, name: str):
        self.begin(name)
        try:
            yield
        finally:
            self.end(name)


class Tracer:
    """Profiles `steps` steady steps of the window, from the training
    thread: the driver calls `step_dispatched(i, read_loss)` after each
    dispatch. Only the device is traced (the host tracer would record
    every chunk of a host-side transpose: 376 MB and half the steps for
    12 image batches, PERF.md section 6); the host's side is `Spans`.
    The two clocks are tied at the end: the last traced step's loss
    reaches the host as its program ends on the device. The trace goes
    under TMPDIR and is removed once reduced."""

    def __init__(self, jax, first_step: int, steps: int):
        self.jax, self.first, self.steps = jax, first_step, steps
        self.dir = self.stop_at = None
        self.active = False
        self.done = False
        self.host_ns_at_end = None

    def step_dispatched(self, i: int, read_loss) -> None:
        if self.done:
            return
        if not self.active and i >= self.first:
            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            self.jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.active, self.stop_at = True, i + self.steps
        elif self.active and i >= self.stop_at:
            read_loss()
            self.host_ns_at_end = time.perf_counter_ns()
            self.stop()

    def stop(self) -> None:
        if self.active:
            self.jax.profiler.stop_trace()
            self.active, self.done = False, True

    def reduce(self, spans: Spans):
        import trace_reduce

        if not self.done:
            return None
        try:
            return trace_reduce.reduce_dir(self.dir, spans.rows,
                                           self.host_ns_at_end)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def metric_in_cell(metric: dict, cell: dict, reported: set) -> bool:
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def device_record(jax, devices) -> dict:
    """The peak on the fullest chip is what was allocated at the peak
    plus what the runtime reserved for the programs' scratch space: the
    two are disjoint in `memory_stats()` (free = limit - in use -
    reserved; PERF.md section 2)."""
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def main(argv=None, *, benchmark_file: str | None = None,
         require_chip: bool = True) -> int:
    """`benchmark_file` and `require_chip` exist for `chipbench/tests`
    (toy cells on the CPU); the command line has neither."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_path = benchmark_file or os.path.join(ROOT, "BENCHMARK.json")
    bench = load_json(bench_path)
    cell, config, traffic, limits = load_cell(bench_path, args.workload)

    try:
        import jax
        from paddle_tpu import compilation_cache
    except ImportError as e:
        print(f"chipbench: the program is not in this directory: {e}",
              file=sys.stderr)
        return 1
    import peaks as peaks_mod

    if require_chip:
        if jax.default_backend() != "tpu":
            print(f"chipbench: no TPU (backend {jax.default_backend()!r})",
                  file=sys.stderr)
            return 1
        if len(jax.devices()) < cell["chips"]:
            print(f"chipbench: {len(jax.devices())} chips, the cell asks "
                  f"for {cell['chips']}", file=sys.stderr)
            return 1
    devices = jax.devices()[:cell["chips"]]
    peaks = peaks_mod.lookup(devices[0].device_kind) if require_chip else None
    compilation_cache.enable()
    compiles = CompileCounter(jax)

    driver_mod = load_module(os.path.join(HERE, "drivers"), traffic["driver"])
    driver = driver_mod.Driver(config, traffic, args.seed, devices)

    # -- set-up: weights, warm-up, and the first steps `correct` compares
    driver.setup()
    note("set-up done: weights, warm-up and the compared steps")
    tracer = None
    if args.trace:
        tracer = Tracer(jax, traffic["trace_first_step"],
                        traffic["trace_steps"])
    watcher, spans = Watcher(), Spans()
    watcher.start()
    gc.collect()
    gc.freeze()

    # -- the measured window
    t0 = time.perf_counter()
    setup_s = t0 - T_PROCESS_START
    compiles.counting = True
    counters = driver.window(t0 + args.seconds, watcher, tracer, spans)
    watcher.close()
    note("window closed, every step complete")
    if tracer is not None:
        tracer.stop()
        note("trace stopped")
    compiles.counting = False
    counters["window_compiles"] = compiles.n
    completions = watcher.completions
    losses = watcher.losses
    attempted = len(losses)
    failed = sum(1 for x in losses if not x == x or abs(x) == float("inf"))
    window = {"t0": t0, "completions": completions,
              "steps": attempted, "units": attempted * driver.units_per_step,
              "span_s": (completions[-1] - t0) if completions else 0.0}

    device = device_record(jax, devices)
    driver.free()
    gc.unfreeze()
    gc.collect()

    # -- metrics
    metrics = {}
    reported = set(traffic["end_to_end"]) | {"setup_s"}
    if args.trace == 0:
        for m in bench["end_to_end"]:
            if not metric_in_cell(m, cell, reported):
                continue
            if m["name"] == "setup_s":
                value = setup_s
            elif m["name"] in traffic["end_to_end"]:
                value = STATISTICS[traffic["end_to_end"][m["name"]]](window)
            else:
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = None
    if args.trace == 1:
        trace = tracer.reduce(spans)
        note("trace reduced")
        if trace is not None and trace.get("busy_s"):
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            breakdown = {"device_ops": trace["device_ops"][:10],
                         "idle_gaps": trace["idle_gaps"][:10]}
        ctx = {"trace": trace, "window": window, "counters": counters,
               "peaks": peaks, "chips": len(devices), "config": config,
               "traffic": traffic,
               "flops_per_step": driver.model_flops_per_step()}
        for m in bench["per_layer"]:
            if not metric_in_cell(m, cell, reported):
                continue
            reader = load_module(os.path.join(HERE, "layer_metrics"),
                                 m["name"])
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # -- correct: the plain reference, on the freed chip
    import check

    compared = check.compare(driver.program_numbers,
                             driver.reference_numbers("float32"), limits)
    note("reference done")
    correct = failed == 0 and attempted > 0 and all(
        c["ok"] for c in compared.values())

    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: [c["value"], c["limit"]]
                        for k, c in compared.items()}
    print(json.dumps(result), flush=True)
    for k, c in compared.items():
        print(f"check {k}: {c['value']:.6g} limit {c['limit']:.6g} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
