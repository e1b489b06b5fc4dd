"""Milliseconds of the measured window inside any of the program's
compile spans (`compile.*`: tracing, lowering, cache read, backend): 0
where every shape was warmed up in set-up. The inside twin of
`window_compiles`, which counts the same events from the benchmark's own
listener. Source: the union of the program's spans `compile.*` that
overlap `[t0, t0 + span_s]`."""

import os

from loading import HERE, load_module

setup = load_module(os.path.join(HERE, "layer_metrics"), "setup_timeline")


def read(ctx):
    found = setup.recorded()
    w = ctx["window"]
    if found is None or not w["span_s"]:
        return None
    lo = int(w["t0"] * 1e9)
    hi = int((w["t0"] + w["span_s"]) * 1e9)
    rows = [r for r in found[0] if r[2] > lo and r[1] < hi]
    return 1e3 * setup.union_s(rows, "compile.", lo, hi)
