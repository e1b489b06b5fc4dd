"""The dropless expert layer's grouped matrix products' share of their
roofline, in percent: the least time the chip could take for the
products the trace holds, over the time they took. Source: the device
trace, with the rows from the program's counters.

The kernels' events carry the names the program gives them
(`moe_grouped_matmul` for rows @ w and grad @ w.T, forward, recomputed
and backward; `moe_grouped_matmul_dw` for the weight gradient). The
matrix of a product, [experts held, k, n], is read from the event's own
text; the rows a product really had are not in the text (the row buffer
is sized for the worst case), so they are the window's mean of the
driver's counters, `moe.rows_held` over the steps and layers it ran.
The least time is the larger of FLOPs over the bf16 peak and bytes over
the HBM peak (`flops/moe_grouped.py`). Nothing to read (no such kernel,
no counter): `None`, never 0.
"""

import os
import re

from loading import HERE, load_module

KERNEL = re.compile(r"^%?\w*moe_grouped_matmul\w*?[\d.]* = ")
# the one rank-3 array of a product: the experts' matrices (operand, or
# the result of the weight gradient)
MATRICES = re.compile(r"(f32|bf16|f16)\[(\d+),(\d+),(\d+)\]")
BYTES = {"f32": 4, "bf16": 2, "f16": 2}


def read(ctx):
    trace, peaks, counters = ctx["trace"], ctx["peaks"], ctx["counters"]
    steps = ctx["window"]["steps"]
    layers = ctx["config"].get("num_hidden_layers")
    if (not trace or not peaks or not steps or not layers
            or not counters.get("moe.rows_held")):
        return None
    rows = counters["moe.rows_held"] / (steps * layers)
    grouped = load_module(os.path.join(HERE, "flops"), "moe_grouped")
    least = took = 0.0
    for name, (seconds, events) in trace["ops"].items():
        m = KERNEL.match(name) and MATRICES.search(name)
        if not m:
            continue
        flops, nbytes = grouped.product(
            rows=rows, k=int(m[3]), n=int(m[4]), groups=int(m[2]),
            bytes_per_value=BYTES[m[1]])
        least += events * max(flops / peaks["bf16_flops_per_s"],
                              nbytes / peaks["hbm_bytes_per_s"])
        took += seconds
    if not took:
        return None
    return 100.0 * least / took
