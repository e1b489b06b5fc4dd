"""The gated delta rule's forward kernel's share of its roofline, in
percent: the least time the chip could take for the calls the trace
holds, over the time they took. Source: the device trace.

The kernel's events carry the name the program gives it
(`gated_delta_fwd`, and `jvp_gated_delta_fwd_` where it runs under
differentiation); what a call processed is read from the operand shapes
in the event's own text: q, k and v are its first three rank-3 operands
(`<type>[batch*value heads, seq, width]`). The least time is the larger
of FLOPs over the bf16 peak and bytes over the HBM peak
(`flops/gated_delta.py`: the recurrence's three products a position and
head, whatever the chunked form adds). Nothing to read (no such kernel:
a program without the layer): `None`, never 0.
"""

import os
import re

from loading import HERE, load_module

KERNEL = re.compile(r"^%?\w*gated_delta_fwd[\w.]* = ")
OPERAND = re.compile(r"(f32|bf16|f16)\[(\d+),(\d+),(\d+)\]")
BYTES = {"f32": 4, "bf16": 2, "f16": 2}


def operands(name):
    """(dtype, rows, seq, dk, dv) from a kernel event's text, or None."""
    call = name.find("custom-call(")
    found = OPERAND.findall(name[call:]) if call >= 0 else []
    if len(found) < 3:
        return None
    (dtype, rows, seq, dk), _, (_, _, _, dv) = found[:3]
    return dtype, int(rows), int(seq), int(dk), int(dv)


def share(ctx, kernel, count):
    """100 * least / took over the events `kernel` matches, each call's
    work from `count` (`flops/gated_delta.py`'s forward or backward)."""
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not peaks:
        return None
    least = took = 0.0
    for name, (seconds, events) in trace["ops"].items():
        shapes = kernel.match(name) and operands(name)
        if not shapes:
            continue
        dtype, rows, seq, dk, dv = shapes
        flops, nbytes = count(rows=rows, seq=seq, dk=dk, dv=dv,
                              bytes_per_value=BYTES[dtype])
        least += events * max(flops / peaks["bf16_flops_per_s"],
                              nbytes / peaks["hbm_bytes_per_s"])
        took += seconds
    if not took:
        return None
    return 100.0 * least / took


def read(ctx):
    gd = load_module(os.path.join(HERE, "flops"), "gated_delta")
    return share(ctx, KERNEL, gd.forward)
