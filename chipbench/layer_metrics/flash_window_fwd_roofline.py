"""The flash-attention forward kernel's share of its roofline over the
calls whose sliding window cuts, in percent: the least time the chip
could take for the band calls the trace holds, over the time they took.
Source: the device trace.

A model may mix windowed and full layers. The program names the kernel
of a call whose window is narrower than its sequence apart
(`flash_attention_fwd_window`, and `jvp_flash_attention_fwd_window_`
where the forward is recomputed for the backward pass); a full-causal
call's events (`flash_attention_fwd`) are not read here. What each call
processed is read from the operand shapes in the event's own text
(`<type>[batch*heads, seq, head_dim]`), the window from the
configuration (`sliding_window`). The least time is the larger of FLOPs
over the bf16 peak and bytes over the HBM peak (`flops/flash.py`: the
pairs the band admits, w (w + 1) / 2 + (T - w) w a head and sequence, a
floor of the work whatever the kernel's blocks compute beyond the band).
A program that names no such kernel gives no reading.
"""

import os
import re

from loading import HERE, load_module

KERNEL = re.compile(r"^%?(jvp_)?flash_attention_fwd_window[\w.]* = ")
OPERAND = re.compile(r"custom-call\(.*?(f32|bf16|f16)\[(\d+),(\d+),(\d+)\]")
BYTES = {"f32": 4, "bf16": 2, "f16": 2}


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    window = ctx["config"].get("sliding_window")
    if not trace or not peaks or not window:
        return None
    flash = load_module(os.path.join(HERE, "flops"), "flash")
    least = took = 0.0
    for name, (seconds, events) in trace["ops"].items():
        m = KERNEL.match(name) and OPERAND.search(name)
        if not m:
            continue
        flops, nbytes = flash.forward(
            batch=1, heads=int(m[2]), seq=int(m[3]), head_dim=int(m[4]),
            window=window, bytes_per_value=BYTES[m[1]])
        least += events * max(flops / peaks["bf16_flops_per_s"],
                              nbytes / peaks["hbm_bytes_per_s"])
        took += seconds
    if not took:
        return None
    return 100.0 * least / took
