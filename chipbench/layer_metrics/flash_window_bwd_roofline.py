"""The flash-attention backward kernels' share of their roofline over
the calls whose sliding window cuts, in percent: the least time the
chip could take for the band backward calls the trace holds, over the
time their kernels took. Source: the device trace.

The program names the kernels of a call whose window is narrower than
its sequence apart (`flash_attention_bwd_dkv_window`,
`flash_attention_bwd_dq_window`); a full-causal call's are not read
here. One backward is one `dkv` event and the `dq` event beside it: the
calls are counted by the `dkv` events, the time is both kernels'. What
a call processed is read from the operand shapes in the `dkv` event's
own text, the window from the configuration (`sliding_window`). The
least time is the larger of FLOPs over the bf16 peak and bytes over the
HBM peak (`flops/flash_bwd.py`: the five matmul terms a pair needs over
the pairs the band admits, whatever the kernels recompute or compute
beyond the band). A program that names no such kernel gives no reading.
"""

import os
import re

from loading import HERE, load_module

KERNEL = re.compile(r"^%?\w*flash_attention_bwd_(dkv|dq)_window[\w.]* = ")
OPERAND = re.compile(r"custom-call\(.*?(f32|bf16|f16)\[(\d+),(\d+),(\d+)\]")
BYTES = {"f32": 4, "bf16": 2, "f16": 2}


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    window = ctx["config"].get("sliding_window")
    if not trace or not peaks or not window:
        return None
    flash_bwd = load_module(os.path.join(HERE, "flops"), "flash_bwd")
    least = took = 0.0
    for name, (seconds, events) in trace["ops"].items():
        kernel = KERNEL.match(name)
        if not kernel:
            continue
        took += seconds
        m = OPERAND.search(name)
        if kernel[1] != "dkv" or not m:
            continue
        flops, nbytes = flash_bwd.backward(
            batch=1, heads=int(m[2]), seq=int(m[3]), head_dim=int(m[4]),
            window=window, bytes_per_value=BYTES[m[1]])
        least += events * max(flops / peaks["bf16_flops_per_s"],
                              nbytes / peaks["hbm_bytes_per_s"])
    if not took or not least:
        return None
    return 100.0 * least / took
