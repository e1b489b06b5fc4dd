"""The flash-attention backward kernels' share of their roofline, in
percent: the least time the chip could take for the backward calls the
trace holds, over the time their kernels took. Source: the device trace.

One backward is one `flash_attention_bwd_dkv` event and whatever other
`flash_attention_bwd_*` kernels ran beside it (`flash_attention_bwd_dq`):
the calls are counted by the `dkv` events, the time is all of theirs.
What a call processed is read from the operand shapes in the `dkv`
event's own text (`<type>[batch*heads, seq, head_dim]`), as the
forward's reader does. The least time is the larger of FLOPs over the
bf16 peak and bytes over the HBM peak (`flops/flash_bwd.py`: the five
matmul terms a pair needs, causal inside the configuration's window,
whatever the kernels recompute); at the shapes seen so far the FLOPs
bound it. A program with no such kernel (the backward as `jnp` scans,
before PR 32) gives no reading.
"""

import os
import re

from loading import HERE, load_module

KERNEL = re.compile(r"^%?\w*flash_attention_bwd_(\w+?)[\d.]* = ")
OPERAND = re.compile(r"custom-call\(.*?(f32|bf16|f16)\[(\d+),(\d+),(\d+)\]")
BYTES = {"f32": 4, "bf16": 2, "f16": 2}


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not peaks:
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
            window=ctx["config"].get("sliding_window"),
            bytes_per_value=BYTES[m[1]])
        least += events * max(flops / peaks["bf16_flops_per_s"],
                              nbytes / peaks["hbm_bytes_per_s"])
    if not took or not least:
        return None
    return 100.0 * least / took
