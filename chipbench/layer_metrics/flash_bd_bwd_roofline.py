"""The flash-attention backward kernels' share of their roofline under
the block-diffusion mask, in percent: the least time the chip could
take for the backward calls the trace holds, over the time their
kernels took. Source: the device trace.

One backward is one `flash_attention_bwd_dkv` event and whatever other
`flash_attention_bwd_*` kernels ran beside it: the calls are counted by
the `dkv` events, the time is all of theirs. What a call processed is
read from the operand shapes in the `dkv` event's own text
(`<type>[batch*heads, 2L, head_dim]`), the block length from the cell's
traffic. The least time is the larger of FLOPs over the bf16 peak and
bytes over the HBM peak (`flops/flash_block_diffusion.py`: five matmul
terms for each of the L^2 + L Bd admitted pairs, whatever the kernels
recompute).
"""

import os
import re

from loading import HERE, load_module

KERNEL = re.compile(r"^%?\w*flash_attention_bwd_(\w+?)[\d.]* = ")
OPERAND = re.compile(r"custom-call\(.*?(f32|bf16|f16)\[(\d+),(\d+),(\d+)\]")
BYTES = {"f32": 4, "bf16": 2, "f16": 2}


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    block_length = ctx["traffic"].get("block_length")
    if not trace or not peaks or not block_length:
        return None
    flash = load_module(os.path.join(HERE, "flops"), "flash_block_diffusion")
    least = took = 0.0
    for name, (seconds, events) in trace["ops"].items():
        kernel = KERNEL.match(name)
        if not kernel:
            continue
        took += seconds
        m = OPERAND.search(name)
        if kernel[1] != "dkv" or not m:
            continue
        flops, nbytes = flash.backward(
            batch=1, heads=int(m[2]), positions=int(m[3]),
            head_dim=int(m[4]), block_length=block_length,
            bytes_per_value=BYTES[m[1]])
        least += events * max(flops / peaks["bf16_flops_per_s"],
                              nbytes / peaks["hbm_bytes_per_s"])
    if not took or not least:
        return None
    return 100.0 * least / took
