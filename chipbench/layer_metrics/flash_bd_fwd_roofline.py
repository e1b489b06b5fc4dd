"""The flash-attention forward kernel's share of its roofline under the
block-diffusion mask, in percent: the least time the chip could take
for the calls the trace holds, over the time they took. Source: the
device trace.

The kernel's events carry the name the program gives them
(`flash_attention_fwd`, and `jvp_flash_attention_fwd_` where the
forward is recomputed for the backward pass); what each call processed
is read from the operand shapes in the event's own text
(`<type>[batch*heads, 2L, head_dim]`), the block length from the cell's
traffic. The least time is the larger of FLOPs over the bf16 peak and
bytes over the HBM peak (`flops/flash_block_diffusion.py`: L^2 + L Bd
admitted pairs a head and sequence, a floor of the work whatever the
kernel's blocks compute beyond the mask).
"""

import os
import re

from loading import HERE, load_module

KERNEL = re.compile(r"^%?(jvp_)?flash_attention_fwd[\w.]* = ")
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
        m = KERNEL.match(name) and OPERAND.search(name)
        if not m:
            continue
        flops, nbytes = flash.forward(
            batch=1, heads=int(m[2]), positions=int(m[3]),
            head_dim=int(m[4]), block_length=block_length,
            bytes_per_value=BYTES[m[1]])
        least += events * max(flops / peaks["bf16_flops_per_s"],
                              nbytes / peaks["hbm_bytes_per_s"])
        took += seconds
    if not took:
        return None
    return 100.0 * least / took
