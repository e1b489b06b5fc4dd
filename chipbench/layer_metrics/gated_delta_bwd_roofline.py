"""The gated delta rule's backward kernel's share of its roofline, in
percent: the least time the chip could take for the backward calls the
trace holds, over the time they took. Source: the device trace.

One backward is one `gated_delta_bwd` event (every kernel whose name
starts so is counted, should the backward ever be split); what it
processed is read from its operands as the forward's reader does
(`gated_delta_fwd_roofline.operands`), the least time from
`flops/gated_delta.py`'s backward: twice the forward's products, and
the inputs, the output's gradient and the five gradients moved once.
Nothing to read: `None`, never 0.
"""

import os
import re

from loading import HERE, load_module

KERNEL = re.compile(r"^%?\w*gated_delta_bwd[\w.]* = ")


def read(ctx):
    fwd = load_module(os.path.join(HERE, "layer_metrics"),
                      "gated_delta_fwd_roofline")
    gd = load_module(os.path.join(HERE, "flops"), "gated_delta")
    return fwd.share(ctx, KERNEL, gd.backward)
