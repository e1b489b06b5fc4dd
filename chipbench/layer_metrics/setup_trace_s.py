"""Seconds of set-up spent tracing jitted functions to jaxprs: the
Python of the model, the loss and the optimizer, once a shape; the
persistent cache saves none of it. Source: the union of the program's
spans `compile.trace:<fun>` that closed before the window."""

import os

from loading import HERE, load_module

setup = load_module(os.path.join(HERE, "layer_metrics"), "setup_timeline")


def read(ctx):
    return setup.setup_union_s(ctx, "compile.trace:")
