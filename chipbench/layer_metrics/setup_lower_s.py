"""Seconds of set-up spent lowering jaxprs to StableHLO modules, the
Pallas -> Mosaic lowering of the flash and grouped kernels inside it;
the persistent cache saves none of it. Source: the union of the
program's spans `compile.lower:<fun>` that closed before the window."""

import os

from loading import HERE, load_module

setup = load_module(os.path.join(HERE, "layer_metrics"), "setup_timeline")


def read(ctx):
    return setup.setup_union_s(ctx, "compile.lower:")
