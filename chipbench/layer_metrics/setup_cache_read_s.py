"""Seconds of set-up spent reading and deserialising executables from
the persistent compile cache: what a warm run pays in place of XLA's
compiles. Source: the sum of the program's spans `compile.cache_read`
that closed before the window."""

import os

from loading import HERE, load_module

setup = load_module(os.path.join(HERE, "layer_metrics"), "setup_timeline")


def read(ctx):
    return setup.setup_sum_s(ctx, "compile.cache_read")
