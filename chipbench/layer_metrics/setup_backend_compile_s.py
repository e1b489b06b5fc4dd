"""Seconds of set-up inside `compile_or_get_cached` less the cache's
reads: XLA's compiles on a miss; on a hit what is left is the cache
key's hashing and bookkeeping. Source: the union of the program's spans
`compile.backend:<fun>` that closed before the window, less
`setup_cache_read_s` (each read lies inside its backend span)."""

import os

from loading import HERE, load_module

setup = load_module(os.path.join(HERE, "layer_metrics"), "setup_timeline")


def read(ctx):
    backend = setup.setup_union_s(ctx, "compile.backend:")
    if backend is None:
        return None
    return backend - setup.setup_sum_s(ctx, "compile.cache_read")
