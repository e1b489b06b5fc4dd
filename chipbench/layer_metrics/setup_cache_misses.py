"""Executables compiled and written to the persistent cache: 0 marks a
warm run, more says that this run's `setup_s` was a cold one. The window
compiles nothing and the readers run before the reference, so the
process's count is set-up's. Source: the program's counter
`compile.cache_misses`."""

import os

from loading import HERE, load_module

setup = load_module(os.path.join(HERE, "layer_metrics"), "setup_timeline")


def read(ctx):
    found = setup.recorded()
    if found is None:
        return None
    return found[1].get("compile.cache_misses", 0)
