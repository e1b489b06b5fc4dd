"""Seconds of set-up inside `import paddle_tpu` (the package imports its
layers eagerly; jax is loaded before it in the benchmark, so it is not in
the row). Source: the program's span `import.paddle_tpu`."""

import os

from loading import HERE, load_module

setup = load_module(os.path.join(HERE, "layer_metrics"), "setup_timeline")


def read(ctx):
    rows = setup.setup_rows(ctx)
    if rows is None:
        return None
    took = [r[2] - r[1] for r in rows if r[0] == "import.paddle_tpu"]
    return sum(took) / 1e9 if took else None
