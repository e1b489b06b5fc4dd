"""Host bytes handed to `jax.device_put` per batch, from the shapes of
the host leaves. Source: the program's counters `feeder.h2d_bytes` over
`feeder.batches` (see `program_timeline.counter_ratio` for what the
ratio spans)."""

import os

from loading import HERE, load_module

timeline = load_module(os.path.join(HERE, "layer_metrics"), "program_timeline")


def read(ctx):
    return timeline.counter_ratio(ctx, "feeder.h2d_bytes",
                                  "feeder.batches", "feeder.device_put")
