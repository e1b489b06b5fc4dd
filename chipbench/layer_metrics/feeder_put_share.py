"""Share of the window the training thread spent in `jax.device_put`
of the batches (the host-side relayout and the copy's enqueue), in
percent. Source: the program's span `feeder.device_put`."""

import os

from loading import HERE, load_module

timeline = load_module(os.path.join(HERE, "layer_metrics"), "program_timeline")


def read(ctx):
    return timeline.share(ctx, "feeder.device_put")
