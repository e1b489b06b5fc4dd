"""Mean time the feeder's worker thread took to pull one raw batch out
of the batch reader (the user's reader and `data.batch`'s list), in ms.
Source: the program's span `feeder.read`."""

import os

from loading import HERE, load_module

timeline = load_module(os.path.join(HERE, "layer_metrics"), "program_timeline")


def read(ctx):
    return timeline.mean_ms(ctx, "feeder.read")
