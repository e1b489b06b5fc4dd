"""Mean time the feeder's worker thread took to convert one raw batch
(`stack_columns`: `np.stack` of the samples), in ms. Source: the
program's span `feeder.convert`."""

import os

from loading import HERE, load_module

timeline = load_module(os.path.join(HERE, "layer_metrics"), "program_timeline")


def read(ctx):
    return timeline.mean_ms(ctx, "feeder.convert")
