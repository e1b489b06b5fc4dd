"""Share of the window the training thread spent waiting on the
feeder's queue (`q.get()` in `DataFeeder.__call__`), in percent: the
feeder's worker thread (reading, stacking) was not ahead. Source: the
program's span `feeder.queue_wait`."""

import os

from loading import HERE, load_module

timeline = load_module(os.path.join(HERE, "layer_metrics"), "program_timeline")


def read(ctx):
    return timeline.share(ctx, "feeder.queue_wait")
