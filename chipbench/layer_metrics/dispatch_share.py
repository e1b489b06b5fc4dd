"""Share of the window the training thread spent splitting the batch,
splitting the rng and calling the jitted step (which returns at
enqueue; long when the runtime's queue or a donated buffer blocks), in
percent. Source: the program's span `trainer.dispatch`."""

import os

from loading import HERE, load_module

timeline = load_module(os.path.join(HERE, "layer_metrics"), "program_timeline")


def read(ctx):
    return timeline.share(ctx, "trainer.dispatch")
