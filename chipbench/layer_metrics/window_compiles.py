"""jax compile events (`/jax/core/compile/*`) inside the measured
window: 0 where every shape was warmed up in set-up."""


def read(ctx):
    return ctx["counters"].get("window_compiles")
