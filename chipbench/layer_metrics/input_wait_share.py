"""Share of the window's span the training thread spent inside `next()`
of the batch iterator, in percent. Source: the benchmark's wrapper
around the iterator (a counter of seconds)."""


def read(ctx):
    w = ctx["window"]
    if "input_wait_s" not in ctx["counters"] or not w["span_s"]:
        return None
    return 100.0 * ctx["counters"]["input_wait_s"] / w["span_s"]
