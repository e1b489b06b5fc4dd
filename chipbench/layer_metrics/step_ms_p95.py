"""95th percentile of the interval between consecutive step
completions, all steps of the window, in ms. Source: the host clock of
the watcher thread. (An end-to-end metric in ISSUE 25; its spread from
run to run, 3% to 22%, is wider than any bound may be: PERF.md.)"""

import statistics


def read(ctx):
    done = ctx["window"]["completions"]
    if len(done) < 21:
        return None
    gaps = [1e3 * (b - a) for a, b in zip(done, done[1:])]
    return statistics.quantiles(gaps, n=20)[18]
