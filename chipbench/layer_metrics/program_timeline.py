"""What the program's own step timeline recorded inside the measured
window: the shared reading of the six `trainer / input` metrics that
come from `paddle_tpu.obs.trace.default_timeline()` (rows `(name,
start_ns, end_ns, seq, parent)` on `time.perf_counter_ns`, the clock of
`window["t0"]`, and named counters). Not a metric itself.

The rows are cut to `[t0, t0 + span_s]`. If the ring has wrapped past
the window's start (no row it holds started before `t0`), the interval
starts at its oldest row instead, and shares are of the interval the
rows cover. A program from before the timeline, or a cell that runs
neither `Trainer` nor `DataFeeder`, has nothing to read: every function
here then returns `None`.
"""


def _timeline():
    try:
        from paddle_tpu.obs.trace import default_timeline
    except ImportError:
        return None
    return default_timeline()


def window_rows(ctx):
    """`(rows, lo_ns, hi_ns)`: the rows that overlap the covered part
    of the window, and that part; or `None`."""
    tl = _timeline()
    w = ctx["window"]
    if tl is None or not w["span_s"]:
        return None
    lo = int(w["t0"] * 1e9)
    hi = int((w["t0"] + w["span_s"]) * 1e9)
    rows = tl.rows()
    if rows and all(r[1] >= lo for r in rows):
        lo = min(r[1] for r in rows)
    rows = [r for r in rows if r[2] > lo and r[1] < hi]
    if not rows or hi <= lo:
        return None
    return rows, lo, hi


def share(ctx, name):
    """Time inside spans `name`, as a percentage of the covered
    interval."""
    cut = window_rows(ctx)
    if cut is None:
        return None
    rows, lo, hi = cut
    inside = [min(r[2], hi) - max(r[1], lo) for r in rows if r[0] == name]
    if not inside:
        return None
    return 100.0 * sum(inside) / (hi - lo)


def mean_ms(ctx, name):
    """Mean duration of the spans `name` that lie inside the covered
    interval, in ms."""
    cut = window_rows(ctx)
    if cut is None:
        return None
    rows, lo, hi = cut
    whole = [r[2] - r[1] for r in rows
             if r[0] == name and r[1] >= lo and r[2] <= hi]
    if not whole:
        return None
    return sum(whole) / len(whole) / 1e6


def counter_ratio(ctx, numerator, denominator, witness):
    """`numerator / denominator` of the timeline's counters, if the
    window holds a span `witness` (so a cell that never counted reports
    nothing). The counters run from the start of the process: set-up's
    few batches are in both, which leaves a ratio of equal batches as
    it is; a delta over the window needs a reading at `t0`, which only
    `run.py` can take."""
    cut = window_rows(ctx)
    if cut is None or not any(r[0] == witness for r in cut[0]):
        return None
    counters = _timeline().counters()
    if not counters.get(denominator):
        return None
    return counters.get(numerator, 0) / counters[denominator]
