"""What the program's compile recorder wrote before the measured window:
the shared reading of the `compile cache` / `entry` metrics that move
`setup_s`, and of `window_compile_ms`. The twin of `program_timeline.py`,
which cuts the same rows to the window. Not a metric itself.

The rows are those of `paddle_tpu.obs.trace.default_timeline()`: `(name,
start_ns, end_ns, seq, parent)` on `time.perf_counter_ns`, the clock of
`window["t0"]`. `compilation_cache.install_listeners()` turns each jax
compile event into one: `compile.trace:<fun>`, `compile.lower:<fun>`,
`compile.backend:<fun>`, `compile.cache_read` (inside the backend row
that closes next), and counts `compile.cache_requests`, `_hits`,
`_misses` and `compile.backend_compiles`; the package stamps
`import.paddle_tpu`. Traces nest and threads overlap, so a phase's
seconds are the **union** of its rows, never their sum. (The program
leaves no row for a trace under 100 us: a jitted `jnp` wrapper passed
through while a step is traced, inside that step's own row.)

Every function returns `None`, never 0 and never a partial sum, where
there is nothing to trust: a program from before the recorder (no
`compile.*` row and no `compile.*` counter at all), or a ring that is
full (set-up's rows may have been pushed out).
"""


def _timeline():
    try:
        from paddle_tpu.obs.trace import TIMELINE_KEEP, default_timeline
    except ImportError:
        return None
    return default_timeline(), TIMELINE_KEEP


def recorded():
    """`(rows, counters)` of a program with the recorder whose ring still
    holds everything it wrote; or `None`."""
    found = _timeline()
    if found is None:
        return None
    tl, keep = found
    rows, counters = tl.rows(), tl.counters()
    if len(rows) >= keep:
        return None
    if not any(name.startswith("compile.")
               for name in [r[0] for r in rows] + list(counters)):
        return None
    return rows, counters


def setup_rows(ctx):
    """The rows that closed before the window's `t0`; or `None`."""
    found = recorded()
    if found is None:
        return None
    t0 = int(ctx["window"]["t0"] * 1e9)
    return [r for r in found[0] if r[2] <= t0]


def union_s(rows, prefix, lo=None, hi=None):
    """Seconds covered by the rows whose name starts with `prefix`, each
    cut to `[lo, hi]` where given."""
    spans = sorted((r[1] if lo is None else max(r[1], lo),
                    r[2] if hi is None else min(r[2], hi))
                   for r in rows if r[0].startswith(prefix))
    total, covered_to = 0, None
    for start, end in spans:
        if covered_to is None or start > covered_to:
            covered_to = start
        if end > covered_to:
            total += end - covered_to
            covered_to = end
    return total / 1e9


def setup_union_s(ctx, prefix):
    rows = setup_rows(ctx)
    return None if rows is None else union_s(rows, prefix)


def setup_sum_s(ctx, name):
    """Rows `name` do not overlap one another (one thread's reads, one
    import): their sum."""
    rows = setup_rows(ctx)
    if rows is None:
        return None
    return sum(r[2] - r[1] for r in rows if r[0] == name) / 1e9
