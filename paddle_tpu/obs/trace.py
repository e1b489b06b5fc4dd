"""Span tracing: the per-request / per-step audit trail (`Tracer`)
and the hot loop's interval recorder (`Timeline`).

Two recorders for two jobs. `Tracer` answers "what happened to request
rr7": one mutable span per id, point events, exactly one terminal
outcome, a lock and a dict per span. `Timeline` answers "where did the
last steps' time go": a bounded ring of closed intervals, no lock, no
ids, about a microsecond a span, always on. Use `Tracer` where a unit
of work has an identity and an outcome to audit; use `Timeline` inside
a loop that runs every step.

The audit trail first.

Request-ids are minted once — at `ServingRouter.submit` (`rr<N>`) or
by a standalone `ServingServer` (`req<N>`) — and the id rides the
request through replica -> `ServingServer.step()` -> `DecodeEngine`
prefill/decode -> `PagePool` admit/evict, and trainer iteration ->
pserver push/pull. Each hop appends an *event* to the request's span;
the span ends EXACTLY ONCE, with the terminal outcome as a tag
(completed/expired/shed/failed for serve; ok/rollback/drain for
train). That makes the exactly-once accounting contract auditable
per request, not just in aggregate: `tests/test_obs.py` kills a
replica mid-burst and asserts every minted id has exactly one
terminal span whose outcomes sum to the fleet counters.

Overhead rules (same as the registry): host-side only, no jax
imports, no device values in tags/events — a span is a few dict ops
off the jitted bodies. Clock is injectable so ManualClock chaos runs
get deterministic durations.

A span that is ended twice does not assert (production telemetry
must not take the server down); the second end is recorded in
`Tracer.double_ends` and the test suite asserts that stays zero.
"""

from __future__ import annotations

import bisect
import collections
import threading
import time
from typing import Callable, Deque, Dict, List, Optional

__all__ = ["Span", "Tracer", "Timeline", "default_timeline"]

#: finished spans kept in the tracer ring (flight recorder keeps its
#: own, possibly longer, ring)
DEFAULT_KEEP = 1024


class Span:
    """One traced unit of work. Mutable while open; `end()` (via the
    owning Tracer) freezes it with a terminal outcome tag."""

    __slots__ = ("trace_id", "name", "start", "end_time", "tags",
                 "events", "_tracer")

    def __init__(self, trace_id: str, name: str, start: float,
                 tracer: "Tracer", tags: Optional[Dict[str, object]]
                 = None):
        self.trace_id = trace_id
        self.name = name
        self.start = start
        self.end_time: Optional[float] = None
        self.tags: Dict[str, object] = dict(tags or {})
        self.events: List[Dict[str, object]] = []
        self._tracer = tracer

    @property
    def open(self) -> bool:
        return self.end_time is None

    @property
    def outcome(self) -> Optional[str]:
        return self.tags.get("outcome")

    def event(self, name: str, **data: object) -> None:
        """Append a point-in-time event (admitted, retried,
        redistributed, page_admit, push, ...). No-op on a closed
        span except for a `late_event` tally on the tracer — late
        stragglers must not resurrect a terminal span."""
        if self.end_time is not None:
            self._tracer.late_events += 1
            return
        self.events.append(
            {"t": self._tracer.clock(), "name": name, **data})

    def duration(self) -> Optional[float]:
        if self.end_time is None:
            return None
        return self.end_time - self.start

    def to_dict(self) -> Dict[str, object]:
        return {
            "trace_id": self.trace_id,
            "name": self.name,
            "start": self.start,
            "end": self.end_time,
            "tags": dict(self.tags),
            "events": list(self.events),
        }


class Tracer:
    """Mints and finishes audit spans; forwards finished spans to an
    optional sink (the flight recorder's `note_span`). For intervals
    of a hot loop (no id, no outcome) use `Timeline`.

    Live spans are indexed by trace_id so instrumentation points deep
    in the stack (PagePool hooks, pserver client) can attach events
    knowing only the id. The live index is bounded implicitly by the
    server's own admission control (slots + queue cap); finished
    spans go to a fixed ring."""

    def __init__(self, *, clock: Optional[Callable[[], float]] = None,
                 sink: Optional[Callable[[Span], None]] = None,
                 keep: int = DEFAULT_KEEP):
        self.clock = clock if clock is not None else time.monotonic
        self.sink = sink
        self._lock = threading.Lock()
        self._live: Dict[str, Span] = {}
        self.finished: Deque[Span] = collections.deque(maxlen=keep)
        self.started = 0
        self.ended = 0
        self.double_ends = 0
        self.late_events = 0

    # -- lifecycle ---------------------------------------------------------

    def start(self, trace_id: str, name: str,
              **tags: object) -> Span:
        """Open a span. A second start() for a live id records a
        `respan` tag on the existing span and returns it — ids are
        minted once, so this only happens on instrumentation bugs
        and must not fork the audit trail."""
        with self._lock:
            existing = self._live.get(trace_id)
            if existing is not None and existing.open:
                existing.tags["respan"] = (
                    int(existing.tags.get("respan", 0)) + 1)
                return existing
            span = Span(trace_id, name, self.clock(), self, tags)
            self._live[trace_id] = span
            self.started += 1
            return span

    def get(self, trace_id: str) -> Optional[Span]:
        with self._lock:
            return self._live.get(trace_id)

    def event(self, trace_id: str, name: str, **data: object) -> None:
        """Attach an event to a live span by id; silently dropped for
        unknown ids (a component may be traced standalone)."""
        span = self.get(trace_id)
        if span is not None:
            span.event(name, **data)

    def end(self, trace_id_or_span, outcome: str,
            **tags: object) -> Optional[Span]:
        """Terminate a span with its outcome tag. Exactly-once: a
        second end bumps `double_ends` and changes nothing."""
        if isinstance(trace_id_or_span, Span):
            span = trace_id_or_span
        else:
            span = self.get(trace_id_or_span)
        if span is None:
            return None
        with self._lock:
            if span.end_time is not None:
                self.double_ends += 1
                return span
            span.end_time = self.clock()
            span.tags["outcome"] = outcome
            span.tags.update(tags)
            self._live.pop(span.trace_id, None)
            self.finished.append(span)
            self.ended += 1
        if self.sink is not None:
            try:
                self.sink(span)
            except Exception:
                pass  # telemetry must never take the caller down
        return span

    # -- audit -------------------------------------------------------------

    def live_ids(self) -> List[str]:
        with self._lock:
            return sorted(self._live)

    def terminal_outcomes(self) -> Dict[str, List[str]]:
        """trace_id -> [outcome per finished span]. The exactly-once
        audit: every id should map to exactly one outcome."""
        out: Dict[str, List[str]] = {}
        with self._lock:
            for span in self.finished:
                out.setdefault(span.trace_id, []).append(
                    span.tags.get("outcome", "?"))
        return out

    def outcome_counts(self) -> Dict[str, int]:
        """Tally of finished-span outcomes — comparable 1:1 with the
        server/router ledger counters."""
        out: Dict[str, int] = {}
        with self._lock:
            for span in self.finished:
                oc = str(span.tags.get("outcome", "?"))
                out[oc] = out.get(oc, 0) + 1
        return out

    def counters(self) -> Dict[str, int]:
        """Tracer self-accounting, registry-source shaped."""
        with self._lock:
            return {
                "spans_started": self.started,
                "spans_ended": self.ended,
                "spans_live": len(self._live),
                "double_ends": self.double_ends,
                "late_events": self.late_events,
            }


# -- the step timeline -----------------------------------------------------

#: rows the timeline ring keeps: a 20 s window of the image cell is
#: about 115 steps of 10 rows
TIMELINE_KEEP = 8192


class _Stacks(threading.local):
    """Each thread's stack of the names of its open spans."""

    def __init__(self):
        self.stack: List[str] = []


class _OpenInterval:
    """One `with timeline.span(...)`: pushes its name on the thread's
    stack, and appends the closed row on the way out, error or not."""

    __slots__ = ("_tl", "_name", "_seq", "_stack", "_start", "_keep")

    def __init__(self, tl: "Timeline", name: str, seq: Optional[int]):
        self._tl, self._name, self._seq = tl, name, seq
        self._keep = True

    def discard(self) -> None:
        """Close without a row: for the `next()` that found the end of
        its iterator, which is no batch's interval."""
        self._keep = False

    def __enter__(self) -> "_OpenInterval":
        self._stack = self._tl._open.stack
        self._stack.append(self._name)
        self._start = self._tl.clock_ns()
        return self

    def __exit__(self, *exc) -> bool:
        end = self._tl.clock_ns()
        stack = self._stack
        stack.pop()
        if self._keep:
            self._tl._rows.append(
                (self._name, self._start, end, self._seq,
                 stack[-1] if stack else None))
        return False


class Timeline:
    """Interval recorder for a hot loop: a bounded ring of closed rows
    `(name, start_ns, end_ns, seq, parent)` plus named counters.

    `name` is the span, `seq` the batch ordinal that every span of one
    batch shares, `parent` the name of the span that encloses it on the
    same thread (`None` at the root). The clock is
    `time.perf_counter_ns` unless injected: the clock of the chip
    benchmark's own spans, which a traced run ties to the device clock,
    so these rows and the device's idle gaps lie on one timeline.

    The record path takes no lock (`deque.append` is atomic; a counter
    belongs to the one thread that counts it), imports no jax, holds no
    device value, and closes its span in `__exit__` whatever the body
    raised. Reading (`rows`, `counters`, `summary`) copies first and
    may run on any thread. For per-id audit spans with outcomes use
    `Tracer`.
    """

    def __init__(self, *, clock_ns: Optional[Callable[[], int]] = None,
                 keep: int = TIMELINE_KEEP):
        self.clock_ns = (clock_ns if clock_ns is not None
                         else time.perf_counter_ns)
        self._rows: Deque[tuple] = collections.deque(maxlen=keep)
        self._counters: Dict[str, int] = {}
        self._open = _Stacks()

    # -- record ------------------------------------------------------------

    def span(self, name: str, seq: Optional[int] = None) -> _OpenInterval:
        """`with timeline.span("trainer.dispatch", batch_id): ...`"""
        return _OpenInterval(self, name, seq)

    def add(self, name: str, start_ns: int, end_ns: int,
            seq: Optional[int] = None) -> None:
        """Append one closed row that was timed elsewhere (a jax
        compile event hands its duration over as it ends): its parent
        is the innermost span open on the calling thread, as for a row
        that `span` closes."""
        stack = self._open.stack
        self._rows.append((name, start_ns, end_ns, seq,
                           stack[-1] if stack else None))

    def count(self, name: str, n: int = 1) -> None:
        """Add `n` to counter `name`. One thread owns each name."""
        self._counters[name] = self._counters.get(name, 0) + n

    # -- read --------------------------------------------------------------

    def rows(self, since_ns: Optional[int] = None) -> List[tuple]:
        """The ring's rows in the order they closed, or those that
        started at or after `since_ns`."""
        rows = list(self._rows)
        if since_ns is None:
            return rows
        return [r for r in rows if r[1] >= since_ns]

    def counters(self) -> Dict[str, int]:
        return dict(self._counters)

    def summary(self, since_ns: Optional[int] = None
                ) -> Dict[str, Dict[str, float]]:
        """Per span name `total_s / count / mean_ms / max_ms / self_s`:
        the per-scope timer table of a pass. Self time is a span's
        duration minus what the rows that name it as `parent`, and lie
        inside it, cover."""
        rows = self.rows(since_ns)
        out: Dict[str, Dict[str, float]] = {}
        by_name: Dict[str, List[tuple]] = {}
        for name, start, end, _seq, _parent in rows:
            e = out.setdefault(name, {"total_s": 0.0, "count": 0,
                                      "max_ms": 0.0, "self_s": 0.0})
            dur = end - start
            e["total_s"] += dur / 1e9
            e["self_s"] += dur / 1e9
            e["count"] += 1
            e["max_ms"] = max(e["max_ms"], dur / 1e6)
            by_name.setdefault(name, []).append((start, end))
        starts = {}
        for name, spans in by_name.items():
            spans.sort()
            starts[name] = [s for s, _ in spans]
        for _name, start, end, _seq, parent in rows:
            spans = by_name.get(parent)
            if not spans:
                continue
            i = bisect.bisect_right(starts[parent], start) - 1
            if i >= 0 and end <= spans[i][1]:
                out[parent]["self_s"] -= (end - start) / 1e9
        for e in out.values():
            e["mean_ms"] = 1e3 * e["total_s"] / e["count"]
        return dict(sorted(out.items()))


_default_timeline: Optional[Timeline] = None
_default_timeline_lock = threading.Lock()


def default_timeline() -> Timeline:
    """The process-wide timeline `Trainer` and `DataFeeder` record on
    unless handed another: always on, so the last steps are there for a
    post-mortem (and for the chip benchmark's readers) whether or not
    anyone asked beforehand. Tests pass their own."""
    global _default_timeline
    with _default_timeline_lock:
        if _default_timeline is None:
            _default_timeline = Timeline()
        return _default_timeline
