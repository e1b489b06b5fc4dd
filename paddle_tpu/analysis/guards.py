"""Runtime enforcement of the compiled-execution contract.

graftlint (static) catches the code SHAPES that cause recompiles and
host round-trips; these guards catch the EVENTS at runtime — in tests
("the decode loop compiles exactly once and never again"), and
opted-in around production hot loops (`paddle_tpu serve/train
--transfer-guard`).

- `RecompileGuard`: counts XLA backend compilations inside a `with`
  region and names them, from the compile recorder
  (`compilation_cache.install_listeners`, the repo's one hook on
  `jax.monitoring`): the count is the difference of the timeline
  counter `compile.backend_compiles` (one per real backend compile),
  the names are the `<fun_name>`s of the `compile.backend:*` rows
  that closed in the region. No log stream, no jax config flag.
  `max_compiles=0` (default) makes any compile in the region a
  `RecompileError` — the steady-state assertion.

- `no_implicit_transfers`: thin wrapper over
  `jax.transfer_guard("disallow")` — implicit host->device transfers
  (e.g. feeding a step numpy arrays per call) raise instead of
  silently re-staging every step. Explicit transfers
  (`jax.device_put`, `jnp.asarray`, `jax.device_get`) stay allowed:
  the guard forces the hot loop to NAME its sanctioned transfers.
  NOTE: on the CPU backend device->host reads are zero-copy and not
  guarded, so CPU tests exercise the host->device direction only.

- `LockOrderGuard`: the runtime half of graftlock (locklint LK002 is
  the static half) — a lockdep-style sanitizer. While active, every
  `threading.Lock()`/`RLock()` (and therefore every `Condition`/
  `Event`/`Queue` built on them) is instrumented: per-thread
  held-lock stacks feed a process-global acquisition-order graph,
  and the FIRST acquisition that would invert an established order
  raises `LockOrderError` naming both sites — before the inner
  acquire, so the probe reports the deadlock instead of hanging in
  it. Spans held longer than `max_held_s` land in `held_reports`
  and the flight recorder. The chaos suites (router kill, fleet
  SIGKILL, edge disconnect, pserver failover) run under it so every
  existing fault scenario doubles as a race/deadlock probe.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from paddle_tpu import compilation_cache
from paddle_tpu.obs.trace import default_timeline


class RecompileError(RuntimeError):
    """A guarded steady-state region compiled more than allowed."""


class TransferError(RuntimeError):
    """Reserved for future explicit-transfer accounting; implicit
    transfer violations surface as jax's own XlaRuntimeError from
    `jax.transfer_guard` (re-raised unchanged so the device/runtime
    context is not lost)."""


class RecompileGuard:
    """Assert a region of host code does not trigger XLA compiles.

    >>> step = jax.jit(f)
    >>> step(x)                          # warmup: the ONE compile
    >>> with RecompileGuard(name="train step") as g:
    ...     for _ in range(3):
    ...         x = step(x)              # steady state: no compiles
    >>> g.compiles
    0

    `max_compiles` > 0 allows a known number (e.g. a region expected
    to compile exactly once: max_compiles=1 plus asserting
    `g.compiles == 1` afterwards). On violation `__exit__` raises
    `RecompileError` naming what compiled (the `fun_name` jax's
    compile event carries). Re-entrant use of distinct instances
    nests fine; one instance is single-use."""

    def __init__(self, max_compiles: int = 0, *,
                 name: str = "steady-state region"):
        if max_compiles < 0:
            raise ValueError(
                f"max_compiles must be >= 0, got {max_compiles}")
        self.max_compiles = max_compiles
        self.name = name
        self._entered = False
        #: the counter and the clock at entry
        self._compiles0 = self._t0_ns = 0
        #: (compiles, names) while the region is not open: nothing
        #: before it, what it read once it has ended
        self._result: Optional[Tuple[int, List[str]]] = (0, [])

    # -- results -----------------------------------------------------------

    def _read(self) -> Tuple[int, List[str]]:
        if self._result is not None:
            return self._result
        tl = default_timeline()
        prefix = compilation_cache.BACKEND_ROW_PREFIX
        # a counter, so a wrapped ring cannot lose a compile (it can
        # lose a name)
        return (tl.counters().get(compilation_cache.BACKEND_COMPILES, 0)
                - self._compiles0,
                [r[0][len(prefix):] for r in tl.rows()
                 if r[0].startswith(prefix) and r[2] >= self._t0_ns])

    @property
    def compiles(self) -> int:
        """Backend compiles observed in the region."""
        return self._read()[0]

    @property
    def compiled_names(self) -> List[str]:
        """Names of the computations compiled in the region, in the
        order their compiles ended."""
        return list(self._read()[1])

    # -- context -----------------------------------------------------------

    def __enter__(self) -> "RecompileGuard":
        if self._entered:
            raise RuntimeError("RecompileGuard is single-use — make "
                               "a new one per region")
        compilation_cache.install_listeners()
        tl = default_timeline()
        self._compiles0 = tl.counters().get(
            compilation_cache.BACKEND_COMPILES, 0)
        self._t0_ns = tl.clock_ns()
        self._entered, self._result = True, None
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._result = self._read()
        if exc_type is not None:
            return
        if self.compiles > self.max_compiles:
            names = self.compiled_names
            # lazy, peek-only: importing guards must never allocate obs
            # state, and a process without a flight recorder pays zero.
            # A recorder that exists gets the offending names in its
            # ring BEFORE the raise — the steady-state recompile lands
            # in the next fault dump with the computation named.
            try:
                from paddle_tpu.obs.flight import peek_default
                rec = peek_default()
                if rec is not None:
                    rec.record("guard", "recompile-violation",
                               region=self.name,
                               compiles=self.compiles,
                               max_compiles=self.max_compiles,
                               compiled_names=names)
            except Exception:
                pass
            detail = f": compiled {', '.join(names)}" if names else ""
            raise RecompileError(
                f"{self.name} triggered {self.compiles} XLA "
                f"compile(s), allowed {self.max_compiles}{detail} — "
                f"a steady-state loop is recompiling (changing "
                f"shapes/dtypes/static args, or a jit built per "
                f"call)")


@contextlib.contextmanager
def no_implicit_transfers(level: str = "disallow"):
    """`with no_implicit_transfers():` — implicit host<->device
    transfers in the region raise (jax.transfer_guard). `level` may
    be any jax transfer-guard level ("allow", "log", "disallow",
    "log_explicit", "disallow_explicit")."""
    try:
        with jax.transfer_guard(level):
            yield
    except Exception as e:
        # same peek-only flight hook as RecompileGuard: an implicit
        # transfer caught by the guard lands in the ring before it
        # propagates, so the next dump names the violation
        try:
            from paddle_tpu.obs.flight import peek_default
            rec = peek_default()
            if rec is not None:
                rec.record("guard", "transfer-violation",
                           level=level, error=str(e))
        except Exception:
            pass
        raise


class LockOrderError(RuntimeError):
    """A guarded region acquired locks in an order that inverts an
    already-established order (or re-entered a non-reentrant lock on
    the same thread) — the message names both sites."""


#: originals captured at import: the guard's own bookkeeping must run
#: on REAL locks (a wrapped internal lock would recurse), and
#: uninstall must restore exactly these
_ORIG_LOCK = threading.Lock
_ORIG_RLOCK = threading.RLock

#: the single active guard (wrappers consult this on every op; after
#: `__exit__` surviving wrappers see None and degrade to plain
#: forwarding, so locks created under the guard keep working forever)
_lo_guard: Optional["LockOrderGuard"] = None
_lo_install_mu = _ORIG_LOCK()

_THREADING_FILE = threading.__file__


def _lo_site(skip_self: bool = True) -> str:
    """'pkg/module.py:123' of the nearest caller frame outside this
    module and threading.py — the acquisition site a violation
    names."""
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        if fn != __file__ and fn != _THREADING_FILE:
            parts = fn.replace("\\", "/").split("/")
            return f"{'/'.join(parts[-2:])}:{f.f_lineno}"
        f = f.f_back
    return "<unknown>"


class _GuardedLock:
    """Wrapper over a real Lock/RLock that reports every blocking
    acquisition to the active LockOrderGuard. Implements the
    `_release_save`/`_acquire_restore`/`_is_owned` protocol so
    `threading.Condition` built on a wrapped lock works unchanged
    (wait() keeps the held stack honest)."""

    def __init__(self, reentrant: bool) -> None:
        self._inner = (_ORIG_RLOCK if reentrant else _ORIG_LOCK)()
        self._reentrant = reentrant
        self._owner: Optional[int] = None
        self._depth = 0
        self._acq_t = 0.0
        self._acq_site = ""
        self._birth_site = _lo_site()
        guard = _lo_guard
        self._lo_name = (guard._register(self) if guard is not None
                         else f"{'RLock' if reentrant else 'Lock'}"
                              f"@{self._birth_site}")

    def __repr__(self) -> str:
        return f"<LockOrderGuard.{self._lo_name}>"

    # -- core protocol -----------------------------------------------------

    def acquire(self, blocking: bool = True,
                timeout: float = -1) -> bool:
        guard = _lo_guard
        me = threading.get_ident()
        if guard is None:
            return self._inner.acquire(blocking, timeout)
        if self._reentrant and self._owner == me:
            # same-thread RLock reentrancy: the sanctioned pattern —
            # no order check, no edge, just depth
            ok = self._inner.acquire(blocking, timeout)
            if ok:
                self._depth += 1
            return ok
        site = _lo_site()
        if blocking:
            # BEFORE the inner acquire: an inverted order must raise
            # here, not hang in the deadlock it predicts
            guard._before_acquire(self, me, site)
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            # trylock (blocking=False) can't deadlock, so it records
            # no incoming edge — but once held it IS held: it goes on
            # the stack so later acquisitions see it as a source
            guard._after_acquire(self, me, site,
                                 record_edges=blocking)
        return ok

    def release(self) -> None:
        guard = _lo_guard
        me = threading.get_ident()
        if guard is not None and self._owner == me:
            if self._reentrant and self._depth > 1:
                self._depth -= 1
                self._inner.release()
                return
            guard._before_release(self, me)
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc: Any) -> None:
        self.release()

    # -- Condition compatibility -------------------------------------------
    # CPython's Condition adopts these from the lock when present;
    # wait() must fully release (popping the held stack) and restore
    # without recording edges (the re-acquire after a wait is not a
    # programmer-chosen order).

    def _release_save(self):
        guard = _lo_guard
        me = threading.get_ident()
        if guard is not None and self._owner == me:
            guard._before_release(self, me)
        if self._reentrant:
            return self._inner._release_save()
        self._inner.release()
        return None

    def _acquire_restore(self, state) -> None:
        if self._reentrant:
            self._inner._acquire_restore(state)
        else:
            self._inner.acquire()
        guard = _lo_guard
        if guard is not None:
            guard._after_acquire(self, threading.get_ident(),
                                 _lo_site(), record_edges=False)

    def _is_owned(self) -> bool:
        if self._reentrant:
            return self._inner._is_owned()
        return self._owner == threading.get_ident() \
            or (self._owner is None and self._inner.locked())

    def _at_fork_reinit(self) -> None:
        self._inner._at_fork_reinit()
        self._owner = None
        self._depth = 0


class LockOrderGuard:
    """lockdep for the fleet: `with LockOrderGuard() as g:` patches
    `threading.Lock`/`RLock` so every lock BORN in the region is
    instrumented (Condition/Event/Queue resolve the factories at call
    time, so they are covered too). Per-thread held stacks feed a
    global order graph; the first acquisition that would invert an
    established order raises `LockOrderError` in the acquiring thread
    naming both sites — and is recorded in `g.violations`, which
    `__exit__` re-raises from, so an inversion swallowed by a worker
    thread still fails the test. Holding any lock longer than
    `max_held_s` lands in `g.held_reports` and the flight recorder.

    One guard may be active at a time (the patch is process-global);
    an instance is single-use. Locks created before the region are
    NOT tracked — build the system under test inside the guard.

    >>> with LockOrderGuard(max_held_s=0.25) as g:
    ...     stack = make_fleet(...)          # locks born instrumented
    ...     run_chaos(stack)
    >>> assert g.violations == []
    """

    def __init__(self, *, max_held_s: float = 0.25,
                 raise_on_violation: bool = True,
                 name: str = "lock-order guard") -> None:
        if max_held_s <= 0:
            raise ValueError(
                f"max_held_s must be > 0, got {max_held_s}")
        self.max_held_s = max_held_s
        self.raise_on_violation = raise_on_violation
        self.name = name
        self.violations: List[str] = []
        self.held_reports: List[Dict[str, Any]] = []
        self._entered = False
        #: strong refs to every wrapper born in the region: edge keys
        #: are id()s, and a collected lock's id must not be recycled
        #: into a false edge
        self._locks: List[_GuardedLock] = []
        #: id(src) -> {id(dst): (src_name, dst_name, site)} — site is
        #: where dst was taken while src was held (first occurrence
        #: kept: lockdep semantics, the order is ESTABLISHED once)
        self._edges: Dict[int, Dict[int, Tuple[str, str, str]]] = {}
        self._tls = threading.local()
        self._mu = _ORIG_LOCK()

    # -- bookkeeping -------------------------------------------------------

    def _stack(self) -> List[Tuple["_GuardedLock", str, float]]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _register(self, lock: _GuardedLock) -> str:
        with self._mu:
            self._locks.append(lock)
            n = len(self._locks)
        kind = "RLock" if lock._reentrant else "Lock"
        return f"{kind}#{n}({lock._birth_site})"

    def _find_path(self, src: int, targets: Dict[int, str]
                   ) -> Optional[List[Tuple[str, str, str]]]:
        """DFS over the order graph from `src` to any id in
        `targets`: a path means the inverse of the acquisition being
        attempted is already established (catches N-cycles, not just
        direct inversions). Caller holds self._mu."""
        seen = {src}
        path: List[Tuple[str, str, str]] = []

        def dfs(n: int) -> bool:
            for dst, edge in self._edges.get(n, {}).items():
                if dst in seen:
                    continue
                seen.add(dst)
                path.append(edge)
                if dst in targets or dfs(dst):
                    return True
                path.pop()
            return False

        return path if dfs(src) else None

    def _violation(self, msg: str) -> None:
        with self._mu:
            self.violations.append(msg)
        try:
            from paddle_tpu.obs.flight import peek_default
            rec = peek_default()
            if rec is not None:
                rec.record("guard", "lock-order-violation",
                           guard=self.name, detail=msg)
        except Exception:
            pass
        if self.raise_on_violation:
            raise LockOrderError(msg)

    # -- wrapper callbacks -------------------------------------------------

    def _before_acquire(self, lock: _GuardedLock, me: int,
                        site: str) -> None:
        if lock._owner == me and not lock._reentrant:
            self._violation(
                f"self-deadlock: non-reentrant {lock._lo_name} "
                f"re-acquired at {site} while already held by this "
                f"thread (taken at {lock._acq_site}) — this blocks "
                f"forever; use an RLock or split the critical "
                f"section")
            return
        held = self._stack()
        if not held:
            return
        with self._mu:
            targets = {id(h): h._lo_name for h, _, _ in held
                       if h is not lock}
            path = self._find_path(id(lock), targets) \
                if targets else None
        if path:
            src_name, dst_name, est_site = path[0]
            chain = " -> ".join([path[0][0]]
                                + [e[1] for e in path])
            holder = next(s for h, s, _ in held
                          if h._lo_name == path[-1][1])
            self._violation(
                f"lock order inverted: acquiring {lock._lo_name} at "
                f"{site} while holding {path[-1][1]} (taken at "
                f"{holder}), but the opposite order {chain} was "
                f"established at {est_site} ({src_name} held when "
                f"{dst_name} was taken) — two threads on these "
                f"paths deadlock")

    def _after_acquire(self, lock: _GuardedLock, me: int, site: str,
                       record_edges: bool) -> None:
        stack = self._stack()
        if record_edges and stack:
            with self._mu:
                for h, _, _ in stack:
                    if h is lock:
                        continue
                    self._edges.setdefault(id(h), {}).setdefault(
                        id(lock), (h._lo_name, lock._lo_name, site))
        lock._owner = me
        lock._depth = 1
        lock._acq_t = time.monotonic()
        lock._acq_site = site
        stack.append((lock, site, lock._acq_t))

    def _before_release(self, lock: _GuardedLock, me: int) -> None:
        span = time.monotonic() - lock._acq_t
        stack = self._stack()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i][0] is lock:
                del stack[i]
                break
        lock._owner = None
        lock._depth = 0
        if span > self.max_held_s:
            report = {"lock": lock._lo_name, "held_s": span,
                      "acquired_at": lock._acq_site,
                      "released_at": _lo_site(),
                      "bound_s": self.max_held_s}
            with self._mu:
                self.held_reports.append(report)
            try:
                from paddle_tpu.obs.flight import peek_default
                rec = peek_default()
                if rec is not None:
                    rec.record("guard", "lock-held-too-long",
                               guard=self.name, **report)
            except Exception:
                pass

    # -- context -----------------------------------------------------------

    def __enter__(self) -> "LockOrderGuard":
        global _lo_guard
        if self._entered:
            raise RuntimeError("LockOrderGuard is single-use — make "
                               "a new one per region")
        with _lo_install_mu:
            if _lo_guard is not None:
                raise RuntimeError(
                    "another LockOrderGuard is already active — the "
                    "threading patch is process-global, one at a "
                    "time")
            self._entered = True
            threading.Lock = lambda: _GuardedLock(False)  # type: ignore[misc]
            threading.RLock = lambda: _GuardedLock(True)  # type: ignore[misc]
            _lo_guard = self
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        global _lo_guard
        with _lo_install_mu:
            threading.Lock = _ORIG_LOCK  # type: ignore[misc]
            threading.RLock = _ORIG_RLOCK  # type: ignore[misc]
            _lo_guard = None
        if exc_type is not None:
            return
        if self.violations and self.raise_on_violation:
            # an inversion raised inside a worker thread is swallowed
            # by Thread.run — surface it where the test can see it
            raise LockOrderError(self.violations[0])


@contextlib.contextmanager
def steady_state(name: str = "steady-state region", *,
                 max_compiles: int = 0,
                 transfers: Optional[str] = "disallow"):
    """The combined contract for a hot loop: no (re)compiles AND no
    implicit transfers. The shape the ISSUE's regression tests
    assert on the decode loop and the train step."""
    guard = RecompileGuard(max_compiles, name=name)
    if transfers is None:
        with guard as g:
            yield g
        return
    with guard as g, jax.transfer_guard(transfers):
        yield g
