"""Persistent XLA compilation cache, wired for fleet restarts.

A fleet serving millions of users restarts processes constantly —
deploys, preemptions, router failover — and every restart used to pay
full retrace+compile of the engine's jitted bodies before the first
token moved (ROADMAP item 3). jax ships a persistent on-disk
compilation cache; this module is the ONE place the repo configures
it, with three production requirements the raw knobs don't enforce:

- **Placed from outside, or at one fixed path.** Where
  `JAX_COMPILATION_CACHE_DIR` is set, jax already reads it into
  `jax_compilation_cache_dir` and `enable()` sets no directory — only
  thresholds and listeners — so whoever runs the process (a fleet
  launcher, the chip tool) decides where entries live and finds them
  again. Where it is not set, the directory is `<checkout>/.jax_cache`
  (gitignored): the path is part of jax's cache key, so a directory
  that moves never hits. jax's own key already covers its version, the
  backend and the device topology, so there is no namespacing
  subdirectory — and `enable()` never touches the backend, which keeps
  a parent process off the chip its children need.
- **Corrupt/stale entries degrade to a MISS, never an error.**
  `jax_raise_persistent_cache_errors` stays False (asserted, not
  assumed: `enable()` pins it), so a truncated write from a killed
  process or a garbage file costs one recompile, not an outage.
- **Observable.** `install_listeners()` is the repo's one hook on
  jax.monitoring: every trace, lowering, cache read and backend
  compile becomes a row of `obs.trace.default_timeline()`, named by
  phase and function, and the cache's requests, hits and misses its
  counters (the table at `_PHASE_ROWS`). `counters()` reports
  `compile_cache_hits` / `compile_cache_misses` for the obs registry
  and the serving server from them, `compile_seconds()` is what a
  stretch of the process spent compiling, and `RecompileGuard` reads
  the same rows (docs/OBSERVABILITY.md).

Everything the CLI compiles — serve engine bodies, the train step,
infer forwards — flows through XLA's one compile entry point, so a
single `enable()` near process start covers all of them.
docs/SERVING.md "AOT artifacts & compile cache" is the operational
guide; what a warm cache saved on the chip is in PERF.md (`setup_s`).
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Dict, Optional

import jax
from jax._src import compilation_cache as _jax_cc

from paddle_tpu.obs.trace import default_timeline

#: the cache entries written by a *tiny* test model still matter: a
#: fleet restart wants EVERY jitted body cached, not just the ones XLA
#: took >1s to compile (the upstream default threshold).
_MIN_COMPILE_TIME_SECS = 0
_MIN_ENTRY_SIZE_BYTES = -1

#: jax's three compile phases, each fired with `fun_name=` as the phase
#: ends -> the prefix of the row `<prefix>:<fun_name>`. Traces nest (an
#: inner jit traced inside an outer one fires inside the outer's
#: interval), so a reader takes the union of these rows, never their sum.
#: `compile.lower` holds the Pallas -> Mosaic lowering of a kernel;
#: `compile.backend` covers `compile_or_get_cached`: the cache key's
#: hashing, then the read or the XLA compile.
_TRACE, _BACKEND = "compile.trace", "compile.backend"
_PHASE_ROWS = {
    "/jax/core/compile/jaxpr_trace_duration": _TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": _BACKEND,
}
#: jax fires `jaxpr_trace_duration` for every jitted `jnp` wrapper the
#: tracing of a step passes through: 2,650 of the dense LM cell's 3,184
#: traces a set-up, 4,240 of the block-diffusion cell's 4,975 took under
#: 50 us and summed to 0.03-0.06 s, nearly all inside a longer trace's
#: row (PERF.md section 6, PR 39). They would fill the ring the step
#: spans live in, so a trace shorter than this leaves no row.
_TRACE_ROW_FLOOR_S = 1e-4
#: a successful disk read: no name of its own, it lies inside the
#: `compile.backend:<fun_name>` row that closes next
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: what `RecompileGuard` reads: the names after this prefix, and the
#: counter of backend compiles, hit or miss
BACKEND_ROW_PREFIX = _BACKEND + ":"
BACKEND_COMPILES = "compile.backend_compiles"
_REQUESTS, _HITS = "compile.cache_requests", "compile.cache_hits"
#: per cache-eligible compile; per successful read; per entry written
_COUNT_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": _REQUESTS,
    "/jax/compilation_cache/cache_hits": _HITS,
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}

_lock = threading.Lock()
_listeners_installed = False
#: what `compile.cache_requests` / `compile.cache_hits` read at the last
#: `reset_counters()`
_baseline = {_REQUESTS: 0, _HITS: 0}
_enabled_dir: Optional[str] = None

#: where entries land when the environment does not say: one fixed,
#: gitignored directory at the root of this checkout
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def _on_duration(event: str, duration: float, fun_name=None, **_kw) -> None:
    """jax fires a duration on leaving the phase, on the thread that
    ran it: the row ends at the timeline's clock now and started
    `duration` earlier, under whatever span that thread has open."""
    prefix = _PHASE_ROWS.get(event)
    if prefix is not None:
        if prefix == _TRACE and duration < _TRACE_ROW_FLOOR_S:
            return
        name = f"{prefix}:{fun_name}"
    elif event == _CACHE_READ_EVENT:
        name = "compile.cache_read"
    else:
        return
    tl = default_timeline()
    end = tl.clock_ns()
    tl.add(name, end - int(duration * 1e9), end)
    if prefix == _BACKEND:
        _count(tl, BACKEND_COMPILES)


def _on_event(event: str, **_kw) -> None:
    counter = _COUNT_EVENTS.get(event)
    if counter is not None:
        _count(default_timeline(), counter)


def _count(tl, counter: str) -> None:
    # compiles come from any thread (the serve path's) and a timeline
    # counter has one owner: the lock stands in for it. Compile events
    # are rare and never on a step's path.
    with _lock:
        tl.count(counter)


def install_listeners() -> None:
    """Idempotently hook jax.monitoring: one duration listener and one
    event listener for the whole repo, for the life of the process.
    Touches no backend."""
    global _listeners_installed
    with _lock:
        if _listeners_installed:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listeners_installed = True


def reset_counters() -> None:
    """`counters()` counts from here on."""
    now = default_timeline().counters()
    for key in _baseline:
        _baseline[key] = now.get(key, 0)


def counters() -> Dict[str, int]:
    """Hits/misses since the last reset: the timeline's
    `compile.cache_hits` and `compile.cache_requests` against their
    readings then. A miss is a request that was no hit (a corrupt
    entry, or one never written, counts). Keys are bare (`hits`,
    `misses`): the obs registry prepends its source prefix, so
    registering under "compile_cache" exports the documented
    `compile_cache_hits` / `compile_cache_misses` series
    (docs/OBSERVABILITY.md)."""
    now = default_timeline().counters()
    hits = now.get(_HITS, 0) - _baseline[_HITS]
    requests = now.get(_REQUESTS, 0) - _baseline[_REQUESTS]
    return {"hits": hits, "misses": max(requests - hits, 0)}


def compile_seconds(since_ns: int = 0) -> float:
    """Seconds of the process, from `since_ns` on the timeline's clock,
    that lie inside some `compile.*` row: the union of the intervals
    (they nest and, across threads, overlap), so never more than the
    wall time."""
    spans = sorted((max(start, since_ns), end)
                   for name, start, end, _seq, _parent
                   in default_timeline().rows()
                   if name.startswith("compile.") and end > since_ns)
    total, covered_to = 0, since_ns
    for start, end in spans:
        if end > covered_to:
            total += end - max(start, covered_to)
            covered_to = end
    return total / 1e9


def enabled_dir() -> Optional[str]:
    """The directory entries are landing in, or None."""
    return _enabled_dir


def enable(cache_dir: Optional[str] = None) -> str:
    """Turn jax's persistent compilation cache on and pin the
    fleet-safe knobs: cache everything (no min compile time / entry
    size), enable XLA-level subcaches, and NEVER raise on a corrupt
    entry — a bad read logs a warning and recompiles
    (tests/test_artifact_cache.py proves it).

    The directory: where `JAX_COMPILATION_CACHE_DIR` is set it wins and
    nothing here writes `jax_compilation_cache_dir` (an explicit
    `cache_dir` that disagrees is ignored with a warning); otherwise
    `cache_dir`, or `DEFAULT_DIR`. Returns the directory. Idempotent;
    does not initialise the backend. Call near process start, before
    the first jit executes, or early compiles simply miss."""
    global _enabled_dir
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        if cache_dir and os.path.abspath(
                os.path.expanduser(cache_dir)) != os.path.abspath(env_dir):
            warnings.warn(
                f"{ENV_VAR}={env_dir!r} places the compile cache; "
                f"ignoring the requested directory {cache_dir!r}")
        path = env_dir
    else:
        path = os.path.abspath(os.path.expanduser(cache_dir or DEFAULT_DIR))
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_TIME_SECS)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      _MIN_ENTRY_SIZE_BYTES)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    # corrupt/stale entries MUST degrade to a miss (the whole point
    # of a cache a fleet can trust) — pin it, don't assume it
    jax.config.update("jax_raise_persistent_cache_errors", False)
    # jax latches its cache-backend singleton at the FIRST compile: a
    # process that compiled anything before `enable()` would silently
    # never write an entry. Resetting it makes the next compile re-read
    # the config, so enabling mid-process (tests, notebooks) works.
    _jax_cc.reset_cache()
    install_listeners()
    _enabled_dir = path
    return path


def disable() -> None:
    """Turn the persistent cache off (in-memory jit caching is
    untouched). Counters keep their values for post-mortem reads.
    A directory placed by the environment is the environment's to
    remove: with `JAX_COMPILATION_CACHE_DIR` set this only forgets
    that `enable()` ran."""
    global _enabled_dir
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", None)
    _jax_cc.reset_cache()
    _enabled_dir = None
