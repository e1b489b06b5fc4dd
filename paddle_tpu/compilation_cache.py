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
- **Observable.** `install_listeners()` hooks jax.monitoring's
  cache events; `counters()` reports `compile_cache_hits` /
  `compile_cache_misses` for the obs registry and the serving
  server (docs/OBSERVABILITY.md).

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

#: the cache entries written by a *tiny* test model still matter: a
#: fleet restart wants EVERY jitted body cached, not just the ones XLA
#: took >1s to compile (the upstream default threshold).
_MIN_COMPILE_TIME_SECS = 0
_MIN_ENTRY_SIZE_BYTES = -1

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_REQ_EVENT = "/jax/compilation_cache/compile_requests_use_cache"

_lock = threading.Lock()
_listeners_installed = False
_counts = {"hits": 0, "requests": 0}
_enabled_dir: Optional[str] = None

#: where entries land when the environment does not say: one fixed,
#: gitignored directory at the root of this checkout
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def _on_event(event: str, **kwargs) -> None:
    if event == _HIT_EVENT:
        _counts["hits"] += 1
    elif event == _REQ_EVENT:
        _counts["requests"] += 1


def install_listeners() -> None:
    """Idempotently hook jax.monitoring's persistent-cache events.
    jax fires `cache_hits` on a successful disk read and
    `compile_requests_use_cache` per cache-eligible compile; misses
    are requests minus hits (there is no dedicated miss event)."""
    global _listeners_installed
    with _lock:
        if _listeners_installed:
            return
        jax.monitoring.register_event_listener(_on_event)
        _listeners_installed = True


def reset_counters() -> None:
    _counts["hits"] = 0
    _counts["requests"] = 0


def counters() -> Dict[str, int]:
    """Hits/misses since the last reset. Keys are bare (`hits`,
    `misses`): the obs registry prepends its source prefix, so
    registering under "compile_cache" exports the documented
    `compile_cache_hits` / `compile_cache_misses` series
    (docs/OBSERVABILITY.md)."""
    hits = _counts["hits"]
    return {"hits": hits,
            "misses": max(_counts["requests"] - hits, 0)}


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
