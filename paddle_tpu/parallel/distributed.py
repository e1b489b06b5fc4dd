"""Multi-host bootstrap + cross-host coordination.

The TPU-native replacement for the reference's cluster bring-up: etcd
registration with leases and once-only parameter init (reference:
go/pserver/etcd_client.go, go/pserver/service.go:260 FinishInitParams)
and the pserver pass barriers (reference: pserver/ParameterServer2.h
waitPassStart/waitPassFinish). On TPU pods, jax.distributed's
coordinator service plays etcd's role; XLA collectives over ICI/DCN
replace the RPC barriers.

Single-process (one host, N chips) needs none of this — every helper is
a safe no-op there.
"""

from __future__ import annotations

import logging
import os
import sys
from typing import Optional

import jax
import numpy as np
from jax._src import xla_bridge

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the multi-host job. Must run before any other jax call
    (anything that initializes the XLA backend — including
    jax.devices()/process_count() — makes distributed init impossible,
    so this function deliberately touches no other jax API first).

    With explicit args, failures propagate (the user asked for a
    cluster). With no args, jax's own cluster auto-detection decides:
    "no cluster environment found" is treated as benign single-process;
    any OTHER bring-up failure (coordinator unreachable, timeout)
    propagates rather than silently degrading to N independent
    single-process jobs.

    On Cloud TPU pods all three args are auto-detected; pass them
    explicitly for other clusters (reference analog:
    --pservers/--trainer_id flags + etcd discovery).
    """
    global _initialized
    if _initialized:
        return
    auto = (coordinator_address is None and num_processes is None
            and process_id is None)
    if xla_bridge.backends_are_initialized():
        if jax.process_count() > 1:
            _initialized = True
            return  # already joined
        if auto:
            # too late to join a cluster, but nothing suggests one was
            # requested — benign for single-process use
            import warnings

            warnings.warn(
                "paddle_tpu.parallel.distributed.initialize() called "
                "after the XLA backend initialized; multi-host join is "
                "no longer possible in this process.")
            return
        raise RuntimeError(
            "distributed.initialize(coordinator_address=...) must be the "
            "first jax-touching call in the process")
    _enable_cpu_collectives()
    if auto:
        try:
            jax.distributed.initialize()
        except ValueError as e:
            # jax raises exactly this when auto-detection finds no
            # cluster — the benign single-process case
            if "coordinator_address" in str(e):
                return
            raise
    else:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    _initialized = True


def _enable_cpu_collectives() -> None:
    """When the job is pinned to the CPU backend (JAX_PLATFORMS=cpu: CI
    gangs), XLA:CPU refuses multi-process computations unless a
    cross-process collectives transport is configured — the default is
    none, and every collective then dies with INVALID_ARGUMENT
    "Multiprocess computations aren't implemented on the CPU backend".
    Selecting jax's bundled gloo TCP transport before the coordinator
    handshake makes CPU gangs first-class. TPU/GPU paths are untouched
    (their collectives ride ICI/DCN/NCCL and ignore this flag)."""
    # jax reads JAX_PLATFORMS into this config value at import
    if "cpu" not in (jax.config.jax_platforms or ""):
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def is_primary() -> bool:
    """True on the process that should write checkpoints/logs (the
    save-model-election winner in the reference, go/master/service.go:481
    — deterministic here instead of elected)."""
    return jax.process_index() == 0


def sync_hosts(name: str = "sync") -> None:
    """Cross-host barrier (waitPassStart/Finish equivalent)."""
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def broadcast_from_primary(pytree):
    """Make host-local values identical everywhere by broadcasting the
    primary's copy (FinishInitParams-style once-only init)."""
    if jax.process_count() <= 1:
        return pytree
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(pytree)


def abort(reason: str, exit_code: int = 75) -> None:
    """Force-exit THIS process immediately (`os._exit` — no atexit, no
    flushing of device work). The clean abort for a wedged collective:
    the main thread is blocked in an uninterruptible device wait, so
    exceptions and signals cannot reach it; process death is the only
    unstick, and under gang scheduling (k8s JobSet restartPolicy — the
    etcd-lease-expiry analog, reference: go/master/etcd_client.go) a
    non-zero exit restarts the whole job into the checkpoint-resume
    path. Used by train.resilience.Watchdog as the default timeout
    action."""
    logging.getLogger(__name__).critical(
        "aborting process %d: %s", os.getpid(), reason)
    try:
        sys.stderr.write(f"paddle_tpu ABORT: {reason}\n")
        sys.stderr.flush()
    except Exception:
        pass
    os._exit(exit_code)


def replicated_agree(value) -> bool:
    """Check a host-local scalar agrees across processes (sanity check
    for data-parallel determinism; returns True single-process)."""
    if jax.process_count() <= 1:
        return True
    from jax.experimental import multihost_utils

    ref = multihost_utils.broadcast_one_to_all(np.asarray(value))
    return bool(np.all(np.asarray(value) == ref))
