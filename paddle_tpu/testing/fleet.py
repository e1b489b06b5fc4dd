"""Fleet test support: replica builders a CHILD PROCESS can
import by name.

A `serve.fleet.ReplicaSpec` carries a `"module:function"` builder
string across the spawn boundary — the child imports it and calls it
to construct its `ServingServer`. This module is where the repo's
own tests keep those builders:

- `build_tiny_server` — the chaos-suite replica: the same tiny
  deterministic transformer every serving test uses (vocab=61,
  dim=32, 2 layers), optionally booted from a PR9 engine artifact so
  a child skips its jit compiles (`save_tiny_artifact` writes a
  matching bundle parent-side; identical seed -> identical weights
  -> the manifest verifies in the child).
- `idle_server` — a no-engine `ServingServer` duck type that boots
  in milliseconds: the orphan-watchdog and supervisor-lifecycle
  tests need real PROCESSES, not real models.
- `orphan_fleet_main` — a supervisor-in-a-subprocess driver for the
  orphan-leak test: boots a fleet of idle replicas, reports the
  child pids up a pipe, then parks forever waiting to be SIGKILLed —
  proving the grandchildren exit on the watchdog alone (no drain, no
  atexit ran).
"""

from __future__ import annotations

import time
import types
from typing import Optional

#: the chaos-suite model geometry — shared with tests/test_router.py
TINY = dict(vocab=61, dim=32, n_layers=2, n_heads=4,
            attn_impl="dense")


def _tiny_engine(*, slots: int = 2, max_len: int = 32,
                 page_size: int = 4, seed: int = 0):
    import jax

    from paddle_tpu.models import transformer as T
    from paddle_tpu.serve.engine import DecodeEngine

    cfg = T.TransformerConfig(**TINY)
    params = T.init_params(jax.random.key(seed), cfg)
    return DecodeEngine(params, cfg, slots=slots, max_len=max_len,
                        page_size=page_size)


def build_tiny_server(*, slots: int = 2, max_len: int = 32,
                      page_size: int = 4, seed: int = 0,
                      max_queue: int = 64, max_retries: int = 1,
                      buckets=(16,), artifact: Optional[str] = None):
    """Replica builder for fleet tests: tiny deterministic
    transformer behind a `ServingServer`. Pass `artifact` (written
    by `save_tiny_artifact` with the SAME seed/geometry/buckets) to
    boot from the AOT bundle — the cheap-replica path autoscaling
    leans on; a mismatched bundle degrades to the jit path, never a
    failed boot."""
    from paddle_tpu.serve.server import ServingServer

    engine = _tiny_engine(slots=slots, max_len=max_len,
                          page_size=page_size, seed=seed)
    return ServingServer(
        engine, max_queue=max_queue, max_retries=max_retries,
        buckets=tuple(buckets) if buckets else None,
        artifact_path=artifact)


def save_tiny_artifact(path: str, *, buckets=(16,), slots: int = 2,
                       max_len: int = 32, page_size: int = 4,
                       seed: int = 0) -> str:
    """Write the PR9 engine artifact `build_tiny_server(artifact=...)`
    boots from. Must be called with the same geometry/seed/buckets
    the replicas use or their manifest check will (safely) fall back
    to jit."""
    from paddle_tpu.serve.artifact import save_engine_artifact

    engine = _tiny_engine(slots=slots, max_len=max_len,
                          page_size=page_size, seed=seed)
    save_engine_artifact(engine, path, buckets=buckets)
    return path


class _IdleServer:
    """The minimum surface `ReplicaTransportServer` + the supervisor
    lifecycle touch, with no engine behind it: boots in milliseconds,
    serves nothing. Process-lifecycle tests (orphan watchdog,
    spawn/reap) want many real processes and zero model cost."""

    def __init__(self):
        self.engine = types.SimpleNamespace(
            paged=False, prefix_cache=False, page_size=0)
        self.role = "unified"
        self.max_retries = 0
        self.default_deadline_ms = None
        self.results: dict = {}
        self.queue: list = []
        self.draining = False

    @property
    def queue_space(self) -> int:
        return 0

    def load(self) -> int:
        return 0

    def ping(self) -> None:
        pass

    def step(self) -> bool:
        return False

    def pending_requests(self) -> list:
        return []

    def counters(self) -> dict:
        return {}

    def reconcile(self) -> None:
        pass

    def ready_handoffs(self) -> list:
        return []

    def drain(self, *, grace_s=None,
              reason: str = "drain requested") -> None:
        self.draining = True

    def withdraw_queued(self, req_id: int):
        return None

    def submit(self, prompt, **kwargs):
        raise ValueError("idle test replica accepts no traffic")


def idle_server(*, data_plane: Optional[str] = None) -> _IdleServer:
    # `data_plane` arrives when the supervisor owns an arena (the
    # name is injected into every replica's kwargs); an idle replica
    # serves no KV so it simply declines to attach.
    del data_plane
    return _IdleServer()


def orphan_cluster_main(conn) -> None:
    """Subprocess driver for the cluster orphan-CHAIN test: become
    the supervisor of two leaseless `cluster.agent` children, each
    owning one idle replica GRANDCHILD; report every pid up the pipe
    (agents first, then grandchildren), then park until SIGKILLed.
    The test asserts the whole three-deep tree exits on the watchdog
    chain alone: supervisor dies -> the agents' pipes EOF -> agents
    fence their replicas and exit -> the replicas' pipes EOF too.
    No drain, no atexit, no layer survives its parent."""
    from paddle_tpu.cluster.agent import AgentProcess, AgentSpec
    from paddle_tpu.serve.fleet import ReplicaSpec

    spec = ReplicaSpec(builder="paddle_tpu.testing.fleet:idle_server")
    agents = [AgentProcess(AgentSpec(host_id=f"host-{i}",
                                     replica_spec=spec)).start()
              for i in range(2)]
    agent_pids, replica_pids = [], []
    for a in agents:
        info = a.wait_ready()
        agent_pids.append(a.pid)
        replica_pids.extend(info["pids"])
    conn.send(agent_pids + replica_pids)
    while True:
        time.sleep(3600)        # waiting for SIGKILL


def orphan_data_fleet_main(conn) -> None:
    """Subprocess driver for the DATA-PLANE orphan test: become a
    supervisor that owns the fleet's shared-memory arena, scatter a
    payload into it (this process is the segments' owner), report
    {arena name, ticket, replica pids} up the pipe, then park until
    SIGKILLed. The test asserts the whole tree dies on the watchdog
    chain (no drain, no atexit — the arena's unlink never ran) and
    that attaching to the orphaned arena BY NAME still reclaims every
    dead-owner segment: shared memory has no kernel-mediated cleanup,
    so the reclaim sweep is the only thing standing between a
    supervisor SIGKILL and a permanent /dev/shm leak."""
    from paddle_tpu.serve.fleet import FleetSupervisor, ReplicaSpec

    spec = ReplicaSpec(builder="paddle_tpu.testing.fleet:idle_server")
    sup = FleetSupervisor(spec, min_replicas=2, max_replicas=2,
                          data_plane_segs=8, data_plane_seg_kb=1)
    sup.start()
    ticket = sup.arena.scatter([b"orphaned kv bytes " * 64])
    conn.send({"arena": sup.arena.name, "ticket": ticket,
               "pids": [p.pid for p in sup.procs.values()
                        if p is not None]})
    while True:
        time.sleep(3600)        # waiting for SIGKILL


def orphan_fleet_main(conn) -> None:
    """Subprocess driver for the orphan-leak test: become a
    supervisor of idle replica PROCESSES, report their pids, then
    park until SIGKILLed. The test then asserts the grandchildren
    exit on the parent-death watchdog alone — this process never
    drains, never reaps, and its atexit hooks never run (that is the
    point)."""
    from paddle_tpu.serve.fleet import FleetSupervisor, ReplicaSpec

    spec = ReplicaSpec(builder="paddle_tpu.testing.fleet:idle_server")
    sup = FleetSupervisor(spec, min_replicas=2, max_replicas=2)
    sup.start()
    conn.send([p.pid for p in sup.procs.values() if p is not None])
    while True:
        time.sleep(3600)        # waiting for SIGKILL
