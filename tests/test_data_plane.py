"""Zero-copy data plane: shared-memory KV arena + batched control RPC.

The tentpole claim (ROADMAP, docs/SERVING.md "Zero-copy data plane"):
a KV migration's page bytes move through a `serve.shm_arena.ShmArena`
both replicas map — the control frame carries only a picklable ticket
(tag + segment ids + sizes) — and the arena's on-shared-memory
ownership ledger makes the path crash-safe: a SIGKILL on either side
of a transfer leaves segments a reclaim sweep provably frees, never a
wrong answer and never a permanent /dev/shm leak. Proven here at
every layer:

- arena unit surface: scatter/gather round-trips (zero-copy within a
  segment, counted assembly across), the free-list cap, idempotent
  free, attach-by-name with a version gate, `reconcile()` catching
  both leaks and phantom expectations;
- orphan reclamation under REAL death (forked children SIGKILL
  themselves through `FaultPlan.wrap_arena` mid-scatter / mid-adopt):
  dead-owner segments reclaim, live-owner segments survive the same
  sweep, and a reclaimed ticket is refused as STALE by `gather` —
  exactly-once never depends on sweep timing;
- the multi-part wire framing the control plane rides
  (`wire.send_frames`/`recv_frames`): legacy interop, the 1 GiB cap
  enforced across the SUM of parts before allocation, and the
  truncated-frame regression (a peer dying after the header is a dead
  stream, not short data);
- disaggregated-fleet parity over the arena: greedy and speculative
  decode stay bit-exact vs solo `generate()` through an arena-backed
  migration, every ACK frees its ticket, and the pickle-fallback arm
  (`FaultPlan(arena_error_at=...)`) produces the SAME tokens with a
  `data_plane_fallbacks` counter + flight event — never a wrong
  answer;
- batched control RPC (`transport.ProcessReplica`): handoff ACKs
  defer onto the next sweep frame, `rpc_frames_coalesced` counts the
  frames that never hit the wire, and per-stream `partial_tokens`
  polls are served from the partials block every sweep reply already
  carries (the PR17 edge's poll loop stops costing one RPC per token);
- real-process SIGKILL chaos (slow lane): source killed mid-scatter,
  destination killed mid-adopt, and the supervisor itself SIGKILLed —
  each ends with exactly one outcome per request, zero leaked
  segments after the reclaim sweep the supervisor's own `sweep()`
  drives, and bit-exact completions on the survivors.
"""

import multiprocessing
import os
import signal
import socket
import struct
import time

import numpy as np
import pytest

import jax

from paddle_tpu.models import transformer as T
from paddle_tpu.obs.flight import FlightRecorder
from paddle_tpu.serve.engine import DecodeEngine
from paddle_tpu.serve.fleet import (FleetSupervisor, ReplicaProcess,
                                    ReplicaSpec)
from paddle_tpu.serve.router import ServingRouter
from paddle_tpu.serve.server import (MigrationRefusedError,
                                     ServingServer)
from paddle_tpu.serve.shm_arena import (ArenaError, ArenaFull,
                                        ArenaUnavailable, ShmArena,
                                        _pid_alive, attach_cached)
from paddle_tpu.serve.transport import (ProcessReplica, ReplicaClient,
                                        ReplicaTransportServer)
from paddle_tpu.testing.faults import FaultPlan, ManualClock
from paddle_tpu.wire import (MAX_PARTS, recv_frames, send_frame,
                             send_frames)

pytestmark = [pytest.mark.data]

CFG = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                          attn_impl="dense")
BUCKETS = (16,)

#: env every replica child gets (the parent conftest pins cpu + 8
#: virtual devices; children re-assert cpu and need only 1)
CHILD_ENV = {"JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def engines(params):
    """Three warmed engines (prefill + two decode), migration bodies
    pre-compiled by one throwaway fleet pass so the per-test call
    phase pays traffic, not compiles."""
    engs = [DecodeEngine(params, CFG, slots=2, max_len=32, page_size=4,
                         prefill_chunk=8)
            for _ in range(3)]
    warm = np.arange(11, dtype=np.int32)
    for e in engs:
        e.serve([warm], max_new=2, buckets=BUCKETS)
    clk = ManualClock()
    router = _make_fleet(engs, clk, None)
    router.submit(np.arange(1, 12, dtype=np.int32), max_new=2)
    router.run()
    return engs


def _make_fleet(engines, clk, arena, *,
                roles=("prefill", "decode", "decode"), wrap=None,
                speculative=False, flight=None, **router_kw):
    """Disaggregated fleet with the shared arena handed to every
    server as a live OBJECT (in-process replicas share one mapping —
    attach-by-name is the cross-process path, covered below)."""
    servers = []
    for i, (eng, role) in enumerate(zip(engines, roles)):
        if wrap and wrap.get(i) is not None:
            eng = wrap[i](eng)
        servers.append(ServingServer(
            eng, role=role, max_queue=16, clock=clk, buckets=BUCKETS,
            max_retries=2, data_plane=arena, flight=flight,
            speculative=(speculative and role == "decode")))
    return ServingRouter(servers, clock=clk, probe_interval_s=1e9,
                         **router_kw)


def ref_tokens(params, prompt, max_new):
    out = T.generate(params, CFG, jax.numpy.asarray(prompt)[None, :],
                     steps=max_new)
    return [int(t) for t in np.asarray(out[0, len(prompt):])]


def prompts_for(n, seed, lo=9, hi=14):
    r = np.random.RandomState(seed)
    return [r.randint(1, 60, (int(r.randint(lo, hi)),)).astype(np.int32)
            for _ in range(n)]


@pytest.fixture
def mk_arena():
    made = []

    def make(**kw):
        a = ShmArena(**kw)
        made.append(a)
        return a

    yield make
    for a in made:
        a.close(destroy=True)


# ---------------------------------------------------------------------------
# arena unit surface (no jax, no engines)


class TestArena:
    def test_scatter_gather_roundtrip_zero_copy(self, mk_arena):
        arena = mk_arena(seg_size=1024, n_segs=8)
        parts = [b"hello, pages",
                 np.arange(64, dtype=np.int32).tobytes()]
        t = arena.scatter(parts)
        assert t["arena"] == arena.name
        assert t["nbytes"] == sum(len(p) for p in parts)
        got = arena.gather(t)
        assert [bytes(g) for g in got] == [bytes(p) for p in parts]
        # both parts lie inside one segment: pure views, nothing
        # assembled
        assert arena.bytes_gather_copied == 0
        assert arena.segments_live() == len(t["segs"]) == 1
        arena.adopt(t)
        # the ACK path: free returns the segments and replays as a
        # no-op (the router may resend a lost ACK)
        assert arena.free(t) == 1
        assert arena.free(t) == 0
        assert arena.segments_live() == 0
        c = arena.counters()
        assert c["arena_scatters"] == 1
        assert c["arena_adoptions"] == 1
        assert c["arena_frees"] == 1
        assert c["arena_bytes_scattered"] == t["nbytes"]
        arena.reconcile()

    def test_segment_spanning_part_is_assembled(self, mk_arena):
        arena = mk_arena(seg_size=1024, n_segs=8)
        blob = bytes(range(256)) * 10           # 2560 B -> 3 segments
        t = arena.scatter([blob])
        assert len(t["segs"]) == 3
        [got] = arena.gather(t)
        assert bytes(got) == blob
        assert arena.bytes_gather_copied == len(blob)
        arena.free(t)
        arena.reconcile()

    def test_arena_full_is_transient(self, mk_arena):
        arena = mk_arena(seg_size=1024, n_segs=8)
        t1 = arena.scatter([b"x" * 7000])       # 7 of 8 segments
        with pytest.raises(ArenaFull):
            arena.scatter([b"y" * 2048])
        # nothing was half-claimed by the refusal
        assert arena.segments_live() == 7
        arena.free(t1)
        t2 = arena.scatter([b"y" * 2048])
        arena.free(t2)
        arena.reconcile()

    def test_attach_by_name_and_version_gate(self, mk_arena):
        arena = mk_arena(seg_size=1024, n_segs=4)
        other = ShmArena(arena.name, create=False)
        t = arena.scatter([b"cross-process bytes"])
        [got] = other.gather(t)
        assert bytes(got) == b"cross-process bytes"
        other.adopt(t)                  # the destination-side stamp
        assert arena.free(t) == 1       # the SOURCE owns the release
        other.close()
        h1 = attach_cached(arena.name)
        assert attach_cached(arena.name) is h1   # one handle/process
        h1.close()
        with pytest.raises(ArenaUnavailable):
            ShmArena("pt-arena-no-such-arena", create=False)
        # a same-name arena from an incompatible build is refused,
        # never misread
        arena._led[1] = 999
        with pytest.raises(ArenaUnavailable, match="version mismatch"):
            ShmArena(arena.name, create=False)
        arena._led[1] = ShmArena.VERSION

    def test_reconcile_catches_leak_and_phantom(self, mk_arena):
        arena = mk_arena(seg_size=1024, n_segs=4)
        t = arena.scatter([b"z" * 10])
        with pytest.raises(AssertionError, match="arena leak"):
            arena.reconcile()           # live ticket nobody expected
        arena.reconcile([t["tag"]])
        arena.free(t)
        with pytest.raises(AssertionError, match="lost"):
            arena.reconcile([t["tag"]])   # expectation without segments
        arena.reconcile()


# ---------------------------------------------------------------------------
# orphan reclamation under real death (forked children, SIGKILL)


def _child_scatter_then_exit(arena, conn, blob):
    # forked children reuse the INHERITED handle: fork shares the
    # mapping, and attaching by name would double-register the arena
    # with the parent's resource tracker
    t = arena.scatter([blob])
    conn.send(t)
    conn.close()
    os._exit(0)                 # dies un-ACKed: its segments leak


def _child_scatter_killed(arena, blob, plan_kwargs):
    FaultPlan(**plan_kwargs).wrap_arena(arena)
    arena.scatter([blob])       # SIGKILLs itself mid-write
    os._exit(1)                 # pragma: no cover - never reached


def _child_adopt_killed(arena, ticket, plan_kwargs):
    FaultPlan(**plan_kwargs).wrap_arena(arena)
    arena.adopt(ticket)         # SIGKILLs itself mid-stamp
    os._exit(1)                 # pragma: no cover - never reached


def _fork(fn, *args):
    p = multiprocessing.get_context("fork").Process(target=fn,
                                                    args=args)
    p.start()
    return p


class TestOrphanReclaim:
    # fork-based children touch ONLY the arena (numpy over shm) and
    # os._exit before any JAX work, so jax's fork-vs-threads warning
    # does not apply here
    pytestmark = [
        pytest.mark.faults,
        pytest.mark.filterwarnings("ignore:os.fork:RuntimeWarning")]

    def test_stale_ticket_refused_after_reclaim(self, mk_arena):
        arena = mk_arena(seg_size=1024, n_segs=8)
        parent, child = multiprocessing.get_context("fork").Pipe()
        p = _fork(_child_scatter_then_exit, arena, child, b"k" * 100)
        ticket = parent.recv()
        p.join(10.0)
        assert p.exitcode == 0
        c = arena.counters()
        assert c["arena_segments_leaked"] == len(ticket["segs"]) == 1
        assert arena.reclaim_orphans() == 1
        # the ticket outlived its segments: gather must refuse, never
        # hand back whatever lands there next
        with pytest.raises(ArenaError, match="stale ticket"):
            arena.gather(ticket)
        assert arena.free(ticket) == 0          # idempotent with reclaim
        arena.reconcile()

    def test_source_killed_mid_scatter_leaks_all_claimed(self,
                                                         mk_arena):
        arena = mk_arena(seg_size=1024, n_segs=8)
        # 1500 B claims 2 segments up front; the kill after the FIRST
        # write must leak BOTH (claimed is owned, written or not)
        p = _fork(_child_scatter_killed, arena, b"s" * 1500,
                  dict(arena_kill_scatter_at=0))
        p.join(10.0)
        assert p.exitcode == -signal.SIGKILL
        c = arena.counters()
        assert c["arena_segments_leaked"] == 2
        assert arena.reclaim_orphans() == 2
        assert arena.reclaim_orphans() == 0     # sweep replay: no-op
        arena.reconcile()

    def test_destination_killed_mid_adopt_costs_nothing(self,
                                                        mk_arena):
        arena = mk_arena(seg_size=1024, n_segs=8)
        t = arena.scatter([b"q" * 1500])        # 2 segments
        # kill before the SECOND stamp: a mixed ledger (one ADOPTED
        # with a dead adopter, one still INFLIGHT) — but the live
        # SOURCE owns both, so nothing leaks and nothing reclaims
        p = _fork(_child_adopt_killed, arena, t,
                  dict(arena_kill_adopt_at=1))
        p.join(10.0)
        assert p.exitcode == -signal.SIGKILL
        c = arena.counters()
        assert c["arena_segments_leaked"] == 0
        assert arena.reclaim_orphans() == 0
        [got] = arena.gather(t)                 # bytes still whole
        assert bytes(got) == b"q" * 1500
        assert arena.free(t) == 2               # the normal ACK path
        arena.reconcile()

    def test_both_sides_killed_one_sweep_reclaims_all(self, mk_arena):
        arena = mk_arena(seg_size=1024, n_segs=8)
        parent, child = multiprocessing.get_context("fork").Pipe()
        pa = _fork(_child_scatter_killed, arena, b"a" * 1500,
                   dict(arena_kill_scatter_at=0))     # leaks 2
        pb = _fork(_child_scatter_then_exit, arena, child, b"b" * 100)
        dead_ticket = parent.recv()                   # leaks 1
        mine = arena.scatter([b"m" * 10])       # must SURVIVE the sweep
        pa.join(10.0)
        pb.join(10.0)
        assert (pa.exitcode, pb.exitcode) == (-signal.SIGKILL, 0)
        assert arena.counters()["arena_segments_leaked"] == 3
        assert arena.reclaim_orphans() == 3
        arena.reconcile([mine["tag"]])
        with pytest.raises(ArenaError, match="stale ticket"):
            arena.gather(dead_ticket)
        [got] = arena.gather(mine)
        assert bytes(got) == b"m" * 10
        arena.free(mine)
        arena.reconcile()


# ---------------------------------------------------------------------------
# multi-part wire framing (the control plane's transport idiom)


class TestMultiPartWire:
    def test_roundtrip_and_legacy_interop(self):
        a, b = socket.socketpair()
        try:
            parts = [b"head", b"", b"x" * 70000]
            send_frames(a, parts)
            assert recv_frames(b) == parts
            # a legacy single frame arrives as a one-element list:
            # old clients keep working against new servers
            send_frame(a, b"legacy")
            assert recv_frames(b) == [b"legacy"]
        finally:
            a.close()
            b.close()

    def test_truncated_multipart_frame_is_a_dead_stream(self):
        # regression: the peer dies after the header promised 12
        # payload bytes but delivered 3 — the receiver must raise,
        # not hang and not deliver short data as a frame
        a, b = socket.socketpair()
        try:
            hdr = struct.pack("<II", 0xFFFFFFFF, 2)
            hdr += struct.pack("<2Q", 5, 7)
            a.sendall(hdr + b"abc")
            a.close()
            with pytest.raises(ConnectionError, match="mid-frame"):
                recv_frames(b)
        finally:
            b.close()

    def test_summed_cap_enforced_before_allocation(self):
        a, b = socket.socketpair()
        try:
            # every part is under the cap; the SUM is over it — the
            # header alone is refused, no payload byte was ever sent
            # so nothing could have been allocated
            hdr = struct.pack("<II", 0xFFFFFFFF, 3)
            hdr += struct.pack("<3Q", 500, 500, 500)
            a.sendall(hdr)
            with pytest.raises(ConnectionError, match="exceeds"):
                recv_frames(b, max_frame=1024)
        finally:
            a.close()
            b.close()

    def test_part_count_cap(self):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack("<II", 0xFFFFFFFF, MAX_PARTS + 1))
            with pytest.raises(ConnectionError, match="part cap"):
                recv_frames(b)
        finally:
            a.close()
            b.close()

    def test_sender_refuses_oversized_sum(self):
        a, b = socket.socketpair()
        try:
            with pytest.raises(ValueError, match="multi-part frame"):
                send_frames(a, [b"x" * 600, b"y" * 600],
                            max_frame=1024)
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# disaggregated fleet over the arena (in-process, bit-exact parity)


class TestArenaFleet:
    def test_greedy_parity_zero_copies_acked_free(self, params,
                                                  engines, mk_arena):
        arena = mk_arena(seg_size=4096, n_segs=32)
        clk = ManualClock()
        router = _make_fleet(engines, clk, arena)
        prompts = prompts_for(3, seed=7)
        ids = [router.submit(p, max_new=5) for p in prompts]
        res = router.run()
        for p, rr in zip(prompts, ids):
            assert res[rr].outcome == "completed"
            assert res[rr].tokens == ref_tokens(params, p, 5)
            assert res[rr].replica in (1, 2)    # landed on decode tier
        c = router.counters()
        assert c["migrations"] == 3
        assert c["fleet_data_plane_fallbacks"] == 0
        # every migration moved its bytes through the arena exactly
        # once, and every ACK freed its ticket
        assert arena.scatters == 3 and arena.adoptions == 3
        assert arena.frees == 3
        assert arena.bytes_scattered > 0
        assert arena.bytes_gathered == arena.bytes_scattered
        assert arena.segments_live() == 0
        arena.reconcile()
        router.reconcile()

    @pytest.mark.slow  # tier-1 budget guard: the data lane runs it
    def test_speculative_parity_over_arena(self, params, engines,
                                           mk_arena):
        arena = mk_arena(seg_size=4096, n_segs=32)
        clk = ManualClock()
        router = _make_fleet(engines, clk, arena, speculative=True)
        prompts = prompts_for(2, seed=11)
        ids = [router.submit(p, max_new=6) for p in prompts]
        res = router.run()
        for p, rr in zip(prompts, ids):
            assert res[rr].outcome == "completed"
            assert res[rr].tokens == ref_tokens(params, p, 6)
        c = router.counters()
        assert c["migrations"] == 2
        assert c["fleet_spec_rounds"] > 0
        assert c["fleet_data_plane_fallbacks"] == 0
        assert arena.scatters == 2 and arena.segments_live() == 0
        arena.reconcile()
        router.reconcile()

    def test_export_scatters_once_and_ack_frees(self, params, engines,
                                                mk_arena):
        arena = mk_arena(seg_size=4096, n_segs=16)
        srv = ServingServer(engines[0], role="prefill", buckets=BUCKETS,
                            clock=lambda: 0.0, data_plane=arena)
        rid = srv.submit(np.arange(1, 12, dtype=np.int32), max_new=4)
        srv.run()
        p1 = srv.export_request(rid)
        assert p1["kv"] is None                 # bytes never pickled
        t1 = p1["kv_ref"]["ticket"]
        # an RPC retry (or a retargeted destination) re-exports the
        # SAME ticket — never a second scatter to leak
        p2 = srv.export_request(rid)
        assert p2["kv_ref"]["ticket"] == t1
        assert arena.scatters == 1
        # handoff ledger == arena live tags (the reconcile join)
        assert arena.live_tags(os.getpid()) == {t1["tag"]}
        srv.handoff_complete(rid)
        assert arena.segments_live() == 0
        assert srv.counters()["data_plane_fallbacks"] == 0
        srv.reconcile()
        arena.reconcile()


class TestArenaFleetChaos:
    pytestmark = [pytest.mark.faults]

    def test_fallback_parity_bit_exact(self, params, engines,
                                       mk_arena):
        """The arena refuses the FIRST scatter: the payload rides the
        legacy pickle path with a counter + flight event and the SAME
        tokens; the next migration is back on the zero-copy path."""
        arena = mk_arena(seg_size=4096, n_segs=32)
        plan = FaultPlan(arena_error_at=0)
        plan.wrap_arena(arena)
        clk = ManualClock()
        flight = FlightRecorder(clock=clk)
        router = _make_fleet(engines, clk, arena, flight=flight)
        prompt = np.arange(2, 14, dtype=np.int32)
        rr = router.submit(prompt, max_new=6)
        res = router.run()
        assert plan.count("arenaerr") == 1
        assert res[rr].outcome == "completed"
        assert res[rr].tokens == ref_tokens(params, prompt, 6)
        c = router.counters()
        assert c["migrations"] == 1
        assert c["fleet_data_plane_fallbacks"] == 1
        assert arena.scatters == 0 and arena.segments_live() == 0
        falls = [e for e in flight.events()
                 if e["kind"] == "data_plane" and e["name"] == "fallback"]
        assert len(falls) == 1 and falls[0]["where"] == "scatter"
        # the fault was transient: the next migration scatters again
        p2 = np.arange(4, 16, dtype=np.int32)
        r2 = router.submit(p2, max_new=4)
        res = router.run()
        assert res[r2].outcome == "completed"
        assert res[r2].tokens == ref_tokens(params, p2, 4)
        assert arena.scatters == 1 and arena.segments_live() == 0
        assert router.counters()["fleet_data_plane_fallbacks"] == 1
        arena.reconcile()
        router.reconcile()

    def test_gather_failure_refuses_then_cancels_bit_exact(
            self, params, engines, mk_arena):
        """A ticket reclaimed between export and import (the orphan
        sweep racing a slow destination): the import REFUSES
        transiently — the destination never admits — and the source's
        cancel path decodes locally from its still-pinned copy."""
        arena = mk_arena(seg_size=4096, n_segs=16)
        src = ServingServer(engines[0], role="prefill", buckets=BUCKETS,
                            clock=lambda: 0.0, data_plane=arena)
        prompt = np.arange(3, 14, dtype=np.int32)
        rid = src.submit(prompt, max_new=4)
        src.run()
        payload = src.export_request(rid)
        arena.free(payload["kv_ref"]["ticket"])   # the simulated race
        dst = ServingServer(engines[1], role="decode", buckets=BUCKETS,
                            clock=lambda: 0.0, data_plane=arena)
        with pytest.raises(MigrationRefusedError, match="gather"):
            dst.import_request(payload)
        assert dst.counters()["data_plane_fallbacks"] == 1
        assert dst.stats.requests == 0            # never admitted
        dst.reconcile()
        src.cancel_handoff(rid)
        res = src.run()
        assert res[rid].outcome == "completed"
        assert res[rid].tokens == ref_tokens(params, prompt, 4)
        src.reconcile()
        arena.reconcile()

    def test_destination_death_retargets_the_same_ticket(
            self, params, engines, mk_arena):
        """The first destination dies mid-import: the retarget
        re-exports the SAME ticket (one scatter total), the survivor
        gathers the same segments, and the final ACK frees them."""
        arena = mk_arena(seg_size=4096, n_segs=32)
        clk = ManualClock()
        plan = FaultPlan(router_kill_import_at=0)
        router = _make_fleet(
            engines, clk, arena,
            wrap={1: lambda e: plan.wrap_replica_engine(e, clock=clk)})
        prompt = np.arange(2, 14, dtype=np.int32)
        rr = router.submit(prompt, max_new=6)
        res = router.run()
        assert plan.count("importkill") == 1
        assert res[rr].outcome == "completed"
        assert res[rr].tokens == ref_tokens(params, prompt, 6)
        assert res[rr].replica == 2         # the surviving destination
        c = router.counters()
        assert c["replicas_lost"] == 1
        assert c["migration_retargets"] == 1
        assert arena.scatters == 1          # the ticket was REUSED
        assert arena.segments_live() == 0
        arena.reconcile()
        router.reconcile()


# ---------------------------------------------------------------------------
# batched control RPC (ProcessReplica over an in-thread transport)


@pytest.fixture
def transport(engines):
    srv = ServingServer(engines[0], max_queue=8, max_retries=2,
                        buckets=BUCKETS)
    ts = ReplicaTransportServer(srv).start()
    client = ReplicaClient(ts.addr, connect_timeout=2.0,
                           io_timeout=30.0)
    yield ts, srv, client
    ts.shutdown()


class TestBatchedControlPlane:
    def test_acks_coalesce_onto_the_sweep_frame(self, params,
                                                engines):
        srv = ServingServer(engines[0], role="prefill", max_queue=8,
                            max_retries=2, buckets=BUCKETS)
        ts = ReplicaTransportServer(srv).start()
        try:
            client = ReplicaClient(ts.addr, connect_timeout=2.0,
                                   io_timeout=30.0)
            rep = ProcessReplica(client)
            prompts = prompts_for(2, seed=2)
            for p in prompts:
                rep.submit(p, max_new=4)
            while len(rep.ready_handoffs()) < 2:
                rep.step()
            f0 = client.frames
            r1, r2 = rep.ready_handoffs()
            rep.handoff_complete(r1)    # deferred: no frame moves
            rep.handoff_complete(r2)
            assert client.frames == f0
            # the mirror filters released handoffs without an RPC
            assert rep.ready_handoffs() == []
            rep.step()                  # ONE frame carries all 3 ops
            assert client.frames == f0 + 1
            assert rep.rpc_frames_coalesced == 2
            assert rep.rpc_deferred_errors == 0
            # a cancel is urgent (the source must resume decoding
            # NOW): it flushes immediately instead of deferring
            prompt = np.arange(2, 13, dtype=np.int32)
            r3 = rep.submit(prompt, max_new=4)
            while r3 not in rep.ready_handoffs():
                rep.step()
            f1 = client.frames
            rep.cancel_handoff(r3)
            assert client.frames == f1 + 1
            while r3 not in rep.results:
                rep.step()
            assert (rep.results[r3].tokens
                    == ref_tokens(params, prompt, 4))
            assert rep.rpc_deferred_errors == 0
            rep.reconcile()
        finally:
            ts.shutdown()

    def test_tickets_cross_the_wire_in_place_of_kv_bytes(
            self, params, engines, mk_arena):
        """The same migrations over real socket transport, once with
        the KV payloads pickled onto the control frame and once with
        the arena: every request completes with the same tokens, no
        migration falls back, the arena is empty again after the
        ACKs, and the export + import calls of a migration moved
        fewer bytes over the sockets, the frame carrying a ticket."""
        prompts = prompts_for(3, seed=7)

        def arm(arena):
            servers = [ServingServer(eng, role=role, max_queue=16,
                                     buckets=BUCKETS, max_retries=2,
                                     data_plane=arena)
                       for eng, role in zip(
                           engines, ("prefill", "decode", "decode"))]
            transports = [ReplicaTransportServer(s).start()
                          for s in servers]
            try:
                clients = [ReplicaClient(ts.addr, connect_timeout=2.0,
                                         io_timeout=30.0)
                           for ts in transports]
                reps = [ProcessReplica(c) for c in clients]
                wire = [0]
                for rep, client in zip(reps, clients):
                    for name in ("export_request", "import_request"):
                        def counted(*a, _call=getattr(rep, name),
                                    _c=client, **k):
                            b0 = _c.bytes_sent + _c.bytes_recv
                            try:
                                return _call(*a, **k)
                            finally:
                                wire[0] += (_c.bytes_sent + _c.bytes_recv
                                            - b0)
                        setattr(rep, name, counted)
                router = ServingRouter(reps, probe_interval_s=1e9)
                ids = [router.submit(p, max_new=5) for p in prompts]
                res = router.run()
                router.reconcile()
                assert all(res[i].outcome == "completed" for i in ids)
                c = router.counters()
                assert c["migrations"] == len(prompts)
                assert c.get("fleet_data_plane_fallbacks", 0) == 0
                return [res[i].tokens for i in ids], wire[0]
            finally:
                for ts in transports:
                    ts.shutdown()

        toks_pickled, wire_pickled = arm(None)
        arena = mk_arena(seg_size=4096, n_segs=32)
        toks_arena, wire_arena = arm(arena)
        assert toks_arena == toks_pickled == [
            ref_tokens(params, p, 5) for p in prompts]
        assert arena.scatters == arena.frees == len(prompts)
        assert arena.segments_live() == 0
        arena.reconcile()
        assert 0 < wire_arena < wire_pickled
        # the KV bytes themselves went through the arena, not the wire
        assert wire_pickled - wire_arena >= arena.bytes_scattered // 2

    def test_partials_ride_the_sweep_frame(self, transport, params):
        ts, srv, client = transport
        rep = ProcessReplica(client)
        prompt = np.arange(1, 12, dtype=np.int32)
        rid = rep.submit(prompt, max_new=6)
        seen = []
        for _ in range(64):
            rep.step()
            if rid in rep.results:
                break
            f = client.frames
            part = rep.partial_tokens(rid)
            # served from the partials block the step reply already
            # carried — the poll itself costs ZERO wire frames
            assert client.frames == f
            if len(part) > len(seen):
                seen = part
        final = rep.results[rid].tokens
        assert final == ref_tokens(params, prompt, 6)
        assert seen and seen == final[:len(seen)]
        assert rep.rpc_frames_coalesced >= len(seen)


# ---------------------------------------------------------------------------
# real-process SIGKILL chaos (the slow lane: scripts/fault_smoke.sh data)


CONFIG_SRC = """\
import jax

from paddle_tpu.models import transformer as T


def get_serve_config():
    cfg = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                              attn_impl="dense")
    return dict(params=T.init_params(jax.random.key(0), cfg), cfg=cfg,
                slots=2, max_len=32, page_size=4)
"""


def _proc_gone(pid):
    try:
        with open(f"/proc/{pid}/stat") as f:
            state = f.read().rsplit(")", 1)[1].split()[0]
    except (FileNotFoundError, ProcessLookupError):
        return True
    return state == "Z"


def _await(cond, timeout_s=30.0, poll_s=0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(poll_s)
    return cond()


def _chaos_fleet(tmp_path, roles):
    """A FleetSupervisor whose spawn seam boots REAL replica
    processes from a heterogeneous role/fault-plan list (the
    supervisor's own spec stays homogeneous): each child runs
    `testing.faults:build_chaos_replica`, attaches the supervisor's
    arena by name, and arms its own FaultPlan — the SIGKILL happens
    INSIDE the child, mid-transfer, where no parent-side proxy could
    reach. Extra clean decode entries feed below-floor repair."""
    from paddle_tpu.testing.fleet import save_tiny_artifact

    art = str(tmp_path / "engine.tar")
    save_tiny_artifact(art, buckets=BUCKETS)
    config = tmp_path / "serve_config.py"
    config.write_text(CONFIG_SRC)
    queue = list(roles) + [("decode", None)] * 3
    booted = []
    sup = None

    def spawn(_spec):
        role, plan = queue.pop(0)
        spec = ReplicaSpec(
            builder="paddle_tpu.testing.faults:build_chaos_replica",
            kwargs=dict(config=str(config), role=role, artifact=art,
                        buckets=list(BUCKETS), max_retries=1,
                        data_plane=sup.arena.name, fault_plan=plan),
            env=dict(CHILD_ENV))
        proc = ReplicaProcess(spec).start()
        proc.wait_ready(120.0)
        booted.append(proc)
        client = ReplicaClient(proc.addr, connect_timeout=1.0,
                               io_timeout=30.0, retries=8)
        return ProcessReplica(client, proc=proc, clock=sup.clock)

    sup = FleetSupervisor(
        ReplicaSpec(builder="paddle_tpu.testing.faults:"
                            "build_chaos_replica"),
        min_replicas=len(roles), max_replicas=len(roles), spawn=spawn,
        data_plane_segs=16, data_plane_seg_kb=2)
    assert sup.arena is not None
    sup.start()
    return sup, booted


def _reap(sup, booted):
    sup.shutdown(drain=False)
    for proc in booted:
        if proc.alive():
            proc.kill()


@pytest.mark.slow
@pytest.mark.heavyweight
def test_sigkill_source_mid_scatter_zero_leaked_segments(tmp_path,
                                                         params):
    """The prefill replica SIGKILLs itself after writing the FIRST
    arena segment of its first export — the ticket never existed
    anywhere, the claimed segments have a dead owner. The router's
    source-death path resubmits every parked request to the decode
    tier (bit-exact), and the supervisor's OWN sweep reclaims every
    orphaned segment: zero leaked, exactly one outcome each."""
    sup, booted = _chaos_fleet(
        tmp_path, [("prefill", dict(arena_kill_scatter_at=0)),
                   ("decode", None), ("decode", None)])
    try:
        prompts = prompts_for(4, seed=3)
        rids = [sup.submit(p, max_new=4) for p in prompts]
        res = sup.run()
        assert sorted(res) == sorted(rids)      # exactly one outcome
        assert all(res[r].outcome == "completed" for r in rids)
        for p, r in zip(prompts, rids):
            assert res[r].tokens == ref_tokens(params, p, 4)
        assert sup.router.counters()["replicas_lost"] >= 1
        c = sup.counters()
        assert c["arena_segments_leaked"] == 0
        assert c["arena_segments_live"] == 0
        assert c["arena_segments_reclaimed"] >= 1
        sup.reconcile()
    finally:
        _reap(sup, booted)


@pytest.mark.slow
@pytest.mark.heavyweight
def test_sigkill_destination_mid_adopt_zero_leaked_segments(tmp_path,
                                                            params):
    """The first decode replica SIGKILLs itself mid-adopt — AFTER
    gathering the bytes, before the stamp, its import reply lost.
    The dead destination's admission died with it (exactly-once needs
    no transaction), the retarget re-exports the SAME ticket to the
    survivor, and the source's ACK-driven free leaves zero segments
    live — the destination's death cost the arena nothing."""
    sup, booted = _chaos_fleet(
        tmp_path, [("prefill", None),
                   ("decode", dict(arena_kill_adopt_at=0)),
                   ("decode", None)])
    try:
        prompts = prompts_for(2, seed=5)
        rids = [sup.submit(p, max_new=4) for p in prompts]
        res = sup.run()
        assert sorted(res) == sorted(rids)
        assert all(res[r].outcome == "completed" for r in rids)
        for p, r in zip(prompts, rids):
            assert res[r].tokens == ref_tokens(params, p, 4)
        rc = sup.router.counters()
        assert rc["replicas_lost"] >= 1
        c = sup.counters()
        assert c["arena_segments_leaked"] == 0
        assert c["arena_segments_live"] == 0
        sup.reconcile()
    finally:
        _reap(sup, booted)


@pytest.mark.slow
@pytest.mark.heavyweight
def test_supervisor_sigkill_orphaned_arena_reclaimed():
    """Kill the SUPERVISOR itself — the arena's creator — with
    SIGKILL: no drain, no atexit, the unlink never runs. The replica
    children exit on the parent-death watchdog (the 3-deep chain:
    test -> supervisor -> replicas), and attaching to the orphaned
    arena BY NAME still audits and reclaims every dead-owner segment;
    this test then owns the unlink the dead supervisor couldn't."""
    from paddle_tpu.testing.fleet import orphan_data_fleet_main

    ctx = multiprocessing.get_context("spawn")
    parent_conn, child_conn = ctx.Pipe()
    sup_proc = ctx.Process(target=orphan_data_fleet_main,
                           args=(child_conn,))
    sup_proc.start()
    child_conn.close()
    assert parent_conn.poll(60.0), "supervisor never reported"
    info = parent_conn.recv()
    assert info["pids"] and all(not _proc_gone(p)
                                for p in info["pids"])
    os.kill(sup_proc.pid, signal.SIGKILL)       # no cleanup runs
    sup_proc.join(10.0)
    assert _await(lambda: all(_proc_gone(p) for p in info["pids"])), \
        f"orphaned replicas survive: {info['pids']}"
    parent_conn.close()
    arena = ShmArena(info["arena"], create=False)
    try:
        assert not _pid_alive(info["ticket"]["tag"] >> 24)
        c = arena.counters()
        assert c["arena_segments_live"] >= 1
        assert c["arena_segments_leaked"] == c["arena_segments_live"]
        n = arena.reclaim_orphans()
        assert n == len(info["ticket"]["segs"])
        with pytest.raises(ArenaError, match="stale ticket"):
            arena.gather(info["ticket"])
        arena.reconcile()
    finally:
        arena.close(destroy=True)
