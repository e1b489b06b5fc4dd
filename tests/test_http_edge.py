"""HTTP front door: the streaming network edge over the serving fleet.

The network-edge fault model (docs/RELIABILITY.md), proven at three
depths:

- **The cancel seam** — `ServingServer.cancel` / `partial_tokens` and
  the router's failover-safe forwarding: a cancel is a deadline pulled
  to now, so the PROVEN expire/retire path frees the slot, its pages,
  and any parked handoff pins; partials read the live emitted prefix.
- **The wire** — real sockets against `HttpEdge`: chunked streaming
  parity with the solo decode, malformed/oversized frames answered
  in-band without touching the router, slow-loris reads closed on the
  timeout alone, X-Deadline-Ms expiry mid-stream, disconnect-cancel
  leak accounting, overload answered 429 + Retry-After with the
  admission queue bounded, graceful drain (503 newcomers, in-flight
  finishes, the report lands).
- **The real thing** (slow/heavyweight) — live HTTP streams over real
  replica processes while `FaultPlan` SIGKILLs one mid-burst: every
  client stream still ends in exactly one completed outcome with
  bit-exact greedy tokens — the failover is invisible on the wire.
"""

import itertools
import json
import socket
import threading
import time

import numpy as np
import pytest

import jax

from paddle_tpu.models import transformer as T
from paddle_tpu.serve.engine import DecodeEngine
from paddle_tpu.serve.http_edge import HttpEdge
from paddle_tpu.serve.router import ServingRouter
from paddle_tpu.serve.server import ServingServer
from paddle_tpu.testing.faults import FaultPlan
from paddle_tpu.testing.fleet import TINY, save_tiny_artifact
from paddle_tpu.testing.traffic import (TrafficShape, closed_loop,
                                        open_loop, slo_report,
                                        stream_generate)

pytestmark = [pytest.mark.edge, pytest.mark.faults]

CFG = T.TransformerConfig(**TINY)

CHILD_ENV = {"JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.key(0), CFG)


def ref_tokens(params, prompt, max_new):
    out = T.generate(params, CFG, jax.numpy.asarray(prompt)[None, :],
                     steps=max_new)
    return [int(t) for t in np.asarray(out[0, len(prompt):])]


def mk_stack(params, *, max_queue=16, **edge_kw):
    """Fresh engine -> server -> 1-replica router -> started edge.
    Fresh per test: the leak-accounting assertions need books no
    earlier test wrote in."""
    eng = DecodeEngine(params, CFG, slots=2, max_len=32, page_size=4)
    srv = ServingServer(eng, max_queue=max_queue, buckets=(16,))
    router = ServingRouter([srv])
    edge = HttpEdge(router, **edge_kw).start()
    return edge, router, srv


def wait_idle(edge, router, timeout_s=20.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if (edge.counters()["active_streams"] == 0
                and not router.sweep()):
            return True
        time.sleep(0.02)
    return False


def hold_fleet(edge, while_):
    """Replace the edge's drive tick with one under which the fleet
    stands still for as long as `while_()` holds: the tick then reports
    no work, so the drive loop parks OUTSIDE the edge's lock and the
    connection handlers run. What the tests below raced the wall
    clock for (a client's FIN against the last token, a burst against
    the fleet's speed) is so decided by an event the edge counts.
    `while_` runs under the edge's lock, like the tick: it reads
    `edge._stats`, not `edge.counters()`, which would take the lock
    again. Returns an Event set when the fleet is first held."""
    real_tick = edge._sweep_fn
    held = threading.Event()

    def tick():
        if while_():
            held.set()
            return False
        return real_tick()

    edge._sweep_fn = tick
    return held


def raw_exchange(addr, blob, timeout_s=5.0):
    """Send raw bytes, read to EOF — the malformed-input client."""
    with socket.create_connection(addr, timeout=timeout_s) as s:
        s.sendall(blob)
        out = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                return out
            out += chunk


# ---------------------------------------------------------------------------
# the cancel/partial seams (no HTTP involved)


def test_server_cancel_frees_mid_generation(params):
    """Cancel pulls the deadline to now: the in-flight request ends
    `expired` with its partial prefix, the slot and its pages retire
    through the proven machinery, and the books reconcile."""
    eng = DecodeEngine(params, CFG, slots=2, max_len=32, page_size=4)
    srv = ServingServer(eng, max_queue=8, buckets=(16,))
    prompt = np.asarray([1, 2, 3, 4], np.int32)
    rid = srv.submit(prompt, max_new=12)
    seen = []

    def chop(_srv, _step):
        seen.append(len(srv.partial_tokens(rid)))
        if len(seen) == 3:
            assert srv.cancel(rid, reason="test cancel")

    srv.on_step.append(chop)
    res = srv.run()[rid]
    assert res.outcome == "expired"
    # the partial prefix survives into the terminal result, and it is
    # a prefix of the solo greedy decode
    full = ref_tokens(params, [1, 2, 3, 4], 12)
    assert list(res.tokens) == full[:len(res.tokens)]
    assert len(res.tokens) < 12
    # post-terminal partials read the ledger; a second cancel is a
    # no-op returning False
    assert srv.partial_tokens(rid) == list(res.tokens)
    assert not srv.cancel(rid)
    srv.reconcile()
    pool = srv.engine.pool
    assert pool.pages_in_use - pool.evictable() == 0


def test_router_cancel_queued_and_unknown(params):
    """A queued (never-scheduled) request cancels before any decode
    step; unknown ids are a False no-op, not an error."""
    eng = DecodeEngine(params, CFG, slots=2, max_len=32, page_size=4)
    srv = ServingServer(eng, max_queue=8, buckets=(16,))
    router = ServingRouter([srv])
    rid = router.submit(np.asarray([5, 6, 7], np.int32), max_new=4)
    assert router.cancel(rid, reason="before any step")
    res = router.run()[rid]
    assert res.outcome == "expired"
    assert res.tokens == []
    assert not router.cancel(10_000)
    assert router.partial_tokens(10_000) == []
    router.reconcile()


# ---------------------------------------------------------------------------
# the wire: streaming protocol


def test_stream_parity_and_nonstream(params):
    """Chunked streaming hands over exactly the solo greedy decode,
    in order; `stream: false` returns the same tokens in one JSON
    body; TTFT/ITG land in the bound histograms."""
    from paddle_tpu.obs import MetricsRegistry

    registry = MetricsRegistry()
    edge, router, srv = mk_stack(params, registry=registry)
    try:
        prompt = [1, 2, 3, 4, 5]
        want = ref_tokens(params, prompt, 6)
        r = stream_generate(edge.addr, prompt, 6)
        assert r.status == 200 and r.outcome == "completed"
        assert r.tokens == want
        assert r.ttft_s is not None and r.ttft_s > 0
        r2 = stream_generate(edge.addr, prompt, 6, sampling=None)
        assert r2.tokens == want
        # non-stream mode: same payload, single body
        blob = json.dumps({"prompt": prompt, "max_new": 6,
                           "stream": False}).encode()
        raw = raw_exchange(
            edge.addr,
            f"POST /v1/generate HTTP/1.1\r\nHost: e\r\n"
            f"Content-Length: {len(blob)}\r\n\r\n".encode() + blob)
        body = json.loads(raw.split(b"\r\n\r\n", 1)[1])
        assert body["outcome"] == "completed"
        assert body["tokens"] == want
        assert body["n_tokens"] == 6
        snap = {s["name"] for s in registry.snapshot()["series"]}
        assert "edge_ttft_seconds_bucket" in snap
        assert "edge_requests" in snap
        c = edge.counters()
        assert c["requests"] == 3 == c["completed"]
    finally:
        edge.close()


@pytest.mark.locks      # rides with the LK003 hot-path fix
def test_stream_bit_exact_across_many_chunks(params,
                                             lock_order_guard):
    """The LK003 hot-path contract: `_snapshot` reads the partial
    tokens UNDER the router lock and the chunked socket write happens
    OUTSIDE it. The stream goes through many snapshot/write cycles,
    and the concatenation of every chunk must still be bit-exact against the
    solo greedy decode — proving the restructure drops the lock
    without ever tearing or reordering the stream. Runs under
    LockOrderGuard so a regression that re-nests the write under the
    lock shows up as an order violation, not just a slow stream.

    The fleet moves in lockstep with the stream: it emits again only
    once the edge has stamped a chunk for what it emitted last (the
    edge reads its injectable clock once at submit and once a chunk),
    so every emission is a snapshot/write cycle of its own however the
    threads are scheduled."""
    stamps = itertools.count()
    seen = [0]                  # readings of the edge's clock so far

    def edge_clock():
        seen[0] += 1
        return float(next(stamps))

    edge, router, srv = mk_stack(params, clock=edge_clock)
    emitted = [0, 0]            # tokens of request 0, sweeps that added
    real_tick = edge._sweep_fn

    def counting_tick():
        busy = real_tick()
        n = len(router.partial_tokens(0))
        if n > emitted[0]:
            emitted[:] = n, emitted[1] + 1
        return busy

    edge._sweep_fn = counting_tick
    hold_fleet(edge, lambda: seen[0] - 1 < emitted[1])
    try:
        prompt = [2, 4, 6]
        want = ref_tokens(params, prompt, 8)
        ticks = itertools.count()
        r = stream_generate(edge.addr, prompt, 8,
                            clock=lambda: float(next(ticks)))
        assert r.status == 200 and r.outcome == "completed"
        assert r.tokens == want         # bit-exact, in order
        # the lockstep spread the stream over several chunks: some
        # inter-token gap is nonzero, so parity was across REAL
        # snapshot/write cycles, not one lucky final chunk
        assert any(g > 0 for g in r.gaps_s)
        assert emitted[1] > 1 and seen[0] - 1 == emitted[1]
        assert wait_idle(edge, router)
    finally:
        edge.close()


def test_healthz_and_metrics(params):
    from paddle_tpu.obs import MetricsRegistry

    edge, router, srv = mk_stack(params, registry=MetricsRegistry())
    try:
        raw = raw_exchange(edge.addr,
                           b"GET /healthz HTTP/1.1\r\nHost: e\r\n\r\n")
        assert b" 200 " in raw.split(b"\r\n", 1)[0]
        payload = json.loads(raw.split(b"\r\n\r\n", 1)[1])
        assert payload == {"draining": False,
                           "queue_space": 16, "active_streams": 0}
        # histogram series appear once observations exist: stream one
        # request, then scrape
        stream_generate(edge.addr, [1, 2, 3], 2)
        raw = raw_exchange(edge.addr,
                           b"GET /metrics HTTP/1.1\r\nHost: e\r\n\r\n")
        assert b"edge_connections" in raw
        assert b"edge_ttft_seconds_bucket" in raw
    finally:
        edge.close()


def test_malformed_never_touch_the_fleet(params):
    """Every malformed/oversized/unknown frame is answered in-band
    with its proper status — and the router's admission ledger never
    hears about any of them."""
    edge, router, srv = mk_stack(params, max_header_bytes=512,
                                 max_body_bytes=256)
    try:
        cases = [
            # (raw request, expected status)
            (b"NONSENSE\r\n\r\n", b" 400 "),
            (b"GET /nope HTTP/1.1\r\nHost: e\r\n\r\n", b" 404 "),
            (b"GET /v1/generate HTTP/1.1\r\nHost: e\r\n\r\n", b" 405 "),
            (b"POST /v1/generate HTTP/1.1\r\nHost: e\r\n\r\n", b" 411 "),
            (b"POST /v1/generate HTTP/1.1\r\nHost: e\r\n"
             b"Content-Length: zero\r\n\r\n", b" 400 "),
            # declared body over the cap: refused BEFORE a byte is read
            (b"POST /v1/generate HTTP/1.1\r\nHost: e\r\n"
             b"Content-Length: 99999\r\n\r\n", b" 413 "),
            # header block over the cap: refused as it accumulates
            (b"GET /healthz HTTP/1.1\r\n"
             + b"X-Filler: " + b"a" * 4096 + b"\r\n\r\n", b" 431 "),
            # body that is not JSON
            (b"POST /v1/generate HTTP/1.1\r\nHost: e\r\n"
             b"Content-Length: 9\r\n\r\nnot json!", b" 400 "),
            # JSON but no usable prompt
            (b"POST /v1/generate HTTP/1.1\r\nHost: e\r\n"
             b"Content-Length: 13\r\n\r\n{\"prompt\": 3}", b" 400 "),
        ]
        for raw_req, status in cases:
            raw = raw_exchange(edge.addr, raw_req)
            assert status in raw.split(b"\r\n", 1)[0], (raw_req, raw)
        assert router.counters()["requests"] == 0
        assert edge.counters()["requests"] == 0
        assert edge.counters()["malformed_400"] > 0
    finally:
        edge.close()


def test_slow_loris_closed_on_timeout_alone(params):
    """A client feeding header bytes slower than the read deadline is
    closed WITHOUT a reply and without touching the router."""
    edge, router, srv = mk_stack(params, header_timeout_s=0.2,
                                 body_timeout_s=0.2)
    try:
        with socket.create_connection(edge.addr, timeout=5.0) as s:
            s.sendall(b"POST /v1/generate HTTP/1.1\r\n")  # ...stall...
            s.settimeout(5.0)
            assert s.recv(4096) == b""      # closed, no reply owed
        # same defense on the BODY read: headers complete, body stalls
        with socket.create_connection(edge.addr, timeout=5.0) as s:
            s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: e\r\n"
                      b"Content-Length: 64\r\n\r\n{\"pro")
            s.settimeout(5.0)
            assert s.recv(4096) == b""
        assert edge.counters()["hangups"] == 2
        assert router.counters()["requests"] == 0
    finally:
        edge.close()


def test_deadline_header_expires_request(params):
    """X-Deadline-Ms rides the submit into the fleet's own deadline
    machinery: a budget far smaller than the decode ends `expired`
    with whatever prefix was produced."""
    edge, router, srv = mk_stack(params)
    try:
        r = stream_generate(edge.addr, [1, 2, 3], 12, deadline_ms=0.01)
        assert r.status == 200
        assert r.outcome == "expired"
        assert len(r.tokens) < 12
        # malformed deadline header: 400 in-band
        blob = json.dumps({"prompt": [1], "max_new": 2}).encode()
        raw = raw_exchange(
            edge.addr,
            f"POST /v1/generate HTTP/1.1\r\nHost: e\r\n"
            f"X-Deadline-Ms: soon\r\n"
            f"Content-Length: {len(blob)}\r\n\r\n".encode() + blob)
        assert b" 400 " in raw.split(b"\r\n", 1)[0]
    finally:
        edge.close()


# ---------------------------------------------------------------------------
# disconnect cancellation


@pytest.mark.locks      # chaos lane re-run under LockOrderGuard
def test_disconnect_mid_stream_frees_slot_and_pages(
        params, lock_order_guard):
    """The tentpole invariant: a client vanishing mid-stream costs
    the fleet NOTHING durable — the in-flight request is force-
    expired through the deadline/retire path, its slot and pages
    free (pages still resident are cache-only and evictable), the
    books reconcile, and the next client is served normally.

    The client's FIN does not race the last token: once the request
    has the two tokens the client leaves after, the fleet stands still
    until the edge has counted the disconnect."""
    edge, router, srv = mk_stack(params)
    hold_fleet(edge, lambda: (len(router.partial_tokens(0)) >= 2
                              and not edge._stats["disconnect_cancels"]))
    try:
        r = stream_generate(edge.addr, [1, 2, 3, 4], 12,
                            abort_after_tokens=2)
        assert r.aborted and len(r.tokens) >= 2
        assert wait_idle(edge, router)
        c = edge.counters()
        assert c["disconnect_cancels"] == 1
        assert c["active_streams"] == 0
        # the ledger shows the force-expire, with the partial prefix
        (rid, res), = router.results.items()
        assert res.outcome == "expired"
        assert len(res.tokens) < 12
        router.reconcile()
        srv.reconcile()
        pool = srv.engine.pool
        assert pool.pages_in_use - pool.evictable() == 0
        assert all(req is None for req in srv._slot_req)
        # the fleet is still fully serviceable
        want = ref_tokens(params, [9, 8, 7], 4)
        r2 = stream_generate(edge.addr, [9, 8, 7], 4)
        assert r2.outcome == "completed" and r2.tokens == want
    finally:
        edge.close()


def test_disconnect_while_queued_cancels_before_decode(params):
    """A client that vanishes while its request is still QUEUED
    (both slots busy) is cancelled before it ever takes a slot: the
    fleet starts once both holders are in, and with both decoding it
    stands still until the edge has counted the disconnect, so no slot
    comes free before it."""
    edge, router, srv = mk_stack(params)
    hold_fleet(edge, lambda: edge._stats["requests"] < 2)
    held = hold_fleet(edge, lambda: (
        all(router.partial_tokens(i) for i in (0, 1))
        and not edge._stats["disconnect_cancels"]))
    try:
        holders = [
            threading.Thread(
                target=stream_generate,
                args=(edge.addr, [1, 2, 3 + i], 10), daemon=True)
            for i in range(2)
        ]
        for t in holders:
            t.start()
        assert held.wait(30.0)
        assert edge.counters()["requests"] == 2
        assert all(req is not None for req in srv._slot_req)
        # both slots busy: this one queues (the edge sends NOTHING
        # until tokens flow), then its client leaves without ever
        # reading a byte
        blob = json.dumps({"prompt": [4, 5, 6],
                           "max_new": 10}).encode()
        s = socket.create_connection(edge.addr, timeout=5.0)
        s.sendall(f"POST /v1/generate HTTP/1.1\r\nHost: e\r\n"
                  f"Content-Length: {len(blob)}\r\n\r\n".encode()
                  + blob)
        deadline = time.monotonic() + 10.0
        while (time.monotonic() < deadline
               and edge.counters()["requests"] < 3):
            time.sleep(0.01)
        assert edge.counters()["requests"] == 3
        s.close()                   # the queued client vanishes
        for t in holders:
            t.join(timeout=20.0)
        assert wait_idle(edge, router)
        assert edge.counters()["disconnect_cancels"] == 1
        router.reconcile()
        srv.reconcile()
    finally:
        edge.close()


# ---------------------------------------------------------------------------
# overload backpressure


def test_overload_sheds_429_and_bounds_the_queue(params):
    """An open-loop burst far beyond capacity sheds 429 + Retry-After
    AT THE EDGE; the admission queue never grows past its bound, and
    every admitted request still completes. Beyond capacity whatever
    the machine's load: the fleet stands still until the edge has
    given each arrival of the burst its verdict."""
    edge, router, srv = mk_stack(params, max_queue=3)
    depth = [0]
    real_sweep = router.sweep

    def recording_sweep():
        depth[0] = max(depth[0], len(srv.queue))
        return real_sweep()

    edge._sweep_fn = recording_sweep
    try:
        # warm the decode path so the burst meets a live fleet
        stream_generate(edge.addr, [1, 2], 2)
        verdicts = lambda c: c["requests"] + c["shed_429"]
        all_in = verdicts(edge.counters()) + 30
        hold_fleet(edge, lambda: verdicts(edge._stats) < all_in)
        shape = TrafficShape(out_base=6, out_cap=10)
        burst = open_loop(edge.addr, shape, phases=((200.0, 30),),
                          seed=7)
        rep = slo_report(burst, 1.0)
        assert rep["shed_429"] > 0
        assert rep["completed"] > 0
        assert rep["completed"] + rep["shed_429"] == len(burst)
        sheds = [r for r in burst if r.status == 429]
        assert all(r.retry_after is not None for r in sheds)
        assert depth[0] <= 3
        assert wait_idle(edge, router)
        router.reconcile()
        assert edge.counters()["shed_429"] == rep["shed_429"]
    finally:
        edge.close()


def test_closed_loop_holds_slo_under_fair_load(params):
    """The harness's own sanity bar: closed-loop users (self-
    limiting) against a healthy fleet complete everything, and the
    report's percentiles are well-formed."""
    edge, router, srv = mk_stack(params)
    try:
        shape = TrafficShape(out_base=2, out_cap=6)
        t0 = time.monotonic()
        results = closed_loop(edge.addr, shape, users=3,
                              requests_per_user=2, seed=3)
        rep = slo_report(results, time.monotonic() - t0)
        assert rep["completed"] == 6 == rep["requests"]
        assert rep["sustained_qps"] > 0
        assert rep["p99_ttft_s"] >= rep["p50_ttft_s"] > 0
        assert rep["tokens_streamed"] > 0
    finally:
        edge.close()


# ---------------------------------------------------------------------------
# graceful drain


def test_drain_503_in_flight_finishes_report_lands(params, tmp_path):
    """The SIGTERM sequence without the signal: drain() stops
    admission (newcomers answer 503 + Retry-After), the in-flight
    stream runs to its natural end, wait_drained() goes idle and the
    drain report lands atomically. In flight means decoding: from its
    first token on the fleet stands still until the drain is in."""
    report = tmp_path / "drain.json"
    edge, router, srv = mk_stack(params,
                                 drain_report_path=str(report))
    held = hold_fleet(edge, lambda: (router.partial_tokens(0)
                                     and not edge.draining))
    try:
        got = {}

        def one(key, **kw):
            got[key] = stream_generate(edge.addr, [1, 2, 3], 8, **kw)

        t = threading.Thread(target=one, args=("inflight",),
                             daemon=True)
        t.start()
        assert held.wait(30.0)
        edge.drain(reason="test drain")
        late = stream_generate(edge.addr, [4, 5], 2)
        assert late.status == 503
        assert late.retry_after is not None
        t.join(timeout=20.0)
        assert got["inflight"].outcome == "completed"
        assert got["inflight"].tokens == ref_tokens(params,
                                                    [1, 2, 3], 8)
        assert edge.wait_drained(timeout_s=20.0)
        payload = json.loads(report.read_text())
        assert payload["kind"] == "edge_drain_report"
        assert payload["reason"] == "test drain"
        assert payload["edge"]["shed_503"] == 1
        assert payload["fleet"]["completed"] >= 1
    finally:
        edge.close()


# ---------------------------------------------------------------------------
# the real thing: SIGKILL under live HTTP load


@pytest.mark.fleet
@pytest.mark.slow
@pytest.mark.heavyweight
def test_sigkill_replica_under_live_http_load(params, tmp_path):
    """THE edge chaos bar, on real OS processes: live HTTP streams
    over a 3-replica process fleet while FaultPlan SIGKILLs one
    mid-burst. Every client stream must end in exactly one completed
    outcome with bit-exact greedy tokens — the `sent` high-water mark
    makes redistribution invisible on the wire (a survivor regrows
    the identical prefix; only tokens beyond it are written)."""
    from paddle_tpu.serve.fleet import FleetSupervisor, ReplicaSpec

    art = str(tmp_path / "engine.tar")
    save_tiny_artifact(art, buckets=(16,))
    spec = ReplicaSpec(
        builder="paddle_tpu.testing.fleet:build_tiny_server",
        kwargs=dict(artifact=art, buckets=(16,), max_retries=1),
        env=dict(CHILD_ENV))
    sup = FleetSupervisor(spec, min_replicas=3, max_replicas=3)
    sup.start()
    # LATE-bound sweep: wrap_fleet replaces `sup.sweep`, and the wrap
    # is only installed below once streams are live (the drive thread
    # sweeps from the moment the edge starts — a fixed sweep count
    # would burn down before any client connected)
    edge = HttpEdge(sup.router, sweep_fn=lambda: sup.sweep(),
                    submit_fn=sup.submit,
                    drain_fn=lambda why: sup.drain(reason=why)
                    ).start()
    try:
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, CFG.vocab, (4 + i % 5,)
                               ).astype(np.int32) for i in range(8)]
        results = [None] * len(prompts)

        def client(i):
            results[i] = stream_generate(edge.addr, prompts[i], 8,
                                         timeout_s=120.0)

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        # arm the kill only when every replica holds live work, so
        # the victim provably dies with streams in flight
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if (edge.counters()["active_streams"] >= 4
                    and all(r.pending
                            for r in sup.router.replicas)):
                break
            time.sleep(0.005)
        assert all(r.pending for r in sup.router.replicas), \
            "fleet never reached the armed state"
        FaultPlan(fleet_sigkill_at=0,
                  fleet_sigkill_replica=1).wrap_fleet(sup)
        for t in threads:
            t.join(timeout=120.0)
        assert all(r is not None for r in results)
        # exactly one completed outcome per stream, tokens bit-exact
        # with the solo decode: the kill never reached a client
        for p, r in zip(prompts, results):
            assert r.status == 200 and r.outcome == "completed"
            assert r.tokens == ref_tokens(params, p, 8)
        sup.reconcile()
        c = sup.router.counters()
        assert c["replicas_lost"] == 1
        assert c["redistributed"] >= 1
        assert c["completed"] == len(prompts)
        assert c["failed"] == 0 and c["shed"] == 0
        # the supervisor repaired the fleet back to its floor
        assert sup.counters()["procs_alive"] == 3
    finally:
        edge.close()
        sup.shutdown(drain=False)
