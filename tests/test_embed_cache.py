"""Tiered hot-row embedding cache + streaming online learning (ISSUE 19).

Three depths:

- **The cache alone** — a fake backing with injectable watermarks and
  failover counters proves the freshness machinery row by row: the
  staleness bound (`shard_wm - row_wm <= max_staleness`) decides every
  serve, misses coalesce into ONE pull per lookup, the rewind and
  failover resets drop exactly the affected shard, the vectorized fast
  path answers bit-identically to the classifying slow path, and the
  steady state is zero-recompile / zero-implicit-transfer under
  RecompileGuard + transfer_guard("disallow").
- **The shared surface** — `PServerEmbedding` and
  `HostOffloadEmbedding` both satisfy `LookupSurface` structurally
  (no isinstance anywhere), and the cache runs unchanged over the
  host-offload backing in static mode.
- **Chaos over real shards** — a FaultPlan kills a primary mid-read:
  the client fails over, the cache notices the new authority via the
  failover counter and re-validates, and every row served afterwards
  is bit-equal to ground truth (no stale-beyond-bound read ever). A
  second plan kills the streaming trainer mid-stream; the reformed
  trainer (same id, fresh client) replays through a lost ACK and the
  final table equals the exact numpy ledger — pushes exactly-once
  through the reform.
"""

import json
import socket

import numpy as np
import pytest

import jax

from paddle_tpu.native.pserver import PServerGroup
from paddle_tpu.native.taskqueue import TaskQueue
from paddle_tpu.parallel.pserver_client import (PServerClient,
                                                PServerEmbedding)
from paddle_tpu.parallel.sparse import (HostOffloadEmbedding,
                                        LookupSurface)
from paddle_tpu.serve.ctr import CtrServer, init_tower
from paddle_tpu.serve.embed_cache import TieredEmbedCache
from paddle_tpu.testing.faults import FaultError, FaultPlan
from paddle_tpu.train.online import StreamingTrainer

pytestmark = pytest.mark.ctr

DIM = 4


class FakeBacking:
    """Injectable-everything backing: values are `row * scale +
    version` so a served vector proves exactly which table version it
    came from; watermarks and failover counters are plain lists the
    test mutates."""

    def __init__(self, vocab=32, n_shards=2, dim=DIM):
        self.vocab, self.dim = vocab, dim
        self._n = n_shards
        self.rows_per = vocab // n_shards
        self.wms = [0] * n_shards
        self.fo = [0] * n_shards
        self.version = 0            # payload generation, not watermark
        self.pull_calls = []

    def value(self, r):
        return np.full(self.dim, 10.0 * r + self.version, np.float32)

    def pull_rows(self, table, ids):
        ids = np.asarray(ids).reshape(-1)
        self.pull_calls.append(sorted(int(i) for i in ids))
        rows = np.stack([self.value(int(r)) for r in ids])
        return rows.astype(np.float32), list(self.wms)

    def owner_of(self, ids):
        ids = np.asarray(ids).reshape(-1)
        owner = ids // self.rows_per
        owner[(ids < 0) | (ids >= self.vocab)] = -1
        return owner.astype(np.int64)

    @property
    def n_shards(self):
        return self._n

    def poll_watermarks(self, table):
        return list(self.wms)

    def shard_failovers(self):
        return list(self.fo)


def mk_cache(**kw):
    fake = FakeBacking()
    kw.setdefault("hot_rows", 16)
    kw.setdefault("host_rows", 24)
    cache = TieredEmbedCache(fake, **kw)
    return fake, cache


# ---------------------------------------------------------------------------
# the cache alone


def test_lookup_contract_and_miss_coalescing():
    """OOB ids -> zero vectors; duplicates classify once; ALL misses
    of a lookup land in ONE pull (one ranged RPC per shard inside the
    backing — never one per row)."""
    fake, cache = mk_cache()
    out = np.asarray(cache.lookup([3, 17, 3, -1, 99, 17]))
    assert out.shape == (6, DIM)
    assert np.array_equal(out[0], fake.value(3))
    assert np.array_equal(out[1], fake.value(17))
    assert np.array_equal(out[2], fake.value(3))
    assert np.array_equal(out[3], np.zeros(DIM))
    assert np.array_equal(out[4], np.zeros(DIM))
    # one pull, unique needed rows only
    assert fake.pull_calls == [[3, 17]]
    c = cache.counters()
    assert c["pulls"] == 1 and c["rows_pulled"] == 2
    assert c["misses"] == 2
    assert cache.reconcile()["ok"]


def test_fast_path_matches_slow_path_bitwise():
    """The vectorized steady-state answer must be indistinguishable
    from the classifying slow path — same values, same zero rows."""
    fake, cache = mk_cache()
    q = np.asarray([5, 2, 9, -1, 5, 40], np.int64)
    slow = np.asarray(cache.lookup(q))   # first call fills (slow path)
    fast = np.asarray(cache.lookup(q))   # all-resident (fast path)
    assert np.array_equal(slow, fast)
    c = cache.counters()
    assert c["hits_device"] > 0
    assert cache.reconcile()["ok"]


def test_staleness_bound_decides_every_serve():
    """A row is served from cache iff its shard's known watermark is
    within `max_staleness` of the row's fill stamp — at the bound it
    still serves, one past the bound it refills."""
    fake, cache = mk_cache(max_staleness=2)
    cache.lookup([1])                      # fill at wm 0
    fake.version = 1                       # backing moves on
    fake.wms[0] = 2                        # staleness 2 == bound
    cache.refresh()
    out = np.asarray(cache.lookup([1]))[0]
    assert out[0] == 10.0                  # still the OLD value: bound holds
    assert cache.counters()["stale_refills"] == 0
    fake.wms[0] = 3                        # staleness 3 > bound
    cache.refresh()
    out = np.asarray(cache.lookup([1]))[0]
    assert out[0] == 11.0                  # refilled: never stale beyond bound
    assert cache.counters()["stale_refills"] == 1
    assert cache.reconcile()["ok"]


def test_push_feed_invalidates_without_polling():
    """`note_watermark` (the on_watermark seam) advances the ledger
    with zero RPCs: a push the cache hears about makes max_staleness=0
    rows refill on next touch."""
    fake, cache = mk_cache(max_staleness=0)
    cache.lookup([2, 3])
    fake.version = 5
    fake.wms[0] = 1
    cache.note_watermark(0, 1)             # what a push ACK would feed
    out = np.asarray(cache.lookup([2]))[0]
    assert out[0] == 25.0                  # row 2, version 5
    assert cache.counters()["stale_refills"] == 1


def test_watermark_rewind_drops_only_that_shard():
    """A rewind (failover to a prefix backup) conservatively drops the
    shard's rows; the other shard keeps serving from cache."""
    fake, cache = mk_cache()
    cache.lookup([1, 20])                  # shard 0 and shard 1 rows
    fake.wms = [4, 4]
    cache.refresh()
    cache.lookup([1, 20])
    pulls_before = cache.counters()["pulls"]
    cache.note_watermark(0, 1)             # REWIND on shard 0
    assert cache.counters()["invalidations_rewind"] == 1
    cache.lookup([1, 20])
    c = cache.counters()
    assert c["pulls"] == pulls_before + 1
    # the pull re-fetched ONLY shard 0's row
    assert fake.pull_calls[-1] == [1]


def test_failover_counter_invalidates_shard():
    """A failover the watermark doesn't reveal (counter diff) still
    invalidates: new authority means re-validate."""
    fake, cache = mk_cache()
    cache.lookup([1, 20])
    fake.fo[1] += 1
    cache.lookup([1, 20])
    c = cache.counters()
    assert c["invalidations_failover"] == 1
    assert fake.pull_calls[-1] == [20]


def test_refresh_stale_moves_refills_off_the_read_path():
    """The maintenance tick re-pulls stale rows in one batch; the
    next lookup is then a pure hit with NO stale refill in its own
    latency."""
    fake, cache = mk_cache(max_staleness=0)
    cache.lookup([1, 2, 3])
    fake.version = 7
    fake.wms[0] = 1
    cache.note_watermark(0, 1)
    n = cache.refresh_stale()
    assert n == 3
    out = np.asarray(cache.lookup([1, 2, 3]))
    assert out[0][0] == 17.0               # fresh values...
    c = cache.counters()
    assert c["stale_refills"] == 0         # ...without a read-path refill
    assert c["refresh_rows"] == 3
    assert cache.reconcile()["ok"]


def test_host_eviction_retires_device_slot():
    """The arena strictly replicates host entries: evicting a row from
    the host tier retires its device slot too, and the evicted row
    misses (not serves stale) on next touch."""
    fake, cache = mk_cache(hot_rows=4, host_rows=4)
    cache.lookup([0, 1, 2, 3])
    cache.lookup([4, 5, 6])                # evicts 0..2 from host
    c = cache.counters()
    assert c["evictions_host"] == 3
    out = np.asarray(cache.lookup([0]))
    assert np.array_equal(out[0], fake.value(0))
    assert cache.reconcile()["ok"]
    assert cache.counters()["entries_device"] <= 4


def test_overflow_lookup_serves_from_host_tier():
    """More live rows than the arena holds: the lookup still answers
    (host-tier assembly) and counts the overflow."""
    fake, cache = mk_cache(hot_rows=4, host_rows=24)
    ids = list(range(12))
    out = np.asarray(cache.lookup(ids))
    for i in ids:
        assert np.array_equal(out[i], fake.value(i))
    assert cache.counters()["overflow_lookups"] == 1


@pytest.mark.analysis
def test_steady_state_zero_recompile_zero_implicit_transfer():
    """After warmup, lookups at seen widths are ZERO fresh compiles
    and move nothing implicitly: slots cross via explicit device_put,
    hot rows never re-cross."""
    from paddle_tpu.analysis.guards import RecompileGuard

    fake, cache = mk_cache()
    q1 = np.asarray([1, 2, 3, 20, 21], np.int64)
    q2 = np.asarray([2, 3, 1, 20, -1], np.int64)    # same width bucket
    cache.lookup(q1)
    cache.lookup(q2)                                 # warmup both paths
    with RecompileGuard(name="embed cache steady state") as g:
        with jax.transfer_guard("disallow"):
            for _ in range(4):
                cache.lookup(q1).block_until_ready()
                cache.lookup(q2).block_until_ready()
    assert g.compiles == 0


# ---------------------------------------------------------------------------
# the shared lookup surface


def test_lookup_surface_is_structural():
    """Both embedding backings satisfy the one `LookupSurface`
    protocol — the drift that motivated it (missing alltoall_* on the
    host-offload side) stays fixed."""
    host = HostOffloadEmbedding(8, DIM)
    assert isinstance(host, LookupSurface)

    class _StubClient:
        num_rows, dim, n_shards = 8, DIM, 1

    ps = PServerEmbedding(_StubClient())
    assert isinstance(ps, LookupSurface)
    # and the cache-backing quintet is present on both
    for obj in (host, ps):
        for name in ("pull_rows", "owner_of", "poll_watermarks",
                     "shard_failovers"):
            assert callable(getattr(obj, name))
        assert isinstance(obj.n_shards, int)


def test_cache_over_host_offload_static_mode():
    """The cache runs unchanged over `HostOffloadEmbedding`
    (watermarks=None -> static mode: entries never stale), answering
    bit-equal to the backing's own lookup."""
    emb = HostOffloadEmbedding(16, DIM)
    table = emb.init(jax.random.key(0))
    cache = TieredEmbedCache(emb, table, hot_rows=8, host_rows=12)
    q = np.asarray([3, 0, 15, -1, 3], np.int64)
    want = np.asarray(emb.lookup(table, q))
    got1 = np.asarray(cache.lookup(q))
    got2 = np.asarray(cache.lookup(q))      # fast path
    assert np.array_equal(want, got1)
    assert np.array_equal(want, got2)
    c = cache.counters()
    assert c["hits_device"] > 0 and c["stale_refills"] == 0
    assert cache.reconcile()["ok"]


# ---------------------------------------------------------------------------
# the CTR serving path


def test_ctr_server_scores_and_rejects():
    fake, cache = mk_cache()
    tower = init_tower(jax.random.key(1), DIM)
    srv = CtrServer(cache, tower, slots=4, max_batch=2)
    scores = srv.score(np.asarray([[1, 2, 3, -1], [5, 6, -1, -1]]))
    assert scores.shape == (2,)
    assert np.all((scores > 0) & (scores < 1))
    # smaller batches pad up into the same fixed bucket
    s1 = srv.score(np.asarray([[1, 2, 3, -1]]))
    assert np.array_equal(s1[0], scores[0])
    with pytest.raises(ValueError):
        srv.score(np.zeros((3, 4), np.int64))       # batch too big
    with pytest.raises(ValueError):
        srv.score_request({"not_ids": 1})
    out = srv.score_request({"ids": [[1, 2, 3]]})
    assert out["batch"] == 1 and len(out["scores"]) == 1
    assert srv.counters()["rejected"] == 1


def test_ctr_http_edge_route():
    """POST /v1/ctr/score answers through the edge front door; GET is
    405, no backend bound is 404."""
    from paddle_tpu.serve.http_edge import HttpEdge

    class _StubRouter:
        draining = False
        results = {}

        def sweep(self):
            return False

        def queue_space(self):
            return 8

        def submit(self, *a, **k):
            raise AssertionError("CTR traffic must not touch submit")

        def counters(self):
            return {}

        def drain(self, reason=""):
            pass

    fake, cache = mk_cache()
    tower = init_tower(jax.random.key(1), DIM)
    ctr = CtrServer(cache, tower, slots=4, max_batch=2)
    edge = HttpEdge(_StubRouter(), ctr=ctr).start()
    try:
        blob = json.dumps({"ids": [[1, 2, 3], [5, 6, 7]]}).encode()
        raw = _exchange(edge.addr,
                        f"POST /v1/ctr/score HTTP/1.1\r\nHost: e\r\n"
                        f"Content-Length: {len(blob)}\r\n\r\n"
                        .encode() + blob)
        assert b" 200 " in raw.split(b"\r\n", 1)[0]
        body = json.loads(raw.split(b"\r\n\r\n", 1)[1])
        assert body["batch"] == 2 and len(body["scores"]) == 2
        raw = _exchange(edge.addr,
                        b"GET /v1/ctr/score HTTP/1.1\r\nHost: e\r\n"
                        b"\r\n")
        assert b" 405 " in raw.split(b"\r\n", 1)[0]
        assert edge.counters()["ctr_requests"] == 1
    finally:
        edge.close()
    edge2 = HttpEdge(_StubRouter()).start()     # no CTR backend bound
    try:
        blob = json.dumps({"ids": [[1]]}).encode()
        raw = _exchange(edge2.addr,
                        f"POST /v1/ctr/score HTTP/1.1\r\nHost: e\r\n"
                        f"Content-Length: {len(blob)}\r\n\r\n"
                        .encode() + blob)
        assert b" 404 " in raw.split(b"\r\n", 1)[0]
    finally:
        edge2.close()


def _exchange(addr, blob, timeout_s=5.0):
    with socket.create_connection(addr, timeout=timeout_s) as s:
        s.sendall(blob)
        out = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                return out
            out += chunk


# ---------------------------------------------------------------------------
# chaos over real shards


VOCAB = 16


def _dyadic_grad_fn(payload, rows, dim):
    """Payload-pure deltas with dyadic values: float sums are exact in
    any order, so the expected table is computable in numpy to the
    bit."""
    del rows
    r = int(payload["row"])
    ids = np.asarray([r], np.int64)
    grads = np.full((1, dim), float(payload["delta"]), np.float32)
    return ids, grads


def _expected_table(init, tasks, lr=1.0):
    out = np.array(init, np.float32, copy=True)
    for t in tasks:
        out[t["row"]] -= np.float32(lr) * np.float32(t["delta"])
    return out


def test_watermarks_match_the_push_ledger_under_streaming_pushes():
    """The read path beside a trainer that keeps pushing, over real
    shards: a `StreamingTrainer` pushes sparse deltas between lookups
    of a hot set, the push ACKs feed the cache's ledger, and the
    maintenance tick refreshes stale rows ahead of the reads. At the
    end every served row is accounted for by one of the hit / miss /
    stale counters, the hot set was served from the device tier, and
    the cache's per-shard watermark equals each shard's applied-update
    `version`: the push ledger is the invalidation feed."""
    vocab, shards, n_req, push_every = 64, 2, 24, 4
    r = np.random.RandomState(5)
    hot = r.randint(0, vocab, (8,)).astype(np.int64)
    with PServerGroup(vocab, DIM, n_shards=shards,
                      replicated=False) as grp:
        push = PServerClient(grp.specs, DIM, trainer_id=0)
        push.register()
        push_emb = PServerEmbedding(push)
        table = push_emb.init(jax.random.key(3))
        q = TaskQueue(timeout_ms=5000, max_retries=3)
        for i in range(n_req // push_every):
            q.add_task(json.dumps({"seed": i, "batch": 4, "slots": 4,
                                   "vocab": vocab}).encode())
        trainer = StreamingTrainer(q, push_emb, table, lr=0.05)

        read = PServerClient(grp.specs, DIM, trainer_id=1)
        read.register()
        read_emb = PServerEmbedding(read)
        cache = TieredEmbedCache(read_emb, table, hot_rows=16,
                                 host_rows=32, max_staleness=0)
        cache.bind_push_feed(push)    # same thread: reentrant-safe
        for i in range(n_req):
            if i % push_every == 0:
                assert trainer.step()
                cache.refresh_stale()
            got = np.asarray(cache.lookup(hot))
            assert np.array_equal(got, read.get_rows(hot))
        assert trainer.stats["tasks_done"] == n_req // push_every
        cache.refresh()
        rec = cache.reconcile([p.stats() for p in grp.primaries])
        assert rec["ok"], rec
        assert rec["serves_accounted"]
        assert rec["watermarks_match_push_ledger"]
        c = cache.counters()
        assert c["rows_served"] == n_req * len(hot)
        assert c["misses"] == len(set(hot.tolist()))
        assert c["refresh_rows"] > 0 and c["stale_refills"] == 0
        assert (c["hits_device"] + c["hits_host"]
                == c["rows_served"] - c["misses"])
        assert c["hits_device"] > 0


@pytest.mark.faults
@pytest.mark.pserver
def test_shard_failover_never_serves_stale_beyond_bound():
    """Kill the primary mid-read: the client fails over to the backup,
    the cache sees the failover counter move and re-validates, and
    with max_staleness=0 every row served after every acknowledged
    push is bit-equal to ground truth — no stale read ever."""
    with PServerGroup(VOCAB, DIM, n_shards=2, replicated=True) as grp:
        plan = FaultPlan(pserver_kill_get_at=2)
        plan.wrap_pserver_shard(grp.primaries[0])

        push = PServerClient(grp.specs, DIM, trainer_id=0)
        push.register()
        emb = PServerEmbedding(push)
        table = emb.init(jax.random.key(2))
        init = push.get_rows(np.arange(VOCAB))

        read = PServerClient(grp.specs, DIM, trainer_id=1)
        read.register()
        read_emb = PServerEmbedding(read)
        cache = TieredEmbedCache(read_emb, table, hot_rows=8,
                                 host_rows=12, max_staleness=0)
        cache.bind_push_feed(push)    # same thread: reentrant-safe

        tasks = [{"row": i % VOCAB, "delta": 2.0 ** -(i % 5)}
                 for i in range(12)]
        applied = []
        for i, t in enumerate(tasks):
            emb.apply_row_grads(table, np.asarray([t["row"]]),
                                np.full((1, DIM), t["delta"],
                                        np.float32), 1.0)
            applied.append(t)
            # read a window covering both shards; get #2 kills the
            # shard-0 primary mid-loop and the client fails over
            got = np.asarray(cache.lookup([t["row"], 1, VOCAB - 1]))
            want = _expected_table(init, applied)
            assert np.array_equal(got[0], want[t["row"]]), (
                f"stale read at step {i}")
            assert np.array_equal(got[1], want[1])
            assert np.array_equal(got[2], want[VOCAB - 1])
        assert plan.count("psgetkill") == 1
        assert read.shard_failovers()[0] >= 1
        assert cache.counters()["invalidations_failover"] >= 1
        assert cache.reconcile()["ok"]


@pytest.mark.faults
def test_reform_mid_stream_exactly_once_watermarks():
    """Kill the streaming trainer mid-stream AND drop a push ACK: the
    reformed trainer (same id, fresh client) adopts the shard's
    applied epochs at registration, replays the leased-back task, the
    retried push DUPs out, and the final table equals the exact numpy
    ledger — every delta applied exactly once through the reform."""
    with PServerGroup(VOCAB, DIM, n_shards=1, replicated=False) as grp:
        ack_plan = FaultPlan(pserver_lost_ack_at=2)
        ack_plan.wrap_pserver_shard(grp.primaries[0])

        boot = PServerClient(grp.specs, DIM, trainer_id=0)
        boot.register()
        boot_emb = PServerEmbedding(boot)
        table = boot_emb.init(jax.random.key(5))
        init = boot.get_rows(np.arange(VOCAB))

        tasks = [{"row": i % VOCAB, "delta": 2.0 ** -(i % 6),
                  "seed": i, "vocab": VOCAB} for i in range(8)]
        q = TaskQueue(timeout_ms=200, max_retries=4)
        for t in tasks:
            q.add_task(json.dumps(t).encode())

        def mk_trainer():
            client = PServerClient(grp.specs, DIM, trainer_id=7)
            client.register()       # adopts the applied-epoch watermark
            return StreamingTrainer(q, PServerEmbedding(client), table,
                                    lr=1.0, grad_fn=_dyadic_grad_fn)

        t1 = mk_trainer()
        FaultPlan(online_kill_step_at=4).wrap_online_trainer(t1)
        with pytest.raises(FaultError):
            t1.run(len(tasks))
        done_before = t1.stats["tasks_done"]
        assert done_before < len(tasks)

        # REFORM: fresh instance, same trainer id, same queue. The
        # killed step's task leases back to todo after timeout_ms and
        # the reformed stream consumes the remainder.
        t2 = mk_trainer()
        remaining = len(tasks) - done_before
        assert t2.run(remaining) == remaining

        want = _expected_table(init, tasks)
        got = boot.get_rows(np.arange(VOCAB))
        assert np.array_equal(got, want)
        st = grp.primaries[0].stats()
        # the lost-ACK retry DUPed instead of double-applying, and the
        # push watermark equals exactly one apply per task
        assert ack_plan.count("pslostack") == 1
        assert st["duplicates"] >= 1
        assert st["version"] == len(tasks) + 1     # +1: the init load
