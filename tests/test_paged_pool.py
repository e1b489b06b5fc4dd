"""Paged KV pool: shared-prefix reuse, chunked prefill, exhaustion.

The block-paged pool (serve.paged + ops.paged_attention) must keep the
engine's foundational contract — greedy tokens EXACTLY equal solo
`generate()` — under every new mechanism it introduces: prefix-cache
hits, copy-on-write splits at block boundaries, chunked prefill
interleaved with live decodes, and recompute preemption when an
over-subscribed pool runs out of pages. On top of parity, the pool's
books must balance (PagePool.reconcile) and the capacity win must be
real: at equal HBM budget the paged layout admits >= 2x the dense
layout's concurrent requests on mixed-length traffic (the ISSUE 4
acceptance bound, asserted via page math AND a live run).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.serve.engine import DecodeEngine
from paddle_tpu.serve.paged import PagePool, PoolExhaustedError
from paddle_tpu.serve.server import ServingServer
from paddle_tpu.testing.faults import FaultPlan

CFG = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                          attn_impl="dense")


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.key(0), CFG)


def ref_tokens(params, prompt, max_new, eos_id=None):
    out = T.generate(params, CFG, jnp.asarray(prompt)[None, :],
                     steps=max_new, eos_id=eos_id)
    toks = [int(t) for t in np.asarray(out[0, len(prompt):])]
    if eos_id is not None and eos_id in toks:
        toks = toks[:toks.index(eos_id) + 1]
    return toks


def rng_tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, 61, (n,)) \
        .astype(np.int32)


# -- the host allocator alone (no device work) ---------------------------


class TestPagePool:
    def _pool(self, **kw):
        base = dict(num_pages=8, page_size=4, slots=4,
                    max_pages_per_slot=4)
        base.update(kw)
        return PagePool(**base)

    def test_admit_extend_release_roundtrip(self):
        pool = self._pool()
        toks = rng_tokens(9)
        pages, shared = pool.admit(0, toks, 9)   # blocks 0..2 (pos 9)
        assert len(pages) == 3 and shared == 0
        assert pool.pages_in_use == 3
        # positions 10, 11 stay in block 2; 12 maps block 3
        assert pool.extend(0) is None
        assert pool.extend(0) is None
        blk, page = pool.extend(0)
        assert blk == 3 and pool.pages_in_use == 4
        pool.release(0)
        assert pool.pages_in_use == 0
        pool.release(0)                          # idempotent
        pool.reconcile()

    def test_prefix_share_refcount_and_cow_split(self):
        pool = self._pool()
        toks = rng_tokens(10, seed=1)
        pool.admit(0, toks, 10)
        pool.register(0, toks, 10)               # blocks 0,1 published
        # same leading 8 tokens, divergent block 2: shares 2 pages
        other = np.concatenate([toks[:8], rng_tokens(3, seed=2)])
        pages, shared_len = pool.admit(1, other, 11)
        assert shared_len == 8
        assert pages[:2] == pool.slot_pages[0][:2]      # shared
        assert pages[2] not in pool.slot_pages[0]       # the CoW split
        pool.reconcile()
        pool.release(0)
        # shared pages survive for slot 1 + the cache
        assert all(p in pages for p in pool.slot_pages[1])
        pool.reconcile()
        pool.release(1)
        pool.reconcile()
        # cache still holds the two registered blocks (evictable)
        assert pool.pages_in_use == 2 and pool.evictable() == 2

    def test_alloc_reclaims_cache_only_pages_then_raises(self):
        pool = self._pool(num_pages=4)
        toks = rng_tokens(9, seed=3)
        pool.admit(0, toks, 9)                   # 3 pages
        pool.register(0, toks, 9)                # blocks 0,1 cached
        pool.release(0)                          # 2 cache-only remain
        assert pool.headroom() == 4
        pool.admit(1, rng_tokens(13, seed=4), 13)   # needs 4: evicts
        assert pool.pages_in_use == 4
        with pytest.raises(PoolExhaustedError):
            pool.alloc(1)
        pool.reconcile()

    def test_shareable_blocks_always_leaves_one_position(self):
        pool = self._pool()
        # a fully-cached prompt must still compute its last position
        assert pool.shareable_blocks(8) == 1     # page 4: not 2
        assert pool.shareable_blocks(9) == 2

    def test_admissible_excludes_own_prefix_from_reclaimable(self):
        """The admission gate must mirror admit()'s arithmetic: a
        request's OWN cache-only prefix pages are ref'd before alloc
        (anti-aliasing order), so they are not reclaimable for its own
        allocation. A naive pages_needed<=headroom gate admits this
        shape and admit() then raises spuriously."""
        pool = self._pool()
        toks = rng_tokens(10, seed=30)
        pool.admit(0, toks, 10)
        pool.register(0, toks, 10)           # blocks 0,1 cached
        pool.release(0)                      # ... cache-only now
        pool.admit(1, rng_tokens(20, seed=31), 20)  # co-tenant: 6 pages
        assert pool.pages_free == 0 and pool.evictable() == 2
        # same prefix, block 2 private: need 1 past the 2 shared
        again = np.concatenate([toks[:8], rng_tokens(2, seed=32)])
        assert pool.pages_needed(again, 10) == 1
        assert pool.pages_needed(again, 10) <= pool.headroom()  # naive
        assert not pool.admissible(again, 10)    # the honest gate
        with pytest.raises(PoolExhaustedError):
            pool.admit(2, again, 10)
        pool.reconcile()                     # admit left no residue
        # the gate opens the moment the co-tenant frees its pages
        pool.release(1)
        assert pool.admissible(again, 10)
        pages, shared_len = pool.admit(2, again, 10)
        assert shared_len == 8
        pool.reconcile()

    def test_pages_needed_is_a_pure_probe(self):
        """pages_needed/admissible are re-asked every server loop for
        a deferred queue head — they must not LRU-touch entries (that
        would skew reclaim order) nor fire the fault hook."""
        pool = self._pool()
        a = rng_tokens(10, seed=33)
        pool.admit(0, a, 10)
        pool.register(0, a, 10)
        b = rng_tokens(10, seed=34)
        pool.admit(1, b, 10)
        pool.register(1, b, 10)
        order_before = list(pool._cache)
        events = []
        pool.fault_hook = lambda ev, ctx: events.append(ev)
        assert pool.pages_needed(a, 10) == 1     # shares blocks 0,1
        assert pool.admissible(a, 10)
        assert list(pool._cache) == order_before  # no LRU reorder
        assert events == []                       # no hook traffic


# -- parity under the new mechanisms -------------------------------------


class TestPrefixReuseParity:
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_shared_system_prefix_hits_and_matches(self, params):
        """Co-tenants sharing a 16-token system prefix (2 pages of 8):
        later admissions hit the cache, skip that prefill work, and
        still decode EXACTLY their solo generate() tokens."""
        sys_prefix = rng_tokens(16, seed=10)
        prompts = [np.concatenate([sys_prefix, rng_tokens(n, seed=s)])
                   for n, s in ((5, 11), (3, 12), (7, 13))]
        eng = DecodeEngine(params, CFG, slots=2, max_len=48,
                           page_size=8)
        got = eng.serve(prompts, max_new=8)
        for p, g in zip(prompts, got):
            assert g == ref_tokens(params, p, 8), len(p)
        st = eng.last_stats
        assert st.prefix_hits >= 2, st           # request 2 and 3 hit
        assert st.prefix_misses == 1, st
        eng.pool.reconcile()

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_divergence_exactly_at_page_boundary(self, params):
        """Two prompts identical through block 0 and divergent at
        position page_size exactly: block 0 is shared, block 1 is the
        copy-on-write split — both decode to their solo tokens."""
        head = rng_tokens(8, seed=20)
        a = np.concatenate([head, rng_tokens(6, seed=21)])
        b = np.concatenate([head, rng_tokens(6, seed=22)])
        eng = DecodeEngine(params, CFG, slots=2, max_len=48,
                           page_size=8)
        got = eng.serve([a, b], max_new=8)
        assert got[0] == ref_tokens(params, a, 8)
        assert got[1] == ref_tokens(params, b, 8)
        assert eng.last_stats.prefix_hits == 1
        pool = eng.pool
        pool.reconcile()

    @pytest.mark.slow

    def test_prefix_cache_off(self, params):
        eng = DecodeEngine(params, CFG, slots=2, max_len=48,
                           page_size=8, prefix_cache=False)
        sys_prefix = rng_tokens(16, seed=23)
        prompts = [np.concatenate([sys_prefix, rng_tokens(4, seed=s)])
                   for s in (24, 25)]
        got = eng.serve(prompts, max_new=6)
        for p, g in zip(prompts, got):
            assert g == ref_tokens(params, p, 6)
        assert eng.last_stats.prefix_hits == 0


class TestChunkedPrefill:
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_long_prompt_chunks_and_matches(self, params):
        """A prompt longer than one chunk prefills in fixed chunks
        with decodes interleaved; tokens match solo generate() for
        every co-tenant."""
        eng = DecodeEngine(params, CFG, slots=2, max_len=48,
                           page_size=8, prefill_chunk=8)
        prompts = [rng_tokens(23, seed=30), rng_tokens(4, seed=31),
                   rng_tokens(17, seed=32)]
        got = eng.serve(prompts, max_new=8)
        for p, g in zip(prompts, got):
            assert g == ref_tokens(params, p, 8), len(p)
        # 23 -> 3 chunks, 4 -> 1, 17 -> 3
        assert eng.last_stats.prefill_chunks == 7, eng.last_stats

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_decode_interleaves_with_chunks(self, params):
        """The head-of-line property itself: while the long prompt is
        mid-prefill, the already-active short request keeps emitting —
        decode steps are observed BETWEEN that prompt's chunks."""
        eng = DecodeEngine(params, CFG, slots=2, max_len=64,
                           page_size=8, prefill_chunk=8)
        events = []
        orig_adv, orig_step = eng.prefill_advance, eng.decode_step
        eng.prefill_advance = lambda s, t: (
            events.append("chunk"), orig_adv(s, t))[1]
        eng.decode_step = lambda s: (
            events.append("step"), orig_step(s))[1]
        # short first (admits + activates), then a 4-chunk prompt
        got = eng.serve([rng_tokens(4, seed=33),
                         rng_tokens(30, seed=34)], max_new=12)
        assert got[0] == ref_tokens(params, rng_tokens(4, seed=33), 12)
        assert got[1] == ref_tokens(params, rng_tokens(30, seed=34), 12)
        chunk_idx = [i for i, e in enumerate(events) if e == "chunk"]
        # decode steps happened between the long prompt's chunks
        between = any(
            "step" in events[a + 1:b]
            for a, b in zip(chunk_idx, chunk_idx[1:]))
        assert between, events

    @pytest.mark.slow

    def test_chunked_plus_prefix_hit(self, params):
        """A prefix hit under chunked prefill starts chunking at the
        first private block — both mechanisms compose, parity holds."""
        sys_prefix = rng_tokens(16, seed=35)
        p0 = np.concatenate([sys_prefix, rng_tokens(9, seed=36)])
        p1 = np.concatenate([sys_prefix, rng_tokens(5, seed=37)])
        eng = DecodeEngine(params, CFG, slots=1, max_len=48,
                           page_size=8, prefill_chunk=8)
        got = eng.serve([p0, p1], max_new=6)
        assert got[0] == ref_tokens(params, p0, 6)
        assert got[1] == ref_tokens(params, p1, 6)
        assert eng.last_stats.prefix_hits == 1


# -- exhaustion: preemption, shed/requeue, chaos -------------------------


class TestPoolExhaustion:
    def test_entry_validation_page_granular(self, params):
        """A prompt that fits max_len but not the whole page pool is
        rejected up front — engine.serve() AND server.submit()."""
        eng = DecodeEngine(params, CFG, slots=2, max_len=32,
                           page_size=8, num_pages=2)
        with pytest.raises(ValueError, match="pages"):
            eng.serve([rng_tokens(20, seed=40)], max_new=2)
        srv = ServingServer(eng)
        with pytest.raises(ValueError, match="pages"):
            srv.submit(rng_tokens(20, seed=40), max_new=2)
        assert srv.results[0].outcome == "failed"

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_serve_preempts_and_still_matches(self, params):
        """Over-subscribed plain serve(): slots outnumber pages, so
        mid-decode exhaustion preempts co-tenants back onto the queue
        (stats.retried) — and every request STILL ends with exactly
        its solo generate() prefix (full for completed-at-max_new,
        truncated only by pool capacity)."""
        eng = DecodeEngine(params, CFG, slots=3, max_len=32,
                           page_size=4, num_pages=9)
        prompts = [rng_tokens(n, seed=41 + i)
                   for i, n in enumerate((10, 9, 11, 8))]
        got = eng.serve(prompts, max_new=12)
        for p, g in zip(prompts, got):
            ref = ref_tokens(params, p, 12)
            assert g == ref[:len(g)] and len(g) >= 1, (len(p), g, ref)
        assert sum(len(g) == 12 for g in got) >= 2, got
        eng.pool.reconcile()

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_server_sheds_requeues_on_exhaustion_chaos(self, params):
        """ACCEPTANCE CHAOS: a mixed-length burst through an
        over-subscribed server pool — page exhaustion mid-burst drives
        the preempt/requeue path, every request ends in EXACTLY ONE
        outcome, and the page books balance."""
        eng = DecodeEngine(params, CFG, slots=4, max_len=32,
                           page_size=4, num_pages=12)
        srv = ServingServer(eng, max_queue=16, max_retries=3)
        prompts = [rng_tokens(4 + (3 * i) % 14, seed=50 + i)
                   for i in range(10)]
        for p in prompts:
            srv.submit(p, max_new=10)
        results = srv.run()
        assert len(results) == 10
        srv.reconcile()
        c = srv.counters()
        assert c["completed"] >= 1
        assert c["completed"] + c["failed"] + c["shed"] \
            + c["expired"] == 10
        # completed requests kept greedy parity through preemption
        for p, rid in zip(prompts, range(10)):
            r = results[rid]
            if r.outcome == "completed" and len(r.tokens) == 10:
                assert r.tokens == ref_tokens(params, p, 10), rid
        assert c["pages_in_use"] - eng.pool.evictable() == 0

    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_page_alloc_fault_injection(self, params):
        """FaultPlan pool exhaustion: the nth allocation reports
        exhaustion against a HEALTHY pool — the requeue path must
        carry the victim to completion (retried >= 1, all
        completed)."""
        plan = FaultPlan(serve_page_alloc_error_at=2)
        eng = plan.wrap_engine(
            DecodeEngine(params, CFG, slots=2, max_len=32,
                         page_size=8))
        srv = ServingServer(eng, max_retries=2)
        prompts = [rng_tokens(5, seed=60), rng_tokens(7, seed=61),
                   rng_tokens(6, seed=62)]
        for p in prompts:
            srv.submit(p, max_new=6)
        results = srv.run()
        assert plan.count("pagealloc") == 1, plan.fired
        srv.reconcile()
        assert all(r.outcome == "completed"
                   for r in results.values()), results
        for p, rid in zip(prompts, range(3)):
            assert results[rid].tokens == ref_tokens(params, p, 6)
        assert srv.counters()["retried"] >= 1

    def test_prefix_corruption_detected_and_rejected(self, params):
        """FaultPlan prefix corruption: a poisoned cache entry is
        caught by the lookup's token re-verification — degraded to a
        miss, evicted (prefix_rejected), greedy parity preserved."""
        sys_prefix = rng_tokens(16, seed=70)
        prompts = [np.concatenate([sys_prefix, rng_tokens(4, seed=s)])
                   for s in (71, 72, 73)]
        plan = FaultPlan(serve_prefix_corrupt_at=0)
        eng = plan.wrap_engine(
            DecodeEngine(params, CFG, slots=1, max_len=48,
                         page_size=8))
        srv = ServingServer(eng)
        for p in prompts:
            srv.submit(p, max_new=6)
        results = srv.run()
        assert plan.count("prefixcorrupt") == 1, plan.fired
        for p, rid in zip(prompts, range(3)):
            assert results[rid].tokens == ref_tokens(params, p, 6), rid
        c = srv.counters()
        assert c["prefix_rejected"] == 1, c
        srv.reconcile()


# -- observability -------------------------------------------------------


def test_server_counters_and_drain_report_carry_pool_stats(
        params, tmp_path):
    report_path = str(tmp_path / "drain.json")
    eng = DecodeEngine(params, CFG, slots=2, max_len=48, page_size=8)
    srv = ServingServer(eng, drain_report_path=report_path)
    sys_prefix = rng_tokens(16, seed=80)
    for s in (81, 82):
        srv.submit(np.concatenate([sys_prefix, rng_tokens(4, seed=s)]),
                   max_new=4)
    srv.run()
    c = srv.counters()
    for key in ("pages_in_use", "pages_free", "peak_pages_in_use",
                "prefix_hits", "prefix_misses", "prefill_chunks"):
        assert key in c, key
    assert c["prefill_chunks"] >= 2 and c["peak_pages_in_use"] >= 2
    assert c["prefix_hits"] == 1 and c["prefix_misses"] == 1
    srv.reconcile()
    srv.drain(reason="test")
    srv.run()
    import json

    report = json.loads(open(report_path).read())
    assert "prefix_hits" in report["counters"]


def test_engine_stats_pool_fields(params):
    eng = DecodeEngine(params, CFG, slots=2, max_len=32, page_size=8)
    eng.serve([rng_tokens(5, seed=90), rng_tokens(7, seed=91)],
              max_new=4)
    st = eng.last_stats
    assert st.pages_in_use == 0          # all released at the end
    assert st.pages_free == eng.num_pages
    assert st.peak_pages_in_use >= 2
    assert st.prefill_chunks == 2


# -- the capacity acceptance bound ---------------------------------------


def _peak_concurrency(params, cfg, prompts, max_new, **pool):
    """Serve `prompts` at once through one slot each; return the most
    requests that held a slot together, the results, the counters."""
    eng = DecodeEngine(params, cfg, slots=len(prompts), **pool)
    # retries: an oversubscribed pool preempts and requeues
    srv = ServingServer(eng, max_queue=len(prompts), max_retries=8)
    peak = [0]
    srv.on_step.append(lambda s, _: peak.__setitem__(
        0, max(peak[0], sum(r is not None for r in s._slot_req))))
    for p in prompts:
        srv.submit(p, max_new=max_new)
    results = srv.run()
    srv.reconcile()
    assert all(r.outcome == "completed" for r in results.values())
    return peak[0], results, srv.counters()


@pytest.mark.perf
def test_paged_admits_2x_dense_slots_at_equal_budget(params):
    """ISSUE 4 acceptance: at EQUAL HBM budget the paged pool admits
    >= 2x the dense layout's concurrent requests on a mixed-length
    workload. Dense budget: S_dense slots x max_len positions. Paged:
    the same positions as num_pages x page_size, slots bounded only by
    actual lengths. Asserted twice — by page math over the workload,
    and by a live run's observed concurrency."""
    s_dense, max_len, page = 2, 64, 8
    budget_pages = s_dense * (max_len // page)          # 16 pages
    lens = [6, 7, 5, 7, 6, 5, 7, 6]                     # mixed, short
    prompts = [rng_tokens(n, seed=100 + i)
               for i, n in enumerate(lens)]
    max_new = 4
    # page math: worst-case concurrent need per request (prompt +
    # generated, no prefix sharing assumed)
    need = [(n + max_new) // page + 1 for n in lens]
    fit = 0
    acc = 0
    for n in sorted(need):
        if acc + n > budget_pages:
            break
        acc += n
        fit += 1
    assert fit >= 2 * s_dense, (fit, need, budget_pages)

    peak, results, c = _peak_concurrency(
        params, CFG, prompts, max_new, max_len=max_len, page_size=page,
        num_pages=budget_pages)
    for p, rid in zip(prompts, range(len(prompts))):
        assert results[rid].tokens == ref_tokens(params, p, max_new)
    assert peak >= 2 * s_dense, (peak, c)
    assert c["peak_pages_in_use"] <= budget_pages


@pytest.mark.perf
def test_int8_pool_admits_2x_float_pool_at_equal_bytes(params):
    """At equal HBM bytes the int8 pool (s8 data and one f32 scale per
    position and head, against plain f32) holds `bytes_f / bytes_8`
    times the float pool's pages, and the same oversubscribed traffic
    runs at least twice as many requests at once through it."""
    max_len, page, max_new = 64, 8, 4
    dh = CFG.dim // CFG.n_heads
    bytes_f, bytes_8 = dh * 4, dh * 1 + 4
    pages_f = 8
    pages_8 = pages_f * bytes_f // bytes_8
    assert pages_8 * bytes_8 <= pages_f * bytes_f
    prompts = [rng_tokens(n, seed=200 + i)
               for i, n in enumerate([9, 12, 10, 14, 9, 11, 13, 10, 12, 9])]
    geom = dict(max_len=max_len, page_size=page)
    peak_f, _, c_f = _peak_concurrency(
        params, CFG, prompts, max_new, num_pages=pages_f, **geom)
    cfg8 = dataclasses.replace(CFG, kv_cache_dtype="int8")
    peak_8, _, c_8 = _peak_concurrency(
        params, cfg8, prompts, max_new, num_pages=pages_8, **geom)
    assert c_f["peak_pages_in_use"] <= pages_f
    assert c_8["peak_pages_in_use"] <= pages_8
    assert peak_8 >= 2 * peak_f > 0, (peak_8, peak_f, c_8, c_f)
