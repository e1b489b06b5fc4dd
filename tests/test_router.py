"""Multi-replica serving fleet: the router chaos harness.

`serve.ServingRouter` fronts N `ServingServer` replicas with
prefix-affinity routing (the paged pool's chained block keys ARE the
routing key), circuit-breaker health checks, and replica-loss
redistribution. The headline claim, proven here the same way every
reliability layer in this repo is proven (deterministic
`testing.faults` injection, `ManualClock`, no sleeps): kill a replica
mid-burst under mixed traffic and EVERY router-submitted request
still ends in exactly one outcome (never lost with the device, never
served twice), the fleet counters reconcile, completed requests match
their solo `generate()` decode bit-exactly, and the aggregate
prefix-hit rate recovers after the dead cache's traffic redistributes
onto (initially cold) survivors.
"""

import numpy as np
import pytest

import jax

from paddle_tpu.models import transformer as T
from paddle_tpu.serve.engine import DecodeEngine
from paddle_tpu.serve.paged import chain_keys
from paddle_tpu.serve.policy import RandomRoutingPolicy
from paddle_tpu.serve.router import (QueueFullError, ServingRouter)
from paddle_tpu.serve.server import ServingServer
from paddle_tpu.testing.faults import (FaultPlan, ManualClock,
                                       garbage_prompts)

pytestmark = [pytest.mark.faults, pytest.mark.router]

CFG = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                          attn_impl="dense")


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.key(0), CFG)


# ONE module-scoped engine set shared by every fleet in this file:
# engines are stateless between runs (init_state resets the device
# pool) and their jitted compiles dominate test cost. Fleets differ
# only in the wrappers (fault proxies) and servers around them.
@pytest.fixture(scope="module")
def engines(params):
    engs = [DecodeEngine(params, CFG, slots=2, max_len=32, page_size=4)
            for _ in range(3)]
    # pre-warm each replica's compiles (prefill at the two prompt
    # shapes the fleets use — bare len-11 and the chaos test's (16,)
    # bucket — plus the decode step) so no single test's call phase
    # pays 3x first-compile and trips the tier-1 budget guard
    warm = np.arange(11, dtype=np.int32)
    for e in engs:
        e.serve([warm], max_new=2)
        e.serve([warm], max_new=2, buckets=(16,))
    return engs


def make_fleet(engines, clk, *, wrap=None, max_queue=16, max_retries=2,
               probe_interval_s=1.0, policy=None, buckets=None,
               **router_kw):
    """3 replicas on a shared ManualClock; `wrap[i]` optionally
    wraps replica i's engine (fault proxies)."""
    servers = []
    for i, eng in enumerate(engines):
        if wrap and wrap.get(i) is not None:
            eng = wrap[i](eng)
        servers.append(ServingServer(eng, max_queue=max_queue,
                                     clock=clk, buckets=buckets,
                                     max_retries=max_retries))
    return ServingRouter(servers, clock=clk,
                         probe_interval_s=probe_interval_s,
                         policy=policy, **router_kw)


def routed_to(router, rr_id):
    """Which replica currently holds rr_id (pre-run introspection)."""
    for rep in router.replicas:
        if rr_id in rep.pending.values():
            return rep.rid
    return None


def ref_tokens(params, prompt, max_new):
    out = T.generate(params, CFG, jax.numpy.asarray(prompt)[None, :],
                     steps=max_new)
    return [int(t) for t in np.asarray(out[0, len(prompt):])]


def family_prompts(n, seed, prefix_len=8, tail_len=3, n_families=3,
                   prefix_seed=None):
    """Mixed traffic: `n` prompts cycling over `n_families` distinct
    8-token system prefixes (two full page_size=4 blocks each — the
    affinity chain is non-trivial) plus a unique tail. Pass the same
    `prefix_seed` across waves to keep the FAMILIES stable while the
    tails vary (the recovery-measurement scenario)."""
    pr = np.random.RandomState(seed if prefix_seed is None
                               else prefix_seed)
    r = np.random.RandomState(seed)
    prefixes = [pr.randint(0, 61, (prefix_len,)).astype(np.int32)
                for _ in range(n_families)]
    out = []
    for i in range(n):
        tail = r.randint(0, 61, (tail_len,)).astype(np.int32)
        out.append(np.concatenate([prefixes[i % n_families], tail]))
    return out


class TestRouting:
    def test_affinity_groups_prefix_families(self, params, engines):
        """Each shared-prefix family converges onto ONE replica (its
        chain keys point there after the first routing), so the
        fleet-wide hit rate approaches the single-box rate instead of
        scattering hot prefixes across N cold caches."""
        clk = ManualClock()
        router = make_fleet(engines, clk)
        ps = family_prompts(6, seed=1)
        ids = [router.submit(p, max_new=4) for p in ps]
        res = router.run()
        router.reconcile()
        by_family = {}
        for i, (rid, p) in enumerate(zip(ids, ps)):
            assert res[rid].outcome == "completed"
            assert res[rid].tokens == ref_tokens(params, p, 4)
            by_family.setdefault(i % 3, set()).add(res[rid].replica)
        # one replica per family — affinity, not scatter
        for fam, reps in by_family.items():
            assert len(reps) == 1, (fam, reps)
        c = router.counters()
        # 6 requests, 3 cold first-routings: the rest were affinity
        assert c["affinity_hits"] >= 3
        # and the replica-local caches agree the prefixes were hot
        assert c["fleet_prefix_hits"] >= 3

    def test_affinity_key_matches_pool_derivation(self, engines):
        """The router's routing key IS the pool's cache key: both
        call paged.chain_keys, so 'hot on replica k' is decided by
        exactly the hash replica k's own cache would hit."""
        clk = ManualClock()
        router = make_fleet(engines, clk)
        p = np.arange(11, dtype=np.int32)
        chain = router._chain(p)
        assert chain == chain_keys(p, 11, engines[0].page_size)
        assert chain[0] == ((), (0, 1, 2, 3))
        assert chain[1] == (chain[0], (4, 5, 6, 7))

    def test_spill_to_least_loaded_on_miss(self, engines):
        """Affinity-miss traffic levels across the fleet instead of
        piling onto one replica."""
        clk = ManualClock()
        router = make_fleet(engines, clk)
        r = np.random.RandomState(7)
        used = []
        # submit without running: loads grow as requests queue, so
        # unique-prefix prompts must fan out round-robin-by-load
        for _ in range(6):
            p = r.randint(0, 61, (9,)).astype(np.int32)
            rid = router.submit(p, max_new=2)
            used.append(routed_to(router, rid))
        assert set(used) == {0, 1, 2}, used
        router.run()
        router.reconcile()

    def test_affinity_target_full_spills_not_sheds(self, engines):
        """A FULL affinity target is a miss, not a shed: the burst
        spills to replicas with queue space (one prefill is the cost;
        a shed would lose the request while other replicas idle).
        Only a fleet-wide full queue sheds."""
        clk = ManualClock()
        router = make_fleet(engines, clk, max_queue=2)
        ps = family_prompts(5, seed=61, n_families=1)
        ids = [router.submit(p, max_new=2) for p in ps]
        # the single family overflows its replica's 2-deep queue and
        # fans out instead of shedding
        assert len({routed_to(router, rid) for rid in ids}) >= 2
        res = router.run()
        router.reconcile()
        assert all(res[i].outcome == "completed" for i in ids)
        assert router.stats["shed"] == 0

    def test_random_policy_scatters(self, engines):
        """The control arm: RandomRoutingPolicy ignores the
        affinity map, so even a single shared-prefix family lands on
        several replicas (several cold caches pay the prefill the
        affinity map would have saved)."""
        clk = ManualClock()
        router = make_fleet(engines, clk,
                            policy=RandomRoutingPolicy(seed=3))
        ps = family_prompts(9, seed=1, n_families=1)
        for p in ps:
            router.submit(p, max_new=2)
        res = router.run()
        router.reconcile()
        reps = {r.replica for r in res.values()}
        assert len(reps) >= 2, reps


class TestChaosKill:
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    @pytest.mark.locks  # chaos lane re-run under LockOrderGuard
    def test_kill_midburst_exactly_once_and_hit_rate_recovers(
            self, params, engines, lock_order_guard):
        """THE acceptance chaos run (ISSUE 6): >= 3 replicas under a
        mixed burst (3 prefix families + garbage traffic), one
        replica killed at a decode step MID-burst (slots occupied,
        queue non-empty). Asserts, fleet-wide: every submitted
        request ends in EXACTLY ONE outcome (never zero, never two),
        counters reconcile across the fleet, completed requests are
        bit-exact vs generate(), and after redistribution warms the
        survivors the aggregate prefix-hit rate recovers to within
        10% of the pre-kill rate."""
        clk = ManualClock()
        plan = FaultPlan()             # armed between waves, below
        router = make_fleet(
            engines, clk, buckets=(16,),
            wrap={0: lambda e: plan.wrap_replica_engine(e, clock=clk)})

        # -- warm wave: every family hot somewhere, measure the rate
        warm = family_prompts(6, seed=11, prefix_seed=99)
        warm_ids = [router.submit(p, max_new=4) for p in warm]
        wres = router.run()
        router.reconcile()
        assert all(wres[i].outcome == "completed" for i in warm_ids)
        pre_rate = router.prefix_hit_rate()
        assert pre_rate >= 0.5          # the cache is genuinely warm
        assert router.stats["replicas_lost"] == 0

        # -- the kill burst: arm the fault at the 5th decode step of
        # THIS burst on replica 0 — mid-burst by construction (its
        # two slots are decoding and its queue still holds work)
        plan.router_kill_decode_at = plan._router_decode_counter + 4
        burst = family_prompts(9, seed=12, prefix_seed=99)
        burst_ids = [router.submit(p, max_new=4) for p in burst]
        garbage_failed = 0
        for g in garbage_prompts(61, 16).values():
            try:
                router.submit(g, max_new=2)
            except ValueError:
                garbage_failed += 1
        assert garbage_failed == 6
        res = router.run()
        router.reconcile()              # THE fleet invariant
        assert plan.count("replicakill") == 1
        c = router.counters()
        assert c["replicas_lost"] == 1
        assert c["redistributed"] >= 1  # the dead replica held work
        # exactly-once: every submission has one terminal outcome
        assert len(res) == c["requests"] == len(warm) + len(burst) + 6
        assert (c["completed"] + c["expired"] + c["shed"] + c["failed"]
                == c["requests"])
        assert c["failed"] == 6         # garbage only — no request
        #                                 died with the device
        # completions are still the exact greedy decode — the kill is
        # invisible in the output stream (warm-wave parity is
        # test_affinity_groups' job; the kill-affected burst is THE
        # check here)
        for rid, p in zip(burst_ids, burst):
            assert res[rid].outcome == "completed", (rid, res[rid])
            assert res[rid].tokens == ref_tokens(params, p, 4)
        # redistributed requests finished on survivors
        moved = [rid for rid in burst_ids
                 if res[rid].redistributions > 0]
        assert moved and all(res[rid].replica != 0 for rid in moved)

        # -- recovery wave: the same families, now served by the
        # survivors' warmed caches — aggregate hit rate within 10%
        # of pre-kill
        rec = family_prompts(6, seed=13, prefix_seed=99)
        rec_ids = [router.submit(p, max_new=4) for p in rec]
        res = router.run()
        router.reconcile()
        for rid, p in zip(rec_ids, rec):
            assert res[rid].outcome == "completed"
        # spot-check parity on the recovery wave (full parity is the
        # burst's check above)
        for rid, p in list(zip(rec_ids, rec))[:2]:
            assert res[rid].tokens == ref_tokens(params, p, 4)
        post = router.counters()
        dh = post["fleet_prefix_hits"] - c["fleet_prefix_hits"]
        dm = post["fleet_prefix_misses"] - c["fleet_prefix_misses"]
        post_rate = dh / max(dh + dm, 1)
        assert post_rate >= pre_rate - 0.10, (pre_rate, post_rate)

    def test_kill_preserves_retry_budgets(self, engines):
        """Redistribution carries each harvested request's REMAINING
        retries_left to the survivor — budgets intact: not reset, and
        not billed for the replica's death. The whole fleet run —
        routing, kill, harvest, redistribution — executes under
        transfer_guard('disallow'): the router adds ZERO implicit
        host<->device transfers on top of the already-clean decode
        loop (docs/ANALYSIS.md)."""
        clk = ManualClock()
        plan = FaultPlan(router_kill_decode_at=0)
        router = make_fleet(
            engines, clk, max_retries=2,
            wrap={0: lambda e: plan.wrap_replica_engine(e, clock=clk)})
        ps = family_prompts(4, seed=21, n_families=1)
        ids = [router.submit(p, max_new=3) for p in ps]
        with jax.transfer_guard("disallow"):
            res = router.run()
        router.reconcile()
        assert plan.count("replicakill") == 1
        assert router.stats["redistributed"] >= 1
        for rid in ids:
            assert res[rid].outcome == "completed"
            # retries counts transient requeues: the death handoff
            # consumed none of the budget (retries_left rode over)
            assert res[rid].retries == 0
            assert res[rid].redistributions in (0, 1)

    def test_all_replicas_dead_fails_closed(self, engines):
        """With no survivor, pending requests end FAILED — an
        explicit outcome, not a hang and not silence — and later
        submits shed with 'no routable replica'."""
        clk = ManualClock()
        plans = [FaultPlan(router_kill_decode_at=0) for _ in range(3)]
        router = make_fleet(
            engines, clk,
            wrap={i: (lambda e, p=plans[i]:
                      p.wrap_replica_engine(e, clock=clk))
                  for i in range(3)})
        ps = family_prompts(3, seed=22)
        ids = [router.submit(p, max_new=3) for p in ps]
        res = router.run()
        router.reconcile()
        # kill-at-decode-0 everywhere: nothing ever completes a step
        assert all(res[i].outcome == "failed" for i in ids)
        assert all("replica" in res[i].error for i in ids)
        assert router.counters()["replicas_alive"] == 0
        with pytest.raises(QueueFullError, match="no routable"):
            router.submit(ps[0], max_new=2)
        router.reconcile()


class TestHealth:
    def test_probe_blackhole_opens_breaker_and_recovers(self, engines):
        """Blackholed health probes (the replica is FINE — only its
        probes fail) open the breaker after failure_threshold
        consecutive misses: routing avoids the replica, with NO false
        kill and NO redistribution. Once probes flow again, the
        half-open probe closes the breaker and traffic returns."""
        clk = ManualClock()
        plan = FaultPlan(router_probe_drop_first_n=2)
        router = make_fleet(engines, clk, probe_interval_s=1.0,
                            failure_threshold=2, cooldown_s=5.0)
        plan.wrap_probe(router.replicas[0])
        router.probe_all()              # miss #1
        clk.advance(1.5)
        router.probe_all()              # miss #2 -> open
        assert plan.count("probedrop") == 2
        assert router.replicas[0].breaker.state == "open"
        assert not router.replicas[0].routable()
        # traffic flows around the quarantined replica
        ps = family_prompts(4, seed=31)
        ids = [router.submit(p, max_new=3) for p in ps]
        res = router.run()
        router.reconcile()
        assert all(res[i].outcome == "completed" for i in ids)
        assert all(res[i].replica != 0 for i in ids)
        assert router.stats["replicas_lost"] == 0   # no false kill
        assert router.stats["redistributed"] == 0
        # past cooldown the probes are clean: half-open -> closed
        clk.advance(6.0)
        router.probe_all()
        assert router.replicas[0].breaker.state == "closed"
        assert router.replicas[0].routable()

    def test_failing_half_open_probe_reopens_breaker(self, engines):
        """The breaker contract through the PROBE path: after the
        cooldown, ONE half-open probe decides — a still-blackholed
        probe RE-OPENS the breaker for another full cooldown (it must
        not sit half-open being re-probed every interval)."""
        clk = ManualClock()
        plan = FaultPlan(router_probe_drop_first_n=3)
        router = make_fleet(engines, clk, probe_interval_s=1.0,
                            failure_threshold=2, cooldown_s=5.0)
        rep = router.replicas[0]
        plan.wrap_probe(rep)
        router.probe_all()              # miss #1
        clk.advance(1.5)
        router.probe_all()              # miss #2 -> OPEN
        assert rep.breaker.state == "open" and rep.breaker.trips == 1
        clk.advance(6.0)                # past cooldown: half-open
        router.probe_all()              # miss #3: the deciding probe
        assert plan.count("probedrop") == 3
        assert rep.breaker.state == "open"      # re-opened, not stuck
        clk.advance(1.5)
        router.probe_all()              # still cooling: NOT probed
        assert plan._router_probe_counter == 3
        clk.advance(6.0)                # next half-open: clean probe
        router.probe_all()
        assert rep.breaker.state == "closed" and rep.routable()

    def test_probe_detects_dead_replica_with_queued_work(self,
                                                        engines):
        """A replica that dies holding only QUEUED work (no decode
        ever reaches it to raise) is caught by the health sweep's
        ping — its queue redistributes and every request completes."""
        clk = ManualClock()
        plan = FaultPlan()
        box = {}

        def wrap1(e):
            box["w"] = plan.wrap_replica_engine(e, clock=clk)
            return box["w"]

        router = make_fleet(engines, clk, wrap={1: wrap1})
        ps = family_prompts(6, seed=32)
        ids = [router.submit(p, max_new=3) for p in ps]
        victims = [rid for rid in ids if routed_to(router, rid) == 1]
        assert victims                  # the dead replica held work
        box["w"].dead = True            # device falls off the bus
        res = router.run()              # first sweep probes (due)
        router.reconcile()
        assert router.stats["replicas_lost"] == 1
        assert router.stats["redistributed"] >= len(victims)
        assert all(res[i].outcome == "completed" for i in ids)
        assert all(res[i].replica != 1 for i in ids)

    def test_slow_replica_skew_is_contained(self, params, engines):
        """A persistently slow replica (every decode burns 40ms of
        the shared clock) expires its own deadline-bound long
        requests; the round-robin drive keeps the other replicas
        stepping at full rate, so their requests complete exactly —
        one straggler cannot stall the fleet."""
        clk = ManualClock()
        plan = FaultPlan(router_slow_decode_s=0.04)
        router = make_fleet(
            engines, clk,
            wrap={0: lambda e: plan.wrap_replica_engine(e, clock=clk)})
        slow_ps = family_prompts(2, seed=41, n_families=1)
        fast_ps = family_prompts(2, seed=42, n_families=1)
        # first submit spills to replica 0 (empty fleet, stable
        # order); the second family spills to the next-least-loaded
        slow_ids = [router.submit(p, max_new=20, deadline_ms=100)
                    for p in slow_ps]
        fast_ids = [router.submit(p, max_new=6, deadline_ms=2000)
                    for p in fast_ps]
        assert routed_to(router, slow_ids[0]) == 0
        assert routed_to(router, fast_ids[0]) != 0
        res = router.run()
        router.reconcile()
        for i in slow_ids:
            assert res[i].outcome == "expired"
            assert 0 < len(res[i].tokens) < 20    # died mid-decode
        for i, p in zip(fast_ids, fast_ps):
            assert res[i].outcome == "completed"
            assert res[i].tokens == ref_tokens(params, p, 6)


class TestRetire:
    def test_retire_redistributes_queue_zero_recompute(
            self, params, engines):
        """Planned maintenance: retire_replica stops new routing and
        hands the replica's QUEUE to survivors immediately (those
        requests never started — the handoff is free). Every request
        completes; the retiree serves nothing new."""
        clk = ManualClock()
        router = make_fleet(engines, clk)
        ps = family_prompts(8, seed=51, n_families=2)
        ids = [router.submit(p, max_new=4) for p in ps]
        target = next(rep for rep in router.replicas
                      if rep.server.queue)
        router.retire_replica(target.rid, reason="maintenance")
        res = router.run()
        router.reconcile()
        for rid, p in zip(ids, ps):
            assert res[rid].outcome == "completed"
            assert res[rid].tokens == ref_tokens(params, p, 4)
        assert not target.routable()
        # nothing was in flight pre-retire, so the retiree served 0
        assert all(res[rid].replica != target.rid for rid in ids)
        # a fully-retired fleet fails closed, like a fully-dead one
        for rep in router.replicas:
            router.retire_replica(rep.rid)
        with pytest.raises(QueueFullError, match="no routable"):
            router.submit(ps[0], max_new=2)
        router.reconcile()


class TestCliFleet:
    @pytest.mark.slow
    def test_cli_serve_replicas(self, params, tmp_path):
        """`serve --replicas 2` routes through ServingRouter: ordered
        per-request output lines plus the fleet outcomes trailer.
        (2 replicas — the CLI test covers plumbing, not chaos; the
        >=3-replica chaos criterion lives in TestChaosKill.)"""
        from paddle_tpu.cli import main

        cfg_src = (
            "import jax\n\n\n"
            "def get_serve_config():\n"
            "    from paddle_tpu.models import transformer as T\n"
            "    cfg = T.TransformerConfig(vocab=61, dim=32,"
            " n_layers=2, n_heads=4, attn_impl='dense')\n"
            "    return {'cfg': cfg,"
            " 'params': T.init_params(jax.random.key(0), cfg),"
            " 'slots': 2, 'max_len': 24}\n")
        cfg_file = tmp_path / "serve_cfg.py"
        cfg_file.write_text(cfg_src)
        prompts = tmp_path / "prompts.txt"
        prompts.write_text("1 2 3 4 5\n7 8 9\n1 2 3 4 5\n")
        out = tmp_path / "out.txt"
        assert main(["serve", "--config", str(cfg_file),
                     "--prompts", str(prompts), "--max-new", "4",
                     "--replicas", "2", "--max-queue", "8",
                     "--output", str(out)]) == 0
        lines = out.read_text().strip().splitlines()
        assert len(lines) == 4                # 3 requests + trailer
        for line, p in zip(lines, ([1, 2, 3, 4, 5], [7, 8, 9],
                                   [1, 2, 3, 4, 5])):
            got = [int(t) for t in line.split()]
            assert got == ref_tokens(params,
                                     np.asarray(p, np.int32), 4)
        assert lines[-1].startswith("# outcomes ")
        assert "completed=3" in lines[-1]
        assert "replicas_alive=2" in lines[-1]
