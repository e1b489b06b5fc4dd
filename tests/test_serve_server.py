"""Serving reliability layer: the chaos harness.

Every degradation path of `serve.ServingServer` — shedding, deadline
expiry mid-decode, slot retry after transient faults, graceful drain,
the native-path circuit breaker — is driven deterministically through
`testing.faults.FaultPlan.wrap_engine` + `ManualClock` (no sleeps, no
wall-clock races), the same prove-it-with-fault-injection discipline
`tests/test_resilience.py` established for training. The capstone is
the mixed-burst chaos test: overflow + deadline storm + native-bridge
fault in one run, with the reconciliation invariant (every submitted
request ends in EXACTLY ONE of completed/expired/shed/failed, counters
== request log, pool keeps serving afterward) asserted end-to-end.
"""

import json

import jax
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.serve.engine import DecodeEngine
from paddle_tpu.serve.server import (CircuitBreaker, QueueFullError,
                                     ServingServer)
from paddle_tpu.testing.faults import (FaultPlan, ManualClock,
                                       garbage_prompts)

pytestmark = pytest.mark.faults

CFG = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                          attn_impl="dense")


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.key(0), CFG)


# engines are MODULE-SCOPED and shared across tests/servers: an engine
# is stateless between runs (init_state resets the pool) and its jitted
# prefill/step compiles dominate test cost — sharing amortizes them.
# Fault wrappers (plan.wrap_engine) proxy a shared engine without
# touching it, so even the chaos tests reuse the same compiles.
@pytest.fixture(scope="module")
def eng2(params):
    return DecodeEngine(params, CFG, slots=2, max_len=32)


@pytest.fixture(scope="module")
def eng1(params):
    return DecodeEngine(params, CFG, slots=1, max_len=32)


def ref_tokens(params, prompt, max_new):
    out = T.generate(params, CFG, jax.numpy.asarray(prompt)[None, :],
                     steps=max_new)
    return [int(t) for t in np.asarray(out[0, len(prompt):])]


def prompts_rng(n, lens, seed=0):
    r = np.random.RandomState(seed)
    return [r.randint(0, 61, (l,)).astype(np.int32)
            for l, _ in zip(list(lens) * n, range(n))]


class TestAdmission:
    @pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
    def test_completed_requests_match_generate(self, params, eng2):
        """The reliability layer must not perturb the math: a greedy
        request served through the scheduler equals its solo
        generate() decode, like the raw engine pool."""
        srv = ServingServer(eng2, max_queue=8)
        ps = prompts_rng(4, [5, 9, 3, 7], seed=1)
        ids = [srv.submit(p, max_new=10) for p in ps]
        res = srv.run()
        srv.reconcile()
        for rid, p in zip(ids, ps):
            assert res[rid].outcome == "completed"
            assert res[rid].tokens == ref_tokens(params, p, 10)

    def test_queue_overflow_sheds_with_documented_error(self, eng1):
        """max_queue bound: the incoming request, when cheapest to
        retry, is shed with QueueFullError and a 'load shed' result."""
        srv = ServingServer(eng1, max_queue=2)
        srv.submit(prompts_rng(1, [9], seed=2)[0], max_new=2)
        srv.submit(prompts_rng(1, [7], seed=3)[0], max_new=2)
        cheap = prompts_rng(1, [3], seed=4)[0]
        with pytest.raises(QueueFullError, match="queue full"):
            srv.submit(cheap, max_new=2)
        shed = [r for r in srv.results.values() if r.outcome == "shed"]
        assert len(shed) == 1 and "load shed" in shed[0].error
        res = srv.run()
        srv.reconcile()
        assert srv.stats.shed == 1 and srv.stats.completed == 2

    def test_overflow_displaces_cheapest_queued(self, eng1):
        """An expensive incoming request displaces the cheapest QUEUED
        one instead of being dropped itself — shed cost is bounded by
        the smallest prompt in the queue."""
        srv = ServingServer(eng1, max_queue=2)
        srv.submit(prompts_rng(1, [9], seed=2)[0], max_new=2)
        small = srv.submit(prompts_rng(1, [3], seed=4)[0], max_new=2)
        big = srv.submit(prompts_rng(1, [12], seed=5)[0], max_new=2)
        res = srv.run()
        srv.reconcile()
        assert res[small].outcome == "shed"
        assert "displaced" in res[small].error
        assert res[big].outcome == "completed"

    def test_garbage_prompts_rejected_pool_survives(self, params, eng1):
        """Every canonical malformed input fails synchronously with
        ValueError, is ledgered FAILED, and the pool serves real
        traffic afterwards untouched."""
        srv = ServingServer(eng1,
                            max_queue=8, buckets=(8,))
        for name, g in garbage_prompts(61, 8).items():
            with pytest.raises(ValueError):
                srv.submit(g, max_new=2)
        bad_max_new = prompts_rng(1, [4], seed=6)[0]
        with pytest.raises(ValueError, match="max_new"):
            srv.submit(bad_max_new, max_new=0)
        ok = srv.submit(bad_max_new, max_new=3)
        res = srv.run()
        srv.reconcile()
        assert res[ok].outcome == "completed"
        assert res[ok].tokens == ref_tokens(params, bad_max_new, 3)
        assert srv.stats.failed == len(garbage_prompts(61, 8)) + 1
        assert srv.stats.prefills == 1   # no garbage reached the chip


class TestDeadlines:
    def test_expiry_mid_decode_frees_slot_for_queued(self, params, eng1):
        """THE deadline contract: an expired request stops
        mid-generation (partial tokens kept) and its slot serves a
        queued request to the exact greedy completion."""
        clk = ManualClock()
        srv = ServingServer(eng1, max_queue=8,
                            clock=clk)
        ps = prompts_rng(2, [5, 9], seed=7)
        doomed = srv.submit(ps[0], max_new=50, deadline_ms=5)
        patient = srv.submit(ps[1], max_new=4, deadline_ms=None)
        srv.on_step.append(lambda s, step: clk.advance(0.002))
        res = srv.run()
        srv.reconcile()
        assert res[doomed].outcome == "expired"
        assert 0 < len(res[doomed].tokens) < 50       # stopped mid-run
        assert "mid-generation" in res[doomed].error
        assert res[patient].outcome == "completed"    # slot reused
        assert res[patient].tokens == ref_tokens(params, ps[1], 4)

    def test_queued_expiry_costs_no_prefill(self, eng1):
        """A request that dies waiting never reaches the chip."""
        clk = ManualClock()
        srv = ServingServer(eng1, max_queue=8,
                            clock=clk)
        ps = prompts_rng(2, [5, 6], seed=8)
        runner = srv.submit(ps[0], max_new=8)
        doa = srv.submit(ps[1], max_new=8, deadline_ms=4)
        srv.on_step.append(lambda s, step: clk.advance(0.003))
        res = srv.run()
        srv.reconcile()
        assert res[runner].outcome == "completed"
        assert res[doa].outcome == "expired"
        assert res[doa].tokens == [] and "never admitted" in res[doa].error
        assert srv.stats.prefills == 1

    def test_default_deadline_applies(self, eng1):
        clk = ManualClock()
        srv = ServingServer(eng1, max_queue=8,
                            clock=clk, default_deadline_ms=5)
        rid = srv.submit(prompts_rng(1, [5], seed=9)[0], max_new=50)
        srv.on_step.append(lambda s, step: clk.advance(0.004))
        res = srv.run()
        srv.reconcile()
        assert res[rid].outcome == "expired"


class TestRetry:
    def test_decode_fault_requeues_and_completes(self, params, eng2):
        """A transient decode fault evicts in-flight requests to the
        queue; the retry serves them to the exact same tokens (pure
        state + greedy => the fault is invisible in the output)."""
        plan = FaultPlan(serve_decode_error_at=1)
        srv = ServingServer(plan.wrap_engine(eng2),
                            max_queue=8, max_retries=1)
        ps = prompts_rng(2, [5, 9], seed=10)
        ids = [srv.submit(p, max_new=6) for p in ps]
        res = srv.run()
        srv.reconcile()
        assert plan.count("sdecode") == 1
        for rid, p in zip(ids, ps):
            assert res[rid].outcome == "completed"
            assert res[rid].retries == 1
            assert res[rid].tokens == ref_tokens(params, p, 6)
        assert srv.stats.retried == 2

    def test_prefill_fault_requeues_only_that_request(self, eng2):
        plan = FaultPlan(serve_prefill_error_at=0)
        srv = ServingServer(plan.wrap_engine(eng2),
                            max_queue=8, max_retries=1)
        ps = prompts_rng(2, [5, 7], seed=11)
        ids = [srv.submit(p, max_new=4) for p in ps]
        res = srv.run()
        srv.reconcile()
        assert plan.count("sprefill") == 1
        assert res[ids[0]].outcome == "completed"
        assert res[ids[0]].retries == 1
        assert res[ids[1]].outcome == "completed"
        assert res[ids[1]].retries == 0       # bystander untouched
        assert srv.stats.retried == 1

    def test_retry_budget_exhaustion_fails(self, eng2):
        """A fault that keeps firing ends the request FAILED after
        max_retries requeues — never an infinite loop, never silent."""
        plan = FaultPlan(serve_error_first_n=10)
        srv = ServingServer(plan.wrap_engine(eng2),
                            max_queue=8, max_retries=2)
        rid = srv.submit(prompts_rng(1, [5], seed=12)[0], max_new=4)
        res = srv.run()
        srv.reconcile()
        assert res[rid].outcome == "failed"
        assert "retry budget exhausted" in res[rid].error
        assert srv.stats.retried == 2 and srv.stats.failed == 1


class TestDrain:
    def test_drain_finishes_in_flight_sheds_queue(self, params, eng2,
                                                  tmp_path):
        """Graceful drain: in-flight requests COMPLETE, queued ones
        shed, nothing new admitted, report persisted."""
        report = tmp_path / "drain.json"
        srv = ServingServer(eng2, max_queue=8,
                            drain_report_path=str(report))
        ps = prompts_rng(5, [5, 9, 3, 7, 4], seed=13)
        ids = [srv.submit(p, max_new=6) for p in ps]
        srv.on_step.append(
            lambda s, step: s.drain(reason="test") if step == 2
            else None)
        res = srv.run()
        srv.reconcile()
        outcomes = [res[i].outcome for i in ids]
        assert outcomes.count("completed") == 2      # the 2 in-flight
        assert outcomes.count("shed") == 3           # the queue
        assert all("drain" in res[i].error for i in ids
                   if res[i].outcome == "shed")
        # in-flight finished to full length — drain is graceful
        for rid, p in zip(ids[:2], ps[:2]):
            assert res[rid].tokens == ref_tokens(params, p, 6)
        rep = json.loads(report.read_text())
        assert rep["reason"] == "test"
        assert rep["counters"] == srv.counters()
        assert len(rep["requests"]) == 5

    def test_drain_grace_expires_stragglers(self, eng2):
        clk = ManualClock()
        srv = ServingServer(eng2, max_queue=8, clock=clk,
                            drain_grace_s=0.01)
        ids = [srv.submit(p, max_new=30)
               for p in prompts_rng(2, [5, 6], seed=14)]

        def hook(s, step):
            if step == 2:
                s.drain(reason="grace")
            clk.advance(0.004)

        srv.on_step.append(hook)
        res = srv.run()
        srv.reconcile()
        for rid in ids:
            assert res[rid].outcome == "expired"
            assert 0 < len(res[rid].tokens) < 30
            assert "drain grace" in res[rid].error

    def test_sigterm_triggers_drain(self, eng1):
        """install_signal_handlers: SIGTERM mid-run = drain, mirroring
        train/resilience.py's preemption semantics."""
        import os
        import signal

        srv = ServingServer(eng1, max_queue=8,
                            install_signal_handlers=True)
        ids = [srv.submit(p, max_new=5)
               for p in prompts_rng(3, [5, 6, 4], seed=15)]
        srv.on_step.append(
            lambda s, step: os.kill(os.getpid(), signal.SIGTERM)
            if step == 1 else None)
        res = srv.run()
        srv.reconcile()
        assert res[ids[0]].outcome == "completed"
        assert all(res[i].outcome == "shed" for i in ids[1:])
        assert "signal" in srv.drain_report["reason"]

    def test_submit_while_draining_is_shed(self, eng2):
        srv = ServingServer(eng2, max_queue=8)
        srv.drain(reason="pre")
        with pytest.raises(QueueFullError, match="draining"):
            srv.submit(prompts_rng(1, [4], seed=16)[0], max_new=2)
        srv.run()
        srv.reconcile()
        assert srv.stats.shed == 1


class TestCircuitBreaker:
    def test_trips_to_fallback_and_recovers(self, params, eng2):
        """Repeated native faults open the breaker -> pool falls back
        to the pure-JAX engine and completes everything; after the
        cooldown the half-open probe routes traffic back through the
        healed native side and closes the breaker."""
        clk = ManualClock()
        plan = FaultPlan(serve_error_first_n=2)
        native = plan.wrap_engine(eng2, clock=clk)
        breaker = CircuitBreaker(failure_threshold=2, cooldown_s=1.0,
                                 clock=clk)
        srv = ServingServer(eng2, native_backend=native,
                            breaker=breaker, max_queue=16, clock=clk,
                            max_retries=3)
        ps = prompts_rng(5, [5, 9, 3, 7, 4], seed=17)
        ids = [srv.submit(p, max_new=4) for p in ps[:3]]
        res = srv.run()
        srv.reconcile()
        assert breaker.state == "open" and breaker.trips == 1
        assert plan.count("nativeburst") == 2
        for rid, p in zip(ids, ps[:3]):
            assert res[rid].outcome == "completed"
            assert res[rid].backend == "jax"          # the fallback
            assert res[rid].tokens == ref_tokens(params, p, 4)
        clk.advance(2.0)                              # past cooldown
        ids2 = [srv.submit(p, max_new=4) for p in ps[3:]]
        res2 = srv.run()
        srv.reconcile()
        assert breaker.state == "closed"              # probe passed
        for rid, p in zip(ids2, ps[3:]):
            assert res2[rid].outcome == "completed"
            assert res2[rid].backend == "native"      # recovered
            assert res2[rid].tokens == ref_tokens(params, p, 4)

    def test_failed_probe_reopens(self):
        clk = ManualClock()
        br = CircuitBreaker(failure_threshold=2, cooldown_s=1.0,
                            clock=clk)
        br.record_failure()
        assert br.state == "closed"
        br.record_failure()
        assert br.state == "open" and not br.allow()
        clk.advance(1.5)
        assert br.state == "half-open" and br.allow()
        br.record_failure()                    # probe fails
        assert br.state == "open" and not br.allow()
        clk.advance(1.5)
        assert br.allow()
        br.record_success()                    # probe passes
        assert br.state == "closed" and br.trips == 1


class TestChaos:
    @pytest.mark.slow
    def test_mixed_burst_reconciles_and_keeps_serving(self, params, eng2):
        """The acceptance-criteria chaos run: one burst mixing queue
        overflow, a deadline storm (injected slot stall burning the
        clock), garbage prompts, and a native-bridge fault burst that
        trips the circuit breaker. Asserts: no request is silently
        dropped (every submitted request ends in exactly one terminal
        outcome), outcome counters reconcile with the request log, and
        the pool serves a clean follow-up wave afterwards."""
        clk = ManualClock()
        # native side: fails its first 2 calls -> breaker (threshold
        # 2) opens; fallback side: decode step 4 stalls 50ms -> every
        # tight deadline in flight or queued burns
        plan_native = FaultPlan(serve_error_first_n=2)
        plan_fb = FaultPlan(serve_stall_at=4, serve_stall_s=0.05)
        native = plan_native.wrap_engine(eng2, clock=clk)
        fallback = plan_fb.wrap_engine(eng2, clock=clk)
        breaker = CircuitBreaker(failure_threshold=2, cooldown_s=30.0,
                                 clock=clk)
        srv = ServingServer(fallback, native_backend=native,
                            breaker=breaker, max_queue=4,
                            max_retries=2, clock=clk, buckets=(16,))

        ps = prompts_rng(8, [5, 9, 3, 7, 4, 6, 8, 5], seed=18)
        submitted, shed_sync, failed_sync = [], 0, 0
        # tight deadlines on half the burst: the stall expires them
        deadlines = [None, 20, None, 20, 20, None, 20, None]
        for p, dl in zip(ps, deadlines):
            try:
                submitted.append(srv.submit(p, max_new=6,
                                            deadline_ms=dl))
            except QueueFullError:
                shed_sync += 1
        # garbage rides the same burst
        for g in garbage_prompts(61, 16).values():
            try:
                srv.submit(g, max_new=4)
            except ValueError:
                failed_sync += 1
        assert shed_sync >= 1                 # overflow actually hit
        assert failed_sync == 6               # all garbage rejected

        res = srv.run()
        srv.reconcile()                       # THE invariant
        # the three fault classes all actually fired
        assert plan_native.count("nativeburst") == 2
        assert plan_fb.count("stall") == 1
        assert breaker.trips == 1
        # every submitted request has exactly one terminal outcome
        assert len(res) == srv.stats.requests == 8 + 6
        c = srv.counters()
        assert c["completed"] >= 1
        assert c["expired"] >= 1              # the deadline storm
        assert c["shed"] >= 1                 # the overflow
        assert c["failed"] == 6               # the garbage
        assert c["retried"] >= 1              # the native fault path
        assert (c["completed"] + c["expired"] + c["shed"]
                + c["failed"]) == c["requests"]
        # completed survivors still match the exact greedy decode
        for rid, p in zip(submitted, ps):
            if rid in res and res[rid].outcome == "completed":
                assert res[rid].tokens == ref_tokens(params, p, 6)

        # the engine keeps serving: a clean follow-up wave completes
        ps2 = prompts_rng(3, [4, 6, 5], seed=19)
        ids2 = [srv.submit(p, max_new=4) for p in ps2]
        res2 = srv.run()
        srv.reconcile()
        for rid, p in zip(ids2, ps2):
            assert res2[rid].outcome == "completed"
            assert res2[rid].tokens == ref_tokens(params, p, 4)


class TestCliServeReliable:
    def test_cli_reliability_flags(self, params, tmp_path):
        """`serve --max-queue` routes through ServingServer: ordered
        per-request lines + the outcomes trailer."""
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
        prompts.write_text("1 2 3 4 5\n7 8 9\n")
        out = tmp_path / "out.txt"
        assert main(["serve", "--config", str(cfg_file),
                     "--prompts", str(prompts), "--max-new", "4",
                     "--max-queue", "4",
                     "--output", str(out)]) == 0
        lines = out.read_text().strip().splitlines()
        assert len(lines) == 3                # 2 requests + trailer
        for line, p in zip(lines, ([1, 2, 3, 4, 5], [7, 8, 9])):
            got = [int(t) for t in line.split()]
            assert got == ref_tokens(params,
                                     np.asarray(p, np.int32), 4)
        assert lines[-1].startswith("# outcomes ")
        assert "completed=2" in lines[-1]
