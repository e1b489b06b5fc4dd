"""Benchmark driver: one JSON line per north-star metric, headline LAST.

The driver parses the final JSON line (BENCH_r*.json "parsed") and keeps
the whole tail, so this prints:

  1. seq2seq-attention target tokens/sec/chip   (BASELINE.json north star)
  2. CTR wide&deep sparse rows/sec              (BASELINE.json north star)
  3. ResNet-50 train imgs/sec/chip              (headline, parsed)

The seq2seq/CTR lines run `benchmarks/suite.py --only ...` in a
subprocess with a hard timeout so a pathological compile can never
starve the headline metric.

The chip stages run on a TPU only: each fails when
`core.devices.require_chip()` finds none, at the full published sizes,
with MFU computed from the peaks-table row of the device it ran on
(`core.hw.PEAKS`); a failed stage fails the run. The `--*-only` lanes
that pin `jax_platforms=cpu` are count lanes (tokens, hits, pages,
bytes from shapes) and never yield a device time.

vs_baseline sources:
  - resnet50: 84.1 imgs/sec, the reference's best published ResNet-50
    number (2x Xeon Gold 6148 + MKL-DNN, reference:
    benchmark/IntelOptimizedPaddle.md:42-48 — its K40m GPU table has no
    ResNet-50 entry, so the CPU number is the reference's own headline).
  - seq2seq: the reference's closest published RNN training number —
    LSTM hidden 512, batch 64, seqlen 100 at 184 ms/batch (reference:
    benchmark/README.md:115-126, driver benchmark/paddle/rnn/run.sh)
    = 34,783 processed tokens/sec. The reference has no seq2seq bench;
    this is its RNN-throughput analog.
  - ctr_sparse: the reference publishes no sparse-throughput number
    (vs_baseline: null).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SUITE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "benchmarks", "suite.py")


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def run_child(label: str, cmd, timeout_s: int):
    """Run cmd in a subprocess; return (rc, stdout_lines). Never raises;
    the caller collects the rc and fails the run on any that is not 0.

    One chip, one process: the children run one at a time and this
    parent never initialises a backend. On timeout the child gets
    SIGTERM and a 60s grace period before SIGKILL. Stdout printed
    BEFORE a timeout/crash is still recovered and returned — a metric
    the child already produced must never be lost to a late teardown
    hang.

    The child's stderr is INHERITED (not piped) so per-stage progress
    lines stream live — a stalled run shows exactly which stage
    (lowering/compiling/timing) stalled."""
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=None,
                            text=True)
    out, rc = "", -1
    try:
        out, _ = proc.communicate(timeout=timeout_s)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        log(f"{label}: TIMED OUT after {timeout_s}s — terminating gently")
        proc.terminate()
        try:
            out, _ = proc.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            log(f"{label}: did not exit on SIGTERM; killing")
            proc.kill()
            out, _ = proc.communicate()
    if rc != 0:
        log(f"{label}: rc={rc} (see stderr above)")
    return rc, (out or "").splitlines()


def run_suite_only(name: str, timeout_s: int):
    """Run `suite.py --only <name>`; return (rc, its parsed JSON
    records — whatever was printed, even on timeout/failure)."""
    rc, lines = run_child(name, [sys.executable, SUITE, "--only", name],
                          timeout_s)
    recs = []
    for line in lines:
        line = line.strip()
        if line.startswith("{"):
            try:
                recs.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return rc, recs


def emit(metric: str, value, unit: str, vs_baseline, **extra) -> None:
    print(json.dumps({
        "metric": metric, "value": value, "unit": unit,
        "vs_baseline": vs_baseline, **extra}), flush=True)


def bench_resnet() -> None:
    """Time the headline ResNet-50 train step on the chip (224x224,
    batch 256, 50 steps) and emit one JSON record. No chip: fails."""
    from paddle_tpu import models, optim
    from paddle_tpu.core import dtypes, hw as hw_lib
    from paddle_tpu.core.devices import require_chip
    from paddle_tpu.nn.module import ShapeSpec
    from paddle_tpu.ops import losses
    from paddle_tpu.train.state import TrainState
    from paddle_tpu.train.trainer import make_train_step

    dtypes.set_default_policy(dtypes.bf16_compute_policy())

    devices, peaks = require_chip()
    batch, hw = 256, 224
    model = models.resnet.resnet(50, num_classes=1000)
    rng = jax.random.key(0)
    params, mstate = model.init(rng, ShapeSpec((batch, hw, hw, 3)))
    opt = optim.momentum(0.1, mu=0.9)
    state = TrainState.create(params, mstate, opt)

    def loss_fn(logits, labels):
        return jnp.mean(losses.softmax_cross_entropy(logits, labels))

    step = make_train_step(model, loss_fn, opt, donate=True)

    x = jnp.asarray(np.random.RandomState(0).rand(batch, hw, hw, 3), jnp.float32)
    y = jnp.asarray(np.random.RandomState(1).randint(0, 1000, batch))

    # warmup / compile; the scalar fetch waits for the device
    log(f"resnet50: warmup/compile (batch={batch} hw={hw})")
    state, loss, _ = step(state, rng, (x,), (y,))
    float(loss)

    iters = 50
    log(f"resnet50: timing {iters} steps")
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss, _ = step(state, rng, (x,), (y,))
    float(loss)  # forces execution of the whole dependent chain
    dt = time.perf_counter() - t0

    imgs_per_sec = batch * iters / dt
    baseline = 84.1  # reference ResNet-50 imgs/sec (IntelOptimizedPaddle.md)
    # analytic train FLOPs (3x fwd) over the bf16 peak of THIS device
    mfu_pct = round(
        100 * imgs_per_sec * 3 * hw_lib.FWD_GFLOPS["resnet50"] * 1e9
        / (peaks.bf16_tflops * 1e12), 1)
    emit("resnet50_train_imgs_per_sec_per_chip", round(imgs_per_sec, 1),
         "imgs/sec", round(imgs_per_sec / baseline, 2), mfu_pct=mfu_pct,
         device={"platform": devices[0].platform,
                 "kind": devices[0].device_kind, "count": len(devices)})


def bench_serving() -> None:
    """CPU-runnable paged-KV serving stage: synthetic mixed-length
    traffic (60% sharing a system prefix) through ServingServer over a
    page-pool-oversubscribed DecodeEngine with chunked prefill.
    Reports tokens/s, peak pool occupancy, prefix-cache hit rate, and
    the paged-vs-dense admission ratio at EQUAL HBM budget (the ISSUE
    4 acceptance bound: >= 2x). Forces the CPU backend: a count lane,
    run before the chip stages."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from paddle_tpu.models import transformer as T
    from paddle_tpu.serve.engine import DecodeEngine
    from paddle_tpu.serve.server import ServingServer

    cfg = T.TransformerConfig(vocab=256, dim=64, n_layers=2,
                              n_heads=4, attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)
    s_dense, max_len, page = 4, 192, 16
    budget_pages = s_dense * (max_len // page)          # equal HBM
    slots, max_new, n_req = 16, 24, 48
    eng = DecodeEngine(params, cfg, slots=slots, max_len=max_len,
                       page_size=page, num_pages=budget_pages,
                       prefill_chunk=32)
    r = np.random.RandomState(0)
    sys_prefix = r.randint(0, 256, (32,)).astype(np.int32)
    prompts = []
    for i in range(n_req):
        tail = r.randint(0, 256, (int(r.choice([12, 24, 48, 96])),)) \
            .astype(np.int32)
        prompts.append(np.concatenate([sys_prefix, tail])
                       if i % 5 < 3 else tail)         # 60% share
    srv = ServingServer(eng, max_queue=n_req, max_retries=3)
    peak_active = [0]
    srv.on_step.append(lambda s, _: peak_active.__setitem__(
        0, max(peak_active[0],
               sum(rq is not None for rq in s._slot_req))))
    log(f"serving: warmup/compile (S={slots} pages={budget_pages})")
    srv.submit(prompts[0], max_new=2)
    srv.run()
    warm = srv.counters()          # report timed-window DELTAS only:
    peak_active[0] = 0             # the warmup request's tokens and
    # admission must not inflate tokens/s or the hit rate (its cache
    # registrations stay — steady-state warm cache is the scenario)
    log(f"serving: timing {n_req} mixed-length requests")
    t0 = time.perf_counter()
    rids = [srv.submit(p, max_new=max_new) for p in prompts]
    results = srv.run()
    dt = time.perf_counter() - t0
    srv.reconcile()
    c = srv.counters()
    toks = sum(len(results[r].tokens) for r in rids)
    hits = c["prefix_hits"] - warm["prefix_hits"]
    misses = c["prefix_misses"] - warm["prefix_misses"]
    hit_rate = hits / max(hits + misses, 1)
    occupancy = c["peak_pages_in_use"] / budget_pages
    admit_ratio = peak_active[0] / s_dense
    emit("serve_paged_tokens_per_sec", round(toks / dt, 1),
         "tokens/sec", None, prefix_hit_rate=round(hit_rate, 3),
         pool_occupancy_peak=round(occupancy, 3),
         completed=c["completed"] - warm["completed"],
         retried=c["retried"] - warm["retried"],
         prefill_chunks=c["prefill_chunks"] - warm["prefill_chunks"])
    # equal-HBM admission: the dense layout caps at s_dense concurrent
    # requests; the paged pool's observed concurrency over the same
    # page budget must be >= 2x (tests/test_paged_pool.py asserts the
    # same bound via page math)
    emit("serve_paged_admit_ratio_vs_dense", round(admit_ratio, 2),
         "x dense slots", None, dense_slots=s_dense,
         peak_concurrent=peak_active[0],
         meets_2x=bool(admit_ratio >= 2.0))

    # ISSUE 8 overhead gate: A/B the stage with and without the obs
    # stack and report the tokens/s regression — acceptance < 2%.
    # Protocol: one warm server per arm (the jit compile cache is
    # process-wide, so neither arm pays compile; one warm round each
    # fills the prefix caches), then INTERLEAVED timed rounds with a
    # median-vs-median comparison. Interleaving + median is what the
    # measurement needs to resolve 2%: individual warm rounds jitter
    # ~±8% on CPU scheduler noise, which sequential arms or best-of
    # comparisons inherit wholesale.
    import statistics

    from paddle_tpu.obs import FlightRecorder, MetricsRegistry, Tracer

    def mk_server(tracer=None, flight=None, registry=None):
        e = DecodeEngine(params, cfg, slots=slots, max_len=max_len,
                         page_size=page, num_pages=budget_pages,
                         prefill_chunk=32)
        s = ServingServer(e, max_queue=n_req, max_retries=3,
                          tracer=tracer, flight=flight)
        if registry is not None:
            s.bind_metrics(registry)
        s.submit(prompts[0], max_new=2)
        s.run()
        return s

    def timed_round(s):
        t0 = time.perf_counter()
        rr = [s.submit(p, max_new=max_new) for p in prompts]
        res = s.run()
        rdt = time.perf_counter() - t0
        return sum(len(res[i].tokens) for i in rr) / rdt

    log("serving: obs overhead gate (interleaved A/B rounds)")
    registry = MetricsRegistry()
    flight = FlightRecorder()
    tracer = Tracer(sink=flight.note_span)
    srv_base = mk_server()
    srv_obs = mk_server(tracer=tracer, flight=flight,
                        registry=registry)
    timed_round(srv_base)        # warm round each: fill the prefix
    timed_round(srv_obs)         # caches outside the comparison
    base_rounds, obs_rounds = [], []
    for _ in range(5):
        base_rounds.append(timed_round(srv_base))
        obs_rounds.append(timed_round(srv_obs))
    srv_base.reconcile()
    srv_obs.reconcile()
    rate_base = statistics.median(base_rounds)
    rate_obs = statistics.median(obs_rounds)
    overhead = (rate_base - rate_obs) / rate_base * 100.0
    tc = tracer.counters()
    emit("serve_obs_overhead_pct", round(overhead, 2),
         "% tokens/s lost", None,
         tokens_per_sec_uninstrumented=round(rate_base, 1),
         tokens_per_sec_instrumented=round(rate_obs, 1),
         meets_2pct=bool(overhead < 2.0),
         spans_ended=tc["spans_ended"],
         spans_live=tc["spans_live"],
         double_ends=tc["double_ends"],
         obs_snapshot=registry.snapshot()["series"])
    bench_router(cfg, params)
    bench_speculative(cfg, params)
    bench_cold_start()


def bench_router(cfg, params) -> None:
    """Router stage of the CPU serving bench (ISSUE 6): a 3-replica
    fleet under shared-prefix traffic. Three numbers, all
    CPU-runnable and emitted before the chip gate can starve them:

    - aggregate fleet tokens/s through the router's round-robin
      drive;
    - prefix-hit rate with AFFINITY routing vs RANDOM routing over
      identical traffic (the router's whole reason to exist: affinity
      concentrates each hot prefix on one replica's cache);
    - requests-recovered-after-kill: a replica is killed mid-burst
      (testing.faults) and the wall-clock from kill to the last
      redistributed request completing is the recovery latency."""
    from paddle_tpu.serve.engine import DecodeEngine
    from paddle_tpu.serve.policy import RandomRoutingPolicy
    from paddle_tpu.serve.router import ServingRouter
    from paddle_tpu.serve.server import ServingServer
    from paddle_tpu.testing.faults import FaultPlan

    n_rep, slots, page = 3, 4, 16
    r = np.random.RandomState(1)
    families = [r.randint(0, 256, (32,)).astype(np.int32)
                for _ in range(n_rep)]
    prompts = []
    for i in range(30):
        tail = r.randint(0, 256, (8 + 4 * (i % 3),)).astype(np.int32)
        prompts.append(np.concatenate([families[i % n_rep], tail]))

    def mk_fleet(policy=None, wrap=None, tracer=None, flight=None):
        engines = [DecodeEngine(params, cfg, slots=slots, max_len=128,
                                page_size=page)
                   for _ in range(n_rep)]
        if wrap:
            engines = [wrap.get(i, lambda e: e)(engines[i])
                       for i in range(n_rep)]
        # one shared prompt bucket: every replica compiles ONE
        # prefill shape, so warmup actually covers the traffic
        servers = [ServingServer(e, max_queue=64, max_retries=3,
                                 buckets=(48,),
                                 tracer=tracer, flight=flight)
                   for e in engines]
        return ServingRouter(servers, policy=policy, tracer=tracer,
                             flight=flight)

    def drive(router, max_new=16):
        # warm every replica's compiles OUTSIDE the timed window (3
        # unique throwaway prompts spill one to each replica); rates
        # are timed-window deltas, like the single-box stage
        wr = np.random.RandomState(99)
        for _ in range(n_rep):
            router.submit(wr.randint(0, 256, (40,)).astype(np.int32),
                          max_new=2)
        router.run()
        base = router.counters()
        rids = [router.submit(p, max_new=max_new) for p in prompts]
        t0 = time.perf_counter()
        res = router.run()
        dt = time.perf_counter() - t0
        router.reconcile()
        toks = sum(len(res[i].tokens) for i in rids)
        c = router.counters()
        hits = (c.get("fleet_prefix_hits", 0)
                - base.get("fleet_prefix_hits", 0))
        misses = (c.get("fleet_prefix_misses", 0)
                  - base.get("fleet_prefix_misses", 0))
        return toks, dt, hits / max(hits + misses, 1), c

    log(f"router: affinity fleet ({n_rep} replicas)")
    aff_router = mk_fleet()
    toks, dt, aff_rate, _ = drive(aff_router)
    emit("serve_router_tokens_per_sec", round(toks / dt, 1),
         "tokens/sec", None, replicas=n_rep,
         prefix_hit_rate_affinity=round(aff_rate, 3))
    log("router: random-routing control fleet")
    # separate fleet (fresh caches) over IDENTICAL traffic: the only
    # variable is the routing policy
    _, _, rand_rate, _ = drive(mk_fleet(
        policy=RandomRoutingPolicy(seed=0)))
    emit("serve_router_prefix_hit_rate", round(aff_rate, 3),
         "fraction", None, random_routing=round(rand_rate, 3),
         affinity_advantage=round(aff_rate - rand_rate, 3))

    log("router: kill-recovery fleet")
    from paddle_tpu.obs import FlightRecorder, MetricsRegistry, Tracer

    registry = MetricsRegistry()
    flight = FlightRecorder()
    tracer = Tracer(sink=flight.note_span)
    plan = FaultPlan(router_kill_decode_at=8)
    router = mk_fleet(wrap={0: lambda e: plan.wrap_replica_engine(e)},
                      tracer=tracer, flight=flight)
    router.bind_metrics(registry)
    # recovery latency = kill observed -> last redistributed request
    # done, on the replicas' own clock (time.monotonic)
    kill_t = [None]
    orig_death = router._on_replica_death

    def timed_death(rep, exc):
        kill_t[0] = time.monotonic()
        orig_death(rep, exc)

    router._on_replica_death = timed_death
    rids = [router.submit(p, max_new=16) for p in prompts]
    res = router.run()
    router.reconcile()
    c = router.counters()
    recovered = [res[i] for i in rids
                 if res[i].redistributions > 0
                 and res[i].outcome == "completed"]
    latency = (round(max(r.done_at for r in recovered) - kill_t[0], 3)
               if recovered and kill_t[0] is not None else None)
    # the span-side exactly-once audit, against the same chaos run the
    # counter-side invariant checks: every rr id must carry exactly
    # one terminal outcome even through the kill + redistribution
    outcomes = tracer.terminal_outcomes()
    span_once = (all(len(v) == 1 for v in outcomes.values())
                 and tracer.counters()["double_ends"] == 0)
    emit("serve_router_kill_recovery_latency_s", latency,
         "seconds kill->last recovered", None,
         requests_recovered=len(recovered),
         replicas_lost=c["replicas_lost"],
         redistributed=c["redistributed"],
         completed=c["completed"],
         all_exactly_once=bool(
             c["completed"] + c["expired"] + c["shed"] + c["failed"]
             == c["requests"]),
         span_exactly_once=bool(span_once),
         obs_snapshot=registry.snapshot()["series"])


def bench_disagg() -> None:
    """Disaggregated prefill/decode stage (ISSUE 13): p99 inter-token
    DECODE latency, disaggregated fleet vs unified fleet, over
    IDENTICAL traffic — the whole reason to split the roles. On a
    unified replica every admission's chunked prefill runs inside the
    same drive-loop step as the in-flight decodes, so a steady
    arrival stream inflates the decode tail; on a decode-tier replica
    the KV arrives PRE-FILLED by live block migration and inter-token
    gaps are pure decode steps. Acceptance (ISSUE 13): unified p99 /
    disagg p99 >= 1.3x, with bit-identical greedy outputs across
    arms. Forces the CPU backend; `scripts/perf_smoke.sh disagg`
    drives it as `bench.py --disagg-only`."""
    import statistics

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.models import transformer as T
    from paddle_tpu.obs import MetricsRegistry
    from paddle_tpu.serve.engine import DecodeEngine
    from paddle_tpu.serve.router import ServingRouter
    from paddle_tpu.serve.server import ServingServer

    cfg = T.TransformerConfig(vocab=256, dim=64, n_layers=2,
                              n_heads=4, attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)
    max_len, page, chunk, max_new, n_req = 192, 16, 16, 24, 24
    bucket = 96
    r = np.random.RandomState(3)
    # unique mixed-length prompts (64/80/96 tokens, several prefill
    # chunks each): a steady backlog, so the unified arm is ALWAYS
    # interleaving new admissions' chunks with in-flight decodes
    prompts = [r.randint(0, 256, (64 + 16 * (i % 3),)).astype(np.int32)
               for i in range(n_req)]

    class StepClock:
        """Per-replica SELF-TIME: accumulates only the wall time spent
        inside this replica's own `step()`. The router round-robins
        replicas in ONE thread, so raw wall-clock gaps would charge
        every replica for its siblings' serialized turns — and charge
        the decode tier for the synchronous KV transfer, which real
        disaggregated serving overlaps with decode (the source stays
        paused and pinned; the destination engine is not stalled).
        Self-time models independently-running replicas: a unified
        replica is still charged for its OWN prefill chunks — the
        contended resource disaggregation removes — because chunks
        and decodes share its step()."""

        def __init__(self):
            self.accum, self.t0 = 0.0, None

        def wrap(self, srv):
            orig = srv.step

            def step():
                self.t0 = time.perf_counter()
                try:
                    return orig()
                finally:
                    self.accum += time.perf_counter() - self.t0
                    self.t0 = None
            srv.step = step

        def now(self):
            live = (time.perf_counter() - self.t0) if self.t0 else 0.0
            return self.accum + live

    def gap_hook(samples, clock):
        # inter-token decode gap per request, sampled at the on_step
        # hook (fires once per DECODE step) on the replica's own
        # StepClock: the gap between a request's consecutive
        # emissions includes any prefill chunks this replica ran in
        # between — exactly the interference disaggregation removes.
        # The first token is excluded (that gap is TTFT, a different
        # metric).
        last = {}

        def hook(s, _step):
            t = clock.now()
            for rq in s._slot_req:
                if rq is None:
                    continue
                n = len(s._emitted.get(rq.req_id, ()))
                prev = last.get(rq.req_id)
                if prev and n > prev[0] and prev[0] > 0:
                    d = (t - prev[1]) / (n - prev[0])
                    samples.extend([d] * (n - prev[0]))
                if not prev or n != prev[0]:
                    last[rq.req_id] = (n, t)
        return hook, last

    def p99(samples):
        s = sorted(samples)
        return s[int(round(0.99 * (len(s) - 1)))] if s else None

    def mk_arm(roles, slots_by_role):
        engines, servers = [], []
        warm = np.arange(40, dtype=np.int32)
        for role in roles:
            s = slots_by_role[role]
            e = DecodeEngine(params, cfg, slots=s, max_len=max_len,
                             page_size=page, prefill_chunk=chunk,
                             num_pages=s * (max_len // page))
            e.serve([warm], max_new=2, buckets=(bucket,))  # compile
            engines.append(e)
            servers.append(ServingServer(
                e, role=role, max_queue=2 * n_req,
                buckets=(bucket,)))
        return ServingRouter(servers, probe_interval_s=1e9), servers

    def drive(router, sampled_servers):
        samples, lasts = [], []
        for srv in sampled_servers:
            clock = StepClock()
            clock.wrap(srv)
            hook, last = gap_hook(samples, clock)
            srv.on_step.append(hook)
            lasts.append(last)
        # one routed warm request compiles whatever the per-engine
        # warm-up could not reach (the migration bodies); its samples
        # are discarded with the warm-up
        router.submit(np.arange(50, dtype=np.int32), max_new=4)
        router.run()
        samples.clear()
        for last in lasts:
            last.clear()
        rids = [router.submit(p, max_new=max_new) for p in prompts]
        t0 = time.perf_counter()
        res = router.run()
        dt = time.perf_counter() - t0
        router.reconcile()
        toks = {i: tuple(res[i].tokens) for i in rids}
        assert all(res[i].outcome == "completed" for i in rids)
        return toks, samples, dt

    # -- arm A: unified fleet (2 replicas, every replica does both) --
    log("disagg: unified control fleet (2 replicas)")
    uni_router, uni_servers = mk_arm(
        ("unified", "unified"), {"unified": 8})
    uni_toks, uni_samples, uni_dt = drive(uni_router, uni_servers)

    # -- arm B: disaggregated fleet (1 prefill + 1 decode), same
    # total slot budget, identical traffic; gaps sampled ONLY on the
    # decode tier (the prefill replica decodes only cancelled
    # handoffs — the graceful-degrade path, reported separately) ----
    log("disagg: disaggregated fleet (1 prefill + 1 decode)")
    registry = MetricsRegistry()
    dis_router, dis_servers = mk_arm(
        ("prefill", "decode"), {"prefill": 4, "decode": 12})
    dis_router.bind_metrics(registry)
    dis_toks, dis_samples, dis_dt = drive(
        dis_router, [s for s in dis_servers if s.role == "decode"])

    c = dis_router.counters()
    u99, d99 = p99(uni_samples), p99(dis_samples)
    speedup = (round(u99 / d99, 2)
               if u99 and d99 else None)
    emit("serve_disagg_decode_p99_speedup", speedup,
         "x (unified p99 gap / disagg decode-tier p99 gap)", None,
         unified_p99_ms=round(u99 * 1e3, 2) if u99 else None,
         disagg_p99_ms=round(d99 * 1e3, 2) if d99 else None,
         unified_p50_ms=round(
             statistics.median(uni_samples) * 1e3, 2),
         disagg_p50_ms=round(
             statistics.median(dis_samples) * 1e3, 2),
         meets_1_3x=bool(speedup is not None and speedup >= 1.3),
         greedy_bit_identical=bool(uni_toks == dis_toks),
         migrations=c["migrations"],
         migrated_pages=c["fleet_migrated_out_pages"],
         handoffs_cancelled=c["fleet_handoffs_cancelled"],
         migration_retargets=c["migration_retargets"],
         unified_wall_s=round(uni_dt, 2),
         disagg_wall_s=round(dis_dt, 2),
         requests=n_req, max_new=max_new,
         obs_snapshot=registry.snapshot()["series"])


def bench_data() -> None:
    """Zero-copy data-plane stage (ISSUE 18): the same disaggregated
    migration traffic driven twice over REAL socket transport —
    once with KV payloads pickled onto the control frame (the
    PR13/PR14 path), once with payloads scattered into the
    shared-memory arena so the frame carries only a ticket. The
    questions this answers: how many bytes stop crossing the wire
    per migration, how much of the KV still gets memcpy'd at all
    (spanning-part assembly only — adopted pages are zero-copy
    views), what that does to the export+import transfer time, and
    how many per-sweep control RPCs the batched frame absorbs.
    Acceptance (ISSUE 18): wire bytes per migration reduced vs the
    pickle arm, zero data-plane fallbacks, coalesced frame count
    reported, bit-identical greedy outputs across arms. Forces the
    CPU backend; `scripts/fault_smoke.sh data` drives it as
    `bench.py --data-only`."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.models import transformer as T
    from paddle_tpu.obs import MetricsRegistry
    from paddle_tpu.serve.engine import DecodeEngine
    from paddle_tpu.serve.router import ServingRouter
    from paddle_tpu.serve.server import ServingServer
    from paddle_tpu.serve.shm_arena import ShmArena
    from paddle_tpu.serve.transport import (ProcessReplica,
                                            ReplicaClient,
                                            ReplicaTransportServer)

    cfg = T.TransformerConfig(vocab=256, dim=64, n_layers=2,
                              n_heads=4, attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)
    max_len, page, chunk, max_new, n_req = 128, 16, 16, 12, 12
    bucket = 96
    r = np.random.RandomState(7)
    prompts = [r.randint(0, 256, (64 + 16 * (i % 3),)).astype(np.int32)
               for i in range(n_req)]

    def mk_arm(label, arena):
        # 1 prefill + 1 decode, each a real ServingServer behind a
        # socket transport in its own thread, spoken to through
        # ProcessReplica — the exact stack the cross-process fleet
        # runs, minus fork cost. Both arms share the geometry; only
        # `data_plane` differs.
        log(f"data: building {label} arm (1 prefill + 1 decode)")
        reps, transports = [], []
        warm = np.arange(40, dtype=np.int32)
        for role, slots in (("prefill", 4), ("decode", 8)):
            e = DecodeEngine(params, cfg, slots=slots,
                             max_len=max_len, page_size=page,
                             prefill_chunk=chunk,
                             num_pages=slots * (max_len // page))
            e.serve([warm], max_new=2, buckets=(bucket,))  # compile
            srv = ServingServer(e, role=role, max_queue=2 * n_req,
                                buckets=(bucket,), max_retries=2,
                                data_plane=arena)
            ts = ReplicaTransportServer(srv).start()
            transports.append(ts)
            client = ReplicaClient(ts.addr, connect_timeout=2.0,
                                   io_timeout=60.0)
            reps.append(ProcessReplica(client))
        return (ServingRouter(reps, probe_interval_s=1e9), reps,
                transports)

    def instrument(reps, acc):
        # time + wire-byte cost of each migration's export/import
        # pair, measured around the actual RPCs: the router runs in
        # this one thread, so the client byte deltas bracket exactly
        # the payload-bearing frames.
        for rep in reps:
            client = rep._client
            for name in ("export_request", "import_request"):
                orig = getattr(rep, name)

                def wrapped(*a, __orig=orig, __c=client, **k):
                    t0 = time.perf_counter()
                    b0 = __c.bytes_sent + __c.bytes_recv
                    try:
                        return __orig(*a, **k)
                    finally:
                        acc["s"] += time.perf_counter() - t0
                        acc["bytes"] += (__c.bytes_sent
                                         + __c.bytes_recv - b0)
                setattr(rep, name, wrapped)

    def drive(router, reps):
        acc = {"s": 0.0, "bytes": 0}
        # one routed warm request compiles the migration bodies; its
        # transfer cost is excluded from the measured window
        router.submit(np.arange(50, dtype=np.int32), max_new=4)
        router.run()
        instrument(reps, acc)
        rids = [router.submit(p, max_new=max_new) for p in prompts]
        t0 = time.perf_counter()
        res = router.run()
        dt = time.perf_counter() - t0
        router.reconcile()
        toks = {i: tuple(res[i].tokens) for i in rids}
        assert all(res[i].outcome == "completed" for i in rids)
        return toks, acc, dt

    # -- arm A: pickle-over-socket (no arena) ------------------------
    pk_router, pk_reps, pk_ts = mk_arm("pickle", None)
    pk_toks, pk_acc, pk_dt = drive(pk_router, pk_reps)
    pk_mig = pk_router.counters()["migrations"]
    for ts in pk_ts:
        ts.shutdown()

    # -- arm B: shared-memory arena, same traffic --------------------
    arena = ShmArena(seg_size=64 * 1024, n_segs=64)
    registry = MetricsRegistry()
    arena.bind_metrics(registry)
    ar_router, ar_reps, ar_ts = mk_arm("arena", arena)
    ar_router.bind_metrics(registry)
    ar_toks, ar_acc, ar_dt = drive(ar_router, ar_reps)
    c = ar_router.counters()
    ar_mig = c["migrations"]
    a = arena.counters()
    coalesced = sum(rep.rpc_frames_coalesced for rep in ar_reps)
    arena.reconcile()
    assert a["arena_segments_live"] == 0, a
    for ts in ar_ts:
        ts.shutdown()

    pk_per = pk_acc["bytes"] / max(pk_mig, 1)
    ar_per = ar_acc["bytes"] / max(ar_mig, 1)
    reduction = (round(pk_per / ar_per, 2) if ar_per else None)
    emit("serve_data_plane_wire_bytes_per_migration_reduction",
         reduction, "x (pickle wire bytes / arena wire bytes, per "
         "migration export+import pair)", None,
         pickle_wire_bytes_per_migration=int(pk_per),
         arena_wire_bytes_per_migration=int(ar_per),
         pickle_transfer_ms_mean=round(
             pk_acc["s"] / max(pk_mig, 1) * 1e3, 2),
         arena_transfer_ms_mean=round(
             ar_acc["s"] / max(ar_mig, 1) * 1e3, 2),
         arena_bytes_scattered=a["arena_bytes_scattered"],
         arena_bytes_gathered=a["arena_bytes_gathered"],
         arena_bytes_gather_copied=a["arena_bytes_gather_copied"],
         zero_copy_fraction=round(
             1.0 - a["arena_bytes_gather_copied"]
             / max(a["arena_bytes_gathered"], 1), 4),
         rpc_frames_coalesced=coalesced,
         data_plane_fallbacks=c.get("fleet_data_plane_fallbacks", 0),
         greedy_bit_identical=bool(pk_toks == ar_toks),
         migrations=ar_mig, migrations_pickle_arm=pk_mig,
         pickle_wall_s=round(pk_dt, 2),
         arena_wall_s=round(ar_dt, 2),
         requests=n_req, max_new=max_new,
         obs_snapshot=registry.snapshot()["series"])
    arena.close(destroy=True)


def bench_ctr() -> None:
    """Tiered embedding-cache stage (ISSUE 19): the production CTR
    read path driven twice over identical Zipf traffic from
    `testing.traffic` — once pulling every row straight off the
    pserver shards (one RPC round-trip per lookup), once through the
    `TieredEmbedCache` hot-row arena. A `StreamingTrainer` pushes
    sparse deltas between requests in BOTH arms, so the cached arm
    pays its real freshness tax (watermark advances -> stale refills
    under the `max_staleness` bound) rather than benching an
    immutable table. Acceptance (ISSUE 19): cached hot-set lookup
    p99 at least 3x better than uncached, hit/miss/stale counters
    reconciling against the pserver push ledger. Forces the CPU
    backend; `scripts/perf_smoke.sh ctr` drives it as `bench.py
    --ctr-only`."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.native.pserver import PServerGroup
    from paddle_tpu.native.taskqueue import TaskQueue
    from paddle_tpu.obs import MetricsRegistry
    from paddle_tpu.parallel.pserver_client import (PServerClient,
                                                    PServerEmbedding)
    from paddle_tpu.serve.ctr import CtrServer, init_tower
    from paddle_tpu.serve.embed_cache import TieredEmbedCache
    from paddle_tpu.testing.traffic import TrafficShape
    from paddle_tpu.train.online import StreamingTrainer

    VOCAB, DIM, SHARDS = 8192, 64, 8
    N_REQ, WARMUP, BATCH = 1000, 80, 8
    PUSH_EVERY, MAX_STALE = 8, 8
    shape = TrafficShape(vocab=VOCAB, n_families=32, zipf_alpha=1.2,
                         family_len=16, tail_len=0, seed=11)
    rng = np.random.RandomState(5)
    # identical request sequence for both arms: [BATCH, 16] id blocks
    # of Zipf-popular family rows — the hot set the device arena is
    # supposed to capture
    reqs = []
    for _ in range(N_REQ + WARMUP):
        reqs.append(np.stack([shape.sample(rng)[0] for _ in
                              range(BATCH)]).astype(np.int64))

    with PServerGroup(VOCAB, DIM, n_shards=SHARDS,
                      replicated=False) as grp:
        push_client = PServerClient(grp.specs, DIM, trainer_id=0)
        push_client.register()
        push_emb = PServerEmbedding(push_client)
        table = push_emb.init(jax.random.key(3))

        q = TaskQueue(timeout_ms=5000, max_retries=3)
        n_tasks = 2 * (N_REQ + WARMUP) // PUSH_EVERY + 4
        for i in range(n_tasks):
            q.add_task(json.dumps({"seed": i, "batch": 4, "slots": 4,
                                   "vocab": VOCAB}).encode())
        trainer = StreamingTrainer(q, push_emb, table, lr=0.05)

        read_client = PServerClient(grp.specs, DIM, trainer_id=1)
        read_client.register()
        read_emb = PServerEmbedding(read_client)

        # the cached arm: push watermarks ride the push client's ACK
        # frames straight into the ledger (bind_push_feed is
        # same-thread safe here), and the maintenance tick refreshes
        # stale rows between requests so the staleness bound is met
        # ahead of reads — the production background-refresher shape
        registry = MetricsRegistry()
        cache = TieredEmbedCache(read_emb, table, hot_rows=1024,
                                 host_rows=4096,
                                 max_staleness=MAX_STALE,
                                 registry=registry)
        cache.bind_push_feed(push_client)
        tower = init_tower(jax.random.key(1), DIM)
        srv = CtrServer(cache, tower, slots=shape.family_len,
                        max_batch=BATCH, registry=registry)

        def lookup_uncached(flat):
            return read_emb.lookup(None, flat)

        def timed(fn, flat):
            t0 = time.perf_counter()
            fn(flat).block_until_ready()
            return time.perf_counter() - t0

        # INTERLEAVED arms: each request is looked up through BOTH
        # paths back to back (order alternating), so container noise,
        # GC pressure and the push/maintenance cadence land on the two
        # latency distributions identically — sequential arms on a
        # shared box hand whichever ran in the quieter window a free
        # win. The maintenance tick runs off the timed path.
        log(f"ctr: driving interleaved arms "
            f"({N_REQ} requests + {WARMUP} warmup)")
        import gc

        un_lats, ca_lats = [], []
        un_wall = ca_wall = 0.0
        gc.collect()
        gc.disable()
        try:
            for i, ids in enumerate(reqs):
                if i % PUSH_EVERY == 0:
                    trainer.step()
                    cache.refresh_stale()
                flat = ids.reshape(-1)
                if i % 2 == 0:
                    du = timed(lookup_uncached, flat)
                    dc = timed(cache.lookup, flat)
                else:
                    dc = timed(cache.lookup, flat)
                    du = timed(lookup_uncached, flat)
                if i >= WARMUP:
                    un_lats.append(du)
                    ca_lats.append(dc)
                    un_wall += du
                    ca_wall += dc
        finally:
            gc.enable()

        # end-to-end scores through the CtrServer path (cached arm
        # only — shows the full request cost on top of the gather)
        e2e = []
        for ids in reqs[WARMUP:WARMUP + 100]:
            t0 = time.perf_counter()
            srv.score(ids)
            e2e.append(time.perf_counter() - t0)

        # reconcile the cache's freshness ledger against the actual
        # shard push ledger: poll to the tip, then compare versions
        cache.refresh()
        rec = cache.reconcile([p.stats() for p in grp.primaries])

    un_p99 = float(np.percentile(un_lats, 99))
    ca_p99 = float(np.percentile(ca_lats, 99))
    speedup = un_p99 / max(ca_p99, 1e-9)
    c = cache.counters()
    emit("ctr_lookup_p99", round(ca_p99 * 1e6, 1), "us", None,
         uncached_p99_us=round(un_p99 * 1e6, 1),
         p50_cached_us=round(float(np.percentile(ca_lats, 50)) * 1e6, 1),
         p50_uncached_us=round(float(np.percentile(un_lats, 50)) * 1e6, 1),
         speedup_p99=round(speedup, 2),
         meets_3x=bool(speedup >= 3.0),
         qps_cached=round(N_REQ / ca_wall, 1),
         qps_uncached=round(N_REQ / un_wall, 1),
         e2e_score_p99_us=round(float(np.percentile(e2e, 99)) * 1e6, 1),
         requests=N_REQ, batch=BATCH, ids_per_request=int(
             reqs[0].size),
         hits_device=c["hits_device"], hits_host=c["hits_host"],
         misses=c["misses"], stale_refills=c["stale_refills"],
         refresh_rows=c["refresh_rows"],
         pulls=c["pulls"], rows_pulled=c["rows_pulled"],
         trainer_pushes=trainer.stats["tasks_done"],
         reconcile_ok=bool(rec["ok"]),
         watermarks_match_push_ledger=bool(
             rec.get("watermarks_match_push_ledger", False)),
         obs_snapshot_series=len(registry.snapshot()["series"]))
    if speedup < 3.0:
        log(f"ctr: GATE FAILED — cached p99 {ca_p99 * 1e6:.1f}us vs "
            f"uncached {un_p99 * 1e6:.1f}us ({speedup:.2f}x < 3x)")
        sys.exit(1)
    log(f"ctr: cached p99 {ca_p99 * 1e6:.1f}us vs uncached "
        f"{un_p99 * 1e6:.1f}us ({speedup:.2f}x), "
        f"{c['hits_device']} device hits / {c['stale_refills']} "
        f"stale refills, ledger reconciled={rec['ok']}")


def bench_fleet() -> None:
    """Cross-process fleet stage (ISSUE 14): the two latencies that
    decide whether elastic process replicas are worth running — how
    fast the supervisor REACTS to a load spike (burst arrival ->
    first replacement spawned and routable), and how fast the fleet
    RECOVERS a real SIGKILL (kill observed -> last redistributed
    request completed, exactly-once books intact). Real spawned
    processes booted from a PR9 artifact; forces the CPU backend;
    `scripts/perf_smoke.sh fleet` drives it as `bench.py
    --fleet-only`."""
    import tempfile

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.obs import MetricsRegistry
    from paddle_tpu.serve.fleet import (AutoscalePolicy,
                                        FleetSupervisor, ReplicaSpec)
    from paddle_tpu.testing.faults import FaultPlan
    from paddle_tpu.testing.fleet import save_tiny_artifact

    tmp = tempfile.mkdtemp(prefix="fleet_bench_")
    art = os.path.join(tmp, "engine.tar")
    log("fleet: writing engine artifact (replica boots skip compiles)")
    save_tiny_artifact(art, buckets=(16,))
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}

    def mk_spec():
        return ReplicaSpec(
            builder="paddle_tpu.testing.fleet:build_tiny_server",
            kwargs=dict(artifact=art, buckets=(16,), max_retries=1),
            env=env)

    r = np.random.RandomState(7)
    prompts = [r.randint(0, 61, (6 + i % 5,)).astype(np.int32)
               for i in range(10)]

    # -- stage A: scale-out reaction + scale back to floor ---------------
    log("fleet: scale-out reaction (burst into a 1-replica floor)")
    registry = MetricsRegistry()
    sup = FleetSupervisor(
        mk_spec(), min_replicas=1, max_replicas=3,
        policy=AutoscalePolicy(queue_high=1.0, cooldown_sweeps=2,
                               idle_sweeps=4),
        registry=registry)
    sup.start()
    for p in prompts:
        sup.submit(p, max_new=8)
    t0 = time.monotonic()
    before = sup.stats["scale_out_events"]
    sweeps, peak = 0, 1
    reaction_s, reaction_sweeps = None, None
    while True:
        busy = sup.sweep()
        sweeps += 1
        routable = sup.counters()["replicas_routable"]
        peak = max(peak, routable)
        if (reaction_s is None
                and sup.stats["scale_out_events"] > before):
            reaction_s = round(time.monotonic() - t0, 3)
            reaction_sweeps = sweeps
        if not busy:
            break
    completed = sum(1 for res in sup.router.results.values()
                    if res.outcome == "completed")
    back_to_floor = None
    for extra in range(64):        # idle: autoscaler retires + reaps
        sup.sweep()
        if (sup.counters()["replicas_routable"] <= sup.min_replicas
                and not sup._retiring):
            back_to_floor = extra + 1
            break
    sup.reconcile()
    emit("serve_fleet_scaleout_reaction_s", reaction_s,
         "seconds burst->first spawn routable", None,
         reaction_sweeps=reaction_sweeps, peak_routable=peak,
         scale_out_events=sup.stats["scale_out_events"],
         scale_in_events=sup.stats["scale_in_events"],
         back_to_floor_sweeps=back_to_floor,
         completed=completed, requests=len(prompts),
         obs_snapshot=registry.snapshot()["series"])
    sup.shutdown(drain=False)

    # -- stage B: SIGKILL recovery latency -------------------------------
    log("fleet: SIGKILL recovery (3 procs, kill one mid-burst)")
    registry = MetricsRegistry()
    sup = FleetSupervisor(mk_spec(), min_replicas=3, max_replicas=3,
                          registry=registry)
    sup.start()
    FaultPlan(fleet_sigkill_at=4, fleet_sigkill_replica=1).wrap_fleet(sup)
    # recovery latency = kill observed -> last redistributed request
    # done; done_at is stamped child-side on CLOCK_MONOTONIC, which
    # is system-wide on Linux, so it compares with our clock
    kill_t = [None]
    orig_death = sup.router._on_replica_death

    def timed_death(rep, exc):
        if kill_t[0] is None:
            kill_t[0] = time.monotonic()
        orig_death(rep, exc)

    sup.router._on_replica_death = timed_death
    rids = [sup.submit(p, max_new=8) for p in prompts]
    res = sup.run()
    sup.reconcile()
    c = sup.router.counters()
    recovered = [res[i] for i in rids
                 if res[i].redistributions > 0
                 and res[i].outcome == "completed"]
    latency = (round(max(x.done_at for x in recovered) - kill_t[0], 3)
               if recovered and kill_t[0] is not None else None)
    emit("serve_fleet_kill_recovery_latency_s", latency,
         "seconds kill->last recovered", None,
         requests_recovered=len(recovered),
         replicas_lost=c["replicas_lost"],
         redistributed=c["redistributed"],
         completed=c["completed"],
         procs_respawned=sup.stats["spawned"] - 3,
         all_exactly_once=bool(
             c["completed"] + c["expired"] + c["shed"] + c["failed"]
             == c["requests"]),
         obs_snapshot=registry.snapshot()["series"])
    sup.shutdown(drain=False)


def bench_edge() -> None:
    """HTTP front-door stage (ISSUE 17): the SLO numbers that make
    "heavy traffic" a measured claim — sustained QPS with p99
    time-to-first-token and p99 inter-token gap, measured CLIENT-side
    through real sockets by the traffic harness (closed-loop users
    for honest latency, an open-loop ramp for autoscale pressure),
    plus the two edge chaos economics: what a mid-stream client
    disconnect costs (freed slots, zero leaked pages) and what an
    overload burst sheds at the edge while admitted requests hold
    their SLO. `scripts/fault_smoke.sh edge` drives it as `bench.py
    --edge-only`."""
    import tempfile

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.models import transformer as T
    from paddle_tpu.obs import MetricsRegistry
    from paddle_tpu.serve.engine import DecodeEngine
    from paddle_tpu.serve.fleet import (AutoscalePolicy,
                                        FleetSupervisor, ReplicaSpec)
    from paddle_tpu.serve.http_edge import HttpEdge
    from paddle_tpu.serve.router import ServingRouter
    from paddle_tpu.serve.server import ServingServer
    from paddle_tpu.testing.fleet import save_tiny_artifact
    from paddle_tpu.testing.traffic import (TrafficShape, closed_loop,
                                            open_loop, slo_report,
                                            stream_generate)

    shape = TrafficShape(family_len=8, tail_len=3, out_base=3,
                         out_cap=12)
    cfg = T.TransformerConfig(vocab=61, dim=32, n_layers=2,
                              n_heads=4, attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)

    def tiny_router(max_queue):
        eng = DecodeEngine(params, cfg, slots=2, max_len=32,
                           page_size=4)
        srv = ServingServer(eng, max_queue=max_queue, buckets=(16,))
        return ServingRouter([srv]), srv

    # -- stage A: SLO over an autoscaling PROCESS fleet ------------------
    log("edge: SLO stage (HTTP over an autoscaling process fleet)")
    tmp = tempfile.mkdtemp(prefix="edge_bench_")
    art = os.path.join(tmp, "engine.tar")
    save_tiny_artifact(art, buckets=(16,))
    spec = ReplicaSpec(
        builder="paddle_tpu.testing.fleet:build_tiny_server",
        kwargs=dict(artifact=art, buckets=(16,), max_retries=1),
        env={"JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    registry = MetricsRegistry()
    sup = FleetSupervisor(
        spec, min_replicas=1, max_replicas=3,
        policy=AutoscalePolicy(queue_high=1.0, cooldown_sweeps=2,
                               idle_sweeps=8),
        registry=registry)
    sup.start()
    edge = HttpEdge(sup.router, sweep_fn=sup.sweep,
                    submit_fn=sup.submit,
                    drain_fn=lambda why: sup.drain(reason=why),
                    registry=registry).start()
    # warm the child's serving path before the timed window
    stream_generate(edge.addr,
                    shape.sample(np.random.RandomState(0))[0], 2)
    t0 = time.monotonic()
    results = closed_loop(edge.addr, shape, users=4,
                          requests_per_user=3, seed=1)
    # the RAMP: arrival rate steps up until the queue-depth policy
    # must scale out
    results += open_loop(edge.addr, shape,
                         phases=((4.0, 8), (12.0, 12), (30.0, 15)),
                         seed=2)
    wall = time.monotonic() - t0
    rep = slo_report(results, wall)
    edge.drain(reason="bench stage A done")
    drained = edge.wait_drained(timeout_s=30.0)
    c = sup.router.counters()
    emit("edge_sustained_qps", round(rep["sustained_qps"], 2),
         "completed streams/sec (closed users + open-loop ramp)",
         None,
         requests=rep["requests"], completed=rep["completed"],
         shed_429=rep["shed_429"],
         p99_ttft_s=rep["p99_ttft_s"], p99_itg_s=rep["p99_itg_s"],
         p50_ttft_s=rep["p50_ttft_s"], p50_itg_s=rep["p50_itg_s"],
         tokens_streamed=rep["tokens_streamed"],
         scale_out_events=sup.stats["scale_out_events"],
         drained_clean=bool(drained),
         exactly_once=bool(
             c["completed"] + c["expired"] + c["shed"] + c["failed"]
             == c["requests"]),
         obs_snapshot=registry.snapshot()["series"])
    emit("edge_p99_ttft_s", rep["p99_ttft_s"],
         "seconds to first streamed token, p99 client-side", None,
         p50=rep["p50_ttft_s"],
         server_side_p99=edge._ttft_hist.quantile(0.99)
         if edge._ttft_hist is not None else None)
    emit("edge_p99_itg_s", rep["p99_itg_s"],
         "seconds between streamed tokens, p99 client-side", None,
         p50=rep["p50_itg_s"],
         server_side_p99=edge._itg_hist.quantile(0.99)
         if edge._itg_hist is not None else None)
    edge.close()
    sup.shutdown(drain=False)

    # -- stage B: disconnect chaos economics -----------------------------
    log("edge: disconnect stage (clients vanish mid-stream)")
    registry = MetricsRegistry()
    router, srv = tiny_router(max_queue=16)
    edge = HttpEdge(router, registry=registry).start()
    stream_generate(edge.addr,
                    shape.sample(np.random.RandomState(3))[0], 2)
    aborted = full = 0
    for i in range(8):
        rng = np.random.RandomState(100 + i)
        prompt, _ = shape.sample(rng)
        if i % 2 == 0:
            r = stream_generate(edge.addr, prompt, 12,
                                abort_after_tokens=2)
            aborted += int(r.aborted)
        else:
            r = stream_generate(edge.addr, prompt, 6)
            full += int(r.outcome == "completed")
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        if (edge.counters()["active_streams"] == 0
                and not router.sweep()):
            break
        time.sleep(0.02)
    router.run()
    router.reconcile()
    srv.reconcile()
    # pages still referenced by anything OTHER than the prefix cache
    # (cache-only pages are refcount 1 and evictable on demand — by
    # design they stay resident after release; a pinned page that is
    # NOT evictable is the actual leak)
    pool = srv.engine.pool
    pages_leaked = (0 if pool is None
                    else pool.pages_in_use - pool.evictable())
    emit("edge_disconnect_cancels", edge.counters()
         ["disconnect_cancels"],
         "mid-stream disconnects cancelled (slot+pages freed)", None,
         aborted_clients=aborted, completed_streams=full,
         pages_leaked=int(pages_leaked),
         pages_cached=int(0 if pool is None else pool.evictable()),
         reconcile_clean=True,
         obs_snapshot=registry.snapshot()["series"])
    edge.close()

    # -- stage C: overload burst sheds at the edge -----------------------
    log("edge: overload stage (open-loop burst beyond capacity)")
    registry = MetricsRegistry()
    router, srv = tiny_router(max_queue=4)
    depth = [0]

    def sweep_recording_depth():
        depth[0] = max(depth[0], len(srv.queue))
        return router.sweep()

    edge = HttpEdge(router, sweep_fn=sweep_recording_depth,
                    registry=registry).start()
    stream_generate(edge.addr,
                    shape.sample(np.random.RandomState(4))[0], 2)
    t0 = time.monotonic()
    burst = open_loop(edge.addr, shape, phases=((250.0, 50),), seed=5)
    wall = time.monotonic() - t0
    rep = slo_report(burst, wall)
    router.run()
    router.reconcile()
    srv.reconcile()
    emit("edge_overload_shed_429", rep["shed_429"],
         "requests shed at the edge during a 250qps burst", None,
         admitted_completed=rep["completed"],
         admitted_p99_ttft_s=rep["p99_ttft_s"],
         max_queue=4, max_queue_depth_observed=depth[0],
         queue_bounded=bool(depth[0] <= 4),
         obs_snapshot=registry.snapshot()["series"])
    edge.close()


def bench_cluster() -> None:
    """Multi-host control-plane stage (ISSUE 16): the two latencies
    that price lease-based membership — how fast a host death
    PROPAGATES (agent SIGKILL -> the supervisor observes the eviction
    view change; bounded below by the lease TTL), and how fast the
    reformed fleet produces its first recovered COMPLETION (kill ->
    first redistributed request done). Three real agent processes
    with distinct fake host-ids on one box, topology resolved through
    membership, real clocks with a short TTL; `scripts/fault_smoke.sh
    cluster` drives it as `bench.py --cluster-only`."""
    import tempfile

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.cluster.agent import AgentProcess, AgentSpec
    from paddle_tpu.cluster.membership import (MembershipClient,
                                               MembershipServer,
                                               MembershipService)
    from paddle_tpu.obs import MetricsRegistry
    from paddle_tpu.serve.fleet import FleetSupervisor, ReplicaSpec
    from paddle_tpu.testing.fleet import save_tiny_artifact

    tmp = tempfile.mkdtemp(prefix="cluster_bench_")
    art = os.path.join(tmp, "engine.tar")
    log("cluster: writing engine artifact (replica boots skip compiles)")
    save_tiny_artifact(art, buckets=(16,))
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    rspec = ReplicaSpec(
        builder="paddle_tpu.testing.fleet:build_tiny_server",
        kwargs=dict(artifact=art, buckets=(16,), max_retries=1),
        env=env)

    ttl_s = 2.0
    registry = MetricsRegistry()
    svc = MembershipService(default_ttl_s=ttl_s)
    svc.bind_metrics(registry)          # membership_* counter source
    server = MembershipServer(svc).start()
    log("cluster: booting 3 per-host agents (1 replica each)")
    agents = {}
    sup = None
    try:
        for i in range(3):
            host = f"host-{i}"
            agents[host] = AgentProcess(AgentSpec(
                host_id=host, replica_spec=rspec,
                membership_addr=server.addr, ttl_s=ttl_s,
                renew_interval_s=0.05, report_every=10)).start()
        for a in agents.values():
            a.wait_ready(180.0)
        sup = FleetSupervisor(
            rspec, min_replicas=1, max_replicas=3,
            membership=MembershipClient(server.addr),
            registry=registry)
        sup.start()

        r = np.random.RandomState(7)
        prompts = [r.randint(0, 61, (6 + i % 5,)).astype(np.int32)
                   for i in range(10)]
        rids = [sup.submit(p, max_new=8) for p in prompts]
        log("cluster: SIGKILL host-1's agent mid-burst")
        kill_t = None
        eviction_seen_t = None
        sweeps = 0
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            busy = sup.sweep()
            sweeps += 1
            if sweeps == 4 and kill_t is None:
                victim = agents["host-1"]
                victim.kill()
                victim.proc.join(10.0)
                kill_t = time.monotonic()
            if (kill_t is not None and eviction_seen_t is None
                    and sup.stats["hosts_lost"] >= 1):
                eviction_seen_t = time.monotonic()
            # keep sweeping past the drain until the lease expiry has
            # propagated (that is the latency being measured)
            if not busy and (kill_t is None
                             or eviction_seen_t is not None):
                break
            time.sleep(0.01)
        sup.reconcile()
        res = sup.router.results
        c = sup.router.counters()
        recovered = [res[i] for i in rids
                     if i in res and res[i].redistributions > 0
                     and res[i].outcome == "completed"]
        first_completion = (
            round(min(x.done_at for x in recovered) - kill_t, 3)
            if recovered and kill_t is not None else None)
        view_prop = (round(eviction_seen_t - kill_t, 3)
                     if eviction_seen_t is not None else None)
        snapshot = registry.snapshot()["series"]
        mc = svc.counters()
        emit("cluster_view_propagation_s", view_prop,
             "seconds agent SIGKILL->eviction view change observed",
             None, lease_ttl_s=ttl_s, sweeps=sweeps,
             epoch=mc["epoch"], evictions=mc["evictions"],
             hosts_live=mc["hosts_live"],
             agent_renews=mc.get("agent_renews"),
             obs_snapshot=snapshot)
        emit("cluster_kill_first_completion_s", first_completion,
             "seconds agent SIGKILL->first recovered completion",
             None, requests_recovered=len(recovered),
             replicas_lost=c["replicas_lost"],
             redistributed=c["redistributed"],
             completed=c["completed"],
             hosts_live_after=sup.counters()["hosts_live"],
             all_exactly_once=bool(
                 c["completed"] + c["expired"] + c["shed"] + c["failed"]
                 == c["requests"]))
    finally:
        if sup is not None:
            sup.shutdown(drain=False)
        for a in agents.values():
            a.stop()
        server.shutdown()


def bench_elastic() -> None:
    """Elastic gang-training stage (ISSUE 15): the three numbers that
    decide whether ZeRO + gang supervision is worth running — the
    optimizer-state memory win per replica (the point of ZeRO), the
    step-time overhead of the sharded update vs the replicated arm
    (same psum_scatter, so it should be noise), and the wall-clock
    cost of a real host loss (SIGKILL observed -> first step COMPLETED
    by the reformed gang, which prices boot + gloo rejoin + reshard
    restore + recompile together). Forces the CPU backend;
    `scripts/fault_smoke.sh elastic` drives it as `bench.py
    --elastic-only`."""
    import tempfile

    os.environ["JAX_PLATFORMS"] = "cpu"
    prev = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in prev:
        os.environ["XLA_FLAGS"] = (
            prev + " --xla_force_host_platform_device_count=8").strip()
    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu import nn
    from paddle_tpu.core.mesh import (MeshConfig, batch_sharding,
                                      build_mesh)
    from paddle_tpu.obs import MetricsRegistry
    from paddle_tpu.optim import optimizers as O
    from paddle_tpu.parallel import (make_zero_train_step,
                                     opt_state_bytes_per_replica)
    from paddle_tpu.parallel.launch import GangSupervisor
    from paddle_tpu.parallel.sharding import replicated
    from paddle_tpu.testing.faults import FaultPlan
    from paddle_tpu.train.state import TrainState

    # -- stage A: ZeRO memory win + sharded-update overhead -------------
    log("elastic: ZeRO opt bytes/replica + step overhead (in-process)")
    mesh = build_mesh(MeshConfig(data=8))
    model = nn.Sequential([nn.Dense(256, name="fc1", activation="relu"),
                           nn.Dense(256, name="fc2", activation="relu"),
                           nn.Dense(16, name="out")])
    loss_fn = lambda out, y: jnp.mean((out - y) ** 2)
    opt = O.adam(1e-3)
    params, mstate = model.init(jax.random.key(0),
                                jnp.zeros((8, 64), jnp.float32))
    sz = TrainState.create_zero(params, mstate, opt, mesh)
    sb = TrainState.create_zero(params, mstate, opt, mesh)
    sb = sb._replace(opt_state=jax.tree.map(
        lambda v: jax.device_put(np.asarray(v), replicated(mesh)),
        sb.opt_state))
    bytes_zero = opt_state_bytes_per_replica(sz.opt_state)
    bytes_repl = opt_state_bytes_per_replica(sb.opt_state)
    emit("train_zero_opt_state_bytes_per_replica", bytes_zero,
         "bytes (max over replicas)", None,
         replicated_bytes=bytes_repl,
         shrink_x=round(bytes_repl / max(bytes_zero, 1), 2),
         data_shards=8)

    r = np.random.RandomState(0)
    x = jax.device_put(r.randn(64, 64).astype(np.float32),
                       batch_sharding(mesh))
    y = jax.device_put(r.randn(64, 16).astype(np.float32),
                       batch_sharding(mesh))
    rng = jax.random.key(7)
    step_z = make_zero_train_step(model, loss_fn, opt, mesh,
                                  donate=False)
    step_b = make_zero_train_step(model, loss_fn, opt, mesh,
                                  donate=False, zero_update=False)
    iters = 30

    def timed(step, state):
        state, l, _ = step(state, rng, x, y)          # warmup compile
        jax.block_until_ready(l)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, l, _ = step(state, rng, x, y)
        jax.block_until_ready(l)
        return (time.perf_counter() - t0) / iters * 1e3

    ms_zero = timed(step_z, sz)
    ms_repl = timed(step_b, sb)
    emit("train_zero_step_overhead_pct",
         round((ms_zero - ms_repl) / ms_repl * 100.0, 2),
         "% vs replicated-update arm (same psum_scatter)", None,
         zero_step_ms=round(ms_zero, 3),
         replicated_step_ms=round(ms_repl, 3), iters=iters)

    # -- stage B: SIGKILL -> reformed-gang first step --------------------
    log("elastic: gang kill->resume latency (2 real procs, SIGKILL)")
    tmp = tempfile.mkdtemp(prefix="elastic_bench_")
    registry = MetricsRegistry()
    sup = GangSupervisor(
        "paddle_tpu.testing.gang:build_tiny_job", {},
        workdir=os.path.join(tmp, "work"),
        checkpoint_dir=os.path.join(tmp, "ckpt"),
        num_processes=2, total_steps=8, checkpoint_every=2, seed=0,
        grace_s=3.0)
    sup.bind_metrics(registry)
    plan = FaultPlan(gang_kill_step_at=2, gang_kill_rank=1)
    plan.wrap_gang(sup)
    kill_t = [None]
    inner_tick = sup._tick

    def tick():
        before = plan.count("gangkill")
        inner_tick()
        if kill_t[0] is None and plan.count("gangkill") > before:
            kill_t[0] = time.time()

    sup._tick = tick
    out = sup.run(deadline_s=300.0)
    res = sorted(out["results"], key=lambda q: q["rank"])[0]
    # heartbeats are written on EndIteration with wall time, so the
    # reformed gang's FIRST heartbeat stamps "first step completed"
    hb = json.load(open(os.path.join(tmp, "work", "hb_1_0.json")))
    latency = (round(hb["t"] - kill_t[0], 3)
               if kill_t[0] is not None else None)
    c = sup.counters()
    emit("train_gang_kill_resume_latency_s", latency,
         "seconds SIGKILL->reformed gang's first step done", None,
         restored_step=res["restored_step"],
         final_step=res["final_step"],
         reforms=c["reforms"], members_lost=c["members_lost"],
         reshard_restores=res["counters"].get("reshard_restores"),
         exactly_once=bool(
             res["steps"] == list(range(res["restored_step"], 8))),
         obs_snapshot=registry.snapshot()["series"])


def bench_speculative(cfg, params) -> None:
    """Speculative-decoding stage (ISSUE 9): plain vs speculative
    serving over IDENTICAL repetitive traffic — the n-gram proposer's
    win case (templated replies, structured extraction: the model
    re-emits spans it has already produced), which is what the stage
    measures: the ceiling the one-launch verify step buys when drafts
    mostly land. The stage uses its own small-vocab model whose
    greedy output actually settles into re-emitted spans (the
    bench_serving cfg's output is near-novel, which the proposer
    correctly degrades to ~0-draft rounds on — that arm would measure
    proposer overhead, not speculation). Protocol mirrors the obs
    overhead gate (one warm server per arm, then interleaved timed
    rounds, median vs median) because the 1.3x acceptance bound has
    to be resolved through the same ±8% CPU scheduler jitter. Greedy
    token parity between the arms is asserted on a dedicated untimed
    round; acceptance rate comes from timed-window DELTA counters so
    warmup drafts don't dilute it."""
    import statistics

    from paddle_tpu.models import transformer as T
    from paddle_tpu.serve.engine import DecodeEngine
    from paddle_tpu.serve.policy import SchedulerPolicy
    from paddle_tpu.serve.server import ServingServer

    del cfg, params                  # stage-local model (see above)
    cfg = T.TransformerConfig(vocab=64, dim=64, n_layers=2,
                              n_heads=4, attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)
    slots, page, max_len, max_new = 4, 16, 160, 48
    policy = SchedulerPolicy()
    policy.spec_draft_max = 8
    r = np.random.RandomState(7)
    base = r.randint(0, 64, (12,)).astype(np.int32)
    prompts = []
    for i in range(12):
        period = np.concatenate([base] * 4)
        prompts.append(period[: 24 + 12 * (i % 3)].copy())

    def mk(spec):
        e = DecodeEngine(params, cfg, slots=slots, max_len=max_len,
                         page_size=page,
                         num_pages=slots * (max_len // page),
                         prefill_chunk=32, policy=policy)
        s = ServingServer(e, max_queue=64, max_retries=3,
                          buckets=(64,), speculative=spec)
        s.submit(prompts[0], max_new=2)
        s.run()
        return s

    def round_results(s):
        t0 = time.perf_counter()
        rr = [s.submit(p, max_new=max_new) for p in prompts]
        res = s.run()
        dt = time.perf_counter() - t0
        toks = [list(res[i].tokens) for i in rr]
        return sum(len(t) for t in toks) / dt, toks

    log("speculative: warmup/compile (plain + spec arms)")
    srv_plain = mk(False)
    srv_spec = mk(True)
    log("speculative: parity round (untimed)")
    _, toks_plain = round_results(srv_plain)
    _, toks_spec = round_results(srv_spec)
    parity = toks_plain == toks_spec
    c0 = srv_spec.counters()
    log("speculative: interleaved timed rounds")
    plain_rounds, spec_rounds = [], []
    for _ in range(5):
        plain_rounds.append(round_results(srv_plain)[0])
        spec_rounds.append(round_results(srv_spec)[0])
    c1 = srv_spec.counters()
    srv_plain.reconcile()
    srv_spec.reconcile()
    rate_plain = statistics.median(plain_rounds)
    rate_spec = statistics.median(spec_rounds)
    proposed = c1["draft_proposed"] - c0["draft_proposed"]
    accepted = c1["draft_accepted"] - c0["draft_accepted"]
    emit("serve_spec_tokens_per_sec", round(rate_spec, 1),
         "tokens/sec", None,
         tokens_per_sec_plain=round(rate_plain, 1),
         speedup_vs_plain=round(rate_spec / rate_plain, 2),
         meets_1_3x=bool(rate_spec >= 1.3 * rate_plain),
         greedy_parity=bool(parity),
         draft_max=policy.spec_draft_max,
         acceptance_rate=round(accepted / max(proposed, 1), 3),
         draft_proposed=proposed, draft_accepted=accepted,
         spec_rounds=c1["spec_rounds"] - c0["spec_rounds"],
         spec_rolled_back=(c1["spec_rolled_back"]
                           - c0["spec_rolled_back"]))


def bench_kernels() -> None:
    """Kernel-portfolio stage (ISSUE 12), CPU-runnable, pre-chip-gate.

    Two A/Bs, both recorded through a MetricsRegistry snapshot like the
    cold-start stage:

    1. int8-vs-float serving at EQUAL HBM BYTES: two engines over the
       same byte budget — the float pool gets its pages, the int8 pool
       gets `bytes_f / bytes_8` times as many (s8 data + f32 scale per
       position/head vs plain f32). Oversubscribed traffic measures the
       2x-concurrency claim as an admit-ratio A/B (peak concurrent int8
       / peak concurrent float) plus tokens/s for each arm. The int8
       arm pins `ragged_impl` to the jnp path explicitly: interpret-
       mode Pallas on CPU measures the emulator, not the kernel — the
       kernel's win is a chip-gate question; THIS stage measures what
       half-the-bytes buys in admitted users at identical math
       (tests/test_ragged_int8.py owns kernel-vs-oracle bit-parity).
    2. overlap-vs-naive sharded matmul on the 8-virtual-device mesh:
       per-step wall time of the bidirectional gather ring and the
       reduce-scatter ring vs their all_gather/psum_scatter naive arms,
       plus the weight-streaming blocked form — medians over
       interleaved rounds, parity vs the jnp oracle asserted on every
       arm. Virtual devices share one host, so ring-vs-naive deltas
       here are schedule-shape numbers, not interconnect overlap — the
       chip ratio is the campaign's question; this stage proves the
       arms run and records the baseline curve.
    """
    # 8 virtual CPU devices for the matmul stage: XLA reads the flag at
    # BACKEND INIT, which hasn't happened yet in this fresh child (jax
    # is imported, but no computation has run)
    prev = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in prev:
        os.environ["XLA_FLAGS"] = (
            prev + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    import statistics

    from paddle_tpu.models import transformer as T
    from paddle_tpu.obs import MetricsRegistry
    from paddle_tpu.serve.engine import DecodeEngine
    from paddle_tpu.serve.server import ServingServer

    registry = MetricsRegistry()

    # -- stage 1: int8-vs-float admit ratio at equal HBM bytes ---------
    cfg_f = T.TransformerConfig(vocab=64, dim=64, n_layers=2,
                                n_heads=4, attn_impl="dense")
    cfg_8 = T.TransformerConfig(vocab=64, dim=64, n_layers=2,
                                n_heads=4, attn_impl="dense",
                                kv_cache_dtype="int8")
    params = T.init_params(jax.random.key(0), cfg_f)
    s_dense, max_len, page = 3, 128, 16
    slots, max_new, n_req = 24, 16, 36
    pages_f = s_dense * (max_len // page)
    dh = cfg_f.dim // cfg_f.n_heads
    # per (position, kv-head): f32 data vs s8 data + one f32 scale
    bytes_f, bytes_8 = dh * 4, dh * 1 + 4
    pages_8 = pages_f * bytes_f // bytes_8
    r = np.random.RandomState(0)
    prompts = [r.randint(0, 64, (int(r.choice([12, 24, 48])),))
               .astype(np.int32) for _ in range(n_req)]

    def serve_arm(label, cfg, num_pages, ragged_impl):
        eng = DecodeEngine(params, cfg, slots=slots, max_len=max_len,
                           page_size=page, num_pages=num_pages,
                           prefill_chunk=32, ragged_impl=ragged_impl)
        srv = ServingServer(eng, max_queue=n_req, max_retries=3)
        peak = [0]
        srv.on_step.append(lambda s, _: peak.__setitem__(
            0, max(peak[0], sum(rq is not None for rq in s._slot_req))))
        log(f"kernels: {label} arm warmup/compile "
            f"(pages={num_pages})")
        srv.submit(prompts[0], max_new=2)
        srv.run()
        peak[0] = 0
        log(f"kernels: {label} arm timing {n_req} requests")
        t0 = time.perf_counter()
        rids = [srv.submit(p, max_new=max_new) for p in prompts]
        res = srv.run()
        dt = time.perf_counter() - t0
        srv.reconcile()
        toks = sum(len(res[i].tokens) for i in rids)
        return toks / dt, peak[0], [list(res[i].tokens) for i in rids]

    rate_f, peak_f, toks_f = serve_arm("float", cfg_f, pages_f, None)
    rate_8, peak_8, toks_8 = serve_arm("int8", cfg_8, pages_8, "jnp")
    concurrency_ratio = peak_8 / max(peak_f, 1)
    registry.gauge("kernels_serve_tokens_per_sec_float").set(rate_f)
    registry.gauge("kernels_serve_tokens_per_sec_int8").set(rate_8)
    registry.gauge("kernels_admit_ratio_int8_vs_float").set(
        concurrency_ratio)

    # -- stage 2: overlap-vs-naive sharded matmul ----------------------
    from jax.sharding import Mesh

    from paddle_tpu.parallel import blocked_matmul as BM

    p = min(8, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:p]), ("x",))
    dim = 512                      # divisible by every p <= 8
    rm = np.random.RandomState(1)
    x = jnp.asarray(rm.standard_normal((dim, dim)), jnp.float32)
    w = jnp.asarray(rm.standard_normal((dim, dim)), jnp.float32)
    ref = BM.matmul_reference(x, w)
    # arms built OUTSIDE any loop (fresh jit wrappers in a timing loop
    # are the GL004 recompile hazard the lint gate rejects)
    arms = {
        "gather_overlap": jax.jit(BM.collective_matmul(
            mesh, axis="x", mode="gather", overlap=True)),
        "gather_naive": jax.jit(BM.collective_matmul(
            mesh, axis="x", mode="gather", overlap=False)),
        "reduce_overlap": jax.jit(BM.collective_matmul(
            mesh, axis="x", mode="reduce", overlap=True)),
        "reduce_naive": jax.jit(BM.collective_matmul(
            mesh, axis="x", mode="reduce", overlap=False)),
        "stream": jax.jit(BM.blocked_matmul(mesh, axis="x")),
    }
    log(f"kernels: matmul arms warmup/compile (p={p}, {dim}^3)")
    max_err = 0.0
    for name, fn in arms.items():
        out = fn(x, w).block_until_ready()      # compile + parity
        max_err = max(max_err, float(jnp.max(jnp.abs(out - ref))))
    log("kernels: matmul interleaved timed rounds")
    samples = {name: [] for name in arms}
    for _ in range(7):
        for name, fn in arms.items():
            t0 = time.perf_counter()
            fn(x, w).block_until_ready()
            samples[name].append(time.perf_counter() - t0)
    step_ms = {name: statistics.median(ts) * 1000
               for name, ts in samples.items()}
    for name, ms in step_ms.items():
        registry.gauge(f"kernels_matmul_{name}_ms").set(ms)

    series = registry.snapshot()["series"]
    emit("kernels_int8_vs_float_serving", round(concurrency_ratio, 2),
         "x float concurrency", None,
         tokens_per_sec_float=round(rate_f, 1),
         tokens_per_sec_int8=round(rate_8, 1),
         peak_concurrent_float=peak_f, peak_concurrent_int8=peak_8,
         pages_float=pages_f, pages_int8=pages_8,
         equal_hbm_bytes=pages_f * bytes_f >= pages_8 * bytes_8,
         dense_slots=s_dense,
         meets_2x=bool(concurrency_ratio >= 2.0),
         completed_float=len(toks_f), completed_int8=len(toks_8))
    emit("kernels_matmul_overlap_vs_naive",
         round(step_ms["reduce_naive"] / step_ms["reduce_overlap"], 2),
         "x naive step time (reduce ring)", None,
         mesh_devices=p, dim=dim, max_abs_err_vs_oracle=max_err,
         gather_speedup=round(
             step_ms["gather_naive"] / step_ms["gather_overlap"], 2),
         **{f"step_ms_{k}": round(v, 2) for k, v in step_ms.items()},
         obs_snapshot=series)


def _cold_start_engine():
    """The tiny paged engine BOTH the cold-start parent (artifact
    export) and its children (measurement) build. The configs must be
    byte-identical: the artifact manifest hashes params and geometry,
    and any drift here turns the artifact arm into a silent jit
    fallback (artifact_fallbacks > 0 in the emitted record)."""
    from paddle_tpu.models import transformer as T
    from paddle_tpu.serve.engine import DecodeEngine

    cfg = T.TransformerConfig(vocab=61, dim=32, n_layers=2, n_heads=4,
                              attn_impl="dense")
    params = T.init_params(jax.random.key(0), cfg)
    eng = DecodeEngine(params, cfg, slots=2, max_len=64, page_size=16,
                       num_pages=8)
    return eng, (32,)


def bench_cold_start_child(mode: str, workdir: str) -> None:
    """One fresh-process cold-start sample (`--cold-start-child`).

    Measures process-side time from function entry to the first
    completed reply of a tiny serve — the fleet-restart cost the
    persistent compile cache and the engine artifact exist to cut.
    Modes: `off` (no cache), `cold` (cache enabled, empty dir),
    `warm` (same dir, disk hits), `artifact` (cache + exported-engine
    bundle loaded at server boot). One JSON line on stdout carries the
    timing plus the proof counters: compile-cache hit/miss deltas and
    artifact_loads/artifact_fallbacks."""
    t0 = time.perf_counter()
    from paddle_tpu import compilation_cache
    from paddle_tpu.obs.registry import MetricsRegistry
    from paddle_tpu.serve.server import ServingServer

    if mode != "off":
        compilation_cache.enable(os.path.join(workdir, "xla-cache"))
    eng, buckets = _cold_start_engine()
    art = os.path.join(workdir, "engine.tar")
    srv = ServingServer(eng, max_queue=8, buckets=buckets,
                        artifact_path=art if mode == "artifact" else None)
    prompt = (np.arange(1, 9, dtype=np.int32) * 7) % 61
    rid = srv.submit(prompt, max_new=4)
    res = srv.run()
    dt = time.perf_counter() - t0
    toks = [int(t) for t in res[rid].tokens]
    # export through the obs registry (the path cli._obs_stack wires
    # for live servers) and read back from the snapshot so the emitted
    # number is the registry's, not a parallel bookkeeping path
    reg = MetricsRegistry()
    reg.gauge("cold_start_s").set(dt)
    reg.register_source("compile_cache", compilation_cache.counters)
    series = {r["name"]: r["value"] for r in reg.snapshot()["series"]}
    c = srv.counters()
    print(json.dumps({
        "mode": mode,
        "cold_start_s": round(dt, 3),
        "tokens": toks,
        "registry_cold_start_s": series.get("cold_start_s"),
        "compile_cache_hits": int(series.get("compile_cache_hits", 0)),
        "compile_cache_misses": int(series.get("compile_cache_misses",
                                               0)),
        "artifact_loads": c.get("artifact_loads", 0),
        "artifact_fallbacks": c.get("artifact_fallbacks", 0),
    }), flush=True)


def bench_cold_start() -> None:
    """Fleet cold-start stage (ROADMAP item 3): fresh processes, four
    arms — cache off / cold cache / warm cache / warm cache + engine
    artifact. The artifact arm runs TWICE and reports the second run:
    exported-program HLO differs from the jit path's, so its first run
    pays its own XLA compiles into the cache exactly like a cold
    replica would; the measured run is the steady-state fleet restart.
    Gate (ISSUE acceptance): warm OR artifact >= 2x faster than off,
    with warm cache hits > 0 and artifact_fallbacks == 0."""
    os.environ["JAX_PLATFORMS"] = "cpu"   # children inherit; the
    jax.config.update("jax_platforms", "cpu")  # stage never claims a chip
    import shutil
    import tempfile

    workdir = tempfile.mkdtemp(prefix="ptpu-coldstart-")
    me = os.path.abspath(__file__)

    def child(mode):
        _, lines = run_child(
            f"cold-start child ({mode})",
            [sys.executable, me, "--cold-start-child", mode, workdir],
            300)
        for line in lines:
            line = line.strip()
            if line.startswith("{"):
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("mode") == mode:
                    return rec
        return None

    try:
        log("cold-start: baseline child (cache off)")
        off = child("off")
        log("cold-start: cold-cache child (populates persistent cache)")
        cold = child("cold")
        log("cold-start: warm-cache child (measures disk-hit restart)")
        warm = child("warm")
        log("cold-start: exporting engine artifact bundle")
        from paddle_tpu.serve.artifact import save_engine_artifact
        eng, buckets = _cold_start_engine()
        save_engine_artifact(eng, os.path.join(workdir, "engine.tar"),
                             buckets=buckets)
        log("cold-start: artifact child 1/2 (warms exported-program "
            "cache entries)")
        child("artifact")
        log("cold-start: artifact child 2/2 (measured)")
        art = child("artifact")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if not (off and cold and warm and art):
        emit("serve_cold_start_s", None, "seconds", None,
             error="cold-start child produced no record (see stderr)")
        return
    speed_warm = off["cold_start_s"] / max(warm["cold_start_s"], 1e-9)
    speed_art = off["cold_start_s"] / max(art["cold_start_s"], 1e-9)
    emit("serve_cold_start_s",
         min(warm["cold_start_s"], art["cold_start_s"]), "seconds",
         None,
         cold_start_off_s=off["cold_start_s"],
         cold_start_cold_s=cold["cold_start_s"],
         cold_start_warm_s=warm["cold_start_s"],
         cold_start_artifact_s=art["cold_start_s"],
         speedup_warm_vs_off=round(speed_warm, 2),
         speedup_artifact_vs_off=round(speed_art, 2),
         meets_2x=bool(speed_warm >= 2.0 or speed_art >= 2.0),
         warm_cache_hits=warm["compile_cache_hits"],
         warm_cache_misses=warm["compile_cache_misses"],
         cold_cache_misses=cold["compile_cache_misses"],
         artifact_loads=art["artifact_loads"],
         artifact_fallbacks=art["artifact_fallbacks"],
         greedy_parity=bool(off["tokens"] == art["tokens"]
                            and off["tokens"] == warm["tokens"]))


def main():
    # ONE process per chip: this parent never initialises a backend —
    # it would hold the chip and lock every child stage out of it.
    # Children run strictly one after another.
    failed = []

    def stage(label, cmd, timeout_s):
        rc, lines = run_child(label, cmd, timeout_s)
        if rc != 0:
            failed.append(label)
        return [l.strip() for l in lines if l.strip().startswith("{")]

    me = os.path.abspath(__file__)
    # CPU count lanes first: the children pin the cpu backend before
    # any computation, so they never open the chip
    for line in stage("serving (cpu child)",
                      [sys.executable, me, "--serving-only"], 600):
        print(line, flush=True)
    # kernel-portfolio stage: the child sets the 8-virtual-device XLA
    # flag for its own fresh backend, which this parent's env must not
    # inherit
    for line in stage("kernels (cpu child)",
                      [sys.executable, me, "--kernels-only"], 600):
        print(line, flush=True)

    # chip stages: full sizes, each fails without a TPU
    def suite(name, timeout_s):
        rc, recs = run_suite_only(name, timeout_s)
        if rc != 0:
            failed.append(f"suite --only {name}")
        return recs

    for rec in suite("seq2seq", 1150):
        if rec.get("bench") == "seq2seq_attn":
            v = rec["tgt_tokens_per_sec"]
            extra = ({"mfu_pct": rec["mfu_pct"]} if "mfu_pct" in rec
                     else {})
            # reference RNN analog: 64 seqs * 100 tokens / 0.184 s
            emit("seq2seq_attn_tgt_tokens_per_sec_per_chip", v,
                 "tokens/sec", round(v / 34783.0, 2), **extra)

    for rec in suite("ctr", 1150):
        if rec.get("bench") == "ctr_sparse":
            emit("ctr_sparse_rows_per_sec", rec["rows_per_sec"],
                 "rows/sec", None)

    # KV-cache autoregressive decode (the serving-latency analog of the
    # reference's SequenceGenerator; no published reference number).
    # Greedy only here
    for rec in suite("decode_greedy", 550):
        if rec.get("bench") == "decode":
            emit("decode_new_tokens_per_sec", rec["new_tokens_per_sec"],
                 "tokens/sec", None)

    # headline last (the driver parses the final line): batch 256 or
    # nothing — no retry, no smaller batch under the same metric name
    for line in stage("resnet child", [sys.executable, me, "--resnet-only"],
                      800):
        print(line, flush=True)

    if failed:
        log(f"FAILED stages: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--resnet-only":
        bench_resnet()
    elif len(sys.argv) > 1 and sys.argv[1] == "--serving-only":
        bench_serving()
    elif len(sys.argv) > 1 and sys.argv[1] == "--kernels-only":
        bench_kernels()
    elif len(sys.argv) > 1 and sys.argv[1] == "--disagg-only":
        bench_disagg()
    elif len(sys.argv) > 1 and sys.argv[1] == "--data-only":
        bench_data()
    elif len(sys.argv) > 1 and sys.argv[1] == "--fleet-only":
        bench_fleet()
    elif len(sys.argv) > 1 and sys.argv[1] == "--cluster-only":
        bench_cluster()
    elif len(sys.argv) > 1 and sys.argv[1] == "--edge-only":
        bench_edge()
    elif len(sys.argv) > 1 and sys.argv[1] == "--elastic-only":
        bench_elastic()
    elif len(sys.argv) > 1 and sys.argv[1] == "--ctr-only":
        bench_ctr()
    elif len(sys.argv) > 1 and sys.argv[1] == "--cold-start-only":
        bench_cold_start()
    elif len(sys.argv) > 1 and sys.argv[1] == "--cold-start-child":
        bench_cold_start_child(sys.argv[2], sys.argv[3])
    else:
        main()
