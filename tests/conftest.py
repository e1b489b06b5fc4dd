"""Test config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's in-process multi-node simulation strategy
(reference: trainer/tests/test_TrainerOnePass.cpp:127 runs real pservers on
localhost) — here multi-chip sharding is validated on XLA's host platform
with 8 virtual devices. Must set flags before jax initializes.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8"
    ).strip()
# tests place their own compile caches (tmp dirs); a directory handed
# in from outside would win over them (compilation_cache.enable) and be
# read and overwritten by the corrupt-entry tests
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402

# float64 available for numeric gradient checks (the fluid op_test.py
# approach: numeric grads in double precision); float32 remains the default
# dtype for params since initializers request it explicitly.
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--budget-guard", type=float, default=None, metavar="SECONDS",
        help="tier-1 duration budget guard: FAIL the session when any "
             "non-slow test's call phase exceeds this many seconds "
             "(the suite runs near its 870s cap — a single creeping "
             "test eats everyone's headroom). Without the flag the "
             "guard still REPORTS offenders over the default "
             "threshold (10s) in the terminal summary.")


#: report-only threshold when --budget-guard is not passed
_BUDGET_DEFAULT_S = 10.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 fast gate "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers", "faults: fault-injection resilience suite "
        "(testing.faults) — fast and CPU-only, runs IN tier-1; the "
        "marker exists so `-m faults` can run recovery paths alone")
    config.addinivalue_line(
        "markers", "pserver: parameter-server fault-tolerance suite "
        "(native.pserver leases/replication/failover) — a subset of "
        "the faults lane, runs IN tier-1; `-m pserver` (or "
        "`scripts/fault_smoke.sh pserver`) runs it alone")
    config.addinivalue_line(
        "markers", "perf: capacity counts a CPU run can give (e.g. "
        "the paged-pool 2x admission bound; never a time or a rate) "
        "— runs IN tier-1; `-m perf` runs it alone")
    config.addinivalue_line(
        "markers", "analysis: static-analysis + compile-discipline "
        "suite (graftlint/locklint rule fixtures, the repo --check "
        "gate, RecompileGuard steady-state regressions) — fast and "
        "CPU-only, runs IN tier-1; `-m analysis` (or "
        "`scripts/lint_smoke.sh`) runs it alone")
    config.addinivalue_line(
        "markers", "obs: unified observability suite (obs registry/"
        "trace/flight, span exactly-once chaos audit, exporter "
        "schema) — fast and CPU-only, runs IN tier-1; `-m obs` (or "
        "`scripts/obs_smoke.sh`) runs it alone")
    config.addinivalue_line(
        "markers", "router: multi-replica serving-fleet suite "
        "(serve.router affinity/failover/redistribution chaos) — a "
        "subset of the faults lane, runs IN tier-1; `-m router` (or "
        "`scripts/fault_smoke.sh router`) runs it alone")
    config.addinivalue_line(
        "markers", "pallas: interpret-mode Pallas kernel parity suite "
        "(ragged paged-attention vs the jnp oracle, bit-identity "
        "under jit) — fast cases run IN tier-1, the heavy ragged "
        "sweeps are additionally marked slow; `-m pallas` runs the "
        "lane alone")
    config.addinivalue_line(
        "markers", "kernels: sharded-matmul primitive suite "
        "(parallel.blocked_matmul ring/stream forms vs the jnp oracle "
        "across shard counts, pipeline tensor-parallel opt-in parity) "
        "— fast cases run IN tier-1; `-m kernels` runs the lane "
        "alone")
    config.addinivalue_line(
        "markers", "speculative: speculative-decoding suite (n-gram "
        "draft proposer, verify/commit/rollback, greedy parity vs "
        "baseline under transfer_guard) — fast, runs IN tier-1; "
        "`-m speculative` runs it alone")
    config.addinivalue_line(
        "markers", "disagg: disaggregated prefill/decode fleet suite "
        "(tiered routing, live KV-block migration, prefix seeding, "
        "migration chaos) — fast, runs IN tier-1; `-m disagg` (or "
        "`scripts/fault_smoke.sh disagg`) runs it alone")
    config.addinivalue_line(
        "markers", "fleet: cross-process serving-fleet suite "
        "(serve.fleet/serve.transport: socket-transport replicas, "
        "SIGKILL chaos, elastic autoscaling, rolling upgrades, the "
        "orphan watchdog) — runs IN tier-1; `-m fleet` (or "
        "`scripts/fault_smoke.sh fleet`, which runs "
        "-m 'fleet and faults') runs it alone")
    config.addinivalue_line(
        "markers", "edge: HTTP front-door suite (serve.http_edge + "
        "testing.traffic: chunked streaming, disconnect cancellation, "
        "overload backpressure, slow-loris hardening, graceful drain) "
        "— fast cases run IN tier-1, the live-load SIGKILL chaos case "
        "is heavyweight/slow; `-m edge` (or `scripts/fault_smoke.sh "
        "edge`, which runs -m 'edge and faults') runs the lane alone")
    config.addinivalue_line(
        "markers", "heavyweight: the ONE deliberate chaos heavyweight "
        "a suite may carry — exempt from the tier-1 budget guard "
        "(real process boots + a mid-burst SIGKILL cannot fit the "
        "per-test threshold; everything else must)")
    config.addinivalue_line(
        "markers", "aot: AOT serving-artifact + persistent "
        "compile-cache suite (engine bundle round-trip parity, "
        "manifest-mismatch fallback, corrupt-entry miss, subprocess "
        "cache-warm restart) — fast, runs IN tier-1; `-m aot` runs "
        "it alone")
    config.addinivalue_line(
        "markers", "cluster: multi-host control-plane suite "
        "(cluster.membership lease/epoch fencing, per-host agents, "
        "standby failover, membership-resolved topology) — fast "
        "cases run IN tier-1, the real-process chaos case is "
        "heavyweight/slow; `-m cluster` (or `scripts/fault_smoke.sh "
        "cluster`) runs the lane alone")
    config.addinivalue_line(
        "markers", "elastic: elastic gang-training suite (ZeRO-"
        "sharded optimizer state, reshard-on-restore checkpoints, "
        "gang supervision chaos) — fast cases run IN tier-1, the "
        "real-process chaos cases are heavyweight/slow; `-m elastic` "
        "(or `scripts/fault_smoke.sh elastic`) runs the lane alone")
    config.addinivalue_line(
        "markers", "data: zero-copy data-plane suite "
        "(serve.shm_arena: shared-memory KV arena, orphan "
        "reclamation, stale-ticket refusal, pickle-fallback parity, "
        "batched control RPC) — fast cases run IN tier-1, the "
        "real-process SIGKILL chaos cases are heavyweight/slow; "
        "`-m data` (or `scripts/fault_smoke.sh data`, which runs "
        "-m 'data and faults') runs the lane alone")
    config.addinivalue_line(
        "markers", "locks: graftlock concurrency suite (locklint "
        "LK002-LK005 rule fixtures, the LockOrderGuard runtime "
        "sanitizer, chaos lanes re-run under the guard) — fast and "
        "CPU-only, runs IN tier-1; `-m locks` (or "
        "`scripts/lint_smoke.sh`, which adds the `--check` gate and "
        "one fault-lane run under the guard) runs it alone")
    config.addinivalue_line(
        "markers", "ctr: tiered embedding-cache + CTR serving suite "
        "(serve.embed_cache staleness bounds / batched miss-fill / "
        "zero-recompile gather, train.online streaming exactly-once, "
        "shard-failover + reform-mid-stream chaos) — fast cases run "
        "IN tier-1; `-m ctr` (or `scripts/fault_smoke.sh ctr`, "
        "which runs -m 'ctr and faults') runs the lane alone")


def pytest_runtest_logreport(report):
    """Collect call-phase durations of tests that are NOT marked slow
    for the tier-1 budget guard (the slow lane is excluded from the
    870s gate, so only fast-lane creep matters)."""
    if report.when != "call":
        return
    keywords = getattr(report, "keywords", {})
    # `heavyweight` is the budget guard's one sanctioned exemption:
    # the chaos test that boots real replica processes and SIGKILLs
    # one mid-burst cannot meet the per-test threshold
    if "slow" in keywords or "heavyweight" in keywords:
        return
    # stash on the report's session via terminal summary access below
    _budget_records.append((report.nodeid, report.duration))


_budget_records = []


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """The tier-1 budget guard (docs: ROADMAP 'near the 870s cap'):
    list every non-slow test whose call phase ran past the threshold.
    Report-only by default; `--budget-guard S` makes offenders FAIL
    the session so the cap regression is caught at review time, and
    `scripts/lint_smoke.sh` documents the invocation."""
    limit = config.getoption("--budget-guard")
    threshold = _BUDGET_DEFAULT_S if limit is None else limit
    offenders = sorted((d, nid) for nid, d in _budget_records
                       if d > threshold)
    if not offenders:
        return
    terminalreporter.section("tier-1 budget guard")
    for d, nid in offenders:
        terminalreporter.write_line(
            f"  {d:7.1f}s  {nid}   (non-slow test over "
            f"{threshold:.0f}s — mark it `slow` or shrink it)")
    if limit is not None:
        terminalreporter.write_line(
            f"budget guard FAILING the session: {len(offenders)} "
            f"non-slow test(s) over {limit:.0f}s")


def pytest_sessionfinish(session, exitstatus):
    # computed from the raw records, not the summary stash: hook
    # ordering between this and the terminal reporter's own
    # sessionfinish is not guaranteed
    limit = session.config.getoption("--budget-guard")
    if limit is not None and any(d > limit
                                 for _, d in _budget_records):
        session.exitstatus = 1


@pytest.fixture(autouse=True, scope="session")
def _hermetic_compile_cache(tmp_path_factory):
    """Point the default persistent compile cache at a per-session
    tmp dir. In-process `cli.main(["serve"/"train"/"infer", ...])`
    calls (test_cli, test_serve_server, test_router) enable the cache
    PROCESS-GLOBALLY at `compilation_cache.DEFAULT_DIR` —
    `<checkout>/.jax_cache`, which the chip tool copies with the tree —
    and every later jit in the pytest process then reads whatever
    entries previous runs left there. A stale entry deserializes into
    a wrong executable SILENTLY (observed: the HostOffloadEmbedding
    host-scatter update becoming a no-op whenever a CLI serve test ran
    first — a wrong-ANSWER ordering flake, not a crash). Tests must
    never read or fill the checkout's cache; the default-enabled code
    path itself stays exercised against the fresh dir."""
    from paddle_tpu import compilation_cache

    compilation_cache.DEFAULT_DIR = str(
        tmp_path_factory.mktemp("xla-cache"))
    yield


@pytest.fixture
def lock_order_guard():
    """Run a chaos test under the graftlock runtime sanitizer: every
    threading.Lock/RLock the test's stack creates is instrumented,
    the process-global acquisition-order graph is checked on every
    acquire, and the test FAILS (at teardown) if any order inversion
    was observed. `raise_on_violation=False` so a violation does not
    kill a worker thread mid-scenario and cascade into unrelated
    assertion noise — the teardown assert reports every recorded
    violation at once."""
    from paddle_tpu.analysis.guards import LockOrderGuard

    with LockOrderGuard(raise_on_violation=False,
                        name="chaos-lane") as g:
        yield g
    assert g.violations == [], (
        "lock-order violations under the chaos lane:\n  "
        + "\n  ".join(g.violations))


@pytest.fixture
def rng():
    return jax.random.key(0)


@pytest.fixture
def np_rng():
    return np.random.RandomState(0)
