"""Static-analysis pass (graftlint/locklint) + runtime guard tests.

Three layers, mirroring docs/ANALYSIS.md:

1. Per-rule fixture snippets: every rule has a must-flag case AND a
   near-miss it must NOT flag (the false-positive contract is as much
   of the tool's value as the detection).
2. The repo gate itself: `--check` against the committed baseline
   exits 0 — zero unbaselined findings at HEAD — and the two
   locklint-hardened modules stay clean.
3. RecompileGuard/transfer-guard regression tests: the DecodeEngine
   decode loop and the jitted train step compile EXACTLY ONCE and
   hit zero recompiles / zero implicit transfers over 3+ steady-state
   iterations — the "every hot path stays inside one compiled XLA
   program" contract, enforced at runtime.
"""

import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.analysis.graftlint import Finding, lint_source
from paddle_tpu.analysis.guards import (RecompileError, RecompileGuard,
                                        no_implicit_transfers,
                                        steady_state)
from paddle_tpu.analysis.locklint import (lint_lock_graph,
                                          lint_locks_source)
from paddle_tpu.analysis.run import (apply_baseline, collect_findings,
                                     run_cli)

pytestmark = pytest.mark.analysis


def rules_of(src):
    return {f.rule for f in lint_source(textwrap.dedent(src), "t.py")}


# -- rule fixtures: one must-flag + one near-miss per rule ---------------


class TestGL001HostSync:
    def test_item_flagged(self):
        assert "GL001" in rules_of("""
            import jax
            @jax.jit
            def f(x):
                return x * x.item()
        """)

    def test_float_of_traced_flagged(self):
        assert "GL001" in rules_of("""
            import jax
            @jax.jit
            def f(x):
                return float(x)
        """)

    def test_numpy_on_traced_flagged(self):
        assert "GL001" in rules_of("""
            import jax, numpy as np
            @jax.jit
            def f(x):
                return np.asarray(x)
        """)

    def test_print_of_traced_flagged(self):
        assert "GL001" in rules_of("""
            import jax
            @jax.jit
            def f(x):
                print(x)
                return x
        """)

    def test_device_get_flagged(self):
        assert "GL001" in rules_of("""
            import jax
            @jax.jit
            def f(x):
                return jax.device_get(x)
        """)

    def test_near_miss_static_print_and_host_float(self):
        # printing shapes (host metadata) in traced code is fine, and
        # float() in plain host code is fine
        assert not rules_of("""
            import jax
            @jax.jit
            def f(x):
                print("shape:", x.shape)
                return x
            def host(loss):
                return float(loss)
        """)


class TestGL002TracedControlFlow:
    def test_if_on_traced_flagged(self):
        assert "GL002" in rules_of("""
            import jax
            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """)

    def test_while_and_assert_flagged(self):
        src_rules = rules_of("""
            import jax
            @jax.jit
            def f(x):
                assert x > 0
                while x.sum() > 0:
                    x = x - 1
                return x
        """)
        assert "GL002" in src_rules

    def test_scan_body_is_traced(self):
        assert "GL002" in rules_of("""
            from jax import lax
            def outer(xs):
                def body(c, x):
                    if x > 0:
                        c = c + x
                    return c, x
                return lax.scan(body, 0.0, xs)
        """)

    def test_near_miss_shape_branch_and_is_none(self):
        # shape/dtype reads are host metadata; `is None` is
        # host-decidable; host functions branch freely
        assert not rules_of("""
            import jax
            @jax.jit
            def f(x, y=None):
                if x.shape[0] > 4:
                    x = x[:4]
                if y is not None:
                    x = x + y
                return x
        """)

    def test_near_miss_lambda_param_taint_is_scoped(self):
        # a host variable sharing a lambda param's name must not be
        # flagged after the lambda (the param taint dies with it)
        assert not rules_of("""
            import jax
            @jax.jit
            def f(x):
                n = 3
                g = lambda n: n + 1
                if n > 2:
                    return g(x)
                return x
        """)

    def test_jit_site_static_argnames_not_tainted(self):
        # the engine idiom: jax.jit(self._impl, static_argnames=...)
        # makes `flag` a compile-time python value — branching on it
        # is the DESIGN, not a bug
        assert not rules_of("""
            import jax
            class E:
                def __init__(self):
                    self._j = jax.jit(self._impl,
                                      static_argnames=("flag",))
                def _impl(self, x, flag):
                    if flag:
                        return x * 2
                    return x
        """)


class TestGL003WeakDtype:
    def test_bare_literal_ctor_flagged(self):
        assert "GL003" in rules_of("""
            import jax.numpy as jnp
            def f():
                return jnp.array(2.0)
        """)

    def test_full_literal_flagged(self):
        assert "GL003" in rules_of("""
            import jax.numpy as jnp
            def f(s):
                return jnp.full(s, 1e-8)
        """)

    def test_undtyped_arange_flagged(self):
        assert "GL003" in rules_of("""
            import jax.numpy as jnp
            def f(t):
                return jnp.arange(t)
        """)

    def test_near_miss_explicit_dtype(self):
        assert not rules_of("""
            import jax.numpy as jnp
            def f(s, t):
                a = jnp.array(2.0, dtype=jnp.float32)
                b = jnp.full(s, 1e-8, jnp.float32)
                c = jnp.arange(t, dtype=jnp.int32)
                d = jnp.asarray(s)       # non-literal payload
                return a, b, c, d
        """)


class TestGL004RecompileHazards:
    def test_jit_in_loop_flagged(self):
        assert "GL004" in rules_of("""
            import jax
            def f(fs, x):
                outs = []
                for g in fs:
                    outs.append(jax.jit(g)(x))
                return outs
        """)

    def test_list_static_argnums_flagged(self):
        assert "GL004" in rules_of("""
            import jax
            def f(g):
                return jax.jit(g, static_argnums=[0, 1])
        """)

    def test_set_iteration_in_traced_flagged(self):
        assert "GL004" in rules_of("""
            import jax
            @jax.jit
            def f(x):
                t = x
                for k in set((1, 2, 3)):
                    t = t + k
                return t
        """)

    def test_near_miss_hoisted_jit_and_sorted_set(self):
        assert not rules_of("""
            import jax
            def build(g):
                return jax.jit(g, static_argnums=(0, 1))
            @jax.jit
            def f(x):
                t = x
                for k in sorted(set((1, 2, 3))):
                    t = t + k
                return t
        """)


class TestGL005TracerLeak:
    def test_store_on_self_flagged(self):
        assert "GL005" in rules_of("""
            import jax
            class A:
                def run(self, x):
                    return jax.jit(self._step)(x)
                def _step(self, x):
                    self.last = x * 2
                    return x
        """)

    def test_append_to_closure_flagged(self):
        assert "GL005" in rules_of("""
            import jax
            acc = []
            @jax.jit
            def f(x):
                acc.append(x * 2)
                return x
        """)

    def test_near_miss_local_accumulator(self):
        # the engine's own idiom: new_caches is bound INSIDE the
        # traced scope, collecting across a nested closure — legal
        assert not rules_of("""
            import jax
            @jax.jit
            def f(pairs, x):
                new_caches = []
                def attn(k, v):
                    new_caches.append((k, v))
                    return x
                for k, v in pairs:
                    x = attn(k, v)
                return x, tuple(new_caches)
        """)

    def test_near_miss_functional_update_api(self):
        # `.update(...)` whose RESULT is used is an optimizer-style
        # functional API, not a dict mutation
        assert not rules_of("""
            import jax
            def make(optimizer):
                @jax.jit
                def step(state, grads):
                    params, opt = optimizer.update(grads, state)
                    return params, opt
                return step
        """)


class TestGL006ImportTimeCompute:
    def test_module_level_flagged(self):
        assert "GL006" in rules_of("""
            import jax.numpy as jnp
            TABLE = jnp.zeros((10,))
        """)

    def test_default_arg_flagged(self):
        assert "GL006" in rules_of("""
            import jax.numpy as jnp
            def f(x, w=jnp.ones((3,))):
                return x * w
        """)

    def test_near_miss_inside_function_and_main_block(self):
        assert not rules_of("""
            import jax.numpy as jnp
            def f():
                return jnp.zeros((10,))
            if __name__ == "__main__":
                print(jnp.zeros((2,)))
        """)

    def test_near_miss_module_level_lambda_body(self):
        # a lambda BODY doesn't run at import — only its construction
        assert not rules_of("""
            import jax.numpy as jnp
            _pad = lambda x: jnp.maximum(x, 0)
            TABLE = {"relu": lambda x: jnp.maximum(x, 0)}
        """)


class TestGL007ObsDiscipline:
    """GL007 only bites inside serve/ and train/ — the modules under
    the obs instrumentation contract."""

    @staticmethod
    def rules_at(src, path):
        return {f.rule
                for f in lint_source(textwrap.dedent(src), path)}

    def test_time_time_flagged_in_serve(self):
        assert "GL007" in self.rules_at("""
            import time
            def step(self):
                t0 = time.time()
                return t0
        """, "paddle_tpu/serve/x.py")

    def test_bare_print_flagged_in_train(self):
        assert "GL007" in self.rules_at("""
            def report(n):
                print(n)
        """, "paddle_tpu/train/x.py")

    def test_near_miss_monotonic_and_other_module(self):
        # the injectable-clock default is fine, and the same bare
        # print outside the instrumented tree is out of scope
        assert "GL007" not in self.rules_at("""
            import time
            def step(self):
                return time.monotonic()
        """, "paddle_tpu/serve/x.py")
        assert "GL007" not in self.rules_at("""
            def report(n):
                print(n)
        """, "paddle_tpu/native/x.py")

    def test_traced_print_stays_gl001(self):
        # print of a traced value is GL001's finding — GL007 must not
        # double-report it
        rules = self.rules_at("""
            import jax
            @jax.jit
            def f(x):
                print(x)
                return x
        """, "paddle_tpu/serve/x.py")
        assert "GL001" in rules and "GL007" not in rules

    def test_disable_with_reason_suppresses(self):
        assert "GL007" not in self.rules_at("""
            def report(n):
                print(n)  # graftlint: disable=GL007(user-facing dump)
        """, "paddle_tpu/train/x.py")


class TestSuppression:
    SRC = """
        import jax
        @jax.jit
        def f(x):
            y = float(x)  # graftlint: disable=GL001({})
            return y
    """

    def test_disable_with_reason_suppresses(self):
        assert not rules_of(self.SRC.format("test exercises the sync"))

    def test_bare_disable_does_not_count(self):
        # the reason is REQUIRED — a naked disable still reports
        assert "GL001" in rules_of(self.SRC.format(""))

    def test_comment_block_above_statement(self):
        assert not rules_of("""
            import jax
            @jax.jit
            def f(x):
                # graftlint: disable=GL001(reason spans the block
                # above the statement)
                y = float(x)
                return y
        """)


# -- locklint -------------------------------------------------------------


LOCKED_SRC = """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0
        self.err = None

    def locked_inc(self):
        with self._lock:
            self.n += 1

    def racy_inc(self):{}
        self.n += 1
"""


class TestLocklint:
    def test_mixed_discipline_flagged(self):
        fs = lint_locks_source(LOCKED_SRC.format(""), "t.py")
        assert [f.rule for f in fs] == ["LK001"]
        assert "self.n" in fs[0].message

    def test_holds_lock_annotation_clears(self):
        src = LOCKED_SRC.format(
            "\n        # locklint: holds-lock(caller locks)")
        assert lint_locks_source(src, "t.py") == []

    def test_near_miss_consistently_unlocked(self):
        # no locked mutation site -> no discipline to enforce
        # (single-threaded classes don't get nagged)
        src = LOCKED_SRC.replace(
            "        with self._lock:\n            self.n += 1",
            "        self.n += 1")
        assert lint_locks_source(src, "t.py") == []

    def test_init_is_exempt(self):
        src = """
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def inc(self):
        with self._lock:
            self.n += 1
"""
        assert lint_locks_source(src, "t.py") == []

    def test_hardened_modules_stay_clean(self):
        # the PR's lock-discipline sweep: the native runtimes and the
        # pserver client must have zero unannotated findings
        fs = collect_findings([
            "paddle_tpu/native/taskqueue.py",
            "paddle_tpu/native/pserver.py",
            "paddle_tpu/serve/server.py",
            "paddle_tpu/parallel/pserver_client.py",
        ], rules=["LK001"])
        assert fs == [], [str(f) for f in fs]


class TestHAMasterSnapshotErrorRegression:
    """The genuine race locklint surfaced: HAMaster._loop wrote
    last_snapshot_error OUTSIDE _snap_lock (a stale failure could
    overwrite a newer success), and a failed MANUAL checkpoint()
    recorded nothing. Now checkpoint() itself records under the
    lock."""

    def test_manual_checkpoint_failure_records_error(self, tmp_path):
        from paddle_tpu.native.taskqueue import HAMaster

        ha = HAMaster(str(tmp_path), interval_s=0)  # no cadence thread
        try:
            orig = ha.queue.snapshot
            ha.queue.snapshot = lambda path: (_ for _ in ()).throw(
                OSError("disk full"))
            with pytest.raises(OSError):
                ha.checkpoint()
            assert "disk full" in ha.last_snapshot_error
            ha.queue.snapshot = orig
            ha.checkpoint()
            assert ha.last_snapshot_error is None
            assert ha.last_snapshot_time is not None
        finally:
            ha.stop(final_snapshot=False)


# -- graftlock: the LK002-LK005 concurrency rules -------------------------


def lk(src, rules, path="t.py"):
    return [f.rule for f in lint_locks_source(
        textwrap.dedent(src), path, rules=rules)]


class TestLK002LockOrderCycles:
    CYCLE = """
        import threading
        class A:
            def __init__(self):
                self._router = threading.Lock()
                self._pool = threading.Lock()
            def fwd(self):
                with self._router:
                    with self._pool:
                        pass
            def rev(self):
                with self._pool:
                    with self._router:
                        pass
    """

    def test_must_flag_inverted_order(self):
        fs = lint_lock_graph(
            {"a.py": textwrap.dedent(self.CYCLE)})
        assert [f.rule for f in fs] == ["LK002"]
        # the message names the full cycle and both sites
        assert "A._router" in fs[0].message
        assert "A._pool" in fs[0].message
        assert "opposite order" in fs[0].message

    def test_near_miss_same_order_twice(self):
        src = self.CYCLE.replace(
            """            def rev(self):
                with self._pool:
                    with self._router:""",
            """            def rev(self):
                with self._router:
                    with self._pool:""")
        assert src != self.CYCLE     # the replace must have landed
        assert lint_lock_graph({"a.py": textwrap.dedent(src)}) == []

    def test_cycle_via_method_call_chain(self):
        # fwd holds router and CALLS a helper that takes pool; rev
        # inverts — the edge comes from the call chain, not a
        # lexical nested with
        src = """
            import threading
            class A:
                def __init__(self):
                    self._router = threading.Lock()
                    self._pool = threading.Lock()
                def fwd(self):
                    with self._router:
                        self._grab()
                def _grab(self):
                    with self._pool:
                        pass
                def rev(self):
                    with self._pool:
                        with self._router:
                            pass
        """
        fs = lint_lock_graph({"a.py": textwrap.dedent(src)})
        assert [f.rule for f in fs] == ["LK002"]

    def test_cross_module_cycle_via_typed_attr(self):
        # serve-side class holds its lock and calls into a cluster-
        # side class that locks; a back-path inverts — only the
        # MERGED graph sees it
        m1 = """
            import threading
            from m2 import Lease
            class Member:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._lease = Lease()
                def tick(self):
                    with self._lock:
                        self._lease.renew()
                def poke(self):
                    with self._lock:
                        pass
        """
        m2 = """
            import threading
            from m1 import Member
            class Lease:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._member = Member()
                def renew(self):
                    with self._mu:
                        pass
                def back(self):
                    with self._mu:
                        self._member.poke()
        """
        fs = lint_lock_graph({"m1.py": textwrap.dedent(m1),
                              "m2.py": textwrap.dedent(m2)})
        assert [f.rule for f in fs] == ["LK002"]
        msg = fs[0].message
        assert "Member._lock" in msg and "Lease._mu" in msg
        # each module alone has no cycle
        assert lint_lock_graph({"m1.py": textwrap.dedent(m1)}) == []
        assert lint_lock_graph({"m2.py": textwrap.dedent(m2)}) == []

    RE_SRC = """
        import threading
        class R:
            def __init__(self):
                self._mu = threading.{}()
            def outer(self):
                with self._mu:
                    self.inner()
            def inner(self):
                with self._mu:
                    pass
    """

    def test_plain_lock_self_cycle_is_deadlock(self):
        fs = lint_lock_graph(
            {"r.py": textwrap.dedent(self.RE_SRC.format("Lock"))})
        assert [f.rule for f in fs] == ["LK002"]
        assert "self-deadlock" in fs[0].message

    def test_rlock_self_cycle_is_reentrancy_not_flagged(self):
        assert lint_lock_graph(
            {"r.py": textwrap.dedent(self.RE_SRC.format("RLock"))}
        ) == []

    def test_suppression_applies(self):
        src = textwrap.dedent(self.CYCLE).replace(
            "with self._pool:\n            with self._router:",
            "with self._pool:\n            # locklint: disable="
            "LK002(order probe fixture)\n            "
            "with self._router:")
        assert src != textwrap.dedent(self.CYCLE)
        assert lint_lock_graph({"a.py": src}) == []

    def test_repo_graph_has_no_cycles(self):
        # the tentpole's standing guarantee: the sanctioned orders in
        # docs/RELIABILITY.md are acyclic at HEAD
        fs = [f for f in collect_findings(["paddle_tpu"],
                                          rules=["LK002"])]
        assert fs == [], [str(f) for f in fs]


class TestLK003BlockingUnderLock:
    def test_must_flag_socket_write_under_lock(self):
        assert lk("""
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._sock = None
                def bad(self):
                    with self._lock:
                        self._sock.sendall(b"x")
        """, ["LK003"]) == ["LK003"]

    def test_near_miss_snapshot_then_write_outside(self):
        assert lk("""
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._sock = None
                def good(self):
                    with self._lock:
                        data = b"x"
                    self._sock.sendall(data)
        """, ["LK003"]) == []

    def test_wait_without_timeout_flagged_with_timeout_clean(self):
        src = """
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._ev = threading.Event()
                def f(self):
                    with self._lock:
                        self._ev.wait({})
        """
        assert lk(src.format(""), ["LK003"]) == ["LK003"]
        assert lk(src.format("timeout=1.0"), ["LK003"]) == []

    def test_condition_wait_on_own_lock_is_the_cv_idiom(self):
        # Condition.wait RELEASES the lock — the one .wait() that is
        # sanctioned under it
        assert lk("""
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Condition()
                def f(self):
                    with self._lock:
                        self._lock.wait()
        """, ["LK003"]) == []

    def test_jit_callable_under_lock(self):
        src = """
            import threading, jax
            class J:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._step = jax.jit(lambda x: x)
                def bad(self, x):
                    with self._lock:
                        return self._step(x)
        """
        assert lk(src, ["LK003"]) == ["LK003"]

    def test_transitive_through_same_class_call(self):
        fs = lint_locks_source(textwrap.dedent("""
            import threading, time
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                def outer(self):
                    with self._lock:
                        self._helper()
                def _helper(self):
                    time.sleep(0.1)
        """), "t.py", rules=["LK003"])
        assert [f.rule for f in fs] == ["LK003"]
        assert "_helper" in fs[0].message

    def test_suppression_applies(self):
        assert lk("""
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._sock = None
                def f(self):
                    with self._lock:
                        # locklint: disable=LK003(ACK-after-tail
                        # ordering requires the send under the lock)
                        self._sock.sendall(b"x")
        """, ["LK003"]) == []


class TestLK004ThreadLifecycle:
    def test_must_flag_fire_and_forget(self):
        assert lk("""
            import threading
            def spawn():
                threading.Thread(target=print).start()
        """, ["LK004"]) == ["LK004"]

    def test_near_miss_daemon(self):
        assert lk("""
            import threading
            def spawn():
                threading.Thread(target=print, daemon=True).start()
        """, ["LK004"]) == []

    def test_near_miss_bound_and_joined(self):
        assert lk("""
            import threading
            class W:
                def start(self):
                    self._t = threading.Thread(target=print)
                    self._t.start()
                def stop(self):
                    self._t.join(timeout=1.0)
        """, ["LK004"]) == []

    def test_listcomp_fanout_join_loop_is_clean(self):
        # the idiomatic shape test_native_runtime uses
        assert lk("""
            import threading
            def fan():
                ts = [threading.Thread(target=print)
                      for _ in range(4)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
        """, ["LK004"]) == []

    def test_holds_lock_target_flagged(self):
        # a FRESH thread holds nothing: a holds-lock annotated
        # target run as a thread body is a contradiction
        fs = lint_locks_source(textwrap.dedent("""
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                def spawn(self):
                    self._t = threading.Thread(target=self._body,
                                               daemon=True)
                # locklint: holds-lock(callers lock first)
                def _body(self):
                    pass
        """), "t.py", rules=["LK004"])
        assert [f.rule for f in fs] == ["LK004"]
        assert "holds-lock" in fs[0].message


class TestLK005SignalSafety:
    def test_must_flag_handler_taking_lock(self):
        fs = lint_locks_source(textwrap.dedent("""
            import signal, threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                def install(self):
                    def handler(signum, frame):
                        self.drain()
                    signal.signal(signal.SIGTERM, handler)
                def drain(self):
                    with self._lock:
                        pass
        """), "t.py", rules=["LK005"])
        assert [f.rule for f in fs] == ["LK005"]
        assert "self._lock" in fs[0].message

    def test_must_flag_handler_logging(self):
        assert lk("""
            import logging, signal
            log = logging.getLogger(__name__)
            def handler(signum, frame):
                log.warning("got %d", signum)
            def install():
                signal.signal(signal.SIGTERM, handler)
        """, ["LK005"]) == ["LK005"]

    def test_near_miss_flag_only_handler(self):
        assert lk("""
            import signal
            class S:
                def install(self):
                    def handler(signum, frame):
                        self._pending = signum
                    signal.signal(signal.SIGTERM, handler)
        """, ["LK005"]) == []

    def test_hardened_signal_surfaces_stay_clean(self):
        # the PR's fix sweep: every signal handler in the package
        # defers to a flag (http_edge, server, resilience)
        fs = collect_findings(["paddle_tpu"], rules=["LK005"])
        assert fs == [], [str(f) for f in fs]


class TestLockSweptModulesStayClean:
    def test_fix_sweep_holds(self):
        # the ISSUE's fix-sweep targets, under every LK rule the
        # per-file pass runs — anything new here must be fixed or
        # land in the baseline with a written reason
        fs = collect_findings([
            "paddle_tpu/serve/http_edge.py",
            "paddle_tpu/serve/transport.py",
            "paddle_tpu/serve/router.py",
            "paddle_tpu/cluster/membership.py",
            "paddle_tpu/serve/shm_arena.py",
        ], rules=["LK001", "LK003", "LK004", "LK005"])
        assert fs == [], [str(f) for f in fs]


# -- LockOrderGuard: the runtime half of graftlock ------------------------


@pytest.mark.locks
class TestLockOrderGuard:
    def test_inversion_raises_naming_both_sites(self):
        from paddle_tpu.analysis.guards import (LockOrderError,
                                                LockOrderGuard)

        with LockOrderGuard(raise_on_violation=False) as g:
            a, b = threading.Lock(), threading.Lock()

            def fwd():
                with a:
                    with b:
                        pass

            def rev():
                with b:
                    with a:
                        pass

            for fn in (fwd, rev):
                t = threading.Thread(target=fn)
                t.start()
                t.join()
        assert len(g.violations) == 1
        msg = g.violations[0]
        assert "lock order inverted" in msg
        assert "test_analysis.py" in msg     # both sites named
        # raise_on_violation=True surfaces it as LockOrderError from
        # __exit__ even when a worker thread swallowed it
        with pytest.raises(LockOrderError, match="inverted"):
            with LockOrderGuard() as g2:
                a, b = threading.Lock(), threading.Lock()
                for first, second in ((a, b), (b, a)):
                    def run(x=first, y=second):
                        try:
                            with x:
                                with y:
                                    pass
                        except LockOrderError:
                            pass        # swallowed in the worker
                    t = threading.Thread(target=run)
                    t.start()
                    t.join()

    def test_cycle_across_three_threads(self):
        # no PAIR is ever inverted — only the 3-cycle A->B->C->A is
        # wrong; DFS reachability must catch it
        from paddle_tpu.analysis.guards import LockOrderGuard

        with LockOrderGuard(raise_on_violation=False) as g:
            a, b, c = (threading.Lock(), threading.Lock(),
                       threading.Lock())

            def run(x, y):
                with x:
                    with y:
                        pass

            for x, y in ((a, b), (b, c), (c, a)):
                t = threading.Thread(target=run, args=(x, y))
                t.start()
                t.join()
        assert len(g.violations) == 1
        assert "established" in g.violations[0]

    def test_rlock_reentrancy_not_flagged(self):
        from paddle_tpu.analysis.guards import LockOrderGuard

        with LockOrderGuard() as g:
            r = threading.RLock()
            with r:
                with r:
                    with r:
                        pass
        assert g.violations == []

    def test_plain_lock_self_deadlock_raises_instead_of_hanging(self):
        from paddle_tpu.analysis.guards import (LockOrderError,
                                                LockOrderGuard)

        try:
            with LockOrderGuard() as g:
                l = threading.Lock()
                l.acquire()
                try:
                    with pytest.raises(LockOrderError,
                                       match="self-deadlock"):
                        l.acquire()
                finally:
                    l.release()
        except LockOrderError:
            pass                     # __exit__ re-raise, expected
        assert len(g.violations) == 1

    def test_held_while_blocking_report(self):
        from paddle_tpu.analysis.guards import LockOrderGuard

        with LockOrderGuard(max_held_s=0.05) as g:
            l = threading.Lock()
            with l:
                time.sleep(0.12)
        assert len(g.held_reports) == 1
        rep = g.held_reports[0]
        assert rep["held_s"] > 0.05 and rep["bound_s"] == 0.05
        assert "test_analysis.py" in rep["acquired_at"]

    def test_trylock_records_no_edge(self):
        from paddle_tpu.analysis.guards import LockOrderGuard

        with LockOrderGuard() as g:
            a, b = threading.Lock(), threading.Lock()

            def try_side():
                with a:
                    if b.acquire(blocking=False):
                        b.release()

            def rev():
                with b:
                    with a:
                        pass

            for fn in (try_side, rev):
                t = threading.Thread(target=fn)
                t.start()
                t.join()
        assert g.violations == []

    def test_condition_event_queue_built_under_guard_work(self):
        import queue

        from paddle_tpu.analysis.guards import LockOrderGuard

        with LockOrderGuard() as g:
            cv = threading.Condition()
            done = []

            def waiter():
                with cv:
                    cv.wait(timeout=2.0)
                    done.append(1)

            t = threading.Thread(target=waiter)
            t.start()
            time.sleep(0.05)
            with cv:
                cv.notify_all()
            t.join()
            ev = threading.Event()
            ev.set()
            assert ev.wait(0.1)
            q = queue.Queue()
            q.put(1)
            assert q.get() == 1
        assert done == [1] and g.violations == []

    def test_locks_survive_guard_exit(self):
        from paddle_tpu.analysis.guards import LockOrderGuard

        with LockOrderGuard():
            l = threading.Lock()
        with l:                      # tracking off, lock still works
            pass
        assert threading.Lock is not type(l)  # patch restored

    def test_single_active_guard(self):
        from paddle_tpu.analysis.guards import LockOrderGuard

        with LockOrderGuard():
            with pytest.raises(RuntimeError, match="already active"):
                with LockOrderGuard():
                    pass


# -- baseline mechanics ---------------------------------------------------


class TestBaseline:
    def F(self, rule="GL001", path="a.py", func="f", line=1):
        return Finding(rule, path, line, 0, func, "m")

    def test_counts_cover_and_excess_reports(self):
        base = {("GL001", "a.py", "f"):
                {"rule": "GL001", "path": "a.py", "func": "f",
                 "count": 1, "reason": "r"}}
        un, stale = apply_baseline([self.F(line=1)], base)
        assert un == [] and stale == []
        un, _ = apply_baseline([self.F(line=1), self.F(line=9)], base)
        assert len(un) == 1 and un[0].line == 9

    def test_stale_entries_surface(self):
        base = {("GL001", "gone.py", "f"):
                {"rule": "GL001", "path": "gone.py", "func": "f",
                 "count": 1, "reason": "r"}}
        un, stale = apply_baseline([], base)
        assert un == [] and stale == [("GL001", "gone.py", "f")]

    def test_repo_gate_is_green(self, capsys):
        # THE acceptance criterion: zero unbaselined findings at HEAD
        rc = run_cli(["--check"])
        out = capsys.readouterr().out
        assert rc == 0, out

    def test_explain_prints_catalog_entry(self, capsys):
        for rid in ("GL001", "LK002", "lk003"):  # case-insensitive
            assert run_cli(["--explain", rid]) == 0
            out = capsys.readouterr().out
            assert rid.upper() in out
            assert "bad:" in out and "good:" in out

    def test_explain_unknown_rule_errors(self, capsys):
        with pytest.raises(SystemExit):
            run_cli(["--explain", "LK999"])

    def test_stale_prune_report_grouped_per_rule(self, tmp_path,
                                                 capsys):
        import json as _json

        # a file with one real LK003 finding, and a baseline holding
        # that entry plus two stale ones under different rules
        src = tmp_path / "mod.py"
        src.write_text(textwrap.dedent("""
            import threading
            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._sock = None
                def f(self):
                    with self._lock:
                        self._sock.sendall(b"x")
        """))
        rel = str(src)
        from paddle_tpu.analysis.run import _rel
        rel = _rel(str(src))
        base = tmp_path / "base.json"
        base.write_text(_json.dumps({"version": 1, "entries": [
            {"rule": "LK003", "path": rel, "func": "S.f",
             "count": 1, "reason": "r", "message": "m"},
            {"rule": "LK003", "path": rel, "func": "S.gone",
             "count": 1, "reason": "r", "message": "m"},
            {"rule": "LK001", "path": rel, "func": "S.old",
             "count": 1, "reason": "r", "message": "m"},
        ]}))
        rc = run_cli(["--check", "--baseline", str(base), str(src)])
        out = capsys.readouterr().out
        assert rc == 0, out          # the live finding is covered
        assert "stale baseline entries to prune (2" in out
        # grouped per rule, each naming its keys
        assert "LK001" in out and "S.old" in out
        assert "S.gone" in out


# -- runtime guards: the two hottest loops --------------------------------


def _small_cfg():
    from paddle_tpu.models import transformer as T

    return T.TransformerConfig(vocab=31, dim=16, n_layers=1,
                               n_heads=2, attn_impl="dense")


class TestRecompileGuardUnit:
    def test_catches_recompile_and_names_it(self):
        f = jax.jit(lambda x: x * 2)
        f(jnp.ones((3,), jnp.float32))
        with pytest.raises(RecompileError):
            with RecompileGuard(name="unit"):
                f(jnp.ones((5,), jnp.float32))   # new shape: compile

    def test_steady_state_passes(self):
        f = jax.jit(lambda x: x * 2)
        x = jnp.ones((4,), jnp.float32)
        f(x)
        with RecompileGuard(name="unit") as g:
            for _ in range(3):
                f(x)
        assert g.compiles == 0

    def test_names_the_offender_with_log_compiles_off(self):
        assert not jax.config.jax_log_compiles

        def offender_of_the_region(x):
            return x * 7 - 1

        f = jax.jit(offender_of_the_region)
        x3, x5 = jnp.ones((3,), jnp.float32), jnp.ones((5,), jnp.float32)
        f(x3)
        with pytest.raises(RecompileError,
                           match=r"compiled jit\(offender_of_the_region\)"):
            with RecompileGuard(name="unit") as g:
                f(x5)                            # new shape: compile
        assert g.compiles == 1
        assert g.compiled_names == ["jit(offender_of_the_region)"]
        f(jnp.ones((6,), jnp.float32))           # after the region:
        assert g.compiles == 1                   # ... not the guard's

    def test_leaves_jax_loggers_and_config_as_it_found_them(self):
        import logging

        loggers = [logging.getLogger(n) for n in
                   ("jax._src.interpreters.pxla", "jax._src.dispatch")]
        look = lambda: [(lg.level, lg.propagate, list(lg.handlers))
                        for lg in loggers] + [jax.config.jax_log_compiles]
        before = look()
        f = jax.jit(lambda x: x - 4)
        x = jnp.ones((2,), jnp.float32)
        with RecompileGuard(max_compiles=1, name="unit") as g:
            during = look()
            f(x)
        assert g.compiles == 1
        assert before == during == look()

    def test_nested_guards_each_count_their_own_region(self):
        f = jax.jit(lambda x: x / 3)
        x2, x3 = jnp.ones((2,), jnp.float32), jnp.ones((3,), jnp.float32)
        with RecompileGuard(max_compiles=2, name="outer") as outer:
            f(x2)
            with RecompileGuard(max_compiles=1, name="inner") as inner:
                f(x3)
        assert (outer.compiles, inner.compiles) == (2, 1)
        assert inner.compiled_names == outer.compiled_names[1:]

    def test_transfer_guard_bites_on_implicit_h2d(self):
        f = jax.jit(lambda x: x + 1)
        f(jnp.ones((4,), jnp.float32))
        with pytest.raises(Exception):
            with no_implicit_transfers():
                f(np.ones((4,), np.float32))     # implicit transfer
        # explicit staging passes
        with no_implicit_transfers():
            f(jax.device_put(np.ones((4,), np.float32)))


class TestDecodeLoopSteadyState:
    """ISSUE acceptance: the decode loop compiles exactly once, then
    zero recompiles and zero implicit transfers over 3+ steady
    iterations — including a page-boundary crossing (the host-side
    page map update must not re-stage anything)."""

    def test_decode_step_compiles_once_then_never(self):
        from paddle_tpu.serve.engine import DecodeEngine

        from paddle_tpu.models import transformer as T

        cfg = _small_cfg()
        params = T.init_params(jax.random.key(0), cfg)
        # page_size 4 + a 3-token prompt => the guarded steady window
        # below crosses a page boundary
        eng = DecodeEngine(params, cfg, slots=2, max_len=16,
                           page_size=4)
        state = eng.init_state()
        r = np.random.RandomState(0)
        state = eng.prefill(
            state, 0, r.randint(0, 31, (3,)).astype(np.int32))
        with RecompileGuard(max_compiles=64, name="warmup") as warm:
            state, *_ = eng.decode_step(state)
            state = eng.ensure_decode_page(state, 0)
        assert warm.compiles >= 1        # the ONE compile happened...
        with steady_state("decode loop", transfers="disallow") as g:
            for _ in range(4):           # ...and never again
                state, toks, lps, was, fin = eng.decode_step(state)
                state = eng.ensure_decode_page(state, 0)
                jax.device_get((toks, lps, was, fin))  # explicit: ok
        assert g.compiles == 0

    def test_int8_kernel_dispatch_adds_zero_compiles(self):
        """ISSUE 12 acceptance: routing an int8 pool through the
        ragged dispatcher (`ragged_impl` pinned to the kernel) must
        add ZERO steady-state compiles — the dequant-fused walk is
        baked into the one decode program at warmup, same as the jnp
        gather it replaced, and page-boundary churn must not re-trace
        the tuple-arena plumbing."""
        from paddle_tpu.models import transformer as T
        from paddle_tpu.serve.engine import DecodeEngine

        cfg = T.TransformerConfig(vocab=31, dim=16, n_layers=1,
                                  n_heads=2, attn_impl="dense",
                                  kv_cache_dtype="int8")
        params = T.init_params(jax.random.key(0), cfg)
        eng = DecodeEngine(params, cfg, slots=2, max_len=16,
                           page_size=4, ragged_impl="pallas")
        state = eng.init_state()
        r = np.random.RandomState(0)
        state = eng.prefill(
            state, 0, r.randint(0, 31, (3,)).astype(np.int32))
        with RecompileGuard(max_compiles=64, name="int8 warmup") as warm:
            state, *_ = eng.decode_step(state)
            state = eng.ensure_decode_page(state, 0)
        assert warm.compiles >= 1
        with steady_state("int8 kernel decode loop",
                          transfers="disallow") as g:
            for _ in range(4):
                state, toks, lps, was, fin = eng.decode_step(state)
                state = eng.ensure_decode_page(state, 0)
                jax.device_get((toks, lps, was, fin))
        assert g.compiles == 0

    def test_full_serve_is_transfer_clean(self):
        """`serve --transfer-guard`'s contract: the WHOLE serve path —
        pool init (explicit device_put staging), admission, decode,
        retire — runs under disallow with greedy parity intact."""
        from paddle_tpu.models import transformer as T
        from paddle_tpu.serve.engine import DecodeEngine

        cfg = _small_cfg()
        params = T.init_params(jax.random.key(0), cfg)
        eng = DecodeEngine(params, cfg, slots=2, max_len=16)
        r = np.random.RandomState(0)
        p = r.randint(0, 31, (5,)).astype(np.int32)
        with no_implicit_transfers():
            got = eng.serve([p], max_new=4, buckets=(8,))
        ref = T.generate(params, cfg, jnp.asarray(p)[None, :],
                         steps=4)
        assert got[0] == [int(t)
                          for t in np.asarray(ref[0, len(p):])]

    def test_served_second_wave_is_compile_free(self):
        """After one serve() wave warmed every body (prefill bucket,
        step, retire), a second wave over the same bucket must not
        compile anything — the continuous-batching promise."""
        from paddle_tpu.serve.engine import DecodeEngine

        from paddle_tpu.models import transformer as T

        cfg = _small_cfg()
        params = T.init_params(jax.random.key(0), cfg)
        eng = DecodeEngine(params, cfg, slots=2, max_len=16)
        r = np.random.RandomState(1)
        mk = lambda n: [r.randint(0, 31, (5,)).astype(np.int32)
                        for _ in range(n)]
        eng.serve(mk(2), max_new=4, buckets=(8,))         # warm wave
        with RecompileGuard(name="second serve wave") as g:
            got = eng.serve(mk(3), max_new=4, buckets=(8,))
        assert g.compiles == 0
        assert len(got) == 3 and all(len(t) for t in got)


class TestTrainStepSteadyState:
    def test_train_step_compiles_once_then_never(self):
        from paddle_tpu import models, optim
        from paddle_tpu.nn.module import ShapeSpec
        from paddle_tpu.ops import losses
        from paddle_tpu.train import Trainer

        trainer = Trainer(
            models.lenet.mlp(10, hidden=(16,)),
            loss_fn=lambda lo, la: jnp.mean(
                losses.softmax_cross_entropy(lo, la)),
            optimizer=optim.sgd(0.1), seed=0)
        state = trainer.init_state(ShapeSpec((8, 28, 28, 1)))
        r = np.random.RandomState(0)
        # the ONE sanctioned per-step transfer is the input batch —
        # staged EXPLICITLY, which is what lets transfers="disallow"
        # hold for everything else
        batch = jax.device_put((
            r.randn(8, 28, 28, 1).astype(np.float32),
            r.randint(0, 10, (8,)).astype(np.int32)))
        rng = jax.random.key(0)
        with RecompileGuard(max_compiles=64, name="warmup") as warm:
            rng, step_rng = jax.random.split(rng)
            state, loss, _ = trainer._train_step(
                state, step_rng, (batch[0],), (batch[1],))
        assert warm.compiles >= 1
        with steady_state("train step", transfers="disallow") as g:
            for _ in range(3):
                # Trainer.train's own per-step idiom: split stays on
                # device, so the ONLY transfer is the explicit batch
                rng, step_rng = jax.random.split(rng)
                state, loss, _ = trainer._train_step(
                    state, step_rng, (batch[0],), (batch[1],))
        assert g.compiles == 0
        assert np.isfinite(float(loss))
