"""The step timeline (obs.trace.Timeline) and its wiring into
`Trainer.train` and `DataFeeder`, and the names the device trace gets
(named scopes, `pallas_call` names). Counts and orderings only: the
clock is injected and nothing here is a time."""

import gc
import itertools
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import data, nn, optim
from paddle_tpu.analysis.guards import RecompileGuard
from paddle_tpu.data import batch as B
from paddle_tpu.nn.module import ShapeSpec
from paddle_tpu.obs.trace import TIMELINE_KEEP, Timeline
from paddle_tpu.ops import losses
from paddle_tpu.train import Trainer, events as E
from paddle_tpu.train.trainer import make_train_step

BATCH, FEATURES, N_BATCHES = 4, 6, 5


def ticking():
    """A clock whose every reading is 1000 ns after the last, on
    whichever thread: orderings are exact and no two stamps are equal."""
    ticks = itertools.count(0, 1000)
    return lambda: next(ticks)


def reader(n_batches=N_BATCHES, fail_at=None):
    def read():
        rng = np.random.RandomState(0)
        for i in range(n_batches * BATCH):
            if fail_at is not None and i == fail_at * BATCH:
                raise OSError("disk gone")
            x = rng.rand(FEATURES).astype(np.float32)
            yield x, int(x.sum() > FEATURES / 2)
    return read


def small_trainer(tl):
    model = nn.Sequential([nn.Dense(8, activation="relu"), nn.Dense(2)])
    loss_fn = lambda lo, la: jnp.mean(losses.softmax_cross_entropy(lo, la))
    tr = Trainer(model, loss_fn, optim.momentum(0.1), seed=0, timeline=tl)
    return tr, tr.init_state(ShapeSpec((BATCH, FEATURES)))


def named(rows, name):
    return [r for r in rows if r[0] == name]


def feeder_threads():
    return [t for t in threading.enumerate()
            if t.name == "paddle_tpu-feeder"]


# -- the recorder ------------------------------------------------------------

def test_ring_keeps_the_newest_and_rows_filters_by_start():
    tl = Timeline(clock_ns=ticking())
    for i in range(TIMELINE_KEEP + 10):
        with tl.span("s", i):
            pass
    rows = tl.rows()
    assert len(rows) == TIMELINE_KEEP == 8192
    assert [r[3] for r in rows] == list(range(10, TIMELINE_KEEP + 10))
    since = rows[100][1]
    assert tl.rows(since_ns=since) == rows[100:]
    assert tl.rows(since_ns=rows[-1][1] + 1) == []


def test_rows_carry_seq_and_the_enclosing_span_of_their_thread():
    tl = Timeline(clock_ns=ticking())
    with tl.span("outer", 7):
        with tl.span("inner", 7):
            pass
        with tl.span("gone", 7) as s:
            s.discard()
    tl.count("things", 3)
    tl.count("things")
    assert tl.rows() == [("inner", 1000, 2000, 7, "outer"),
                         ("outer", 0, 5000, 7, None)]
    assert tl.counters() == {"things": 4}


def test_summary_totals_counts_and_self_time():
    tl = Timeline(clock_ns=ticking())
    for seq in range(3):
        with tl.span("step", seq):          # 6 ticks: 2 in each child,
            with tl.span("wait", seq):      # ... 1 + 1 + ... its own
                pass
            with tl.span("work", seq):
                with tl.span("leaf", seq):
                    pass
    s = tl.summary()
    assert list(s) == ["leaf", "step", "wait", "work"]
    assert {k: v["count"] for k, v in s.items()} == dict.fromkeys(s, 3)
    # step: 0..9000 ns; wait 1000..2000; work 3000..6000... by ticks:
    # enter step, enter wait, exit wait, enter work, enter leaf, exit
    # leaf, exit work, exit step = 7 intervals of 1000 ns
    assert s["step"]["total_s"] == pytest.approx(3 * 7e-6)
    assert s["wait"]["total_s"] == pytest.approx(3 * 1e-6)
    assert s["work"]["total_s"] == pytest.approx(3 * 3e-6)
    assert s["step"]["self_s"] == pytest.approx(3 * (7 - 1 - 3) * 1e-6)
    assert s["work"]["self_s"] == pytest.approx(3 * 2e-6)
    assert s["leaf"]["self_s"] == s["leaf"]["total_s"]
    assert s["step"]["mean_ms"] == pytest.approx(7e-3)
    assert s["step"]["max_ms"] == pytest.approx(7e-3)
    # a child whose parent started before `since_ns` takes nothing off
    # a parent row that is not there
    first_wait = named(tl.rows(), "wait")[0]
    late = tl.summary(since_ns=first_wait[1])
    assert late["step"]["count"] == 2 and late["wait"]["count"] == 3
    assert late["step"]["self_s"] == pytest.approx(2 * 3e-6)


def test_two_threads_lose_no_rows():
    n = 10_000
    tl = Timeline(clock_ns=ticking(), keep=2 * n)

    def record(name):
        for i in range(n):
            with tl.span(name, i):
                tl.count(name)

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=record, args=(name,))
                   for name in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
    finally:
        sys.setswitchinterval(prev)
    assert not any(t.is_alive() for t in threads)
    rows = tl.rows()
    assert len(rows) == 2 * n
    for name in ("a", "b"):
        assert [r[3] for r in named(rows, name)] == list(range(n))
    assert all(r[4] is None for r in rows)      # each thread its own stack
    assert tl.counters() == {"a": n, "b": n}


def test_add_takes_a_closed_row_under_the_threads_open_span():
    tl = Timeline(clock_ns=ticking())
    tl.add("timed.elsewhere", 10, 20)
    with tl.span("outer", 3):
        tl.add("timed.elsewhere", 30, 40, seq=3)
        with tl.span("inner", 3):
            tl.add("timed.elsewhere", 50, 60)
    other = threading.Thread(target=tl.add, args=("other.thread", 70, 80))
    with tl.span("held"):       # another thread's open span is no parent
        other.start()
        other.join(60.0)
    rows = tl.rows()
    assert named(rows, "timed.elsewhere") == [
        ("timed.elsewhere", 10, 20, None, None),
        ("timed.elsewhere", 30, 40, 3, "outer"),
        ("timed.elsewhere", 50, 60, None, "inner")]
    assert named(rows, "other.thread") == [("other.thread", 70, 80, None,
                                            None)]
    # a row's place in the ring is when it was added, whatever its stamps
    assert [r[0] for r in rows] == ["timed.elsewhere"] * 3 + [
        "inner", "outer", "other.thread", "held"]
    # and the summary takes an added row off its parent like any other
    assert tl.summary()["outer"]["self_s"] < tl.summary()["outer"]["total_s"]


def test_add_keeps_the_rings_bound():
    tl = Timeline(clock_ns=ticking(), keep=16)
    for i in range(40):
        tl.add("r", i, i + 1, seq=i)
    assert [r[3] for r in tl.rows()] == list(range(24, 40))


# -- the compile recorder -------------------------------------------------------

@pytest.fixture
def compile_cache(tmp_path):
    """The persistent cache in a directory of the test's own, and the
    default timeline (where the recorder writes) with the stamp to read
    it from."""
    from paddle_tpu import compilation_cache
    from paddle_tpu.obs.trace import default_timeline

    compilation_cache.enable(str(tmp_path / "xla"))
    compilation_cache.reset_counters()
    tl = default_timeline()
    try:
        yield compilation_cache, tl, tl.clock_ns()
    finally:
        compilation_cache.disable()
        compilation_cache.reset_counters()


def compile_rows(tl, since, fun):
    """The recorder's rows since `since` that carry `fun`'s name (tracing
    names it bare, lowering and the backend `jit(<fun>)`), and the cache
    reads, in the order they closed."""
    return [r for r in tl.rows()
            if r[2] >= since and (r[0] == "compile.cache_read"
                                  or r[0].endswith((":" + fun,
                                                    f":jit({fun})")))]


def test_a_fresh_jit_writes_its_phases_by_name_and_a_miss(compile_cache):
    cc, tl, _ = compile_cache

    def fresh_for_the_recorder(x):
        return jnp.tanh(x) * 3.0

    # compiles too: made before the readings
    x = jnp.arange(7.0, dtype=jnp.float32)
    cc.reset_counters()
    before = tl.counters()
    since = tl.clock_ns()
    jax.jit(fresh_for_the_recorder)(x)
    rows = compile_rows(tl, since, "fresh_for_the_recorder")
    assert [r[0] for r in rows] == [
        "compile.trace:fresh_for_the_recorder",
        "compile.lower:jit(fresh_for_the_recorder)",
        "compile.backend:jit(fresh_for_the_recorder)"]
    # each row ends when its event fired and is as long as jax said: the
    # phases follow one another on the timeline's own clock
    assert all(since <= r[1] <= r[2] for r in rows)
    assert rows[0][2] <= rows[1][2] <= rows[2][2]
    assert rows[1][1] >= rows[0][1] and rows[2][1] >= rows[1][1]
    delta = {k: v - before.get(k, 0) for k, v in tl.counters().items()
             if k.startswith("compile.")}
    assert delta == {"compile.cache_requests": 1, "compile.cache_misses": 1,
                     "compile.backend_compiles": 1}
    assert cc.counters() == {"hits": 0, "misses": 1}


def test_a_cached_jit_writes_its_read_inside_its_backend_row(compile_cache):
    cc, tl, _ = compile_cache

    def cached_for_the_recorder(x):
        return jnp.cos(x) - 2.0

    x = jnp.arange(9.0, dtype=jnp.float32)
    f = jax.jit(cached_for_the_recorder)
    f(x)                        # the miss that writes the entry
    jax.clear_caches()
    cc.reset_counters()
    before = tl.counters()
    since = tl.clock_ns()
    f(x)
    rows = compile_rows(tl, since, "cached_for_the_recorder")
    assert [r[0] for r in rows] == [
        "compile.trace:cached_for_the_recorder",
        "compile.lower:jit(cached_for_the_recorder)",
        "compile.cache_read",
        "compile.backend:jit(cached_for_the_recorder)"]
    read, backend = rows[2], rows[3]
    assert backend[1] <= read[1] <= read[2] <= backend[2]
    assert cc.counters() == {"hits": 1, "misses": 0}
    now = tl.counters()
    assert now["compile.cache_hits"] - before.get("compile.cache_hits", 0) == 1
    assert now.get("compile.cache_misses", 0) \
        == before.get("compile.cache_misses", 0)
    # the phase's seconds are the union of its rows: the read lies in the
    # backend's row and adds nothing
    covered = cc.compile_seconds(since)
    assert covered <= (tl.clock_ns() - since) / 1e9
    assert covered <= sum(r[2] - r[1] for r in rows) / 1e9 \
        - (read[2] - read[1]) / 1e9 + 1e-9


def test_a_compile_inside_a_span_names_it_as_parent(compile_cache):
    _, tl, _ = compile_cache

    def compiled_under_a_span(x):
        return x + 5.0

    x = jnp.arange(3.0, dtype=jnp.float32)
    since = tl.clock_ns()
    with tl.span("test.dispatch"):
        jax.jit(compiled_under_a_span)(x)
    rows = compile_rows(tl, since, "compiled_under_a_span")
    assert len(rows) == 3
    assert {r[4] for r in rows} == {"test.dispatch"}


@pytest.mark.parametrize("event,seconds,row", [
    ("/jax/core/compile/jaxpr_trace_duration", 0.25, "compile.trace:f"),
    # a jitted jnp wrapper passed through while tracing: thousands a step
    ("/jax/core/compile/jaxpr_trace_duration", 0.00002, None),
    ("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.00002,
     "compile.lower:f"),
    ("/jax/core/compile/backend_compile_duration", 0.00002,
     "compile.backend:f"),
    ("/jax/compilation_cache/cache_retrieval_time_sec", 0.5,
     "compile.cache_read"),
    ("/jax/compilation_cache/compile_time_saved_sec", 0.5, None),
    ("/jax/core/some_other_duration", 0.5, None),
])
def test_the_listener_rows_an_event_from_its_end_back(event, seconds, row):
    from paddle_tpu import compilation_cache as cc
    from paddle_tpu.obs.trace import default_timeline

    tl = default_timeline()
    since = tl.clock_ns()
    cc._on_duration(event, seconds, **({} if "cache" in event
                                       else {"fun_name": "f"}))
    now = tl.clock_ns()
    added = [r for r in tl.rows() if r[2] >= since]
    if row is None:
        assert added == []
        return
    (name, start, end, seq, parent), = added
    assert (name, seq, parent) == (row, None, None)
    assert since <= end <= now and end - start == int(seconds * 1e9)


def test_install_listeners_twice_registers_once():
    from jax._src import monitoring

    from paddle_tpu import compilation_cache as cc

    cc.install_listeners()
    cc.install_listeners()
    assert monitoring.get_event_duration_listeners().count(
        cc._on_duration) == 1
    assert monitoring.get_event_listeners().count(cc._on_event) == 1


def test_cache_counters_count_from_the_last_reset(compile_cache):
    cc, tl, _ = compile_cache
    x = jnp.arange(5.0, dtype=jnp.float32)
    jax.jit(lambda v: v * 11.0)(x)
    assert cc.counters()["misses"] >= 1
    cc.reset_counters()
    assert cc.counters() == {"hits": 0, "misses": 0}
    # the timeline's counters run on: the reset moved a baseline only
    assert tl.counters()["compile.cache_requests"] >= 1
    jax.jit(lambda v: v * 13.0)(x)
    assert cc.counters() == {"hits": 0, "misses": 1}


# -- the feeder ---------------------------------------------------------------

def test_feeder_records_one_row_of_each_span_a_batch():
    tl = Timeline(clock_ns=ticking())
    feeder = data.DataFeeder(timeline=tl)
    batches = list(feeder(data.batch_reader(reader(), BATCH)))
    assert len(batches) == N_BATCHES
    rows = tl.rows()
    for name in ("feeder.read", "feeder.convert", "feeder.queue_put",
                 "feeder.queue_wait", "feeder.device_put"):
        assert [r[3] for r in named(rows, name)] == list(range(N_BATCHES))
        assert all(r[4] is None for r in named(rows, name))
    c = tl.counters()
    assert c["feeder.batches"] == N_BATCHES
    nbytes = BATCH * FEATURES * 4 + BATCH * 8       # float32 x, int64 y
    assert c["feeder.h2d_bytes"] == N_BATCHES * nbytes == sum(
        np.asarray(leaf).nbytes for b in batches for leaf in b)
    assert 0 <= c["feeder.queue_depth_sum"] <= N_BATCHES * feeder.prefetch
    # of one batch: read, then convert, then the put, then the consumer
    for seq in range(N_BATCHES):
        order = [next(r for r in rows if r[0] == name and r[3] == seq)
                 for name in ("feeder.read", "feeder.convert",
                              "feeder.queue_put", "feeder.device_put")]
        assert [r[1] for r in order] == sorted(r[1] for r in order)


class FullQueue(Timeline):
    """The consumer opens `feeder.queue_wait` only once the worker has
    put all that fits: every `get` finds the queue as full as it can
    be, the one that finds the end sentinel included."""

    def __init__(self, prefetch, **kw):
        super().__init__(**kw)
        self.prefetch = prefetch
        self.put = 0                # batches known to be in or through
        self.moved = threading.Condition()

    def span(self, name, seq=None):
        if name == "feeder.read":
            # the worker reads batch `seq` (or finds the end) after
            # its put of batch `seq - 1` has returned
            with self.moved:
                self.put = seq
                self.moved.notify_all()
        elif name == "feeder.queue_wait":
            want = min(seq + self.prefetch, N_BATCHES)
            with self.moved:
                assert self.moved.wait_for(lambda: self.put >= want, 30.0)
            if seq + self.prefetch > N_BATCHES:
                # the sentinel fits too: it is in once the worker ends
                for t in feeder_threads():
                    t.join(30.0)
                    assert not t.is_alive()
        return super().span(name, seq)


def test_queue_depth_sum_counts_no_reading_for_the_sentinel():
    tl = FullQueue(2, clock_ns=ticking())
    feeder = data.DataFeeder(prefetch=tl.prefetch, timeline=tl)
    batches = list(feeder(data.batch_reader(reader(), BATCH)))
    assert len(batches) == N_BATCHES
    c = tl.counters()
    assert c["feeder.batches"] == N_BATCHES
    # the queue was full at each of the N_BATCHES + 1 gets
    assert c["feeder.queue_depth_sum"] == N_BATCHES * feeder.prefetch


class ThreadNoting(Timeline):
    """A timeline that also notes which thread opened each span."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.threads = {}

    def span(self, name, seq=None):
        self.threads.setdefault(name, set()).add(
            threading.current_thread().name)
        return super().span(name, seq)


SAMPLE_SIDE = 128      # 128 x 128 float32: `SLICED_MIN_SAMPLE_BYTES`


@pytest.fixture
def large_batches(monkeypatch):
    """A batch of BATCH samples of the least size counts as a large
    column, so that `image_reader`'s batches take the sliced copy."""
    assert SAMPLE_SIDE * SAMPLE_SIDE * 4 == B.SLICED_MIN_SAMPLE_BYTES
    monkeypatch.setattr(B, "SLICED_MIN_BYTES",
                        BATCH * B.SLICED_MIN_SAMPLE_BYTES)


def image_reader(n_batches=N_BATCHES):
    pool = np.random.RandomState(0).rand(
        BATCH, SAMPLE_SIDE, SAMPLE_SIDE).astype(np.float32)

    def read():
        for i in range(n_batches * BATCH):
            yield pool[i % BATCH], i % 3
    return read


def stack_threads():
    return {t for t in threading.enumerate()
            if t.name.startswith("paddle_tpu-stack")}


@pytest.mark.parametrize("columns,sliced", [("large", 1), ("small", 0)])
def test_feeder_counts_the_batches_it_stacked_in_slices(
        columns, sliced, large_batches):
    tl = ThreadNoting(clock_ns=ticking())
    read = image_reader() if columns == "large" else reader()
    batches = list(data.DataFeeder(timeline=tl)(
        data.batch_reader(read, BATCH)))
    assert len(batches) == N_BATCHES
    c = tl.counters()
    assert c["feeder.batches"] == N_BATCHES
    assert c["feeder.parallel_stacks"] == sliced * N_BATCHES
    # however the column was copied, the convert is one row a batch, a
    # root of the worker's thread; the copying threads record nothing
    converts = named(tl.rows(), "feeder.convert")
    assert [r[3] for r in converts] == list(range(N_BATCHES))
    assert all(r[4] is None for r in converts)
    assert tl.threads["feeder.convert"] == {"paddle_tpu-feeder"}
    assert not any(name.startswith("paddle_tpu-stack")
                   for names in tl.threads.values() for name in names)
    assert len(tl.rows()) == 5 * N_BATCHES
    if sliced:
        want = np.stack([x for x, _ in itertools.islice(
            image_reader()(), BATCH)])
        for x, y in batches:
            np.testing.assert_array_equal(np.asarray(x), want)


def test_a_custom_convert_fn_is_left_alone_and_counts_nothing(large_batches):
    tl = Timeline(clock_ns=ticking())
    seen = []

    def convert(raw):
        seen.append(len(raw))
        return B.stack_columns(raw)

    feeder = data.DataFeeder(convert_fn=convert, timeline=tl)
    assert len(list(feeder(data.batch_reader(image_reader(), BATCH)))) \
        == N_BATCHES
    assert seen == [BATCH] * N_BATCHES
    assert "feeder.parallel_stacks" not in tl.counters()


def test_two_feeders_share_the_copying_threads(large_batches):
    tl_a, tl_b = Timeline(clock_ns=ticking()), Timeline(clock_ns=ticking())
    a = data.DataFeeder(timeline=tl_a)(
        data.batch_reader(image_reader(), BATCH))
    b = data.DataFeeder(timeline=tl_b)(
        data.batch_reader(image_reader(), BATCH))
    assert len(list(zip(a, b))) == N_BATCHES    # both feeds in flight
    assert 1 <= len(stack_threads()) <= B.STACK_SLICES - 1
    before = stack_threads()
    list(data.DataFeeder(timeline=tl_a)(
        data.batch_reader(image_reader(), BATCH)))
    assert stack_threads() == before        # a third feeder adds none
    for tl in (tl_a, tl_b):
        assert tl.counters()["feeder.parallel_stacks"] >= N_BATCHES


def test_an_error_in_a_slice_reaches_the_consumer(
        monkeypatch, large_batches):
    def failing(out, col, lo, hi):
        if lo:
            raise MemoryError("no room for the slice")

    monkeypatch.setattr(B, "_copy_rows", failing)
    tl = Timeline(clock_ns=ticking())
    it = data.DataFeeder(timeline=tl)(
        data.batch_reader(image_reader(), BATCH))
    with pytest.raises(MemoryError, match="no room for the slice"):
        next(it)
    assert len(named(tl.rows(), "feeder.convert")) == 1     # closed


def test_prefetch_to_device_counts_host_bytes_once():
    tl = Timeline(clock_ns=ticking())
    feeder = data.DataFeeder(timeline=tl)
    ahead = data.feeder.prefetch_to_device(
        feeder(data.batch_reader(reader(), BATCH)), size=2, timeline=tl)
    assert len(list(ahead)) == N_BATCHES
    # the feeder's put and the prefetcher's re-put: two rows a batch,
    # and the second moved nothing
    assert len(named(tl.rows(), "feeder.device_put")) == 2 * N_BATCHES
    assert tl.counters()["feeder.h2d_bytes"] == N_BATCHES * (
        BATCH * FEATURES * 4 + BATCH * 8)


@pytest.mark.parametrize("columns", ["small", "large"])
@pytest.mark.parametrize("how", ["closed", "collected"])
def test_feeder_worker_ends_when_the_consumer_stops_early(
        how, columns, large_batches):
    before = set(feeder_threads())
    tl = Timeline(clock_ns=ticking())
    read = reader if columns == "small" else image_reader
    it = data.DataFeeder(timeline=tl)(
        data.batch_reader(read(n_batches=10_000), BATCH))
    next(it)
    worker, = set(feeder_threads()) - before
    if how == "closed":
        it.close()
    else:
        del it
        gc.collect()
    worker.join(30.0)
    assert not worker.is_alive()
    # the put it was blocked in closed its span like any other
    rows = tl.rows()
    assert len(named(rows, "feeder.queue_put")) in (
        len(named(rows, "feeder.convert")),
        len(named(rows, "feeder.convert")) - 1)


# -- the trainer --------------------------------------------------------------

def test_trainer_step_rows_frame_their_children():
    tl = Timeline(clock_ns=ticking())
    tr, state = small_trainer(tl)
    feeder = data.DataFeeder(timeline=tl)
    seen = []
    tr.train(state, lambda: feeder(data.batch_reader(reader(), BATCH)),
             num_passes=2, event_handler=seen.append)
    rows = tl.rows()
    steps = named(rows, "trainer.step")
    assert [r[3] for r in steps] == 2 * list(range(N_BATCHES))
    assert tl.counters()["trainer.steps"] == 2 * N_BATCHES == sum(
        isinstance(ev, E.EndIteration) for ev in seen)
    assert all(r[4] is None for r in steps)
    for name, parent, per_step in (
            ("trainer.next_batch", "trainer.step", 1),
            ("trainer.dispatch", "trainer.step", 1),
            ("trainer.handler", "trainer.step", 2),
            ("feeder.queue_wait", "trainer.next_batch", 1),
            ("feeder.device_put", "trainer.next_batch", 1)):
        found = named(rows, name)
        assert len(found) == per_step * len(steps), name
        assert {r[4] for r in found} == {parent}, name
    # the worker's rows are roots of their own thread
    assert {r[4] for r in named(rows, "feeder.convert")} == {None}
    for step in steps:
        inside = sorted((r for r in rows if r[4] == "trainer.step"
                         and step[1] <= r[1] and r[2] <= step[2]),
                        key=lambda r: r[1])
        assert [r[0] for r in inside] == [
            "trainer.next_batch", "trainer.handler", "trainer.dispatch",
            "trainer.handler"]
        assert all(r[3] == step[3] for r in inside)
        for a, b in zip(inside, inside[1:]):        # one after the other
            assert a[2] < b[1]
    s = tl.summary()
    assert s["trainer.step"]["self_s"] < s["trainer.step"]["total_s"]


@pytest.mark.parametrize("fault", ["reader", "handler"])
def test_an_error_closes_every_span_and_reaches_the_caller(fault):
    tl = Timeline(clock_ns=ticking())
    tr, state = small_trainer(tl)
    feeder = data.DataFeeder(timeline=tl)

    class Boom(Exception):
        pass

    def handler(ev):
        if isinstance(ev, E.EndIteration) and ev.batch_id == 2:
            raise Boom("handler")

    if fault == "reader":
        batches = lambda: feeder(
            data.batch_reader(reader(fail_at=2), BATCH))
        with pytest.raises(OSError, match="disk gone"):
            tr.train(state, batches)
    else:
        batches = lambda: feeder(data.batch_reader(reader(), BATCH))
        with pytest.raises(Boom, match="handler"):
            tr.train(state, batches, event_handler=handler)
    rows = tl.rows()
    # the iteration that failed is a row like the others, closed
    assert [r[3] for r in named(rows, "trainer.step")] == [0, 1, 2]
    assert tl.counters()["trainer.steps"] == (2 if fault == "reader" else 3)
    if fault == "reader":
        assert [r[3] for r in named(rows, "feeder.read")] == [0, 1, 2]
        # the wait that met the end of the feed is no batch's row
        assert [r[3] for r in named(rows, "feeder.queue_wait")] == [0, 1]
    with tl.span("after") as s:         # nothing left open on this thread
        pass
    assert named(tl.rows(), "after")[0][4] is None


def test_loop_is_clean_under_transfer_guard_and_compiles_nothing_more():
    tl = Timeline(clock_ns=ticking())
    tr, state = small_trainer(tl)
    feeder = data.DataFeeder(timeline=tl)
    batches = lambda: feeder(data.batch_reader(reader(), BATCH))
    read = []
    handler = lambda ev: read.append(
        float(ev.cost) if isinstance(ev, E.EndIteration) else None)
    state = tr.train(state, batches, event_handler=handler)     # warm-up
    with jax.transfer_guard_host_to_device("disallow"), \
            RecompileGuard(name="timed train loop") as g:
        tr.train(state, batches, event_handler=handler)
    assert g.compiles == 0
    assert len(named(tl.rows(), "trainer.step")) == 2 * N_BATCHES


def test_cmd_train_metrics_out_exports_the_timeline(tmp_path, capsys):
    from test_cli import CONFIG

    from paddle_tpu.cli import main

    cfg = tmp_path / "config.py"
    cfg.write_text(CONFIG)
    out = tmp_path / "metrics.prom"
    assert main(["train", "--config", str(cfg), "--batch-size", "32",
                 "--num-passes", "1", "--metrics-out", str(out)]) == 0
    capsys.readouterr()
    series = dict(line.rsplit(" ", 1) for line in
                  out.read_text().splitlines() if not line.startswith("#"))
    assert float(series["train_timeline_trainer_steps"]) >= 4
    assert float(series["train_timeline_feeder_batches"]) >= 4
    assert float(series["train_timeline_feeder_parallel_stacks"]) == 0
    for span in ("trainer_step", "trainer_next_batch", "trainer_dispatch",
                 "trainer_handler", "feeder_read", "feeder_convert",
                 "feeder_queue_put", "feeder_queue_wait",
                 "feeder_device_put"):
        assert float(series[f"train_timeline_{span}_count"]) >= 4
        assert f"train_timeline_{span}_self_s" in series


# -- names on the device -------------------------------------------------------

def _op_names(lowered) -> str:
    return lowered.as_text(debug_info=True)


def _train_step_text():
    model = nn.Sequential([nn.Dense(8, activation="relu"), nn.Dense(2)])
    loss_fn = lambda lo, la: jnp.mean(losses.softmax_cross_entropy(lo, la))
    opt = optim.adam(1e-3).with_transforms(clip_global_norm=1.0)
    step = make_train_step(model, loss_fn, opt, donate=False)
    tr = Trainer(model, loss_fn, opt, timeline=Timeline())
    state = tr.init_state(ShapeSpec((BATCH, FEATURES)))
    return _op_names(step.lower(
        state, jax.random.key(0), jnp.ones((BATCH, FEATURES)),
        jnp.zeros((BATCH,), jnp.int32)))


def _bare_adam_text():
    opt = optim.adam(1e-3)
    params = {"w": jnp.ones((3,))}
    return _op_names(jax.jit(opt.update).lower(
        params, opt.init(params), params, jnp.asarray(0, jnp.int32)))


def _fused_ce_text():
    f = lambda h, k, t: losses.chunked_lm_head_nll(h, k, t, chunk=8).sum()
    return _op_names(jax.jit(jax.grad(f)).lower(
        jnp.ones((2, 8, 4)), jnp.ones((4, 16)),
        jnp.zeros((2, 8), jnp.int32)))


def _flash_bwd_text():
    from paddle_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 256, 2, 64))
    f = lambda q: flash_attention(q, q, q, causal=True).sum()
    return _op_names(jax.jit(jax.grad(f)).lower(q))


@pytest.mark.parametrize("build,scope", [
    (_train_step_text, "/optimizer/"),
    (_train_step_text, "(loss)/"),          # jvp(loss), transpose(jvp(loss))
    (_bare_adam_text, "jit(update)/optimizer/"),
    (_fused_ce_text, "transpose(jvp(fused_ce))/"),
    (_flash_bwd_text, "transpose(jvp(flash_attention_bwd))/"),
], ids=["train_step-optimizer", "train_step-loss", "bare_adam-optimizer",
        "fused_ce", "flash_attention_bwd"])
def test_scope_is_in_the_lowered_op_names(build, scope):
    assert scope in build()


def _rnn_grad_jaxpr(run, init):
    from paddle_tpu.ops import rnn

    p = init(jax.random.key(0), 128, 128)
    x = jnp.ones((8, 4, 128))
    lens = jnp.full((8,), 4, jnp.int32)
    loss = lambda p, x: run(p, x, lens, impl="pallas")[0].sum()
    return str(jax.make_jaxpr(jax.grad(loss))(p, x))


def _ragged_jaxpr(quantized):
    from paddle_tpu.ops import ragged_paged_attention as RPA

    rows, page, hkv, dh, pages = 2, 4, 2, 8, 6
    q = jnp.ones((rows, 1, 2, dh))
    arena = jnp.ones((pages, page, hkv, dh))
    if quantized:
        arena = (jnp.ones((pages, page, hkv, dh), jnp.int8),
                 jnp.ones((pages, page, hkv)))
    table = jnp.zeros((rows, 3), jnp.int32)
    return str(jax.make_jaxpr(
        lambda *a: RPA.ragged_pallas(*a, page_size=page, max_len=12))(
            q, arena, arena, table, jnp.zeros((rows,), jnp.int32),
            jnp.ones((rows,), bool)))


def _kernel_jaxpr(kernel: str) -> str:
    from paddle_tpu.ops import rnn
    from paddle_tpu.ops.flash_attention import flash_attention

    if kernel == "flash_attention_fwd":
        q = jnp.ones((1, 256, 2, 64))
        return str(jax.make_jaxpr(
            lambda q: flash_attention(q, q, q, causal=True))(q))
    if kernel.startswith("ragged"):
        return _ragged_jaxpr(kernel.endswith("int8"))
    if kernel.startswith("gated_delta"):
        from paddle_tpu.ops import gated_delta

        x, g = jnp.ones((1, 16, 1, 8)), -jnp.ones((1, 16, 1))
        loss = lambda q: gated_delta.gated_delta_rule(
            q, x, x, g, -g, chunk=8, impl="pallas").sum()
        return str(jax.make_jaxpr(jax.grad(loss))(x))
    run, init = {"gru": (rnn.gru, rnn.init_gru_params),
                 "lstm": (rnn.lstm, rnn.init_lstm_params),
                 "rnn": (rnn.simple_rnn, rnn.init_rnn_params)}[
                     kernel.split("_")[1]]
    return _rnn_grad_jaxpr(run, init)


@pytest.mark.parametrize("kernel", [
    "flash_attention_fwd", "fused_gru_fwd", "fused_gru_bwd",
    "fused_lstm_fwd", "fused_lstm_bwd", "fused_rnn_fwd", "fused_rnn_bwd",
    "ragged_paged_attention", "ragged_paged_attention_int8",
    "gated_delta_fwd", "gated_delta_bwd"])
def test_every_pallas_call_has_a_name(kernel):
    """The name reaches the device trace's event name, which is how a
    roofline reader finds its kernel."""
    text = _kernel_jaxpr(kernel)
    assert "pallas_call" in text
    assert f"name={kernel}\n" in text or f"name={kernel} " in text


def test_no_pallas_call_in_ops_is_unnamed():
    """Eighteen `pallas_call`s in `ops/` (flash attention's two backward
    kernels since PR 32, the two grouped expert products since PR 34,
    the three row kernels since PR 40, the gated delta rule's two since
    PR 41), eighteen `name=`: a nineteenth brings its own."""
    import pathlib
    import re

    import paddle_tpu.ops as ops

    calls = names = 0
    for path in pathlib.Path(ops.__file__).parent.glob("*.py"):
        src = path.read_text()
        calls += len(re.findall(r"\bpl\.pallas_call\(", src))
        # a flash call whose window cuts adds a suffix to its name
        names += len(re.findall(
            r"^\s+name=\"\w+\"( \+ _name_suffix\(window\))?,$", src, re.M))
    assert calls == names == 18
