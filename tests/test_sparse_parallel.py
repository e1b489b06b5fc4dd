"""Sharded sparse-embedding + collectives tests on the 8-device CPU mesh
(reference test model: gserver/tests/test_CompareSparse.cpp compares
sparse-remote vs dense training in-process)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import mesh as mesh_lib
from paddle_tpu.parallel import (
    ShardedEmbedding,
    collectives,
    rowwise_sgd_update,
    shard_rows,
    sharded_embedding_bag,
    sharded_lookup,
    unique_rows_grad,
)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 virtual devices")

# host/device memory spaces differ per backend: TPU has pinned_host +
# device; XLA:CPU exposes only unpinned_host (compat.memory_kind
# degrades the offload shardings there, so the kinds below are what
# "host table" / "device rows" can legitimately look like)
HOST_KINDS = ("pinned_host", "unpinned_host")
DEV_KINDS = ("device", "unpinned_host", None)


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.build_mesh(mesh_lib.MeshConfig(data=2, model=4))


def _table(vocab=32, dim=6, seed=0):
    return jax.random.normal(jax.random.key(seed), (vocab, dim), jnp.float32)


def test_sharded_lookup_matches_dense(mesh):
    table = _table()
    sharded = shard_rows(table, mesh)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 32, (5, 7)))
    got = sharded_lookup(sharded, ids, mesh)
    want = jnp.take(table, ids, axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_sharded_lookup_under_jit(mesh):
    table = shard_rows(_table(), mesh)
    ids = jnp.asarray([0, 31, 7, 16])
    fn = jax.jit(lambda t, i: sharded_lookup(t, i, mesh))
    np.testing.assert_allclose(
        np.asarray(fn(table, ids)),
        np.asarray(jnp.take(_table(), jnp.asarray([0, 31, 7, 16]), axis=0)),
        rtol=1e-6)


def test_sharded_lookup_gradient_matches_dense(mesh):
    """Backward through the sharded lookup == dense scatter-add grads
    (the SelectedRows semantics check)."""
    table = _table()
    ids = jnp.asarray([1, 1, 5, 31])
    cot = jax.random.normal(jax.random.key(1), (4, 6), jnp.float32)

    def dense_loss(t):
        return jnp.vdot(jnp.take(t, ids, axis=0), cot)

    def sharded_loss(t):
        return jnp.vdot(sharded_lookup(t, ids, mesh), cot)

    g_dense = jax.grad(dense_loss)(table)
    g_sharded = jax.grad(sharded_loss)(shard_rows(table, mesh))
    np.testing.assert_allclose(
        np.asarray(g_sharded), np.asarray(g_dense), rtol=1e-6)


@pytest.mark.slow


def test_sharded_bag_combiners(mesh):
    table = _table()
    sharded = shard_rows(table, mesh)
    ids = jnp.asarray([0, 3, 3, 9, 20])
    seg = jnp.asarray([0, 0, 1, 1, 1])
    for combiner in ("sum", "mean", "sqrtn"):
        got = sharded_embedding_bag(sharded, ids, seg, 2, mesh,
                                    combiner=combiner)
        vecs = jnp.take(table, ids, axis=0)
        sums = jax.ops.segment_sum(vecs, seg, num_segments=2)
        counts = jnp.asarray([2.0, 3.0])[:, None]
        want = {"sum": sums, "mean": sums / counts,
                "sqrtn": sums / jnp.sqrt(counts)}[combiner]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5)
    with pytest.raises(ValueError, match="combiner"):
        sharded_embedding_bag(sharded, ids, seg, 2, mesh, combiner="bogus")


def test_rowwise_sgd_update_sharded_matches_dense(mesh):
    table = _table()
    ids = jnp.asarray([2, 2, 17, 30])  # duplicate rows must both apply
    grads = jax.random.normal(jax.random.key(2), (4, 6), jnp.float32)
    want = rowwise_sgd_update(table, ids, grads, 0.1)  # dense path
    got = rowwise_sgd_update(shard_rows(table, mesh), ids, grads, 0.1, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    # untouched rows unchanged
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(table)[0])


def test_unique_rows_grad():
    ids = jnp.asarray([4, 4, 9, 4])
    grads = jnp.ones((4, 3), jnp.float32)
    uids, summed = unique_rows_grad(ids, grads, max_unique=4)
    got = {int(i): np.asarray(summed)[k] for k, i in enumerate(np.asarray(uids))}
    np.testing.assert_allclose(got[4], [3, 3, 3])
    np.testing.assert_allclose(got[9], [1, 1, 1])


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_sharded_embedding_module_end_to_end(mesh):
    """Tiny sparse-embedding training loop: loss decreases and only
    touched rows move (the test_CompareSparse equivalence idea)."""
    emb = ShardedEmbedding(vocab=30, dim=4, mesh=mesh, init_scale=0.1)
    table = emb.init(jax.random.key(0))
    assert table.shape[0] % 4 == 0  # padded to the axis
    target = jax.random.normal(jax.random.key(3), (4,), jnp.float32)
    ids = jnp.asarray([1, 7, 19])

    def loss_fn(t):
        vecs = emb.lookup(t, ids)
        return jnp.mean((vecs - target) ** 2)

    before = float(loss_fn(table))
    t0 = np.asarray(table).copy()
    for _ in range(20):
        row_grads = jax.grad(
            lambda t: loss_fn(t))(table)  # dense grad for the check below
        touched = jnp.take(row_grads, ids, axis=0)
        table = emb.apply_row_grads(table, ids, touched, lr=0.5)
    after = float(loss_fn(table))
    assert after < before * 0.5, (before, after)
    # untouched rows identical
    t1 = np.asarray(table)
    untouched = [i for i in range(30) if i not in (1, 7, 19)]
    np.testing.assert_allclose(t1[untouched], t0[untouched])


def test_shard_rows_requires_divisible(mesh):
    with pytest.raises(ValueError, match="divisible"):
        shard_rows(_table(vocab=30), mesh)


# ---- collectives ----

def test_device_all_reduce_mean(mesh):
    x = jnp.arange(16, dtype=jnp.float32).reshape(2, 8)
    x_sharded = jax.device_put(
        x, jax.NamedSharding(mesh, P("data")))
    got = collectives.device_all_reduce_mean(x_sharded, mesh)
    want = np.broadcast_to(np.asarray(x).mean(0, keepdims=True), (2, 8))
    np.testing.assert_allclose(np.asarray(got), want)


def test_collectives_in_shard_map(mesh):
    """reduce_scatter then all_gather round-trips to all_reduce."""

    def body(x):
        rs = collectives.reduce_scatter(x, "data")
        return collectives.all_gather(rs, "data")

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                          out_specs=P("data"))
    x = jnp.arange(32, dtype=jnp.float32).reshape(4, 8)
    got = fn(x)
    # per data-shard: full sum broadcast
    want = np.asarray(x).reshape(2, 2, 8).sum(0, keepdims=True)
    want = np.broadcast_to(want, (2, 2, 8)).reshape(4, 8)
    np.testing.assert_allclose(np.asarray(got), want)


def test_ppermute_ring(mesh):
    def body(x):
        return collectives.ppermute_ring(x, "data", shift=1)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                          out_specs=P("data"))
    x = jnp.asarray([[1.0], [2.0]])
    got = np.asarray(fn(x)).reshape(-1)
    np.testing.assert_allclose(got, [2.0, 1.0])


def test_broadcast_from(mesh):
    x = jnp.asarray([[10.0], [20.0]])  # shard0=10, shard1=20 on data axis
    x = jax.device_put(x, jax.NamedSharding(mesh, P("data")))
    got = collectives.device_broadcast_from(x, mesh, source=1)
    np.testing.assert_allclose(np.asarray(got).reshape(-1), [20.0])


# ---- all-to-all exchange path (round-2: VERDICT item 4) ----------------

@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_alltoall_lookup_matches_dense(mesh):
    from paddle_tpu.parallel import alltoall_lookup

    table = _table()
    sharded = shard_rows(table, mesh)
    # ids sharded over the model axis: size divisible by 4
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 32, 24))
    got = alltoall_lookup(sharded, ids, mesh)
    want = jnp.take(table, ids, axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_alltoall_lookup_out_of_range_zero(mesh):
    from paddle_tpu.parallel import alltoall_lookup

    table = _table()
    sharded = shard_rows(table, mesh)
    ids = jnp.asarray([0, -1, 31, 32, 5, -7, 12, 99])
    got = alltoall_lookup(sharded, ids, mesh)
    want = np.take(np.asarray(table), np.clip(np.asarray(ids), 0, 31), axis=0)
    want[[1, 3, 5, 7]] = 0.0
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_alltoall_lookup_skewed_ids(mesh):
    """Worst-case routing: every id owned by one shard — the default
    capacity (K/n) must still be lossless."""
    from paddle_tpu.parallel import alltoall_lookup

    table = _table()
    sharded = shard_rows(table, mesh)
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 8, 16))  # shard 0 only
    got, overflow = alltoall_lookup(sharded, ids, mesh, return_overflow=True)
    want = jnp.take(table, ids, axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    assert int(overflow) == 0


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_alltoall_capacity_overflow_detected(mesh):
    from paddle_tpu.parallel import alltoall_lookup

    table = _table()
    sharded = shard_rows(table, mesh)
    ids = jnp.zeros((16,), jnp.int32)  # all to shard 0, 4 per device
    got, overflow = alltoall_lookup(sharded, ids, mesh, capacity=1,
                                    return_overflow=True)
    # 4 model shards hold 4 ids each, all owned by shard 0: capacity 1
    # keeps one per shard, drops 3 per shard
    assert int(overflow) == 4 * 3
    # kept slots still correct
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(table[0]),
                               rtol=1e-6)


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_alltoall_lookup_grad_flows_to_table(mesh):
    """Autodiff through the owner-routed exchange: table gradient equals
    the dense lookup's scatter-add gradient."""
    from paddle_tpu.parallel import alltoall_lookup

    table = _table(16, 4)
    sharded = shard_rows(table, mesh)
    ids = jnp.asarray(np.random.RandomState(3).randint(0, 16, 8))
    w = jnp.asarray(np.random.RandomState(4).randn(8, 4), jnp.float32)

    def loss_sharded(tab):
        return jnp.sum(alltoall_lookup(tab, ids, mesh) * w)

    def loss_dense(tab):
        return jnp.sum(jnp.take(tab, ids, axis=0) * w)

    g_sharded = jax.grad(loss_sharded)(sharded)
    g_dense = jax.grad(loss_dense)(table)
    np.testing.assert_allclose(np.asarray(g_sharded), np.asarray(g_dense),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # tier-1 budget guard: >10s-class test, slow lane
def test_alltoall_push_row_grads_matches_dense(mesh):
    from paddle_tpu.parallel import alltoall_push_row_grads

    table = _table(32, 6)
    sharded = shard_rows(table, mesh)
    rng = np.random.RandomState(5)
    ids = jnp.asarray(rng.randint(0, 32, 16))
    grads = jnp.asarray(rng.randn(16, 6), jnp.float32)
    lr = 0.3
    new = alltoall_push_row_grads(sharded, ids, grads, lr, mesh)
    want = np.array(table)
    for i, g in zip(np.asarray(ids), np.asarray(grads)):
        want[i] -= lr * g
    np.testing.assert_allclose(np.asarray(new), want, rtol=1e-5, atol=1e-6)


def test_unique_rows_grad_overflow_flag():
    ids = jnp.asarray([1, 2, 3, 4, 1])
    grads = jnp.ones((5, 2))
    _, _, overflow = unique_rows_grad(ids, grads, max_unique=2,
                                      return_overflow=True)
    assert int(overflow) == 2  # 4 distinct ids, bound 2
    _, _, ok = unique_rows_grad(ids, grads, max_unique=4,
                                return_overflow=True)
    assert int(ok) == 0


def test_alltoall_exchange_volume_in_hlo(mesh):
    """The micro-bench claim (VERDICT item 4): compiled HLO's all-to-all
    traffic is ∝ K·D while the psum path all-reduces shards·K·D."""
    from paddle_tpu.parallel import alltoall_lookup

    table = _table(32, 8)
    sharded = shard_rows(table, mesh)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 32, 16))
    k, d, n = 16, 8, 4

    def count_bytes(hlo_text, opname):
        import re
        total = 0
        for m in re.finditer(
                r"(\w+)\[([\d,]*)\][^\n]*" + opname + r"\(", hlo_text):
            dt, shape = m.group(1), m.group(2)
            size = 1
            for s in shape.split(","):
                if s:
                    size *= int(s)
            width = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4}.get(dt, 4)
            total += size * width
        return total

    a2a_hlo = jax.jit(
        lambda t, i: alltoall_lookup(t, i, mesh)).lower(sharded, ids) \
        .compile().as_text()
    psum_hlo = jax.jit(
        lambda t, i: sharded_lookup(t, i, mesh)).lower(sharded, ids) \
        .compile().as_text()

    a2a_bytes = count_bytes(a2a_hlo, "all-to-all")
    ar_bytes = count_bytes(psum_hlo, "all-reduce")
    # vector traffic per device: a2a ~ K/n * D * 4B (+ id ints);
    # psum all-reduce ~ K * D * 4B
    assert a2a_bytes > 0 and ar_bytes > 0
    assert a2a_bytes <= (k * d * 4) + (k * 4) * 2  # ≤ K·D + id traffic
    assert ar_bytes >= k * d * 4  # the psum path moves the full K·D per shard


class TestHostOffloadEmbedding:
    """The >HBM-table story (SURVEY §7 hard part; reference analog:
    SparsePrefetchRowCpuMatrix host-RAM tables with row pulls)."""

    def _emb(self, vocab=32, dim=4):
        from paddle_tpu.parallel.sparse import HostOffloadEmbedding

        return HostOffloadEmbedding(vocab, dim, init_scale=0.1)

    def test_table_lives_in_host_memory(self):
        emb = self._emb()
        table = emb.init(jax.random.key(0))
        assert table.sharding.memory_kind in HOST_KINDS

    def test_lookup_matches_dense_and_lands_on_device(self):
        emb = self._emb()
        table = emb.init(jax.random.key(0))
        ids = jnp.asarray([3, 7, 3, 31])
        rows = jax.jit(emb.lookup)(table, ids)
        assert rows.sharding.memory_kind in DEV_KINDS
        host_np = np.asarray(jax.device_get(table))
        np.testing.assert_allclose(np.asarray(rows), host_np[np.asarray(ids)],
                                   rtol=1e-6)

    def test_row_sparse_update_touches_only_rows(self):
        emb = self._emb()
        table = emb.init(jax.random.key(0))
        before = np.asarray(jax.device_get(table))
        ids = jnp.asarray([2, 2, 5, -1])  # dup + padding id
        grads = jnp.ones((4, 4), jnp.float32)
        new_table = emb.update(
            table, ids, grads, jnp.asarray(0.5, jnp.float32))
        assert new_table.sharding.memory_kind in HOST_KINDS
        after = np.asarray(jax.device_get(new_table))
        np.testing.assert_allclose(after[2], before[2] - 2 * 0.5, rtol=1e-5)
        np.testing.assert_allclose(after[5], before[5] - 0.5, rtol=1e-5)
        untouched = [i for i in range(32) if i not in (2, 5)]
        np.testing.assert_allclose(after[untouched], before[untouched])

    def test_train_step_end_to_end(self):
        """Gradient flows through the host gather: differentiate at the
        gathered rows (CTR-style) and push row grads back."""
        emb = self._emb(vocab=16, dim=3)
        table = emb.init(jax.random.key(0))
        ids = jnp.asarray([1, 4, 9])
        target = jnp.ones((3, 3), jnp.float32)

        @jax.jit
        def grads(table):
            rows = emb.lookup(table, ids)

            def loss_fn(r):
                return jnp.mean((r - target) ** 2)

            return jax.value_and_grad(loss_fn)(rows)

        def step(table):
            loss, row_g = grads(table)
            new_table = emb.update(
                table, ids, row_g, jnp.asarray(1.0, jnp.float32))
            return new_table, loss

        losses = []
        for _ in range(40):
            table, loss = step(table)
            losses.append(float(loss))
        assert table.sharding.memory_kind in HOST_KINDS
        assert losses[-1] < losses[0] * 0.2, (losses[0], losses[-1])
