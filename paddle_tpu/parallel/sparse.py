"""Sharded sparse-embedding training (the reference's "EP" path).

Reference machinery being replaced: embedding tables row-sharded across
parameter servers with trainers prefetching only touched rows
(reference: math/SparseRowMatrix.h:206 SparsePrefetchRowCpuMatrix,
pserver/ParameterServer2.h:510 getParameterSparse,
gserver/gradientmachines/NeuralNetwork.cpp:208-245 prefetch) and
SelectedRows {rows, values} sparse gradients (reference:
framework/selected_rows.h, operators/math/selected_rows_functor.*).

TPU-native design: the table lives row-sharded over the mesh `model`
axis. A lookup runs under shard_map — each shard takes from its local
rows with out-of-range ids masked to zero, then one psum over the model
axis assembles full vectors. The exchange is a single ICI all-reduce
instead of per-row RPCs. Gradients flow through the same program, so
backward is a local scatter-add + the mirrored psum — SelectedRows
semantics without a dense [V, D] gradient materializing per step when
using `rowwise_update` (the reference's sparse-row optimizer update,
parameter/FirstOrderOptimizer.h SparseMomentum analog).
"""

from __future__ import annotations

import functools
from typing import Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.parallel import compat

from paddle_tpu.core.mesh import MODEL_AXIS
from paddle_tpu.ops.embedding import combine_bags


@runtime_checkable
class LookupSurface(Protocol):
    """The ONE shared lookup surface every embedding backing exposes —
    `ShardedEmbedding`, `HostOffloadEmbedding` and the pserver-backed
    `PServerEmbedding` all satisfy it structurally, so call sites (the
    CTR models, the tiered embed cache, the streaming trainer) swap
    backings without a single isinstance check.

    Contract highlights shared by every implementation:
      - `lookup(table, ids)`: [K] ids -> [K, D] rows ON DEVICE;
        out-of-range ids (e.g. -1 padding) give ZERO vectors;
      - `apply_row_grads(table, ids, row_grads, lr)`: row-sparse SGD,
        padding ids dropped (`masked_row_delta` is the one home of that
        rule), returns the updated table handle;
      - `alltoall_lookup` / `alltoall_push_row_grads`: the capacity-
        bounded aliases the distributed CTR call sites use — single-
        process backings honor `return_overflow` with a zero counter.

    Backings that can serve a read-through cache additionally expose
    the `pull_rows`/`owner_of`/`n_shards`/`poll_watermarks`/
    `shard_failovers` surface (see serve.embed_cache.CacheBacking)."""

    vocab: int
    dim: int

    def init(self, rng): ...

    def lookup(self, table, ids): ...

    def apply_row_grads(self, table, ids, row_grads, lr): ...

    def alltoall_lookup(self, table, ids, *, capacity=None,
                        return_overflow: bool = False): ...

    def alltoall_push_row_grads(self, table, ids, row_grads, lr, *,
                                capacity=None): ...


def shard_rows(table, mesh: Mesh, axis: str = MODEL_AXIS):
    """Row-shard a [V, D] table over a mesh axis; V must divide evenly
    (pad the vocab up — the reference's block-sharding padded too)."""
    n = mesh.shape[axis]
    if table.shape[0] % n != 0:
        raise ValueError(
            f"vocab {table.shape[0]} not divisible by {axis} axis size {n}; "
            f"pad the table")
    return jax.device_put(table, NamedSharding(mesh, P(axis, None)))


def sharded_lookup(table, ids, mesh: Mesh, *, axis: str = MODEL_AXIS):
    """Lookup into a row-sharded table: local masked take + one psum.

    table: [V, D] sharded P(axis, None); ids: int array of any shape
    (replicated or data-sharded). Returns [*ids.shape, D] with the
    table's sharding-free (replicated over `axis`) result.

    Out-of-range ids (negative or >= V) return ZERO vectors — unlike
    jnp.take, which wraps/clips. This makes -1 a natural padding id, but
    means sharded and dense lookups only agree on in-range ids.
    """
    n = mesh.shape[axis]
    vocab = table.shape[0]
    rows_per_shard = vocab // n

    def body(tab_shard, ids_local):
        shard = jax.lax.axis_index(axis)
        lo = shard * rows_per_shard
        local = ids_local - lo
        in_range = (local >= 0) & (local < rows_per_shard)
        safe = jnp.clip(local, 0, rows_per_shard - 1)
        vecs = jnp.take(tab_shard, safe, axis=0)
        vecs = jnp.where(in_range[..., None], vecs, 0)
        return jax.lax.psum(vecs, axis_name=axis)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(),
    )
    return fn(table, ids)


def _route_to_owners(ids_local, n: int, rows_per_shard: int, capacity: int):
    """Bucket local ids by owning shard into a fixed [n, capacity] send
    buffer (pad id -1). Returns (send_ids, order, pos_in_run, kept_mask,
    overflow_count). Static shapes throughout (XLA requirement); overflow
    beyond `capacity` per destination is dropped and counted."""
    k = ids_local.shape[0]
    owner = jnp.where(
        (ids_local >= 0) & (ids_local < n * rows_per_shard),
        ids_local // rows_per_shard, n)  # invalid ids -> virtual owner n
    order = jnp.argsort(owner, stable=True)
    sorted_ids = ids_local[order]
    sorted_owner = owner[order]
    first_idx = jnp.searchsorted(sorted_owner, jnp.arange(
        n + 1, dtype=jnp.int32))
    pos_in_run = jnp.arange(k, dtype=jnp.int32) - first_idx[sorted_owner]
    kept = (pos_in_run < capacity) & (sorted_owner < n)
    send = jnp.full((n, capacity), -1, ids_local.dtype)
    send = send.at[sorted_owner, pos_in_run].set(
        jnp.where(kept, sorted_ids, -1), mode="drop")
    counts = first_idx[1:] - first_idx[:-1]  # per-owner demand [n+1]->[n]
    overflow = jnp.sum(jnp.maximum(counts[:n] - capacity, 0))
    return send, order, pos_in_run, kept, overflow


def _local_take(tab_shard, ids_global, lo, rows_per_shard):
    local = ids_global - lo
    ok = (local >= 0) & (local < rows_per_shard)
    safe = jnp.clip(local, 0, rows_per_shard - 1)
    vecs = jnp.take(tab_shard, safe, axis=0)
    return jnp.where(ok[..., None], vecs, 0)


def alltoall_lookup(table, ids, mesh: Mesh, *, axis: str = MODEL_AXIS,
                    capacity: Optional[int] = None,
                    return_overflow: bool = False):
    """Lookup into a row-sharded table via owner-routing + all-to-all —
    the SURVEY §2.8 EP exchange (reference:
    pserver/ParameterServer2.h:510 getParameterSparse pulls only touched
    rows over the network; here the 'network' is ICI all-to-all).

    Unlike sharded_lookup (psum of mostly-zero [K, D] contributions from
    every shard — volume ∝ shards·K·D), this routes each id to its owning
    shard and moves each result vector over ICI exactly once: aggregate
    exchange volume ∝ K·D.

    table: [V, D] sharded P(axis, None).
    ids:   [K] int ids, SHARDED over `axis` (each device owns K/n ids —
           the data-sharded CTR batch layout). K must divide the axis.
    capacity: per-(src, dst) routing slots. Default K/n (always safe —
           worst case every local id hits one owner). Lower values cut
           the exchange volume to capacity·n·D per device but ids beyond
           capacity for one destination are dropped (zero vectors);
           check with return_overflow=True.

    Returns [K, D] vectors (sharded over `axis` like ids), out-of-range
    ids give zero vectors. With return_overflow=True returns
    (vectors, overflow) where overflow is the global count of dropped
    ids (0 when capacity is sufficient).
    """
    n = mesh.shape[axis]
    vocab, dim = table.shape
    rows_per_shard = vocab // n
    k = ids.shape[0]
    enforce_div = k % n == 0
    if not enforce_div:
        raise ValueError(f"ids size {k} not divisible by axis size {n}")
    k_loc = k // n
    cap = capacity if capacity is not None else k_loc

    def body(tab_shard, ids_local):
        shard = jax.lax.axis_index(axis)
        lo = shard * rows_per_shard
        send, order, pos_in_run, kept, overflow = _route_to_owners(
            ids_local, n, rows_per_shard, cap)
        # ship id requests to owners (int traffic, tiny)
        recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=True)  # [n, cap]
        # serve local rows for every requester
        vecs = _local_take(tab_shard, recv, lo, rows_per_shard)  # [n,cap,D]
        # ship vectors back: [j, c] -> requester j's slot c
        back = jax.lax.all_to_all(vecs, axis, 0, 0, tiled=True)
        # un-permute into original id order
        owner_sorted = jnp.clip(ids_local[order] // rows_per_shard, 0, n - 1)
        got = back[owner_sorted, jnp.clip(pos_in_run, 0, cap - 1)]
        got = jnp.where(kept[:, None], got, 0)
        out = jnp.zeros((k_loc, dim), got.dtype).at[order].set(got)
        return out, jax.lax.psum(overflow, axis_name=axis)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis)),
        out_specs=(P(axis, None), P()),
    )
    out, overflow = fn(table, ids)
    return (out, overflow) if return_overflow else out


def alltoall_push_row_grads(table, ids, row_grads, lr,
                            mesh: Mesh, *, axis: str = MODEL_AXIS,
                            capacity: Optional[int] = None):
    """SGD update of only the touched rows with owner-routed grads —
    the sparse push mirroring alltoall_lookup (reference: trainer->pserver
    sparse gradient push, ParameterServer2.h addGradient sparse path).

    ids/row_grads are sharded over `axis` ([K] / [K, D]); grads for the
    same row from different devices accumulate. Returns the updated
    sharded table; no dense [V, D] gradient and no shards·K·D traffic.
    """
    n = mesh.shape[axis]
    vocab, dim = table.shape
    rows_per_shard = vocab // n
    k = ids.shape[0]
    if k % n != 0:
        raise ValueError(f"ids size {k} not divisible by axis size {n}")
    cap = capacity if capacity is not None else k // n

    def body(tab_shard, ids_local, grads_local):
        shard = jax.lax.axis_index(axis)
        lo = shard * rows_per_shard
        send_ids, order, pos_in_run, kept, _ = _route_to_owners(
            ids_local, n, rows_per_shard, cap)
        # pack grads into the same [n, cap, D] layout as the id routing
        sorted_owner = jnp.clip(ids_local[order] // rows_per_shard, 0, n - 1)
        send_g = jnp.zeros((n, cap, dim), grads_local.dtype)
        send_g = send_g.at[sorted_owner, pos_in_run].set(
            jnp.where(kept[:, None], grads_local[order], 0), mode="drop")
        recv_ids = jax.lax.all_to_all(send_ids, axis, 0, 0, tiled=True)
        recv_g = jax.lax.all_to_all(send_g, axis, 0, 0, tiled=True)
        local = recv_ids.reshape(-1) - lo
        ok = (recv_ids.reshape(-1) >= 0) & (local >= 0) & (local < rows_per_shard)
        safe = jnp.clip(local, 0, rows_per_shard - 1)
        contrib = jnp.where(ok[:, None], recv_g.reshape(-1, dim), 0)
        return tab_shard.at[safe].add(
            -lr * contrib.astype(tab_shard.dtype))

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis, None)),
        out_specs=P(axis, None),
    )
    return fn(table, ids, row_grads)


def sharded_embedding_bag(table, ids, segment_ids, num_segments: int,
                          mesh: Mesh, *, axis: str = MODEL_AXIS,
                          combiner: str = "sum"):
    """Bag-combine on top of sharded_lookup: the CTR sparse-feature path.
    Segment-sum happens AFTER the psum so each shard only moves [K, D]
    vectors once over ICI."""
    vecs = sharded_lookup(table, ids, mesh, axis=axis)  # [K, D]
    return combine_bags(vecs, ids, segment_ids, num_segments, combiner,
                        table.dtype)


def masked_row_delta(num_rows: int, dtype, ids, row_grads, lr):
    """(safe_ids, -lr*masked_grads): THE home of the padding-id rule —
    out-of-range ids (e.g. -1 padding) contribute ZERO and are clipped
    in-bounds so a scatter-add can't wrap them to the last row. Shared
    by rowwise_sgd_update and HostOffloadEmbedding."""
    in_range = (ids >= 0) & (ids < num_rows)
    safe = jnp.clip(ids, 0, num_rows - 1)
    contrib = jnp.where(in_range[:, None], row_grads, 0)
    return safe, (-lr * contrib).astype(dtype)



def rowwise_sgd_update(table, ids, row_grads, lr, mesh: Optional[Mesh] = None,
                       *, axis: str = MODEL_AXIS):
    """Apply SGD to ONLY the touched rows (SelectedRows-style update;
    reference: operators/sgd_op kernel's SelectedRows branch +
    SparseRowCpuMatrix sgdUpdate, math/SparseRowMatrix.h:106).

    ids: [K] row indices (duplicates fine — contributions add);
    row_grads: [K, D] gradients for those rows.
    With a mesh, the scatter-add runs under shard_map so each shard only
    touches its local rows and no dense [V, D] gradient ever exists.
    """
    if mesh is None:
        safe, delta = masked_row_delta(table.shape[0], table.dtype, ids,
                                       row_grads, lr)
        return table.at[safe].add(delta)

    n = mesh.shape[axis]
    rows_per_shard = table.shape[0] // n

    def body(tab_shard, ids_g, grads_g):
        shard = jax.lax.axis_index(axis)
        lo = shard * rows_per_shard
        local = ids_g - lo
        in_range = (local >= 0) & (local < rows_per_shard)
        safe = jnp.clip(local, 0, rows_per_shard - 1)
        contrib = jnp.where(in_range[:, None], grads_g, 0)
        return tab_shard.at[safe].add(-lr * contrib.astype(tab_shard.dtype))

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(), P()),
        out_specs=P(axis, None),
    )
    return fn(table, ids, row_grads)


def unique_rows_grad(ids, row_grads, max_unique: Optional[int] = None,
                     *, return_overflow: bool = False):
    """Deduplicate (ids, grads) into (unique_ids, summed_grads) with a
    static size — the SelectedRows merge (reference:
    operators/math/selected_rows_functor.cc MergeAdd). Padding slots get
    id 0 with zero grad, so downstream scatter-adds are no-ops.

    max_unique defaults to ids.size (always safe). If you pass a smaller
    max_unique and the batch has more distinct ids than that, jnp.unique
    truncates — pass return_overflow=True to get a third output counting
    the dropped distinct ids (0 when the bound held) and assert on it;
    only under-size max_unique when the id distribution guarantees the
    bound.
    """
    if max_unique is None:
        max_unique = ids.size
    uids, inv = jnp.unique(
        ids, return_inverse=True, size=max_unique, fill_value=0)
    summed = jax.ops.segment_sum(row_grads, inv.reshape(-1),
                                 num_segments=max_unique)
    if return_overflow:
        flat = jnp.sort(ids.reshape(-1))
        distinct = 1 + jnp.sum(flat[1:] != flat[:-1])
        return uids, summed, jnp.maximum(distinct - max_unique, 0)
    return uids, summed


class ShardedEmbedding:
    """Module-flavored wrapper holding vocab/dim + mesh placement, for use
    inside models that train large sparse tables (reference:
    gserver/layers/TableProjection.cpp + SparseRemoteParameterUpdater)."""

    def __init__(self, vocab: int, dim: int, mesh: Mesh, *,
                 axis: str = MODEL_AXIS, name: str = "embedding",
                 init_scale: float = 0.01):
        n = mesh.shape[axis]
        self.padded_vocab = ((vocab + n - 1) // n) * n
        self.vocab, self.dim, self.mesh, self.axis = vocab, dim, mesh, axis
        self.name = name
        self.init_scale = init_scale

    def init(self, rng):
        # Draw over the REAL vocab, then zero-pad to the sharded shape:
        # jax.random draws are shape-dependent, so sampling the padded
        # shape directly would give every row different init values on
        # every mesh-axis size (an n-way table would not reproduce the
        # single-device run even bit-near). Pad rows are unreachable —
        # ids are < vocab, so no lookup reads them and no grad push
        # touches them — making zeros semantically inert.
        table = jax.random.normal(
            rng, (self.vocab, self.dim), jnp.float32) * self.init_scale
        table = jnp.pad(table, ((0, self.padded_vocab - self.vocab), (0, 0)))
        return shard_rows(table, self.mesh, self.axis)

    def lookup(self, table, ids):
        return sharded_lookup(table, ids, self.mesh, axis=self.axis)

    def alltoall_lookup(self, table, ids, *, capacity=None,
                        return_overflow=False):
        """Owner-routed lookup (preferred at scale — K·D exchange)."""
        return alltoall_lookup(table, ids, self.mesh, axis=self.axis,
                               capacity=capacity,
                               return_overflow=return_overflow)

    def alltoall_push_row_grads(self, table, ids, row_grads, lr, *,
                                capacity=None):
        return alltoall_push_row_grads(
            table, ids, row_grads, lr, self.mesh, axis=self.axis,
            capacity=capacity)

    def bag(self, table, ids, segment_ids, num_segments, combiner="sum"):
        return sharded_embedding_bag(
            table, ids, segment_ids, num_segments, self.mesh,
            axis=self.axis, combiner=combiner)

    def apply_row_grads(self, table, ids, row_grads, lr):
        return rowwise_sgd_update(
            table, ids, row_grads, lr, self.mesh, axis=self.axis)


# ---------------------------------------------------------------------
# host-offloaded tables (> HBM capacity)
# ---------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("vocab", "host_sh", "dev_sh"))
def _host_gather(table, ids, *, vocab: int, host_sh, dev_sh):
    """HostOffloadEmbedding.lookup's body: clip + gather in the host
    region, rows to device memory, out-of-range rows zeroed there."""
    from jax.experimental.compute_on import compute_on

    in_range = (ids >= 0) & (ids < vocab)
    ids_h = jax.device_put(jnp.clip(ids, 0, vocab - 1), host_sh)
    with compute_on("device_host"):
        dnums = lax.GatherDimensionNumbers(
            offset_dims=(1,), collapsed_slice_dims=(0,),
            start_index_map=(0,))
        rows = lax.gather(
            table, ids_h[:, None], dnums,
            slice_sizes=(1, table.shape[1]),
            mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    rows_d = jax.device_put(rows, dev_sh)
    return jnp.where(in_range[:, None], rows_d, 0.0)


class HostOffloadEmbedding:
    """Embedding table stored in HOST memory, touched rows DMA'd to the
    device per step.

    The reference holds giant sparse tables in pserver host RAM and
    trainers pull only the touched rows over the network
    (reference: math/SparseRowMatrix.h:206 SparsePrefetchRowCpuMatrix,
    pserver/ParameterServer2.h:510 getParameterSparse). The single-host
    TPU analog (SURVEY §7 hard part: "possibly host offload for >HBM
    tables"): the table lives in pinned_host memory, the gather runs on
    the host CPU under compute_on('device_host'), and only [K, D]
    touched rows cross PCIe — the HBM never sees the [V, D] table. The
    row-sparse SGD update scatters back on the host the same way.

    Same call surface as ShardedEmbedding/PServerEmbedding (the
    `LookupSurface` protocol: init / lookup / apply_row_grads + the
    alltoall_* aliases), single-process; combine with ShardedEmbedding
    when the table also spans hosts. Also exposes the cache-backing
    quintet (pull_rows/owner_of/n_shards/poll_watermarks/
    shard_failovers) in its degenerate single-authority form, so the
    tiered embed cache slots in front of it exactly as it does in
    front of the pserver tier — no isinstance checks anywhere.
    """

    def __init__(self, vocab: int, dim: int, *, init_scale: float = 0.01,
                 name: str = "host_embedding"):
        self.vocab, self.dim = vocab, dim
        self.init_scale = init_scale
        self.name = name

    def _host_sharding(self, table=None):
        """pinned_host sharding on the table's device (falls back to
        device 0 only when there is no table yet, i.e. at init).
        Backends without a pinned_host space (XLA:CPU exposes only
        unpinned_host) degrade to the device's default space — the
        offload becomes an emulation there, same spirit as update()'s
        annotate_device_placement fallback."""
        from jax.sharding import SingleDeviceSharding

        dev = self._table_device(table)
        return SingleDeviceSharding(
            dev, memory_kind=compat.memory_kind(dev, "pinned_host"))

    @staticmethod
    def _table_device(table):
        """The table's device when known; tracers (inside jit, where
        concrete placement is the enclosing computation's business) and
        absent tables fall back to device 0."""
        try:
            return next(iter(table.sharding.device_set))
        except Exception:
            return jax.devices()[0]

    def _dev_sharding(self, table):
        from jax.sharding import SingleDeviceSharding

        dev = self._table_device(table)
        return SingleDeviceSharding(
            dev, memory_kind=compat.memory_kind(dev, "device"))

    def init(self, rng):
        """Generate the table ON HOST (numpy seeded from the jax key):
        a >HBM table must never materialize in device memory, which
        jax.random.normal on the default device would do."""
        seed = np.asarray(jax.random.key_data(rng)).ravel()
        host_rng = np.random.default_rng([int(s) for s in seed])
        table = (host_rng.standard_normal(
            (self.vocab, self.dim), np.float32) * self.init_scale)
        return jax.device_put(table, self._host_sharding())

    def lookup(self, table, ids):
        """ids [K] -> rows [K, D] on DEVICE; the gather itself runs on
        host so only K*D floats move to HBM. Out-of-range ids (e.g. -1
        padding) return ZERO vectors — the same contract as
        sharded_lookup. Always traced (`_host_gather` is jitted): an
        eager `compute_on` result keeps a host-space aval, which the
        device-side masking then refuses to mix with."""
        return _host_gather(table, jnp.asarray(ids), vocab=self.vocab,
                            host_sh=self._host_sharding(table),
                            dev_sh=self._dev_sharding(table))

    def apply_row_grads(self, table, ids, row_grads, lr):
        """Row-sparse SGD on the host copy: [K, D] grads cross PCIe,
        the scatter-add runs host-side, HBM never holds the table.
        The padding-id masking happens on DEVICE via masked_row_delta
        (the ONE home of that rule, shared with rowwise_sgd_update) —
        the host region must stay free of fresh broadcast constants,
        which land in device memory space and fail to mix."""
        from jax.experimental.compute_on import compute_on

        host_sh = self._host_sharding(table)
        safe, delta = masked_row_delta(self.vocab, table.dtype, ids,
                                       row_grads, lr)
        safe_h = jax.device_put(safe, host_sh)
        delta_h = jax.device_put(delta, host_sh)
        with compute_on("device_host"):
            dnums = lax.ScatterDimensionNumbers(
                update_window_dims=(1,), inserted_window_dims=(0,),
                scatter_dims_to_operand_dims=(0,))
            new_table = lax.scatter_add(
                table, safe_h[:, None], delta_h, dnums,
                mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        # NOTE: a top-level jit defaults its OUTPUT memory to device
        # HBM — use .update() below, or pass out_shardings with
        # memory_kind='pinned_host' for the table output of your own
        # jit. (No in-trace placement annotation here: the result of the
        # host scatter already lives in host space, and an extra
        # annotate_device_placement inside the host region has no
        # registered lowering on some backends.)
        return new_table

    # aliases matching the ShardedEmbedding/PServerEmbedding call
    # sites (the signature drift the lookup-surface unification fixed:
    # this backing was the only one missing them, so swapping it into
    # a distributed CTR call site used to AttributeError)
    def alltoall_lookup(self, table, ids, *, capacity=None,
                        return_overflow: bool = False):
        out = self.lookup(table, ids)
        if return_overflow:
            return out, jnp.zeros((), jnp.int32)
        return out

    def alltoall_push_row_grads(self, table, ids, row_grads, lr, *,
                                capacity=None):
        return self.apply_row_grads(table, ids, row_grads, lr)

    # -- cache-backing surface (degenerate single-authority forms) -----

    def pull_rows(self, table, ids):
        """[K] ids -> ([K, D] float32 host rows, watermarks=None).
        A host-offload table has no push ledger — None tells the cache
        to run in static-source mode (entries never go stale; explicit
        invalidate_all() is the only eviction besides capacity)."""
        return np.asarray(self.lookup(table, ids), np.float32), None

    def owner_of(self, ids) -> np.ndarray:
        ids = np.asarray(ids).reshape(-1)
        owner = np.zeros(ids.shape[0], np.int64)
        owner[(ids < 0) | (ids >= self.vocab)] = -1
        return owner

    @property
    def n_shards(self) -> int:
        return 1

    def poll_watermarks(self, table):
        return None

    def shard_failovers(self):
        return [0]

    def update(self, table, ids, row_grads, lr):
        """Jitted row-sparse update whose output table STAYS pinned in
        host memory — the form to call between steps at top level.

        On TPU the pinning rides jit out_shardings (zero extra copies,
        old table donated). Backends whose compiler can't annotate host
        placement in-program (XLA:CPU — 'annotate_device_placement for
        Host' has no registered lowering) fall back to re-pinning the
        result outside the trace; that emulation round-trips the table
        once, which is fine for tests and irrelevant on TPU."""
        if not hasattr(self, "_jit_update"):
            host_sh = self._host_sharding(table)
            fn = jax.jit(self.apply_row_grads,
                         out_shardings=host_sh,
                         donate_argnums=0)
            try:
                # probe on THROWAWAY buffers (XLA:CPU rejects the host
                # placement only at RUNTIME — 'no registered
                # implementation for annotate_device_placement' — so a
                # compile-only probe would pass and the real call would
                # then fail AFTER donating the caller's table). numpy
                # zeros -> pinned host directly: a >HBM probe must not
                # pass through device memory
                probe_t = jax.device_put(
                    np.zeros(table.shape, table.dtype), host_sh)
                jax.block_until_ready(fn(probe_t, ids, row_grads, lr))
                self._jit_update = fn
            except Exception as e:
                if "annotate_device_placement" not in str(e):
                    raise  # a real user error — don't cache a fallback
                # no donation here either: donating a pinned_host input
                # crashes XLA:CPU outright (hard abort, not an exception)
                plain = jax.jit(self.apply_row_grads)
                self._jit_update = lambda *a: jax.device_put(
                    plain(*a), host_sh)
        return self._jit_update(table, ids, row_grads, lr)
