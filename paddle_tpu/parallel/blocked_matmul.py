"""Sharded matmul primitives: ring collective matmul + blocked streaming.

Every training/serving hot path bottoms out in matmuls, and on a mesh
the naive shape is always the same: one big collective (all_gather /
psum) followed by one big local matmul — the interconnect sits idle
during compute and the MXU sits idle during the collective. The fix is
the classic distributed-linear-algebra decomposition (the "small
library of blocked primitives" design of arxiv 2112.09017): cut the
global matmul into per-shard block products and rotate operands around
a `lax.ppermute` ring ONE block per step, so step s's transfer is in
flight while step s-1's block product runs on the MXU. Three shapes of
the same idea live here:

  * `ring_matmul_gather` — output-dim ring. x row-sharded [m, K],
    w col-sharded [K, n]; instead of all_gather(x) @ w_loc, x blocks
    rotate BOTH directions around the ring (bidirectional halves the
    step count to ceil((p-1)/2)) and each arriving block's [m, n]
    product lands in its output rows immediately.
  * `ring_matmul_reduce` — contracting-dim ring. x col-sharded [M, k],
    w row-sharded [k, N]; instead of psum(x_loc @ w_loc) (a full
    [M, N] partial per device, then a blocking reduction), a per-block
    accumulator rides the ring reduce-scatter style: each device adds
    its own contribution to the block passing through, and block c
    finishes exactly at device c. The per-step local matmul is
    independent of the accumulator hand-off, so they overlap.
  * `stream_matmul` — blocked matmul for weights larger than one
    chip's HBM. w stays K-sharded and RESIDENT [k, N]; the weight
    shards rotate through while each device multiplies the matching
    column block of its (replicated) x. Peak live weight per device is
    2 shards (current + in-flight) = 2|W|/p, vs |W| for the
    all_gather it replaces.

`tp_dense` packages the reduce ring as a Megatron-style row-parallel
dense layer — the opt-in consumer seam used by `parallel.pipeline`'s
`tp_axis` flag.

All primitives are plain jnp + lax collectives called INSIDE
`jax.shard_map`, so they run on the 8-virtual-device CPU mesh
exactly as on a TPU ring; `matmul_reference` is the pure-jnp oracle
every parity test compares against (allclose, not bit-equal: ring
accumulation orders differ from XLA's single-matmul reduction).
Accumulation runs in >=f32 whatever the compute dtype — the same
invariant as the models' attention.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def _acc_dtype(x, w):
    """Accumulate in at least f32 (bf16/f16 inputs upcast; f64 stays)."""
    return jnp.promote_types(jnp.float32, jnp.result_type(x.dtype,
                                                          w.dtype))


def _dot(a, b, acc_dtype):
    return jnp.dot(a, b, preferred_element_type=acc_dtype)


def matmul_reference(x, w):
    """The pure-jnp oracle: one local matmul with the same >=f32
    accumulation contract as the sharded primitives."""
    acc = _acc_dtype(x, w)
    return _dot(x, w, acc).astype(jnp.result_type(x.dtype, w.dtype))


# ---------------------------------------------------------------------------
# in-shard_map primitives (call these inside jax.shard_map)
# ---------------------------------------------------------------------------


def ring_matmul_gather(x_loc, w_loc, *, axis: str, overlap: bool = True):
    """Collective matmul over the OUTPUT (row) dim of x.

    Call INSIDE shard_map. x_loc: this device's row block [m, K] of the
    global [p*m, K] x; w_loc: this device's column block [K, n].
    Returns [p*m, n] — the full-height slab of this device's output
    columns (globally: out sharded P(None, axis)).

    overlap=True runs the bidirectional ring: own block first, then
    per step one forward-travelling and one backward-travelling x
    block arrive while the previous pair's products run; an even ring
    finishes with a single extra forward hop for the antipodal block.
    overlap=False is the naive arm: all_gather(x) then one matmul —
    the comm fully serialised before any compute (the baseline).
    """
    p = jax.lax.axis_size(axis)
    acc = _acc_dtype(x_loc, w_loc)
    out_dtype = jnp.result_type(x_loc.dtype, w_loc.dtype)
    if not overlap or p == 1:
        xg = lax.all_gather(x_loc, axis, axis=0, tiled=True)
        return _dot(xg, w_loc, acc).astype(out_dtype)

    me = lax.axis_index(axis)
    m = x_loc.shape[0]
    n = w_loc.shape[1]
    out = jnp.zeros((p * m, n), dtype=out_dtype)

    def place(buf, blk_idx, prod):
        row0 = (blk_idx % p) * m
        return lax.dynamic_update_slice_in_dim(
            buf, prod.astype(out_dtype), row0, axis=0)

    out = place(out, me, _dot(x_loc, w_loc, acc))
    fwd_perm = [(j, (j + 1) % p) for j in range(p)]
    bwd_perm = [(j, (j - 1) % p) for j in range(p)]
    fwd = x_loc  # after s forward hops: the block of device (me - s)
    bwd = x_loc  # after s backward hops: the block of device (me + s)
    for s in range(1, (p - 1) // 2 + 1):
        fwd = lax.ppermute(fwd, axis, fwd_perm)
        bwd = lax.ppermute(bwd, axis, bwd_perm)
        out = place(out, me - s, _dot(fwd, w_loc, acc))
        out = place(out, me + s, _dot(bwd, w_loc, acc))
    if p % 2 == 0:
        # even ring: the antipodal block arrives on one more fwd hop
        fwd = lax.ppermute(fwd, axis, fwd_perm)
        out = place(out, me - p // 2, _dot(fwd, w_loc, acc))
    return out


def ring_matmul_reduce(x_loc, w_loc, *, axis: str, overlap: bool = True):
    """Collective matmul over the CONTRACTING dim, reduce-scatter ring.

    Call INSIDE shard_map. x_loc: this device's column block [M, k] of
    the global [M, p*k] x (M % p == 0); w_loc: the matching row block
    [k, N]. The global product is sum_j x_j @ w_j; it returns this
    device's ROW block [M/p, N] of that sum (globally: out sharded
    P(axis, None)).

    overlap=True rides a per-block accumulator around the ring: at
    step s every device adds its local product for the block passing
    through (`part` below — independent of the accumulator hand-off,
    so the matmul overlaps the ppermute), and block c completes its
    p stops exactly at device c. overlap=False is the naive arm: the
    full [M, N] partial product, then one blocking psum_scatter.
    """
    p = jax.lax.axis_size(axis)
    big_m = x_loc.shape[0]
    if big_m % p != 0:
        raise ValueError(
            f"ring_matmul_reduce needs M % p == 0, got M={big_m} over "
            f"{p} '{axis}' devices")
    acc_dtype = _acc_dtype(x_loc, w_loc)
    out_dtype = jnp.result_type(x_loc.dtype, w_loc.dtype)
    if not overlap or p == 1:
        full = _dot(x_loc, w_loc, acc_dtype)
        if p == 1:
            return full.astype(out_dtype)
        return lax.psum_scatter(full, axis, scatter_dimension=0,
                                tiled=True).astype(out_dtype)

    me = lax.axis_index(axis)
    m = big_m // p

    def part(blk_idx):
        """This device's contribution to output row-block blk_idx."""
        row0 = (blk_idx % p) * m
        rows = lax.dynamic_slice_in_dim(x_loc, row0, m, axis=0)
        return _dot(rows, w_loc, acc_dtype)

    perm = [(j, (j + 1) % p) for j in range(p)]
    # accumulator for block (me - 1) starts here and travels p-1 hops,
    # finishing at device (me - 1) + (p - 1) == me - 1 ... shifted: the
    # acc ARRIVING after the loop is the one that started at me + 1,
    # i.e. block me — each device ends holding its own finished block.
    acc = part(me - 1)
    for s in range(1, p):
        acc = lax.ppermute(acc, axis, perm)
        acc = acc + part(me - 1 - s)
    return acc.astype(out_dtype)


def stream_matmul(x, w_loc, *, axis: str):
    """Blocked matmul for weights larger than one device's HBM.

    Call INSIDE shard_map. w is K-sharded and stays resident: w_loc
    [k, N] (globally P(axis, None)); x [B, p*k] is replicated. The p
    weight shards rotate around the ring; at each stop the device
    multiplies the matching column block of x, so no device ever holds
    more than 2 weight shards (current + in-flight) — 2|W|/p live
    bytes vs the |W| of all_gather(w). Returns the full [B, N] on
    every device (globally replicated).
    """
    p = jax.lax.axis_size(axis)
    me = lax.axis_index(axis)
    k = w_loc.shape[0]
    acc_dtype = _acc_dtype(x, w_loc)
    out_dtype = jnp.result_type(x.dtype, w_loc.dtype)

    def xblk(blk_idx):
        col0 = (blk_idx % p) * k
        return lax.dynamic_slice_in_dim(x, col0, k, axis=1)

    perm = [(j, (j + 1) % p) for j in range(p)]
    w_cur = w_loc
    acc = _dot(xblk(me), w_cur, acc_dtype)
    for s in range(1, p):
        w_cur = lax.ppermute(w_cur, axis, perm)
        # after s hops this device holds the shard of device (me - s)
        acc = acc + _dot(xblk(me - s), w_cur, acc_dtype)
    return acc.astype(out_dtype)


def tp_dense(x, w_loc, *, axis: str, overlap: bool = True):
    """Row-parallel dense layer: x [B, d] replicated, w d-sharded.

    Call INSIDE shard_map. w_loc [d/p, N] is this device's row block of
    the [d, N] weight; the output [B, N] comes back replicated (the
    Megatron row-parallel linear). overlap=False is the textbook form —
    local partial product then one psum. overlap=True routes through
    `ring_matmul_reduce` (per-block accumulator ring) and all_gathers
    the row blocks back; needs B % p == 0 and p | B, so it falls back
    to the psum form when the batch doesn't tile.
    """
    p = jax.lax.axis_size(axis)
    me = lax.axis_index(axis)
    k = w_loc.shape[0]
    x_me = lax.dynamic_slice_in_dim(x, me * k, k, axis=1)
    if not overlap or p == 1 or x.shape[0] % p != 0:
        acc = _dot(x_me, w_loc, _acc_dtype(x, w_loc))
        return lax.psum(acc, axis).astype(
            jnp.result_type(x.dtype, w_loc.dtype))
    rows = ring_matmul_reduce(x_me, w_loc, axis=axis, overlap=True)
    return lax.all_gather(rows, axis, axis=0, tiled=True)


# ---------------------------------------------------------------------------
# whole-array wrappers (jit-able; shard_map plumbing inside)
# ---------------------------------------------------------------------------


def collective_matmul(mesh: Mesh, *, axis: str, mode: str = "reduce",
                      overlap: bool = True) -> Callable:
    """Build fn(x, w) -> x @ w over global arrays, ring-sharded inside.

    mode="gather": x sharded over its rows, w over its columns
    (`ring_matmul_gather` per shard). mode="reduce": the contracting
    dim sharded (`ring_matmul_reduce`). Either way the caller passes
    and receives ordinary global arrays; shard_map does the cutting.
    """
    if mode == "gather":
        inner = functools.partial(ring_matmul_gather, axis=axis,
                                  overlap=overlap)
        in_specs = (P(axis, None), P(None, axis))
        out_specs = P(None, axis)
    elif mode == "reduce":
        inner = functools.partial(ring_matmul_reduce, axis=axis,
                                  overlap=overlap)
        in_specs = (P(None, axis), P(axis, None))
        out_specs = P(axis, None)
    else:
        raise ValueError(
            f"unknown mode {mode!r}: expected 'gather' or 'reduce'")
    return jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)


def blocked_matmul(mesh: Mesh, *, axis: str) -> Callable:
    """Build fn(x, w) -> x @ w with w K-sharded resident
    (`stream_matmul` per shard): the weight never materialises whole on
    any device; x and the result are replicated."""
    inner = functools.partial(stream_matmul, axis=axis)
    return jax.shard_map(inner, mesh=mesh,
                            in_specs=(P(None, None), P(axis, None)),
                            out_specs=P(None, None), check_vma=False)
