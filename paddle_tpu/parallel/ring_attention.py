"""Long-context attention parallelism: ring attention + Ulysses all-to-all.

The reference's long-sequence story is padding-free LoD batching unrolled
frame-by-frame (reference: gserver/layers/SequenceToBatch.h:41,
RecurrentGradientMachine.cpp:428-775) — memory-linear in sequence length
with no sequence sharding. The TPU-native build makes sequence/context
parallelism first-class instead: shard the time dimension over the mesh
`seq` axis and compute exact attention with

  * ring attention — K/V shards rotate around the `seq` ring via
    `lax.ppermute` while each device keeps its Q shard; a streaming
    (flash-style) softmax merges per-block partial results, so no device
    ever materialises the full [T, T] score matrix or the full K/V.
  * Ulysses all-to-all — `lax.all_to_all` re-shards [T/n, H] -> [T, H/n]
    so each device runs full-sequence attention over a head subset, then
    shards back; cheaper per step on small meshes, needs H % n == 0.

Both are exact (up to fp reassociation) and differentiable; tests compare
against the dense reference on an 8-device CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from paddle_tpu.core.mesh import SEQ_AXIS

NEG_INF = -1e30


def dense_attention(q, k, v, *, causal: bool = False, mask=None):
    """Reference dense attention. q,k,v: [B, T, H, D] -> [B, T, H, D].

    `mask`: optional [B, Tq, Tk] boolean, True = attend. Scores and
    softmax run in f32 whatever the compute dtype (the models' shared
    attention invariant).
    """
    d = q.shape[-1]
    acc_dtype = jnp.promote_types(jnp.float32, q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=acc_dtype) / jnp.sqrt(
        d).astype(acc_dtype)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        cm = (jnp.arange(tq, dtype=jnp.int32)[:, None]
              >= jnp.arange(tk, dtype=jnp.int32)[None, :])
        scores = jnp.where(cm[None, None], scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                      preferred_element_type=acc_dtype).astype(q.dtype)


def _block_attend(q, k, v, q_offset, k_offset, *, causal, scale):
    """Partial attention of a Q block against one K/V block.

    Returns (o, l, m): un-normalised output [B,Tq,H,D], row sum l and row
    max m [B,Tq,H] — the flash-attention streaming-softmax statistics.
    """
    # scores/exp/sums in >=f32 regardless of the compute dtype — the
    # same invariant as the models' dense attention (bf16 running
    # exp-sums degrade with sequence length and break CP==dense parity)
    acc_dtype = jnp.promote_types(jnp.float32, q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=acc_dtype) \
        * scale.astype(acc_dtype)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qpos = q_offset + jnp.arange(tq, dtype=jnp.int32)
        kpos = k_offset + jnp.arange(tk, dtype=jnp.int32)
        cm = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(cm[None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)  # [B,H,Tq]
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=acc_dtype)
    # -> [B,Tq,H] layout for the running stats
    return o, l.transpose(0, 2, 1), m.transpose(0, 2, 1)


def _merge(acc, blk):
    """Merge streaming-softmax partials (o, l, m) from two blocks."""
    o1, l1, m1 = acc
    o2, l2, m2 = blk
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    # stats are [B,Tq,H]; broadcast over the trailing D of the outputs
    o = o1 * a1[..., None] + o2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return o, l, m


def ring_attention(q, k, v, *, axis: str = SEQ_AXIS, causal: bool = False):
    """Exact attention with sequence sharded over the `axis` ring.

    Call INSIDE shard_map. q,k,v: per-shard [B, T_local, H, D] (the global
    sequence is the concatenation over the axis, in axis-index order).
    K/V blocks rotate around the ring once; a streaming softmax merges
    block partials, so peak memory is O(T_local^2) scores per device.
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    t_local = q.shape[1]
    scale = (1.0 / jnp.sqrt(q.shape[-1])).astype(q.dtype)
    q_offset = idx * t_local

    def step(carry, _):
        kb, vb, src, acc = carry
        k_offset = src * t_local
        blk = _block_attend(q, kb, vb, q_offset, k_offset,
                            causal=causal, scale=scale)
        acc = _merge(acc, blk)
        # rotate k/v one step around the ring: shard j -> shard j+1, so
        # after s steps this device holds the block of device (idx - s).
        perm = [(i, (i + 1) % n) for i in range(n)]
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        src = (src - 1) % n
        return (kb, vb, src, acc), None

    b, _, h, d_ = q.shape
    # accumulators match _block_attend's >=f32 partials
    acc_dtype = jnp.promote_types(jnp.float32, q.dtype)
    zero = (
        jnp.zeros((b, t_local, h, d_), acc_dtype),
        jnp.zeros((b, t_local, h), acc_dtype),
        jnp.full((b, t_local, h), NEG_INF, acc_dtype),
    )
    (kb, vb, src, acc), _ = jax.lax.scan(
        step, (k, v, idx, zero), None, length=n)
    o, l, _ = acc
    return (o / l[..., None]).astype(q.dtype)


def ulysses_attention(q, k, v, *, axis: str = SEQ_AXIS,
                      causal: bool = False):
    """Ulysses-style attention: all-to-all seq-shard -> head-shard.

    Call INSIDE shard_map with per-shard [B, T_local, H, D]; needs
    H % axis_size == 0. Each device sees the FULL sequence for H/n heads,
    runs dense attention, and all-to-alls back to sequence sharding.
    """
    n = jax.lax.axis_size(axis)
    # [B, T/n, H, D] -> gather seq, split heads -> [B, T, H/n, D]
    qh = jax.lax.all_to_all(q, axis, split_axis=2, concat_axis=1, tiled=True)
    kh = jax.lax.all_to_all(k, axis, split_axis=2, concat_axis=1, tiled=True)
    vh = jax.lax.all_to_all(v, axis, split_axis=2, concat_axis=1, tiled=True)
    oh = dense_attention(qh, kh, vh, causal=causal)
    return jax.lax.all_to_all(oh, axis, split_axis=1, concat_axis=2,
                              tiled=True)


def make_sequence_parallel_attention(
    mesh: Mesh,
    *,
    kind: str = "ring",
    causal: bool = False,
    batch_axis: Optional[str] = None,
    axis: str = SEQ_AXIS,
):
    """Build a jit-able whole-array attention fn sharded over `axis`.

    Takes global [B, T, H, D] arrays; shard_map internally shards T over
    the seq axis (and optionally B over `batch_axis`).
    """
    if kind == "ring":
        inner = ring_attention
    elif kind == "ulysses":
        inner = ulysses_attention
    else:
        raise ValueError(f"unknown kind {kind!r}: expected 'ring' or 'ulysses'")
    spec = P(batch_axis, axis, None, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    def fn(q, k, v):
        return inner(q, k, v, axis=axis, causal=causal)

    return fn
