"""Fused ragged paged-attention: the page-table walk as ONE kernel.

`ops.paged_attention` reads a slot's cache by materializing a gathered
`[R, max_len, Hkv, Dh]` KV copy per layer per launch (jnp.take), then
runs the attention einsums over it — correct everywhere, but on TPU the
gather round-trips HBM and the copy is pure waste on mixed-length
batches where most rows are far short of `max_len`. The kernel here
("Ragged Paged Attention", PAPERS.md arxiv 2604.15464) walks the page
table DIRECTLY: the grid iterates rows, each program DMAs that row's
mapped pages from the HBM arena into VMEM scratch (all block copies in
flight at once, one semaphore per copy), and runs THE shared attention
body — literally `paged_attention.grouped_masked_attention` — over the
scratch, so no gathered copy ever exists in HBM.

One launch covers the whole ragged mix because the query axis is
per-row positional: `q [R, TQ, H, Dh]` with query i of row r sitting at
absolute position `pos0[r] + i` and attending keys `<= pos0[r] + i`.
Decode rows are TQ=1, prefill chunks TQ=C, speculative verify windows
TQ=K+1 — same kernel, same math, mixed freely in one batch (pad TQ to
the batch max; padded queries are computed and ignored, the engine's
existing bucket discipline).

Int8 `(s8 data, f32 scale)` pair arenas get the SAME one-launch path
with per-page dequantization fused into the DMA pipeline: each page's
int8 data block and its scale plane stream to VMEM as independent
copies, and the moment a block's two copies land it is dequantized in
place on scratch — `(s8 -> f32) * scale`, the exact element sequence of
`paged_attention.kv_dequantize` — while LATER blocks' DMAs are still in
flight. The attend tail then runs over the dequantized scratch,
identical to the float walk, so quantized pools (half the HBM — ~2x the
concurrent users per chip) no longer forfeit the fused read.

Parity contract: `ragged_reference` below IS the jnp oracle — the same
gather + `grouped_masked_attention` the engine has always run (its
int8 branch is the gather+`kv_dequantize` read) — and in interpret mode
the kernel matches it BIT-FOR-BIT for float AND int8 arenas
(tests/test_ragged_attention.py, tests/test_ragged_int8.py): the
interpret path executes the same XLA CPU primitives as the oracle.

Status on the chip: DESELECTED. Mosaic refuses this kernel on a TPU
v5e (jax 0.9.0 / libtpu 0.0.34; docs/KERNELS.md has the messages): the
attend body is the oracle's 5-D grouped einsum, which `tpu.matmul`
rejects ("Expected matmul acc to be 32-bit" for bf16, "Up to 1 batch
dim supported" for f32), and with per-KV-head 2-D dots in its place the
page DMA itself is refused at head_dim 64 ("Slice shape along dimension
3 must be aligned to tiling (128), but is 64"): the `[P, page, Hkv,
Dh]` arena puts (Hkv, Dh) on the tiled minor dims. So
`ragged_attention(impl=None)` is the jnp gather on every backend, by a
static decision, and `impl="pallas"` on a TPU fails at trace time with
the compiler's own words. The kernel that replaces this one (ROADMAP
A2a: a blocked walk with online softmax over a head-major arena) takes
over the `impl=None` slot; until then the kernel stays as the
interpret-mode reference for the walk's addressing and the fused int8
dequant.

Writes are NOT fused: scatters through the page table are cheap
(`write_kv` is a drop-mode scatter of a few rows — it already
quantizes for int8 arenas), it's the read-side materialization that
burns the memory system — so callers write first with the existing jnp
scatter and hand this kernel the read+attend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_util
from paddle_tpu.ops.paged_attention import (
    gather_kv,
    grouped_masked_attention,
)

def _num_key_blocks(page_size: int, max_len: int, max_pages: int) -> int:
    """Blocks that can hold keys the `max_len` slice exposes — the walk
    never fetches pages entirely beyond the oracle's static slice."""
    return min(max_pages, -(-max_len // page_size))


# -- the jnp oracle ------------------------------------------------------


def ragged_reference(q, k_arena, v_arena, page_table, pos0, active, *,
                     page_size: int, max_len: int):
    """The gather-then-attend path, ragged-query shaped: exactly what
    `paged_decode_attention` (TQ=1) and `paged_chunk_attention` (R=1)
    have always computed, with the per-row causal bound `pos0 + i`.
    The kernel's bit-identity target — for int8 pairs `gather_kv`
    dequantizes inside the gathered read, same element math as the
    kernel's fused per-block dequant."""
    del page_size  # addressing is baked into the table; kept for symmetry
    k_read = gather_kv(k_arena, page_table, max_len, q.dtype)
    v_read = gather_kv(v_arena, page_table, max_len, q.dtype)
    tq = q.shape[1]
    ap = pos0[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    valid = (jnp.arange(max_len, dtype=jnp.int32)[None, None, :]
             <= ap[:, :, None]) & active[:, None, None]
    return grouped_masked_attention(q, k_read, v_read, valid[:, None])


# -- the fused kernel ----------------------------------------------------


def _attend_tail(max_len, nblk, r, meta_ref, q_ref, k_scr, v_scr,
                 out_ref):
    """THE shared attend tail over a row's VMEM scratch walk: flatten
    the blocks to the oracle's key axis (table order = position order,
    statically sliced to max_len) and run the shared attention body
    with the per-row causal/active mask."""
    q = q_ref[...]                                     # [1, TQ, H, Dh]
    tq = q.shape[1]
    page_size, hkv, dh = k_scr.shape[1], k_scr.shape[2], k_scr.shape[3]
    k_read = k_scr[...].reshape(1, nblk * page_size, hkv,
                                dh)[:, :max_len].astype(q.dtype)
    v_read = v_scr[...].reshape(1, nblk * page_size, hkv,
                                dh)[:, :max_len].astype(q.dtype)
    pos0 = meta_ref[r, 0]
    act = meta_ref[r, 1] > 0
    ap = pos0 + jnp.arange(tq, dtype=jnp.int32)
    valid = (jnp.arange(max_len, dtype=jnp.int32)[None, :]
             <= ap[:, None]) & act
    out_ref[...] = grouped_masked_attention(q, k_read, v_read,
                                            valid[None, None])


def _walk_kernel(page_size, max_len, nblk,
                 pt_ref, meta_ref, q_ref, k_hbm, v_hbm, out_ref,
                 k_scr, v_scr, sems):
    """One grid program = one row: DMA the row's page walk into VMEM
    (every block copy in flight before the first wait — the copies are
    independent, so the walk overlaps itself), then run THE shared
    attention body over the scratch."""
    del page_size
    r = pl.program_id(0)
    num_pages = k_hbm.shape[0]

    def copy(b, which):
        # sentinel/unmapped entries clip to the last page — same data
        # the oracle's mode="clip" gather reads, masked identically
        pg = jnp.minimum(pt_ref[r, b], num_pages - 1)
        src, dst = (k_hbm, k_scr) if which == 0 else (v_hbm, v_scr)
        return pltpu.make_async_copy(src.at[pg], dst.at[b],
                                     sems.at[b, which])

    def start(b, carry):
        copy(b, 0).start()
        copy(b, 1).start()
        return carry

    def wait(b, carry):
        copy(b, 0).wait()
        copy(b, 1).wait()
        return carry

    jax.lax.fori_loop(0, nblk, start, 0)
    jax.lax.fori_loop(0, nblk, wait, 0)
    _attend_tail(max_len, nblk, r, meta_ref, q_ref, k_scr, v_scr,
                 out_ref)


def _walk_kernel_int8(page_size, max_len, nblk,
                      pt_ref, meta_ref, q_ref,
                      kd_hbm, ks_hbm, vd_hbm, vs_hbm, out_ref,
                      kd_scr, ks_scr, vd_scr, vs_scr,
                      kf_scr, vf_scr, sems):
    """The int8 walk: four independent copy streams per block (K data,
    K scale, V data, V scale — semaphore lanes 0..3), all in flight
    before the first wait. Dequantization is FUSED into the pipeline:
    the moment block b's K copies land it is dequantized onto the
    q-dtype scratch — `(s8 -> f32) * scale`, the exact
    `paged_attention.kv_dequantize` element sequence, which is what
    makes the oracle bit-identity hold — while blocks b+1.. are still
    streaming. The attend tail then reads the dequantized scratch,
    identical to the float walk."""
    del page_size
    r = pl.program_id(0)
    num_pages = kd_hbm.shape[0]
    srcs = (kd_hbm, ks_hbm, vd_hbm, vs_hbm)
    dsts = (kd_scr, ks_scr, vd_scr, vs_scr)

    def copy(b, which):
        pg = jnp.minimum(pt_ref[r, b], num_pages - 1)
        return pltpu.make_async_copy(srcs[which].at[pg],
                                     dsts[which].at[b],
                                     sems.at[b, which])

    # nblk is static: Python loops unroll so the per-block dequant
    # below can index scratch statically
    for b in range(nblk):
        for which in range(4):
            copy(b, which).start()
    dtype = q_ref.dtype
    for b in range(nblk):
        copy(b, 0).wait()
        copy(b, 1).wait()
        kf_scr[b] = (kd_scr[b].astype(jnp.float32)
                     * ks_scr[b][..., None]).astype(dtype)
        copy(b, 2).wait()
        copy(b, 3).wait()
        vf_scr[b] = (vd_scr[b].astype(jnp.float32)
                     * vs_scr[b][..., None]).astype(dtype)
    _attend_tail(max_len, nblk, r, meta_ref, q_ref, kf_scr, vf_scr,
                 out_ref)


def ragged_pallas(q, k_arena, v_arena, page_table, pos0, active, *,
                  page_size: int, max_len: int):
    """The fused launch (interpreted everywhere except on a TPU
    backend, see `pallas_util`). Accepts float arenas AND int8
    `(s8, scale)` pairs."""
    interpret = pallas_util.interpret()
    r, tq, h, dh = q.shape
    quantized = isinstance(k_arena, tuple)
    k_data = k_arena[0] if quantized else k_arena
    _, page, hkv, _ = k_data.shape
    assert page == page_size, (page, page_size)
    nblk = _num_key_blocks(page_size, max_len, page_table.shape[1])
    meta = jnp.stack([pos0.astype(jnp.int32),
                      active.astype(jnp.int32)], axis=1)
    q_spec = pl.BlockSpec((1, tq, h, dh), lambda i, pt, mt: (i, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    if quantized:
        (kd, ks), (vd, vs) = k_arena, v_arena
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(r,),
            in_specs=[q_spec, hbm, hbm, hbm, hbm],
            out_specs=pl.BlockSpec((1, tq, h, dh),
                                   lambda i, pt, mt: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((nblk, page_size, hkv, dh), kd.dtype),
                pltpu.VMEM((nblk, page_size, hkv), ks.dtype),
                pltpu.VMEM((nblk, page_size, hkv, dh), vd.dtype),
                pltpu.VMEM((nblk, page_size, hkv), vs.dtype),
                pltpu.VMEM((nblk, page_size, hkv, dh), q.dtype),
                pltpu.VMEM((nblk, page_size, hkv, dh), q.dtype),
                pltpu.SemaphoreType.DMA((nblk, 4)),
            ],
        )
        kernel = functools.partial(_walk_kernel_int8, page_size,
                                   max_len, nblk)
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((r, tq, h, dh), q.dtype),
            interpret=interpret,
            name="ragged_paged_attention_int8",
        )(page_table.astype(jnp.int32), meta, q, kd, ks, vd, vs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r,),
        in_specs=[q_spec, hbm, hbm],
        out_specs=pl.BlockSpec((1, tq, h, dh),
                               lambda i, pt, mt: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nblk, page_size, hkv, dh), k_arena.dtype),
            pltpu.VMEM((nblk, page_size, hkv, dh), v_arena.dtype),
            pltpu.SemaphoreType.DMA((nblk, 2)),
        ],
    )
    kernel = functools.partial(_walk_kernel, page_size, max_len, nblk)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, tq, h, dh), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(page_table.astype(jnp.int32), meta, q, k_arena, v_arena)


def ragged_attention(q, k_arena, v_arena, page_table, pos0, active, *,
                     page_size: int, max_len: int, impl=None):
    """Dispatch: impl in {None, "jnp", "pallas"}. None and "jnp" are
    the gather-then-attend oracle on every backend: the fused kernel is
    deselected statically because the TPU compiler refuses it (module
    docstring). impl="pallas" forces the kernel — interpret mode
    off-TPU, which is the parity suites' lever; on a TPU it raises the
    compiler's error at trace time, never a quiet substitute."""
    if impl not in (None, "jnp", "pallas"):
        raise ValueError(f"impl must be None|jnp|pallas, got {impl!r}")
    pallas_util.note_traced("ragged_attention", impl or "jnp")
    if impl == "pallas":
        return ragged_pallas(q, k_arena, v_arena, page_table, pos0,
                             active, page_size=page_size,
                             max_len=max_len)
    return ragged_reference(q, k_arena, v_arena, page_table, pos0,
                            active, page_size=page_size,
                            max_len=max_len)
