"""Block-paged KV-cache attention: the page-table gather/scatter path.

A dense decode pool reserves `[S, max_len, Hkv, Dh]` per layer — the
worst case for EVERY slot, even when most requests are short. The
paged layout ("Ragged Paged Attention", PAPERS.md; vLLM's
PagedAttention is the GPU ancestor) keeps ONE `[num_pages, page_size,
Hkv, Dh]` arena per layer plus a static `[S, max_pages_per_slot]`
page table of physical page ids per slot. Shapes stay static — the
jitted step never recompiles — while page allocation/free happens on
the host (serve.paged.PagePool), so pool capacity follows the sum of
ACTUAL sequence lengths rather than slots × worst case, and two slots
can read the same physical page (shared-prefix reuse).

Everything here is pure jnp — gather the slot's pages, run the SAME
grouped-masked attention math as `transformer._cached_attention`,
scatter this step's K/V through the table — so it runs identically on
CPU (tier-1) and TPU. On TPU the gather lowers to XLA dynamic-gather;
a fused Pallas kernel that walks the page table block-by-block inside
the MXU loop (the ragged-paged-attention kernel shape) is the drop-in
upgrade for this module and changes nothing above it.

Numerics contract: reads are gathered in PAGE-TABLE ORDER, which is
position order, then statically sliced to `max_len` — so the key axis
an attention softmax sees is exactly the dense pool's `[max_len]`
axis, value-for-value. A paged pool therefore reproduces the dense
engine's tokens bit-for-bit (tests/test_serve_engine.py runs
unmodified against it, golden transcript included).

Out-of-range discipline (the engine's drop-sentinel convention):
unmapped page-table entries and inactive rows carry the sentinel page
id `num_pages`; scatter writes use mode="drop" so they vanish, and
gather reads clip but are masked by the per-row validity bound.

int8 KV pools ride through unchanged: an arena may be an
`(s8 data, f32 scale)` pair — THE per-(position, kv-head) absmax
convention (`kv_quantize` below, shared with the dense caches via
`transformer._kv_quantize`) quantizes at write and dequantizes inside
the gathered read. The fused kernel (interpret mode only today, see
ops.ragged_paged_attention) runs the same dequant per page block on
VMEM scratch as each DMA lands — identical element math, so both reads
stay bit-equal.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.dtypes import at_least_f32


# -- KV quantization (THE convention, shared with the dense caches) ------


def kv_quantize(x):
    """[..., T, Hkv, Dh] fp -> (s8 data, f32 scale [..., T, Hkv]):
    absmax symmetric per (position, kv-head) — one scale per cached
    vector, so dequant is an elementwise mul XLA fuses into the
    attention einsum's operand read (tests/test_compiled_cost.py::
    TestInt8DecodeLoop). Moved here from models.transformer so the
    paged arena and the dense caches share one definition without an
    ops -> models layering inversion; `transformer._kv_quantize`
    remains the models-side alias."""
    xf = at_least_f32(x)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def kv_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# -- page-table reads / writes -------------------------------------------


def gather_kv(arena, page_table, limit: int, dtype):
    """Read rows' caches through their page tables.

    arena: [P, page, Hkv, Dh] (or an (s8, scale) pair); page_table
    [R, max_pages] int32 (sentinel entries clip — callers mask by
    their validity bound). Returns [R, limit, Hkv, Dh] in `dtype`:
    pages land in table order = position order, statically sliced to
    `limit` so the key axis is exactly the dense pool's."""
    def one(buf):
        g = jnp.take(buf, page_table, axis=0, mode="clip")
        r, mp, page = g.shape[0], g.shape[1], g.shape[2]
        g = g.reshape((r, mp * page) + g.shape[3:])
        return g[:, :limit]

    if isinstance(arena, tuple):
        data, scale = arena
        return kv_dequantize(one(data), one(scale), dtype)
    return one(arena).astype(dtype)


def _scatter(buf, idx_page, idx_off, new):
    """Scatter `new` rows at (page, offset) pairs with the engine's
    drop discipline: a sentinel/out-of-range page id drops the
    write."""
    return buf.at[idx_page, idx_off].set(
        new.astype(buf.dtype), mode="drop")


def write_kv(arena, new, pages, offsets):
    """Write per-row K/V vectors into the arena: new [N, Hkv, Dh] at
    (pages [N], offsets [N]); quantizes first for (s8, scale)
    arenas."""
    if isinstance(arena, tuple):
        data, scale = arena
        nd, nsc = kv_quantize(new)
        return (_scatter(data, pages, offsets, nd),
                _scatter(scale, pages, offsets, nsc))
    return _scatter(arena, pages, offsets, new)


# -- the shared attention body -------------------------------------------


def grouped_masked_attention(q, k_read, v_read, valid):
    """THE masked grouped-head attention math — a line-for-line mirror
    of `transformer._cached_attention`'s read side (f32 scores, -1e30
    mask, softmax in f32, output in q.dtype), factored so the paged
    decode step, the paged prefill chunk, and any future Pallas
    replacement score tokens identically.

    q [B, Tq, H, Dh]; k_read/v_read [B, K, Hkv, Dh] (compact GQA —
    grouped einsums read the 1/G-sized cache directly); valid
    broadcastable over [B, H, Tq, K]."""
    b, tq, h, dh = q.shape
    hkv = k_read.shape[2]
    g = h // hkv  # 1 for MHA — the grouped path IS the only path
    scale = jnp.sqrt(jnp.asarray(dh, q.dtype))
    qg = q.reshape(b, tq, hkv, g, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_read) / scale
    scores = at_least_f32(scores).reshape(b, h, tq, -1)
    scores = jnp.where(valid, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    wg = w.reshape(b, hkv, g, tq, -1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", wg, v_read)
    return out.reshape(b, tq, h, dh)


def page_addresses(pages_row, positions, *, page_size: int):
    """Map absolute positions -> (physical page id, within-page
    offset) through ONE slot's page-table row: clip the block index
    to the table (sentinel entries ride through, so a later
    mode="drop" scatter discards them). THE write-side addressing
    convention — every prefill-chunk writer routes here so the place
    a position is written can never drift from where decode reads
    it."""
    blk = jnp.clip(positions // page_size, 0, pages_row.shape[0] - 1)
    return pages_row[blk], positions % page_size


def paged_decode_attention(q, k, v, k_arena, v_arena, page_table, pos,
                           active, *, page_size: int, max_len: int,
                           impl=None):
    """One decode step for every slot through the page table: write
    each row's single-position K/V at its own (page, offset), gather
    its mapped pages, attend over keys <= pos. The paged counterpart
    of `transformer._cached_attention`'s vector-slot mode.

    q/k/v [S, 1, ·, Dh]; page_table [S, max_pages] (sentinel =
    num_pages on unmapped entries); pos [S] absolute write positions
    (out-of-range sentinel on inactive rows); active [S] bool.
    `impl` forwards to the ragged-read dispatcher (None = auto,
    "jnp"/"pallas" force — the engine's ragged_impl knob).
    Returns (out [S, 1, H, Dh], k_arena, v_arena)."""
    s = q.shape[0]
    assert q.shape[1] == 1, "decode writes are single-position"
    num_pages = (k_arena[0] if isinstance(k_arena, tuple)
                 else k_arena).shape[0]
    max_pages = page_table.shape[1]
    blk = jnp.clip(pos // page_size, 0, max_pages - 1)
    pg = page_table[jnp.arange(s, dtype=jnp.int32), blk]
    # belt + braces: unmapped entries already hold the sentinel, but an
    # inactive row's clipped block index must never resurrect a write
    pg = jnp.where(active, pg, jnp.int32(num_pages))
    off = pos % page_size
    k_arena = write_kv(k_arena, k[:, 0], pg, off)
    v_arena = write_kv(v_arena, v[:, 0], pg, off)
    out = _ragged_read(q, k_arena, v_arena, page_table, pos, active,
                       page_size=page_size, max_len=max_len, impl=impl)
    return out, k_arena, v_arena


def paged_chunk_attention(q, k, v, k_arena, v_arena, pages_row, start,
                          *, page_size: int, max_len: int, impl=None):
    """One prefill CHUNK for one slot: write the chunk's K/V rows at
    positions start..start+C-1 through the slot's page-table row, then
    attend each chunk query over every cached key <= its own absolute
    position — which covers shared-prefix pages ([0, start) filled by
    the cache hit or by earlier chunks) plus the causal part of this
    chunk. This is what makes prefix reuse COPY-FREE: a hit skips
    straight to its first private position and reads the shared pages
    like any other cache content.

    q/k/v [1, C, ·, Dh]; pages_row [max_pages] (this slot's table
    row); start: absolute position of chunk element 0 (traced).
    Returns (out [1, C, H, Dh], k_arena, v_arena)."""
    c = q.shape[1]
    ap = start + jnp.arange(
        c, dtype=jnp.int32)                    # absolute positions
    pg, off = page_addresses(pages_row, ap, page_size=page_size)
    k_arena = write_kv(k_arena, k[0], pg, off)
    v_arena = write_kv(v_arena, v[0], pg, off)
    out = _ragged_read(q, k_arena, v_arena, pages_row[None],
                       jnp.asarray(start, jnp.int32).reshape(1),
                       jnp.ones((1,), bool),
                       page_size=page_size, max_len=max_len, impl=impl)
    return out, k_arena, v_arena


def paged_verify_attention(q, k, v, k_arena, v_arena, page_table, pos,
                           active, *, page_size: int, max_len: int,
                           impl=None):
    """The speculative VERIFY step: write TQ consecutive positions per
    slot starting at its own `pos` (the window = last consumed token +
    the draft), attend every window query over keys <= its absolute
    position, all slots in one launch. Decode's multi-query
    generalization — TQ=1 reproduces `paged_decode_attention`
    bit-for-bit (same addressing, same write, same read).

    Positions this round REwrites may hold a previous round's rejected
    suffix; that's sound by construction — everything below a row's
    `pos` is committed tokens, and every key a query can see (<= pos +
    i < pos + TQ) is rewritten here before the read. The pool side
    (PagePool.reserve/rollback) guarantees the blocks under
    pos..pos+TQ-1 are mapped, so accepted tokens always land.

    q/k/v [S, TQ, ·, Dh]; pos [S] (sentinel out-of-range on inactive
    rows); active [S] bool. Returns (out [S, TQ, H, Dh], k_arena,
    v_arena)."""
    s, tq = q.shape[0], q.shape[1]
    num_pages = (k_arena[0] if isinstance(k_arena, tuple)
                 else k_arena).shape[0]
    ap = pos[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    pg, off = jax.vmap(
        lambda row, p: page_addresses(row, p, page_size=page_size))(
            page_table, ap)
    pg = jnp.where(active[:, None], pg, jnp.int32(num_pages))
    k_arena = write_kv(k_arena, k.reshape((s * tq,) + k.shape[2:]),
                       pg.reshape(-1), off.reshape(-1))
    v_arena = write_kv(v_arena, v.reshape((s * tq,) + v.shape[2:]),
                       pg.reshape(-1), off.reshape(-1))
    out = _ragged_read(q, k_arena, v_arena, page_table, pos, active,
                       page_size=page_size, max_len=max_len, impl=impl)
    return out, k_arena, v_arena


def _ragged_read(q, k_arena, v_arena, page_table, pos0, active, *,
                 page_size: int, max_len: int, impl=None):
    """The shared read+attend tail: dispatch through
    ops.ragged_paged_attention, whose auto mode (impl=None) is the jnp
    gather on every backend until a fused kernel the TPU compiler
    accepts takes that slot — the seam this module's header promised,
    with nothing above it changing."""
    from paddle_tpu.ops import ragged_paged_attention as _rpa  # cycle

    return _rpa.ragged_attention(q, k_arena, v_arena, page_table,
                                 pos0, active, page_size=page_size,
                                 max_len=max_len, impl=impl)
