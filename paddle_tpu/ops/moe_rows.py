"""Rows moved between positions and the expert-ordered row buffer of a
dropless mixture of experts — Pallas TPU kernels.

A dropless layer that holds a share of the experts sizes its row buffer
for every (position, choice) pair, R = T * k rows, and fills its head:
slot s < rows_held holds pair `pair_of_slot[s]`, ordered by expert, and
the slots behind belong to the experts of other chips. These kernels
move only the held rows, as `ops.moe_grouped_matmul` computes only
their tiles:

  * `moe_take_held_rows`   by slot: dst[s] = scale[s] * src[row_of_slot[s]]
                           for s < rows_held, optionally with
                           dot[s] = <other[s], src[row_of_slot[s]]>
  * `moe_sum_held_rows`    by position: y[t] = sum over t's held choices
                           c, in choice order, in float32, of
                           w[t, c] * src[slot_of_pair[t, c]]

Slots past the held rows (and their `dot`) are not written: callers mask
by row, never multiply by zero. No DMA is issued for a choice whose
expert is not held.

How a row moves. Mosaic slices an HBM array along a tiled dimension only
in whole tiles (8 rows), so a row of a `[N, D]` array cannot be one DMA,
and in `[N, 1, D]` bfloat16 still packs two rows a word. A row becomes a
leading index of 32-bit words instead: `[N, 1, W]`, one contiguous run. A
float32 row is its own W = D words; a bfloat16 row is W = ceil(D / 2)
words, word i holding element i in its low half and element i + W in
its high half (bit for bit: a bfloat16 is the high half of its float32).
`_pack_rows` puts a source in words: a position-indexed one (`x`, the
combine's gradient) whole, T rows; the row buffer its held head alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_util

# slots a grid step of the by-slot kernels; positions a grid step of the
# by-position kernel (k rows each at most)
ROW_TILE = 256
POSITION_TILE = 128
# row copies issued a loop iteration (Mosaic unrolls no fori_loop)
_ISSUE = 8
_HIGH = -65536          # 0xFFFF0000: the high half of a 32-bit word


def _tile(n: int, tile: int) -> int:
    """The tile along a dimension of n: n itself up to `tile`, else the
    largest multiple of 8 up to `tile` that divides n."""
    if n <= tile:
        return n
    for t in range(tile - tile % 8, 0, -8):
        if n % t == 0:
            return t
    raise ValueError(f"{n} rows divide into no tile of 8 rows or more")


def _word_width(d: int, dtype) -> int:
    return d if jnp.dtype(dtype).itemsize == 4 else (d + 1) // 2


def _to_words(v):
    """[n, D] -> [n, W] 32-bit words."""
    if v.dtype.itemsize == 4:
        return v
    n, d = v.shape
    w = (d + 1) // 2
    bits = lax.bitcast_convert_type(v.astype(jnp.float32), jnp.int32)
    lo, hi = bits[:, :w], bits[:, w:]
    if d % 2:
        hi = jnp.concatenate([hi, jnp.zeros((n, 1), jnp.int32)], axis=1)
    return hi | lax.shift_right_logical(lo, jnp.int32(16))


def _from_words(words, dtype, d: int):
    """[n, W] words -> [n, D] float32, exact."""
    if jnp.dtype(dtype).itemsize == 4:
        return words.astype(jnp.float32)
    lo = lax.bitcast_convert_type(lax.shift_left(words, jnp.int32(16)),
                                  jnp.float32)
    hi = lax.bitcast_convert_type(words & jnp.int32(_HIGH), jnp.float32)
    return jnp.concatenate([lo, hi], axis=1)[:, :d]


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=pallas_util.VMEM_LIMIT_BYTES)


def _issue(n, start):
    """start(j) for j < n (a Python or a traced int), `_ISSUE` to a loop
    iteration and the rest one at a time."""
    def group(i, carry):
        for u in range(_ISSUE):
            start(i * _ISSUE + u)
        return carry

    def one(j, carry):
        start(j)
        return carry

    lax.fori_loop(0, n // _ISSUE, group, 0)
    lax.fori_loop(n // _ISSUE * _ISSUE, n, one, 0)


def _traced_once(*static):
    """`jax.jit` for an entry point a model calls once a layer: a call
    of a shape already seen reuses its trace and lowering (set-up time;
    the compiled step is the same). The key holds where the kernels
    run, so a process that runs them both interpreted and lowered (the
    tests) never mixes the two."""
    def wrap(fun):
        jitted = jax.jit(fun, static_argnames=(*static, "interpret"))

        @functools.wraps(fun)
        def call(*args, **kwargs):
            return jitted(*args, **kwargs, interpret=pallas_util.interpret())
        return call
    return wrap


def _pack_rows(src, rows, tile: int, interpret: bool):
    """src [N, D] -> [N, 1, W] words, one row a leading index; the tiles
    from the one that holds row `rows` ([1] int32) on are not written."""
    r, d = src.shape
    w = _word_width(d, src.dtype)
    tm = _tile(r, tile)

    def kernel(n_ref, src_ref, out_ref):
        out_ref[...] = _to_words(src_ref[...]).reshape(tm, 1, w)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((r, 1, w), jnp.float32
                                       if src.dtype.itemsize == 4
                                       else jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, d), lambda i, n: (i, 0))],
            out_specs=pl.BlockSpec((tm, 1, w), lambda i, n: (i, 0, 0)),
            grid=((rows[0] + tm - 1) // tm,),
        ),
        compiler_params=_params(),
        interpret=interpret,
        name="moe_pack_rows",
    )(rows, src)


@_traced_once("out_dtype", "tile")
def moe_take_held_rows(src, row_of_slot, rows_held, *, scale=None,
                       other=None, out_dtype=None, tile: int = ROW_TILE,
                       interpret: bool):
    """src [N, D], row_of_slot [R] int32 (rows of src), rows_held int32
    scalar -> dst [R, D] (`out_dtype`, else src's), and with `other`
    [R, D] also dot [R] float32. `scale` [R] float32 multiplies a row
    (in float32) before the cast. Slots from rows_held on: not written,
    but for the rest of the last tile (real rows of src)."""
    n, d = src.shape
    r = row_of_slot.shape[0]
    out_dtype = out_dtype or src.dtype
    tm = _tile(r, tile)
    words = _pack_rows(src, jnp.full((1,), n, jnp.int32), ROW_TILE,
                       interpret)
    w = words.shape[2]
    held = jnp.reshape(rows_held, (1,)).astype(jnp.int32)
    ids = row_of_slot.astype(jnp.int32).reshape(r // tm, 1, tm)

    def kernel(n_ref, ids_ref, words_hbm, *refs):
        refs = list(refs)
        scale_ref = refs.pop(0) if scale is not None else None
        other_ref = refs.pop(0) if other is not None else None
        dst_ref = refs.pop(0)
        dot_ref = refs.pop(0) if other is not None else None
        buf, sem = refs

        def copy(j, row):
            return pltpu.make_async_copy(words_hbm.at[row], buf.at[j],
                                         sem.at[0])

        _issue(tm, lambda j: copy(j, ids_ref[0, j]).start())

        def wait(j, carry):
            copy(0, 0).wait()
            return carry

        lax.fori_loop(0, tm, wait, 0)
        rows = _from_words(buf[...].reshape(tm, w), src.dtype, d)
        if other_ref is not None:
            dot_ref[...] = jnp.sum(other_ref[...].astype(jnp.float32) * rows,
                                   axis=1, keepdims=True)
        if scale_ref is not None:
            rows = scale_ref[...] * rows
        dst_ref[...] = rows.astype(dst_ref.dtype)

    in_specs = [pl.BlockSpec((None, 1, tm), lambda i, n: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [held, ids, words]
    if scale is not None:
        in_specs.append(pl.BlockSpec((tm, 1), lambda i, n: (i, 0)))
        args.append(scale.astype(jnp.float32).reshape(r, 1))
    if other is not None:
        in_specs.append(pl.BlockSpec((tm, d), lambda i, n: (i, 0)))
        args.append(other)
    out_shape = [jax.ShapeDtypeStruct((r, d), out_dtype)]
    out_specs = [pl.BlockSpec((tm, d), lambda i, n: (i, 0))]
    if other is not None:
        out_shape.append(jax.ShapeDtypeStruct((r, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((tm, 1), lambda i, n: (i, 0)))
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=in_specs,
            out_specs=out_specs,
            grid=((held[0] + tm - 1) // tm,),
            scratch_shapes=[pltpu.VMEM((tm, 1, w), words.dtype),
                            pltpu.SemaphoreType.DMA((1,))],
        ),
        compiler_params=_params(),
        interpret=interpret,
        name="moe_take_held_rows",
    )(*args)
    if other is None:
        return outs[0]
    return outs[0], outs[1].reshape(r)


@_traced_once("tile", "row_tile")
def moe_sum_held_rows(src, slot_of_pair, held, weight=None, *,
                      tile: int = POSITION_TILE,
                      row_tile: int = ROW_TILE, interpret: bool):
    """src [R, D] (the row buffer), slot_of_pair [T, k] int32, held
    [T, k] bool, weight [T, k] float32 or None (ones) -> y [T, D]
    float32: y[t] = sum over t's held choices c, in choice order, of
    weight[t, c] * src[slot_of_pair[t, c]]. The held slots are the
    buffer's head (slot < rows held), which alone is read."""
    r, d = src.shape
    t, k = held.shape
    tt = _tile(t, tile)
    tiles = t // tt
    rows_held = jnp.sum(held, dtype=jnp.int32).reshape(1)
    words = _pack_rows(src, rows_held, row_tile, interpret)
    w = words.shape[2]
    # a tile's held pairs first, in pair order: the kernel walks them
    # alone, each to its row of the tile's choice-major buffer
    by_tile = held.reshape(tiles, tt * k)
    order = jnp.argsort(jnp.where(by_tile, 0, 1), axis=1,
                        stable=True).astype(jnp.int32)
    count = jnp.sum(by_tile, axis=1, dtype=jnp.int32)
    slots = jnp.take_along_axis(
        slot_of_pair.astype(jnp.int32).reshape(tiles, tt * k), order, axis=1)
    dest = order % k * tt + order // k
    weighted = weight is not None

    def kernel(count_ref, slots_ref, dest_ref, held_ref, *refs):
        if weighted:
            weight_ref, words_hbm, y_ref, buf, sem = refs
        else:
            words_hbm, y_ref, buf, sem = refs

        def copy(row, slot):
            return pltpu.make_async_copy(words_hbm.at[slot], buf.at[row],
                                         sem.at[0])

        n = count_ref[pl.program_id(0)]
        _issue(n, lambda u: copy(dest_ref[0, u], slots_ref[0, u]).start())

        def wait(u, carry):
            copy(0, 0).wait()
            return carry

        lax.fori_loop(0, n, wait, 0)
        acc = jnp.zeros((tt, d), jnp.float32)
        for c in range(k):
            rows = _from_words(buf[c * tt:(c + 1) * tt].reshape(tt, w),
                               src.dtype, d)
            if weighted:
                rows = weight_ref[:, c:c + 1] * rows
            acc = acc + jnp.where(held_ref[:, c:c + 1] != 0, rows, 0.0)
        y_ref[...] = acc

    pairs = pl.BlockSpec((None, 1, tt * k), lambda i, n: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    by_position = pl.BlockSpec((tt, k), lambda i, n: (i, 0))
    in_specs = [pairs, pairs, by_position]
    args = [count, slots.reshape(tiles, 1, tt * k),
            dest.reshape(tiles, 1, tt * k), held.astype(jnp.int32)]
    if weighted:
        in_specs.append(by_position)
        args.append(weight.astype(jnp.float32))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    args.append(words)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tt, d), lambda i, n: (i, 0)),
            grid=(tiles,),
            scratch_shapes=[pltpu.VMEM((k * tt, 1, w), words.dtype),
                            pltpu.SemaphoreType.DMA((1,))],
        ),
        compiler_params=_params(),
        interpret=interpret,
        name="moe_sum_held_rows",
    )(*args)
