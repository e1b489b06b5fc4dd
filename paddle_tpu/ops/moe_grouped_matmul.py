"""Grouped matrix products for a dropless mixture of experts — Pallas
TPU kernels.

The rows of `lhs` [M, K] are ordered by group (expert): group g owns
rows [offset[g], offset[g] + sizes[g]), the groups follow one another
from row 0, and rows past the last group belong to none (a dropless
layer that holds a share of the experts sizes its row buffer for the
worst case and fills the head of it). Two kernels:

  * `moe_grouped_matmul`      out[rows of g] = lhs[rows of g] @ rhs[g]
  * `moe_grouped_matmul_dw`   out[g] = lhs[rows of g].T @ rhs[rows of g]

Both walk row tiles through a grid whose length is the number of tiles
the groups touch, a traced value: **tiles past the last group's rows
are never visited**, so the work follows the rows held and not the
buffer. A tile that two groups share is visited once for each. `out`
rows that no group owns are left as the buffer was (uninitialised):
callers mask by row, never multiply by zero.

The tile plan (which group and which row tile a grid step works on,
scalar-prefetched) and the two kernels follow the grouped-matmul
kernels that ship with jax (`jax.experimental.pallas.ops.tpu.megablox`),
cut to what this layer needs: whole groups on one chip, K and N
multiples of a lane tile (or no larger than their tiles), an optional
transposed `rhs`.
`grouped_matmul` ties them into one differentiable function.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_util

# (row tile, K tile, N tile); K and N tiles are the largest that divide
# the sizes (`_tiles`).
# Timed on the v5e at [16 groups, 16,384 held rows of 131,072,
# 2048 x 768], a layer's three products forward and backward: 11.25 ms
# at 256 rows, 11.51 at 512, 12.57 at 1024 (PERF.md section 6, PR 34).
DEFAULT_TILING = (256, 2048, 1024)


def _tile_plan(group_sizes, m: int, tm: int, *, visit_empty: bool):
    """(offsets [G+1], group of each grid step, row tile of each grid
    step, number of steps). A group's tiles run from the tile its first
    row lies in to the tile its last row lies in; `visit_empty` gives an
    empty group one step (the dw kernel has to zero its output)."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    group_sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(group_sizes, dtype=jnp.int32)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    n_tiles = jnp.where(group_sizes == 0, 0,
                        (ends + tm - 1) // tm - starts // tm)
    if visit_empty:
        n_tiles = jnp.where(group_sizes == 0, 1, n_tiles)
    steps = tiles_m + g - 1             # every tile once + shared tiles
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), n_tiles,
                           total_repeat_length=steps)
    # a row tile is visited once, and once more for each further group
    # that starts inside it
    starts_inside = (starts % tm != 0) & (group_sizes > 0)
    if visit_empty:
        starts_inside = starts_inside | (group_sizes == 0)
    extra = jnp.zeros((tiles_m,), jnp.int32).at[
        jnp.where(starts_inside, starts // tm, tiles_m)].add(1, mode="drop")
    tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), extra + 1,
                          total_repeat_length=steps)
    return ((offsets.astype(jnp.int32), group_ids, tile_ids),
            jnp.sum(n_tiles, dtype=jnp.int32))


def _rows_of_group(plan, step, tm: int, width: int):
    """[tm, width] bool: the tile's rows that the step's group owns."""
    offsets, group_ids, tile_ids = plan
    g = group_ids[step]
    rows = tile_ids[step] * tm + lax.broadcasted_iota(
        jnp.int32, (tm, width), 0)
    return (rows >= offsets[g]) & (rows < offsets[g + 1])


_LANE = 128


def _tiles(size: int, tile: int, what: str):
    """(tile, number of tiles) along K or N: the size itself where it
    is no larger than `tile`, `tile` where that divides the size, else
    the largest whole number of lane tiles under `tile` that does (K
    2304 under 2048: 1152)."""
    if size <= tile:
        return size, 1
    if size % tile == 0:
        return tile, size // tile
    for smaller in range(tile - tile % _LANE, 0, -_LANE):
        if size % smaller == 0:
            return smaller, size // smaller
    raise ValueError(f"{what} {size} is a multiple of neither its tile "
                     f"{tile} nor a lane tile under it")


def _pad_rows(x, tile: int):
    """Rows up to a whole number of row tiles (none are added at the
    cell's sizes); the added rows belong to no group."""
    tile = min(tile, x.shape[0])
    pad = -x.shape[0] % tile
    return (jnp.pad(x, ((0, pad), (0, 0))) if pad else x), tile


def moe_grouped_matmul(lhs, rhs, group_sizes, *, out_dtype=None,
                       tiling=DEFAULT_TILING, transpose_rhs: bool = False):
    """lhs [M, K], rhs [G, K, N] (or [G, N, K] with `transpose_rhs`),
    group_sizes [G] int32 -> [M, N]; rows outside every group are not
    written."""
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    out_dtype = out_dtype or lhs.dtype
    lhs, tm = _pad_rows(lhs, tiling[0])
    m = lhs.shape[0]
    tk, tiles_k = _tiles(k, tiling[1], "K")
    tn, tiles_n = _tiles(n, tiling[2], "N")
    plan, n_steps = _tile_plan(group_sizes.astype(jnp.int32), m, tm,
                               visit_empty=False)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
        ((1,), (0,)), ((), ()))

    def kernel(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref, acc):
        step, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                    preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _store():
            own = _rows_of_group((offsets, group_ids, tile_ids), step, tm, tn)
            out_ref[...] = jnp.where(
                own, acc[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    def lhs_map(n_i, step, k_i, offsets, group_ids, tile_ids):
        return tile_ids[step], k_i

    def rhs_map(n_i, step, k_i, offsets, group_ids, tile_ids):
        if transpose_rhs:
            return group_ids[step], n_i, k_i
        return group_ids[step], k_i, n_i

    def out_map(n_i, step, k_i, offsets, group_ids, tile_ids):
        return tile_ids[step], n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, tn, tk) if transpose_rhs
                                   else (None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(tiles_n, n_steps, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=pallas_util.VMEM_LIMIT_BYTES),
        interpret=pallas_util.interpret(),
        name="moe_grouped_matmul",
    )(*plan, lhs, rhs)[:rows]


def moe_grouped_matmul_dw(lhs, rhs, group_sizes, *, out_dtype=None,
                          tiling=DEFAULT_TILING):
    """lhs [M, K], rhs [M, N], group_sizes [G] -> [G, K, N]: for each
    group the product of its rows' lhs, transposed, with its rows' rhs
    (the weight gradient of `moe_grouped_matmul`); zeros for an empty
    group."""
    k, n = lhs.shape[1], rhs.shape[1]
    g = group_sizes.shape[0]
    out_dtype = out_dtype or lhs.dtype
    (lhs, tm), (rhs, _) = _pad_rows(lhs, tiling[0]), _pad_rows(rhs, tiling[0])
    m = lhs.shape[0]
    tk, tiles_k = _tiles(k, tiling[1], "K")
    tn, tiles_n = _tiles(n, tiling[2], "N")
    plan, n_steps = _tile_plan(group_sizes.astype(jnp.int32), m, tm,
                               visit_empty=True)

    def kernel(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref, acc):
        step = pl.program_id(2)
        last = pl.num_programs(2) - 1
        group = group_ids[step]
        first_of_group = (step == 0) | (
            group_ids[jnp.maximum(step - 1, 0)] != group)
        last_of_group = (step == last) | (
            group_ids[jnp.minimum(step + 1, last)] != group)

        @pl.when(first_of_group)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(offsets[group + 1] > offsets[group])
        def _accumulate():
            plan_ = (offsets, group_ids, tile_ids)
            # rows of other groups (and of none) count as zeros; the
            # transpose is Mosaic's float32 one
            a = jnp.where(_rows_of_group(plan_, step, tm, tk),
                          lhs_ref[...].astype(jnp.float32), 0.0)
            b = jnp.where(_rows_of_group(plan_, step, tm, tn),
                          rhs_ref[...].astype(jnp.float32), 0.0)
            acc[...] += jnp.dot(a.T.astype(lhs_ref.dtype),
                                b.astype(rhs_ref.dtype),
                                preferred_element_type=jnp.float32)

        @pl.when(last_of_group)
        def _store():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    def lhs_map(n_i, k_i, step, offsets, group_ids, tile_ids):
        return tile_ids[step], k_i

    def rhs_map(n_i, k_i, step, offsets, group_ids, tile_ids):
        return tile_ids[step], n_i

    def out_map(n_i, k_i, step, offsets, group_ids, tile_ids):
        return group_ids[step], k_i, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(tiles_n, tiles_k, n_steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=pallas_util.VMEM_LIMIT_BYTES),
        interpret=pallas_util.interpret(),
        name="moe_grouped_matmul_dw",
    )(*plan, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs, rhs, group_sizes, tiling=DEFAULT_TILING):
    """lhs [M, K] @ rhs[g] [K, N] for the rows of each group g, with
    both gradients as grouped products of the same plan. Rows outside
    every group: not written forward, their lhs gradient not written
    either."""
    return moe_grouped_matmul(lhs, rhs, group_sizes, tiling=tiling)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, tiling):
    return (moe_grouped_matmul(lhs, rhs, group_sizes, tiling=tiling),
            (lhs, rhs, group_sizes))


def _grouped_matmul_bwd(tiling, res, g):
    lhs, rhs, group_sizes = res
    d_lhs = moe_grouped_matmul(g, rhs, group_sizes, out_dtype=lhs.dtype,
                               tiling=tiling, transpose_rhs=True)
    d_rhs = moe_grouped_matmul_dw(lhs, g, group_sizes, out_dtype=rhs.dtype,
                                  tiling=tiling)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
