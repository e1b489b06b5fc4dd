"""Flash attention — Pallas TPU kernel for the hot attention path.

The reference predates attention kernels entirely (its attention is the
additive `simple_attention` composed from layers, reference:
python/paddle/trainer_config_helpers/networks.py:1320); the TPU-native
framework makes fused O(T) -memory attention a first-class op:

  * forward: a Pallas kernel tiled for the MXU (q blocks in VMEM,
    streaming-softmax accumulation over k/v blocks) that never
    materialises the [T, T] score matrix and also emits the row
    log-sum-exp needed by the backward;
  * backward: two Pallas kernels of the same shape, which recompute
    the probabilities from q, k and the saved log-sum-exp block by
    block: `flash_attention_bwd_dkv` accumulates dk and dv over the q
    blocks of one k block, `flash_attention_bwd_dq` accumulates dq over
    the k blocks of one q block. O(T·block) memory, no score-shaped
    array in HBM, operands in the dtype they arrive in. All three
    kernels take the block predicate and the element mask from one
    helper each (`_block_needed`, `_pair_mask`);
  * the forward does per block only the VPU work the block needs, on
    blocks sized from the call's shape: `_block_interior`, the twin of
    `_block_needed`, names the blocks whose every pair attends, and
    those run the streaming softmax with no mask at all; the blocks the
    mask cuts (the diagonal, the band's edge, a row's key length, the
    two diagonals of the block-diffusion square) run it masked; the
    rest are skipped and fetch nothing. Under the block-diffusion mask
    a fourth kind, `_block_diagonal`, takes the noised-against-noised
    blocks of the diagonal, whose pairs all lie in the 128 x 128
    sub-squares on their own diagonal: those run masked on the
    sub-squares alone, in all three kernels. `_forward_blocks` picks the
    blocks: 1024 x 1024 where the sequence carries them, smaller where
    it does not, never padding a sequence further than a 256 x 512
    grid would;
  * a call whose sliding window cuts (narrower than the sequence)
    names its three kernels apart, `flash_attention_fwd_window`,
    `flash_attention_bwd_dkv_window` and `flash_attention_bwd_dq_window`
    (`_name_suffix`), so the trace of a model that mixes windowed and
    full layers can tell their events; an inert window keeps the plain
    names;
  * a differentiated call names the forward kernel's two outputs, o
    and the row log-sum-exp (`REMAT_SAVED`), so a `jax.checkpoint`
    whose policy saves those names (the transformer's `remat`) keeps
    them and runs the forward kernel once; a plain checkpoint runs it
    twice, the second time only to hand the backward kernels the same
    two arrays;
  * composes with the mesh: wrap in shard_map and the seq axis via
    parallel.ring_attention for context parallelism, or shard heads.

On non-TPU backends the kernel runs in Pallas interpret mode (tests) —
production CPU users should prefer ops in dense form.

The per-row key lengths ride scalar prefetch (`lens [BH] i32` lands in
SMEM whole, before the grid starts): Mosaic refuses a blocked rank-1
SMEM operand whose block is neither the array nor a multiple of 128.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_util

NEG_INF = -1e30
# the names a differentiated call gives the forward kernel's output and
# its row log-sum-exp, for a checkpoint's policy (see `flash_attention`)
REMAT_SAVED = ("flash_attention_out", "flash_attention_lse")

# the backward kernels' own blocks (q rows x k rows of one grid step),
# chosen by timing the pair alone on the v5e at bf16[48, 4096, 128],
# causal (PERF.md section 6, PR 32); the forward's are not theirs
BWD_BLOCK_Q = 1024
BWD_BLOCK_K = 1024
_LANE = 128  # TPU minimum tile width (lane count)
# the forward's blocks come from the call's shape (`_forward_blocks`):
# the largest it takes on an axis, timed alone on the v5e under both
# masks (PERF.md section 6, PRs 30, 34 and 35), and the grid no
# sequence is padded past (the blocks of every PR before 35)
_FWD_BLOCK_MAX = 1024
_FWD_GRID_Q = 256
_FWD_GRID_K = 512
# the edge of the sub-squares a diagonal step of the block-diffusion
# mask computes (`_diagonal_sub`): one lane tile, the least edge whose
# slices of the backward's `lse` and `delta` rows are whole lane tiles.
# Measured on one v5e chip, the three kernels alone at bf16[64, 8192,
# 128], L 4096, Bd 4, forward + backward: 29.8-30.0 ms at 128, 30.3-30.4
# at 256, 31.4-31.5 at 512, 33.0-33.2 on whole blocks; a sub-square's
# work falls with its area faster than its fixed cost adds up
_DIAGONAL_SUB = 128


def _fit_block(t: int, grid: int, largest: int) -> int:
    """The forward's block along an axis of t positions: the largest
    multiple of a lane tile, up to `largest`, whose blocks cover t with
    no more padding than blocks of `grid` rows would; a sequence no
    longer than `grid` is one block, itself."""
    grid = min(grid, largest)
    if t <= grid:
        return max(t, 1)
    limit = pl.cdiv(t, grid) * grid
    return next(b for b in range(largest, grid - 1, -_LANE)
                if pl.cdiv(t, b) * b <= limit)


def _forward_vmem_bytes(block_q: int, block_k: int, head_dim: int,
                        itemsize: int) -> int:
    """What one grid step of the forward keeps in VMEM, from its
    shapes: q, k, v and o blocks with the pipeline's second buffers,
    the float32 accumulator, m, l and lse, and six score-shaped float32
    or int32 temporaries (s, p, the rounded p, the mask's integers)."""
    return (2 * itemsize * head_dim * (2 * block_q + 2 * block_k)
            + 4 * block_q * (head_dim + 4 * _LANE)
            + 6 * 4 * block_q * block_k)


def _forward_blocks(t: int, t_kv: int, head_dim: int, dtype):
    """(block_q, block_k) of the forward kernel for a call's shape,
    whatever its mask: `_FWD_BLOCK_MAX` on each axis where the sequence
    carries it (alone on the v5e the causal forward at bf16[48, 4096,
    128] takes 3.2 ms at 1024 x 1024 for 6.2 at 256 x 512, the
    block-diffusion one at bf16[64, 8192, 128] 10.1 for 19.6: the
    float32 softmax on the VPU binds the kernel, and a large block
    spends less of it on `m`, `l` and the accumulator's rescaling),
    smaller where padding to it would compute more rows than a 256 x
    512 grid does (T 1280 stays 1280: 640 x 768), and halved while a
    step's VMEM (`_forward_vmem_bytes`) would pass `VMEM_BUDGET_BYTES`."""
    itemsize = jnp.dtype(dtype).itemsize
    largest = _FWD_BLOCK_MAX
    while True:
        blocks = (_fit_block(t, _FWD_GRID_Q, largest),
                  _fit_block(t_kv, _FWD_GRID_K, largest))
        fits = _forward_vmem_bytes(
            *blocks, head_dim, itemsize) <= pallas_util.VMEM_BUDGET_BYTES
        if fits or largest == _LANE:
            return blocks
        largest //= 2


def _xp(*xs):
    """`jax.numpy` where a block index is traced (a kernel body, an
    `index_map`), `numpy` where all are Python or numpy integers (the
    counts taken while tracing, the tests): the block predicates are
    the same arithmetic on both."""
    return jnp if any(isinstance(x, jax.Array) for x in xs) else np


def _div(a, b: int):
    """a // b for a non-negative int32 scalar or vector and a static b:
    a shift where b is a power of two, a truncating divide elsewhere
    (`jnp.floor_divide` adds a sign correction nothing here needs)."""
    if _xp(a) is np:
        return a // b
    a = jnp.asarray(a, jnp.int32)
    if b & (b - 1) == 0:
        return jax.lax.shift_right_logical(a, jnp.int32(b.bit_length() - 1))
    return jax.lax.div(a, jnp.int32(b))


def _bd_spans(i, block: int, block_diffusion):
    """What block i (of `block` rows) of a block-diffusion sequence
    [noised copy ; clean copy] of 2L positions holds: (has a noised
    part, first and last Bd-block of it, has a clean part, first and
    last Bd-block of that). Scalars; the Bd-block numbers of an absent
    part mean nothing."""
    length, bd = block_diffusion
    xp = _xp(i)
    lo = i * block
    hi = xp.minimum(lo + block, 2 * length) - 1
    return (lo < length, _div(lo, bd), _div(xp.minimum(hi, length - 1), bd),
            hi >= length, _div(xp.maximum(lo, length) - length, bd),
            _div(xp.maximum(hi, length) - length, bd))


def _block_needed(qi, j, n_keys, *, block_q: int, block_k: int,
                  causal: bool, window, block_diffusion=None):
    """Does (q block qi, k block j) hold any pair `_pair_mask` admits?
    The one block predicate of the forward and both backward kernels:
    a k block entirely past the row's key length, entirely above the
    causal diagonal or entirely below the band is skipped (a fully
    invalid block is a no-op anyway: p = 0 — skipping saves the dead
    MXU work; a short row in a long padded batch touches ~len/BK
    blocks, not ~T/BK). Under `block_diffusion` (L, Bd): noised
    queries meet the noised keys of their own Bd-blocks and the clean
    keys of earlier ones, clean queries the clean keys up to their own."""
    needed = j * block_k < n_keys
    if block_diffusion is not None:
        qn, qn0, qn1, qc, _, qc1 = _bd_spans(qi, block_q, block_diffusion)
        kn, kn0, kn1, kc, kc0, _ = _bd_spans(j, block_k, block_diffusion)
        return needed & ((qn & kn & (kn0 <= qn1) & (qn0 <= kn1))
                         | (qn & kc & (kc0 < qn1)) | (qc & kc & (kc0 <= qc1)))
    if causal:
        needed = needed & (j * block_k <= (qi + 1) * block_q - 1)
    if window is not None:
        # sliding window: the block's newest key must reach the oldest
        # key the block's oldest query may see (qpos - window + 1) —
        # blocks entirely below the band skip, so long-T cost is
        # O(T * window), not O(T^2)
        needed = needed & (
            (j + 1) * block_k - 1 >= qi * block_q - window + 1)
    return needed


def _block_interior(qi, j, n_keys, *, block_q: int, block_k: int,
                    causal: bool, window, block_diffusion=None):
    """Does `_pair_mask` admit EVERY pair of (q block qi, k block j)?
    The twin of `_block_needed`, for the forward and both backward
    kernels: such a block runs with no mask. Every key inside the row's
    length, the whole block on or below the diagonal, the whole block
    inside the band. Under `block_diffusion`: clean keys only, of
    Bd-blocks before those of every noised query of the block and up to
    those of every clean one (or, where blocks are no larger than a
    Bd-block, noised queries and noised keys of one Bd-block)."""
    interior = (j + 1) * block_k <= n_keys
    if block_diffusion is not None:
        qn, qn0, qn1, qc, qc0, _ = _bd_spans(qi, block_q, block_diffusion)
        kn, kn0, kn1, kc, _, kc1 = _bd_spans(j, block_k, block_diffusion)
        no = _xp(qi, j).logical_not
        clean = no(kn) & (no(qn) | (kc1 < qn0)) & (no(qc) | (kc1 <= qc0))
        own = (no(kc) & no(qc) & (qn0 == qn1) & (kn0 == kn1)
               & (qn0 == kn0))
        return interior & (clean | own)
    if causal:
        interior = interior & ((j + 1) * block_k - 1 <= qi * block_q)
    if window is not None:
        interior = interior & (
            (qi + 1) * block_q - 1 - j * block_k < window)
    return interior


def _diagonal_sub(block_q: int, block_k: int, block_diffusion):
    """The edge S of the sub-squares a diagonal step computes, or None
    where no block of the call can be one: under `block_diffusion`
    (L, Bd), square blocks of whole sub-squares larger than one, and Bd
    dividing S, so that a Bd-block never straddles two sub-squares. S
    is one lane tile: a sub-square's slice of the backward's `lse` and
    `delta` rows ([1, 1, BQ], lanes) is then whole lane tiles."""
    if block_diffusion is None or block_q != block_k:
        return None
    sub = _DIAGONAL_SUB
    if block_q <= sub or block_q % sub or sub % block_diffusion[1]:
        return None
    return sub


def _block_diagonal(qi, j, n_keys, *, block_q: int, block_k: int,
                    block_diffusion=None, **_):
    """Is (q block qi, k block j) a block of the noised-against-noised
    diagonal whose admitted pairs all lie in the S x S sub-squares on
    its own diagonal (`_diagonal_sub`)? Noised queries and noised keys
    only, the same span of positions: a noised query attends only the
    noised keys of its own Bd-block, and every Bd-block lies inside one
    sub-square. Such a block runs the masked body on its block_q / S
    sub-squares alone, an S / block_q share of its pairs; a Python
    False for every other mask and shape. (The mask takes no key
    lengths: n_keys is the whole sequence.)"""
    if _diagonal_sub(block_q, block_k, block_diffusion) is None:
        return False
    return (qi == j) & ((j + 1) * block_k <= block_diffusion[0])


def _block_kinds(nq: int, nk: int, n_keys: int, **masks):
    """(interior, cut, diagonal, skipped) grid steps of one (batch x
    head) row whose keys are all valid, counted from the three block
    predicates: what a kernel runs unmasked, masked, masked on the
    sub-squares of its diagonal and not at all."""
    qi, j = np.arange(nq)[:, None], np.arange(nk)[None, :]
    needed = np.broadcast_to(_block_needed(qi, j, n_keys, **masks), (nq, nk))
    interior = np.broadcast_to(
        _block_interior(qi, j, n_keys, **masks), (nq, nk))
    diagonal = np.broadcast_to(
        _block_diagonal(qi, j, n_keys, **masks), (nq, nk))
    n_interior, n_needed = int(interior.sum()), int(needed.sum())
    n_diagonal = int(diagonal.sum())
    return (n_interior, n_needed - n_interior - n_diagonal, n_diagonal,
            nq * nk - n_needed)


def _sub_squares(qi, j, n_keys, *, k_major: bool = False, **masks):
    """The S x S sub-squares of diagonal block (qi, j), one by one: the
    rows of the q block that hold its queries (the same rows of the k
    block hold its keys) and a function of no arguments that gives its
    `_pair_mask` (the sub-square's own block numbers on blocks of S)."""
    sub = _diagonal_sub(masks["block_q"], masks["block_k"],
                        masks["block_diffusion"])
    per = masks["block_q"] // sub
    on_sub = dict(masks, block_q=sub, block_k=sub, k_major=k_major)
    for s in range(per):
        yield pl.ds(s * sub, sub), functools.partial(
            _pair_mask, qi * per + s, j * per + s, n_keys, **on_sub)


def _pair_mask(qi, j, n_keys, *, block_q: int, block_k: int, causal: bool,
               window, block_diffusion=None, k_major: bool = False):
    """The one element mask: which (query, key) pairs of block (qi, j)
    attend — key inside the row's length (tail padding and right-padded
    variable-length rows are the SAME mask), on or below the diagonal,
    inside the band. [BQ, BK] bool, or [BK, BQ] with `k_major` (the
    dk/dv kernel works on transposed scores).

    `block_diffusion` (L, Bd) over 2L positions [noised ; clean], with
    blk(i) = (i mod L) // Bd: a noised query attends the noised keys of
    its own Bd-block and the clean keys of earlier Bd-blocks; a clean
    query the clean keys of its own and earlier Bd-blocks, and no
    noised key. Worked on one column of queries and one row of keys;
    the block-shaped part is two products, two compares and an and."""
    shape, q_axis = (((block_k, block_q), 1) if k_major
                     else ((block_q, block_k), 0))
    if block_diffusion is not None:
        length, bd = block_diffusion
        q_shape = (1, block_q) if k_major else (block_q, 1)
        k_shape = (block_k, 1) if k_major else (1, block_k)
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, q_shape, q_axis)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, k_shape, 1 - q_axis)
        q_noised, k_noised = qpos < length, kpos < length
        # start of the query's Bd-block, and the key, inside their halves
        q_start = _div(jnp.where(q_noised, qpos, qpos - length), bd) * bd
        k_in = jnp.where(k_noised, kpos, kpos - length)
        k_in = jnp.where(kpos < n_keys, k_in, 4 * length)   # tail padding
        # a key is admitted inside [low, high): clean keys from 0 to the
        # Bd-block of a noised query, or through that of a clean one;
        # noised keys inside the Bd-block of a noised query, and of no
        # clean one. Integers all the way: Mosaic selects no booleans.
        clean_high = jnp.where(q_noised, q_start, q_start + bd)
        noised_low = jnp.where(q_noised, q_start, 4 * length)
        is_noised = k_noised.astype(jnp.int32)
        low = is_noised * noised_low
        high = clean_high + is_noised * (q_start + bd - clean_high)
        return (k_in >= low) & (k_in < high)
    kpos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 - q_axis)
    valid = kpos < n_keys                      # tail padding / key mask
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, shape, q_axis)
        valid = valid & (qpos >= kpos)
        if window is not None:
            valid = valid & (qpos - kpos < window)
    return valid


def _needed_k_blocks(qi, n_keys, *, block_q: int, block_k: int,
                     causal: bool, window):
    """(first, last) k block `_block_needed` admits for q block qi, in
    closed form, for an `index_map` to clamp to: a skipped grid step
    then names the block the pipeline already holds and copies nothing.
    With no key at all (n_keys 0) it names block 0."""
    last = (jnp.maximum(n_keys, 1) - 1) // block_k
    if causal:
        last = jnp.minimum(last, ((qi + 1) * block_q - 1) // block_k)
    first = 0
    if window is not None:
        first = jnp.maximum(qi * block_q - window + 1, 0) // block_k
    return first, last


def _needed_q_blocks(j, n_keys, n_q_blocks, *, block_q: int, block_k: int,
                     causal: bool, window):
    """(first, last) q block `_block_needed` admits for k block j; a k
    block past the row's length needs none and names `first` alone."""
    first = (j * block_k) // block_q if causal else 0
    last = n_q_blocks - 1
    if window is not None:
        last = jnp.minimum(last, ((j + 1) * block_k + window - 2) // block_q)
    last = jnp.where(j * block_k < n_keys, last, first)
    return first, last


def _bd_needed_k_runs(qi, *, block_q: int, block_k: int, block_diffusion):
    """The k blocks `_block_needed` admits for q block qi under the
    block-diffusion mask, as two runs (first, last, first, last): the
    noised keys of the block's own Bd-blocks, then the clean keys from
    the start of the clean half. A run that does not exist names the
    nearest block of the other."""
    length, bd = block_diffusion
    qn, qn0, qn1, qc, _, qc1 = _bd_spans(qi, block_q, block_diffusion)
    a0, a1 = _div(qn0 * bd, block_k), _div(qn1 * bd + bd - 1, block_k)
    # the last clean Bd-block any query of the block sees; -1: none
    m = jnp.maximum(jnp.where(qn, qn1 - 1, -1), jnp.where(qc, qc1, -1))
    b0 = length // block_k
    b1 = _div(length + jnp.maximum(m, 0) * bd + bd - 1, block_k)
    a0, a1 = jnp.where(qn, a0, b0), jnp.where(qn, a1, b0)
    return a0, a1, jnp.where(m >= 0, b0, a1), jnp.where(m >= 0, b1, a1)


def _bd_needed_q_runs(j, *, block_q: int, block_k: int, block_diffusion):
    """The q blocks `_block_needed` admits for k block j under the
    block-diffusion mask, as two runs: noised queries (of the noised
    keys' own Bd-blocks and, for clean keys, of every later Bd-block),
    then clean queries from the clean keys' first Bd-block to the end."""
    length, bd = block_diffusion
    kn, kn0, kn1, kc, kc0, _ = _bd_spans(j, block_k, block_diffusion)
    # noised queries: [own Bd-blocks] and / or [(kc0 + 1) * bd, L)
    later = (kc0 + 1) * bd
    has_later = kc & (later < length)
    lo = jnp.minimum(jnp.where(kn, kn0 * bd, length),
                     jnp.where(has_later, later, length))
    hi = jnp.where(has_later, length - 1, kn1 * bd + bd - 1)
    a0, a1 = _div(lo, block_q), _div(hi, block_q)
    b0 = _div(length + kc0 * bd, block_q)
    b1 = (2 * length - 1) // block_q
    some_noised = kn | has_later
    a0, a1 = jnp.where(some_noised, a0, b0), jnp.where(some_noised, a1, b0)
    return a0, a1, jnp.where(kc, b0, a1), jnp.where(kc, b1, a1)


def _clamp_to_runs(i, a0, a1, b0, b1):
    """Grid step i clamped to two runs of blocks: a skipped step names
    the block the pipeline already holds, or will need next."""
    return jnp.where(i < b0, jnp.clip(i, a0, a1), jnp.clip(i, b0, b1))



def _attn_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                 m_ref, l_ref, *, scale: float, causal: bool,
                 window, block_diffusion=None):
    """One (batch*head, q-block, k-block) grid step. The innermost grid
    dim walks k/v blocks sequentially (TPU grids are sequential), so
    VMEM scratch (acc/m/l) carries streaming-softmax state across k
    steps; only one [BK, D] k/v tile is resident at a time. A step is
    one of four kinds: interior (`_block_interior`: no mask), diagonal
    (`_block_diagonal`: the masked body on each sub-square of the
    block's diagonal, its rows of acc/m/l alone), cut (needed and
    neither: the mask on the scores and on p), or skipped; an admitted
    pair goes through the same float32 expression in every body.

    Refs: len [BH] i32, scalar-prefetched (row b's valid key count —
    t_kv when no key mask; tail padding and right-padded
    variable-length prompts are the SAME mask); q [1,BQ,D]; k/v
    [1,BK,D]; o [1,BQ,D]; lse [1,BQ,LANE]; scratch acc [BQ,D] f32, m/l
    [BQ,LANE] f32.
    """
    n_keys = len_ref[pl.program_id(0)]
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    masks = dict(block_q=bq, block_k=block_k, causal=causal, window=window,
                 block_diffusion=block_diffusion)
    needed = _block_needed(qi, j, n_keys, **masks)
    interior = _block_interior(qi, j, n_keys, **masks)
    diagonal = _block_diagonal(qi, j, n_keys, **masks)

    def step(valid, rows=slice(None)):
        """One block of the streaming softmax; `valid` None: every pair
        attends, and the block pays for no mask. `rows`: the queries,
        and the keys, of one sub-square of a diagonal step."""
        # native-dtype (e.g. bf16) operands on the MXU, f32 accumulation
        s = jax.lax.dot_general(
            q_ref[0, rows], k_ref[0, rows], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[rows, :1]                       # [BQ, 1]
        l_prev = l_ref[rows, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if valid is not None:
            # mask p too: a row with NO valid key would otherwise see
            # exp(NEG_INF - NEG_INF) = 1 everywhere (NEG_INF is finite)
            # and return the unweighted mean of v; with p zeroed it
            # returns 0, matching the backward's zero grads
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                # [BQ, 1]
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, rows], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[rows] = jax.lax.broadcast_in_dim(
            m_new[:, 0], (m_new.shape[0], m_ref.shape[1]), (0,))
        l_ref[rows] = jax.lax.broadcast_in_dim(
            l_new[:, 0], (l_new.shape[0], l_ref.shape[1]), (0,))

    @pl.when(interior)
    def _interior():
        step(None)

    cut = needed & jnp.logical_not(interior)
    if diagonal is not False:
        cut = cut & jnp.logical_not(diagonal)

        @pl.when(diagonal)
        def _diagonal():
            for rows, valid in _sub_squares(qi, j, n_keys, **masks):
                step(valid(), rows)

    @pl.when(cut)
    def _cut():
        step(_pair_mask(qi, j, n_keys, **masks))

    @pl.when(j == nk - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _name_suffix(window) -> str:
    """A call whose band cuts (a window narrower than the sequence: the
    callers drop an inert one first) names its three kernels apart from
    a full-causal call's, so a trace of a model that mixes the two can
    tell their events."""
    return "_window" if window is not None else ""


def _pad_to(x, size, axis):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_forward(q, k, v, lens, *, causal: bool, block_q: int,
                   block_k: int, window, block_diffusion=None):
    """q,k,v: [BH, T, D]; lens: [BH] i32 valid key counts ->
    (o [BH, T, D], lse [BH, T])."""
    bh, t, d = q.shape
    t_kv = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    if window is not None and window >= t:
        window = None   # causal, Tq == Tkv: the band excludes nothing
    block_q = min(block_q, max(t, 1))
    block_k = min(block_k, max(t_kv, 1))
    tq_pad = pl.cdiv(t, block_q) * block_q
    tk_pad = pl.cdiv(t_kv, block_k) * block_k
    nq, nk = tq_pad // block_q, tk_pad // block_k
    qp = _pad_to(q, tq_pad, 1)
    kp = _pad_to(k, tk_pad, 1)
    vp = _pad_to(v, tk_pad, 1)
    masks = dict(block_q=block_q, block_k=block_k, causal=causal,
                 window=window)
    if window is not None:
        pallas_util.note_traced("flash_attention.mask", "window")
    pallas_util.note_traced("flash_attention.fwd_blocks",
                            f"{block_q}x{block_k}")
    interior, cut, diagonal, skipped = _block_kinds(
        nq, nk, t_kv, block_diffusion=block_diffusion, **masks)
    # a call with no diagonal step keeps the three kinds' text
    diagonal_text = f"diagonal:{diagonal}," if diagonal else ""
    pallas_util.note_traced(
        "flash_attention.fwd_block_kinds",
        f"interior:{interior},cut:{cut},{diagonal_text}skipped:{skipped}")
    if diagonal:
        pallas_util.note_traced(
            "flash_attention.diagonal_sub",
            str(_diagonal_sub(block_q, block_k, block_diffusion)))

    # a skipped step names a block the row needs (the one the pipeline
    # already holds, or will need next) and fetches nothing: under block
    # diffusion about half a row's k blocks, in two runs
    def k_block(b, i, j, lens):
        if block_diffusion is not None:
            return _clamp_to_runs(j, *_bd_needed_k_runs(
                i, block_q=block_q, block_k=block_k,
                block_diffusion=block_diffusion))
        return jnp.clip(j, *_needed_k_blocks(i, lens[b], **masks))

    q_map = lambda b, i, j, lens: (b, i, 0)
    kv_map = lambda b, i, j, lens: (b, k_block(b, i, j, lens), 0)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    o, lse = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          window=window, block_diffusion=block_diffusion),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nk),
            in_specs=[
                vmem((1, block_q, d), q_map),
                vmem((1, block_k, d), kv_map),
                vmem((1, block_k, d), kv_map),
            ],
            out_specs=[
                vmem((1, block_q, d), q_map),
                vmem((1, block_q, _LANE), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, _LANE), jnp.float32),
                pltpu.VMEM((block_q, _LANE), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq_pad, _LANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=pallas_util.VMEM_LIMIT_BYTES),
        interpret=pallas_util.interpret(),
        name="flash_attention_fwd" + _name_suffix(window),
    )(lens.astype(jnp.int32), qp, kp, vp)
    return o[:, :t], lse[:, :t, 0]


_NT = (((1,), (1,)), ((), ()))    # a @ b.T: contract the minor dims


def _recompute(q, k, v, g, lse, delta, valid, *, scale: float,
               k_major: bool):
    """One block's probabilities and score gradients from what the
    forward saved: p = exp(q kT * scale - lse), ds = p * (g vT - delta),
    float32 out of operand-dtype matmuls. [BQ, BK] with lse / delta as
    [BQ, 1] columns or, `k_major`, [BK, BQ] with [1, BQ] rows. The mask
    lands on p, not on the scores: a query with no valid key has lse =
    NEG_INF, and exp(NEG_INF - NEG_INF) would be 1.

    `valid` None: an interior block, whose every pair attends, pays for
    no mask and no select. Its `lse` is finite on every query row (each
    sees every key of the block), and a padded query row has lse 0 and
    q = g = 0, so it adds zeros, as it does under the mask."""
    if k_major:
        q, k, g, v = k, q, v, g
    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(g, v, _NT, preferred_element_type=jnp.float32)
    p = jnp.exp(s * scale - lse)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    return p, p * (dp - delta)


def _backward_steps(compute, qi, j, n_keys, *, k_major: bool = False,
                    **masks):
    """The three computed kinds of a backward grid step, the forward's
    own (`_attn_kernel`): `compute(valid, rows)` with no mask where the
    block is interior (`_block_interior`), on each sub-square where it
    is diagonal (`_block_diagonal`), and under `_pair_mask` on the whole
    block where it is cut (needed and neither). The predicates take the
    block numbers of the pair, (qi, j), in either kernel's order."""
    needed = _block_needed(qi, j, n_keys, **masks)
    interior = _block_interior(qi, j, n_keys, **masks)
    diagonal = _block_diagonal(qi, j, n_keys, **masks)

    @pl.when(interior)
    def _interior():
        compute(None)

    cut = needed & jnp.logical_not(interior)
    if diagonal is not False:
        cut = cut & jnp.logical_not(diagonal)

        @pl.when(diagonal)
        def _diagonal():
            for rows, valid in _sub_squares(qi, j, n_keys, k_major=k_major,
                                            **masks):
                compute(valid(), rows)

    @pl.when(cut)
    def _cut():
        compute(_pair_mask(qi, j, n_keys, k_major=k_major, **masks))


def _bwd_dkv_kernel(len_ref, q_ref, g_ref, lse_ref, delta_ref, k_ref,
                    v_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, causal: bool, window,
                    block_diffusion=None):
    """One (batch*head, k-block, q-block) grid step: the innermost dim
    walks the q blocks, float32 VMEM scratch carries the k block's dk
    and dv across them. Scores are held transposed, [BK, BQ], so both
    accumulating matmuls are plain (pT @ g, dsT @ q) and lse / delta
    enter as [1, BQ] rows, which broadcast along sublanes.

    Refs: len [BH] i32, scalar-prefetched; q/g [1,BQ,D]; lse/delta
    [1,1,BQ] f32; k/v [1,BK,D]; dk/dv [1,BK,D]; scratch [BK,D] f32."""
    n_keys = len_ref[pl.program_id(0)]
    j = pl.program_id(1)
    qi = pl.program_id(2)
    masks = dict(block_q=q_ref.shape[1], block_k=k_ref.shape[1],
                 causal=causal, window=window,
                 block_diffusion=block_diffusion)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute(valid, rows=slice(None)):
        """The block's share of dk and dv, or one sub-square's of a
        diagonal step (`rows`: its queries and keys); `valid`: the mask,
        or None where every pair attends."""
        q, g = q_ref[0, rows], g_ref[0, rows]
        p, ds = _recompute(
            q, k_ref[0, rows], v_ref[0, rows], g, lse_ref[0, :, rows],
            delta_ref[0, :, rows], valid, scale=scale, k_major=True)
        dv_acc[rows] += jnp.dot(p.astype(g.dtype), g,
                                preferred_element_type=jnp.float32)
        dk_acc[rows] += jnp.dot(ds.astype(q.dtype), q,
                                preferred_element_type=jnp.float32)

    _backward_steps(compute, qi, j, n_keys, k_major=True, **masks)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(len_ref, q_ref, g_ref, lse_ref, delta_ref, k_ref,
                   v_ref, dq_ref, dq_acc, *, scale: float, causal: bool,
                   window, block_diffusion=None):
    """One (batch*head, q-block, k-block) grid step, the forward's own
    order: float32 VMEM scratch carries the q block's dq across the k
    blocks. Refs as `_bwd_dkv_kernel`'s; dq [1,BQ,D], scratch [BQ,D]."""
    n_keys = len_ref[pl.program_id(0)]
    qi = pl.program_id(1)
    j = pl.program_id(2)
    masks = dict(block_q=q_ref.shape[1], block_k=k_ref.shape[1],
                 causal=causal, window=window,
                 block_diffusion=block_diffusion)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute(valid, rows=slice(None)):
        """The block's share of dq, or one sub-square's of a diagonal
        step (`rows`: its queries and keys); `valid`: the mask, or None
        where every pair attends."""
        k = k_ref[0, rows]
        _, ds = _recompute(
            q_ref[0, rows], k, v_ref[0, rows], g_ref[0, rows],
            lse_ref[0, 0, rows][:, None], delta_ref[0, 0, rows][:, None],
            valid, scale=scale, k_major=False)
        dq_acc[rows] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

    _backward_steps(compute, qi, j, n_keys, **masks)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_backward(q, k, v, lens, o, lse, g, *, causal: bool,
                    block_q: int, block_k: int, window,
                    block_diffusion=None):
    """q, o, g: [BH, T, D]; k, v: [BH, Tkv, D]; lens: [BH] valid key
    counts; lse: [BH, T] f32 -> (dq, dk, dv) in the operands' dtypes.

    delta = rowsum(g * o) is one XLA pass over g and o; it and lse reach
    both kernels as [BH, 1, T] float32 rows (4 bytes a query, where a
    lane-broadcast block would move 512). Padded query rows have q = g
    = 0 and add nothing; padded keys are past every row's length."""
    bh, t, d = q.shape
    t_kv = k.shape[1]
    if window is not None and window >= t:
        window = None   # causal, Tq == Tkv: the band excludes nothing
    # a sequence shorter than a block takes one block of whole lane tiles
    block_q = min(block_q, pl.cdiv(t, _LANE) * _LANE)
    block_k = min(block_k, pl.cdiv(t_kv, _LANE) * _LANE)
    tq_pad = pl.cdiv(t, block_q) * block_q
    tk_pad = pl.cdiv(t_kv, block_k) * block_k
    nq, nk = tq_pad // block_q, tk_pad // block_k
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    operands = (lens.astype(jnp.int32),
                _pad_to(q, tq_pad, 1), _pad_to(g, tq_pad, 1),
                _pad_to(lse, tq_pad, 1)[:, None, :],
                _pad_to(delta, tq_pad, 1)[:, None, :],
                _pad_to(k, tk_pad, 1), _pad_to(v, tk_pad, 1))
    masks = dict(block_q=block_q, block_k=block_k, causal=causal,
                 window=window)
    # the backward's own blocks, which may differ from the forward's
    interior, cut, diagonal, skipped = _block_kinds(
        nq, nk, t_kv, block_diffusion=block_diffusion, **masks)
    diagonal_text = f"diagonal:{diagonal}," if diagonal else ""
    pallas_util.note_traced(
        "flash_attention.bwd_block_kinds",
        f"interior:{interior},cut:{cut},{diagonal_text}skipped:{skipped}")
    static = dict(scale=1.0 / (d ** 0.5), causal=causal, window=window,
                  block_diffusion=block_diffusion)
    runs = dict(block_q=block_q, block_k=block_k,
                block_diffusion=block_diffusion)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)

    def in_specs(q_block, k_block):
        """`q_block` / `k_block`: (b, outer, inner, lens) -> the q-side
        / k-side block a grid step holds. Clamped along the inner axis
        to the blocks `_block_needed` admits, a skipped step names the
        block the pipeline already has and fetches nothing."""
        q_map = lambda *ids: (ids[0], q_block(*ids), 0)
        row_map = lambda *ids: (ids[0], 0, q_block(*ids))
        k_map = lambda *ids: (ids[0], k_block(*ids), 0)
        return [vmem((1, block_q, d), q_map), vmem((1, block_q, d), q_map),
                vmem((1, 1, block_q), row_map), vmem((1, 1, block_q), row_map),
                vmem((1, block_k, d), k_map), vmem((1, block_k, d), k_map)]

    # outputs and their float32 accumulators follow the outer grid axis
    out_map = lambda b, outer, inner, lens: (b, outer, 0)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=pallas_util.VMEM_LIMIT_BYTES)

    def dkv_q_block(b, j, i, lens):
        if block_diffusion is not None:
            return _clamp_to_runs(i, *_bd_needed_q_runs(j, **runs))
        first, last = _needed_q_blocks(j, lens[b], nq, **masks)
        return jnp.clip(i, first, last)

    def dkv_k_block(b, j, i, lens):
        # a k block past the row's length: its dk, dv are zeros, and it
        # names the last block that holds a key
        return jnp.minimum(j, (jnp.maximum(lens[b], 1) - 1) // block_k)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nk, nq),
            in_specs=in_specs(dkv_q_block, dkv_k_block),
            out_specs=[vmem((1, block_k, d), out_map)] * 2,
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32)] * 2,
        ),
        out_shape=[jax.ShapeDtypeStruct((bh, tk_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, tk_pad, d), v.dtype)],
        compiler_params=params,
        interpret=pallas_util.interpret(),
        name="flash_attention_bwd_dkv" + _name_suffix(window),
    )(*operands)

    def dq_k_block(b, i, j, lens):
        if block_diffusion is not None:
            return _clamp_to_runs(j, *_bd_needed_k_runs(i, **runs))
        first, last = _needed_k_blocks(i, lens[b], **masks)
        return jnp.clip(j, first, last)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nk),
            in_specs=in_specs(lambda b, i, j, lens: i, dq_k_block),
            out_specs=vmem((1, block_q, d), out_map),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, tq_pad, d), q.dtype),
        compiler_params=params,
        interpret=pallas_util.interpret(),
        name="flash_attention_bwd_dq" + _name_suffix(window),
    )(*operands)
    return dq[:, :t], dk[:, :t_kv], dv[:, :t_kv]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, lens_f, causal, block_q, block_k, window, bwd_blocks,
           block_diffusion):
    o, _ = _flash_forward(q, k, v, lens_f, causal=causal, block_q=block_q,
                          block_k=block_k, window=window,
                          block_diffusion=block_diffusion)
    return o


def _flash_fwd(q, k, v, lens_f, causal, block_q, block_k, window,
               bwd_blocks, block_diffusion):
    o, lse = _flash_forward(q, k, v, lens_f, causal=causal, block_q=block_q,
                            block_k=block_k, window=window,
                            block_diffusion=block_diffusion)
    # both outputs of the one kernel call: a checkpoint that kept only o
    # would still run the call for lse when it recomputes
    o = checkpoint_name(o, REMAT_SAVED[0])
    lse = checkpoint_name(lse, REMAT_SAVED[1])
    return o, (q, k, v, lens_f, o, lse)


def _flash_bwd(causal, block_q, block_k, window, bwd_blocks, block_diffusion,
               res, g):
    q, k, v, lens_f, o, lse = res
    pallas_util.note_traced("flash_attention.backward", "pallas")
    with jax.named_scope("flash_attention_bwd"):
        dq, dk, dv = _flash_backward(
            q, k, v, lens_f, o, lse, g, causal=causal,
            block_q=bwd_blocks[0], block_k=bwd_blocks[1], window=window,
            block_diffusion=block_diffusion)
    # lens is carried as f32 so the custom_vjp can hand back an ordinary
    # zero cotangent (int operands would need float0 plumbing)
    return dq, dk, dv, jnp.zeros_like(lens_f)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    key_lens=None, window=None,
                    bwd_block_q: int = BWD_BLOCK_Q,
                    bwd_block_k: int = BWD_BLOCK_K,
                    block_diffusion=None):
    """Fused scaled-dot-product attention.

    q: [B, Tq, H, D]; k, v: [B, Tkv, H, D]. Returns [B, Tq, H, D].
    O(T·block) memory; exact (fp32 accumulation internally).

    key_lens: optional [B] int — row b attends only keys [0, lens[b])
    (right-padded variable-length sequences, e.g. a batched prefill).
    Implemented as the kernel's existing tail-padding bound made
    per-row, so the masked path costs nothing extra.

    window: optional int — sliding-window (local) attention: query t
    attends keys (t-window, t]. Requires causal=True. BOTH directions
    skip out-of-band blocks by the one grid predicate (`_block_needed`:
    past the row's length, above the diagonal, below the band), so
    training costs O(T*window) instead of O(T^2); the backward kernels
    do not fetch a skipped step's blocks either.

    block_q, block_k: the forward kernel's blocks; by default
    `_forward_blocks` takes them from the shape, for every mask: 1024 x
    1024 where the sequence carries it, smaller where that would pad it
    further than a 256 x 512 grid does, one block for a short sequence.
    The forward runs a block with no mask where every pair attends
    (`_block_interior`) and masked only where the mask cuts it.
    bwd_block_q, bwd_block_k: the two backward kernels' (dk/dv and dq),
    their own because what suits them differs; a sequence shorter than
    a block takes one block.

    block_diffusion: optional (L, Bd) — the block-diffusion training
    mask (BD3-LM's vectorised form) over T = 2L positions, the noised
    copy of a sequence and then the clean copy, cut into Bd-token
    blocks: see `_pair_mask`. Not causal, no window, no key_lens. A
    kernel block with no admitted pair (about half of them, in two
    runs a row) is skipped and fetches nothing, in all three kernels;
    a noised block against its own noised keys computes only the
    sub-squares of its diagonal where Bd divides them
    (`_block_diagonal`).

    Under `jax.checkpoint`: a differentiated call names its output and
    its row log-sum-exp (`REMAT_SAVED`), the residuals the backward
    kernels need beside q, k and v. A checkpoint whose policy is
    `save_only_these_names(*REMAT_SAVED)` keeps the two, 2 H D + 4 H
    bytes a position in bf16 (8.3 KB at 32 heads of 128), and its
    backward pass recomputes what surrounds the forward kernel without
    running it again; a plain checkpoint runs the kernel twice. Outside
    a checkpoint the names are the identity, and a call that is not
    differentiated (inference, a prefill) carries none.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, T, H, D], got {q.shape}")
    if block_q is None or block_k is None:
        chosen = _forward_blocks(q.shape[1], k.shape[1], q.shape[3], q.dtype)
        block_q, block_k = block_q or chosen[0], block_k or chosen[1]
    if block_diffusion is not None:
        length, bd = block_diffusion = tuple(int(x) for x in block_diffusion)
        if causal or window is not None or key_lens is not None:
            raise ValueError("block_diffusion is a mask of its own: not "
                             "with causal, window or key_lens")
        if bd < 1 or length % bd or q.shape[1] != 2 * length \
                or k.shape[1] != 2 * length:
            raise ValueError(
                f"block_diffusion (L={length}, Bd={bd}) needs Bd | L and "
                f"2L positions, got Tq {q.shape[1]}, Tkv {k.shape[1]}")
        pallas_util.note_traced("flash_attention.mask", "block_diffusion")
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    b, t, h, d = q.shape
    t_kv = k.shape[1]
    if causal and t != t_kv:
        # the kernel's qpos has no (Tkv-Tq) offset, so its causal mask
        # would silently disagree with the dense path (which aligns
        # queries to the LAST Tq key positions) — refuse rather than
        # diverge (r4 advisor finding)
        raise ValueError(
            f"causal flash attention requires Tq == Tkv, got {t} vs "
            f"{t_kv}; use the dense path for offset cross-attention")
    if key_lens is None:
        lens = jnp.full((b * h,), t_kv, jnp.float32)
    else:
        if key_lens.shape != (b,):
            raise ValueError(
                f"key_lens must be [B]=({b},), got {key_lens.shape}")
        # clamp so out-of-range lengths degrade to the no-mask behavior
        # instead of attending the kernel's zero-padded key tail
        lens = jnp.repeat(
            jnp.minimum(key_lens, t_kv).astype(jnp.float32), h)

    def flat(x, tt):
        return x.transpose(0, 2, 1, 3).reshape(b * h, tt, d)

    o = _flash(flat(q, t), flat(k, t_kv), flat(v, t_kv), lens, causal,
               block_q, block_k, window, (bwd_block_q, bwd_block_k),
               block_diffusion)
    return o.reshape(b, h, t, d).transpose(0, 2, 1, 3)
