"""The chunked gated delta rule — the token mixer of a Gated DeltaNet
layer (Yang, Kautz and Hatamizadeh, arXiv:2412.06464), as Pallas TPU
kernels for the forward and the backward pass.

Per value head, with a state S in R^{dk x dv} that starts at 0, each
position t (query q_t, key k_t, value v_t, log decay g_t <= 0, write
strength beta_t in [0, 1]) runs

    S <- exp(g_t) S
    u_t = beta_t (v_t - S^T k_t)
    S <- S + k_t u_t^T
    o_t = S^T q_t

(`recurrence` below is exactly this, for tests and references). The
kernels compute it a chunk of C positions at a time in the WY form of
Yang et al. (arXiv:2406.06484): with G the cumulative log decay inside
the chunk, gamma = exp(G), the strictly lower C x C matrix
A_ij = beta_i exp(G_i - G_j) k_i . k_j and T = (I + A)^-1,

    W = T diag(beta gamma) K          U = T diag(beta) V - W S
    O = diag(gamma) Q S + (exp(G_i - G_j) q_i . k_j)_{j <= i} U
    S' = exp(G_C) S + (diag(exp(G_C - G)) K)^T U

so a chunk is a handful of MXU products and the state crosses chunks
alone. T comes from block forward substitution by doubling
(`_unit_lower_inverse`: 2 log2 C float32 products), which keeps every
intermediate an entry of the inverse (a Neumann product such as
(I - A)(I + A^2)(I + A^4)... does not: its powers of A grow before they
cancel).

* forward, `gated_delta_fwd`: grid (batch x value head / Hb, chunk),
  a step takes Hb value heads of one chunk, the chunk axis sequential;
  the heads' S in float32 VMEM scratch across the chunks.
  Differentiated, it also writes the state each chunk starts from
  (float32 [BH, chunks, dk, dv]) for the backward;
* backward, `gated_delta_bwd`: the same grid with the chunks in reverse;
  dS (the gradient of the state a chunk leaves) in float32 VMEM
  scratch. Each step recomputes its chunk's A, T, W and U from the
  inputs and the saved state, and writes dq, dk, dv and the gradients
  of G and beta;
* a step runs each head's arithmetic as one head alone would, as
  products batched over its Hb heads; products with q, k, v, U and the
  state take the operands' dtype (the policy's compute dtype, bf16 on
  the chip) with float32 accumulation; the inverse and dA are float32
  products at `HIGHEST`; the decays, A, T and the state stay float32.

`_tiling` takes C and Hb from the call's shapes. A step of one head and
one chunk is mostly fixed cost and the latency of its chain of about
15 small dependent products, not MXU work: Hb heads share the fixed
cost and give the scheduler Hb independent chains, at no extra work,
where a longer chunk pays the inverse's C^2 log C passes a position
for its shorter grid (`PERF.md` section 5 times both on the chip).

Around the kernels, in XLA: the key heads' repeat to the value heads
(value head h reads key head h // (Hv / Hk)), the padding of the
sequence to whole chunks (padded positions have beta = g = 0 and come
after every real one), the cumulative sum of g inside each chunk and
its transpose in the backward pass. Off the chip `auto` takes a `jnp`
path that runs the same chunk functions under `lax.scan` (the kernel
bodies themselves run in interpret mode for `impl="pallas"` there).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_util

#: the tiling's longest chunk and most value heads a grid step
#: (`_tiling`; PERF.md section 5 times the kernels by both)
CHUNK_MAX = 128
HEADS_MAX = 4
#: Mosaic lays out the batched products' transposes only on a chunk of
#: whole 128-lane tiles: a shorter chunk takes one head a step
_LANE = 128
HI = lax.Precision.HIGHEST


def _mm(a, b, ta=False, tb=False, precision=None):
    """a @ b (`ta`: a^T @ b, `tb`: a @ b^T), float32 accumulation."""
    dims = (((0 if ta else 1,), (1 if tb else 0,)), ((), ()))
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=jnp.float32)


def _iota(c):
    return (lax.broadcasted_iota(jnp.int32, (c, c), 0),
            lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _col(row, eye):
    """[1, C] -> [C, 1] without a transpose."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """[C, 1] -> [1, C] without a transpose."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _unit_lower_inverse(a, i, j):
    """(I + a)^-1 for a strictly lower float32 [C, C], C a power of two.
    Level b holds the inverses of the diagonal blocks of b rows, X; the
    next level's blocks of 2b are X - X E X, E the entries of `a` below
    the diagonal blocks inside each 2b block ([[X1, 0], [-X2 L21 X1,
    X2]] for [[L1, 0], [L21, L2]])."""
    c = a.shape[0]
    x = (i == j).astype(jnp.float32)
    shift = 0
    while (1 << shift) < c:
        cut = (((i >> (shift + 1)) == (j >> (shift + 1)))
               & (((i >> shift) & 1) == 1) & (((j >> shift) & 1) == 0))
        e = jnp.where(cut, a, 0.0)
        x = x - _mm(_mm(x, e, precision=HI), x, precision=HI)
        shift += 1
    return x


def _chunk(q, k, v, g, beta, s):
    """The terms of one chunk that the forward and the backward share.
    q, k [C, dk] and v [C, dv] in the operand dtype; g (cumulative log
    decay inside the chunk) and beta [1, C] float32; s [dk, dv] float32,
    the state before the chunk."""
    c = k.shape[0]
    cd = k.dtype
    i, j = _iota(c)
    eye, lower, incl = i == j, j < i, j <= i
    g_col, b_col = _col(g, eye), _col(beta, eye)
    decay = jnp.where(incl, jnp.exp(jnp.where(incl, g_col - g, 0.0)), 0.0)
    kk = _mm(k, k, tb=True)
    a = jnp.where(lower, b_col * decay * kk, 0.0)
    t = _unit_lower_inverse(a, i, j)
    gam = jnp.exp(g_col)                                     # [C, 1]
    lane = lax.broadcasted_iota(jnp.int32, (1, c), 1)
    g_last = jnp.sum(jnp.where(lane == c - 1, g, 0.0), axis=1,
                     keepdims=True)                          # [1, 1]
    kb = (k.astype(jnp.float32) * (b_col * gam)).astype(cd)
    vb = (v.astype(jnp.float32) * b_col).astype(cd)
    td = t.astype(cd)
    w = _mm(td, kb)                                          # [C, dk]
    sd = s.astype(cd)
    u = _mm(td, vb) - _mm(w.astype(cd), sd)                  # [C, dv]
    p = decay * _mm(q, k, tb=True)                           # [C, C]
    kt = (k.astype(jnp.float32) * jnp.exp(g_last - g_col)).astype(cd)
    return dict(eye=eye, lower=lower, incl=incl, lane=lane, g_col=g_col, b_col=b_col, decay=decay,
                kk=kk, a=a, t=t, gam=gam, g_last=g_last, kb=kb, vb=vb, w=w,
                u=u, p=p, kt=kt, sd=sd)


def _chunk_forward(q, k, v, g, beta, s):
    """-> (o [C, dv] float32, the state after the chunk [dk, dv])."""
    x = _chunk(q, k, v, g, beta, s)
    cd = k.dtype
    ud = x["u"].astype(cd)
    o = x["gam"] * _mm(q, x["sd"]) + _mm(x["p"].astype(cd), ud)
    s_next = jnp.exp(x["g_last"]) * s + _mm(x["kt"], ud, ta=True)
    return o, s_next


def _chunk_backward(q, k, v, g, beta, s, do, ds):
    """Gradients of one chunk, from the gradient of its output do [C, dv]
    and of the state it leaves ds [dk, dv] (float32). -> (dq, dk, dv
    float32, dg and dbeta [1, C] float32, the gradient of the state
    before it [dk, dv] float32). dg is the gradient of the cumulative
    decay G."""
    x = _chunk(q, k, v, g, beta, s)
    cd = k.dtype
    f32 = jnp.float32
    eye, lower = x["eye"], x["lower"]
    gam, b_col, decay = x["gam"], x["b_col"], x["decay"]
    ud, sd, dsd = x["u"].astype(cd), x["sd"], ds.astype(cd)
    dod = do.astype(cd)
    # O = diag(gam) Q S + P U
    dq = gam * _mm(dod, sd, tb=True)
    ds0 = _mm((q.astype(f32) * gam).astype(cd), dod, ta=True)
    dg_col = gam * jnp.sum(do.astype(f32) * _mm(q, sd), axis=1,
                           keepdims=True)
    dp = jnp.where(x["incl"], _mm(dod, ud, tb=True), 0.0)
    du = _mm(x["p"].astype(cd), dod, ta=True)
    e = dp * x["p"]                   # G_i - G_j of the decay in P
    dqk = (dp * decay).astype(cd)
    dq = dq + _mm(dqk, k)
    dk = _mm(dqk, q, ta=True)
    # S' = exp(G_C) S + Kt^T U, Kt = diag(exp(G_C - G)) K
    ds0 = ds0 + jnp.exp(x["g_last"]) * ds
    g_last_grad = jnp.exp(x["g_last"]) * jnp.sum(
        jnp.sum(s * ds, axis=1, keepdims=True), axis=0, keepdims=True)
    dkt = _mm(ud, dsd, tb=True)                              # [C, dk]
    du = du + _mm(x["kt"], dsd)
    dk = dk + jnp.exp(x["g_last"] - x["g_col"]) * dkt
    e_kt = jnp.sum(x["kt"].astype(f32) * dkt, axis=1, keepdims=True)
    g_last_grad = g_last_grad + jnp.sum(e_kt, axis=0, keepdims=True)
    dg_col = dg_col - e_kt
    # U = T Vb - W S, W = T Kb
    dud = du.astype(cd)
    dw = -_mm(dud, sd, tb=True)
    ds0 = ds0 - _mm(x["w"].astype(cd), dud, ta=True)
    dwd = dw.astype(cd)
    dt = _mm(dud, x["vb"], tb=True) + _mm(dwd, x["kb"], tb=True)
    td = x["t"].astype(cd)
    dvb = _mm(td, dud, ta=True)
    dkb = _mm(td, dwd, ta=True)
    dv = b_col * dvb
    k_dkb = jnp.sum(k.astype(f32) * dkb, axis=1, keepdims=True)
    db_col = jnp.sum(v.astype(f32) * dvb, axis=1, keepdims=True) + gam * k_dkb
    dk = dk + (b_col * gam) * dkb
    dg_col = dg_col + b_col * gam * k_dkb
    # T = (I + A)^-1: dA = -T^T dT T^T on the strictly lower entries
    t = x["t"]
    da = jnp.where(lower, -_mm(_mm(t, dt, ta=True, precision=HI), t, tb=True,
                               precision=HI), 0.0)
    db_col = db_col + jnp.sum(da * decay * x["kk"], axis=1, keepdims=True)
    dkk = (da * b_col * decay).astype(cd)
    dk = dk + _mm(dkk, k) + _mm(dkk, k, ta=True)
    e = e + da * x["a"]
    dg_col = dg_col + jnp.sum(e, axis=1, keepdims=True)
    dg = (_row(dg_col, eye) - jnp.sum(e, axis=0, keepdims=True)
          + jnp.where(x["lane"] == k.shape[0] - 1, g_last_grad, 0.0))
    return dq, dk, dv, dg, _row(db_col, eye), ds0


# -- the kernels -------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest):
    """One (Hb value heads, chunk) step. Refs: q/k [Hb, C, dk], v/o [Hb,
    C, dv], g/beta [Hb, 1, 1, C] float32; with the states kept, states
    [Hb, 1, dk, dv] float32 (the state the chunk starts from); scratch
    s [Hb, dk, dv] float32."""
    *states, s_ref = rest

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    s = s_ref[...]
    if states:
        states[0][:, 0] = s
    o, s_next = jax.vmap(_chunk_forward)(
        q_ref[...], k_ref[...], v_ref[...], g_ref[:, 0], b_ref[:, 0], s)
    o_ref[...] = o.astype(o_ref.dtype)
    s_ref[...] = s_next


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, ds_ref):
    """One (Hb value heads, chunk) step, the chunks last to first. Refs
    as the forward's, s the state the chunk started from, do [Hb, C,
    dv]; scratch ds [Hb, dk, dv] float32: the gradient of the state the
    chunk leaves."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dq, dk, dv, dg, db, ds0 = jax.vmap(_chunk_backward)(
        q_ref[...], k_ref[...], v_ref[...], g_ref[:, 0], b_ref[:, 0],
        s_ref[:, 0], do_ref[...], ds_ref[...])
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dg_ref[:, 0] = dg
    db_ref[:, 0] = db
    ds_ref[...] = ds0


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=pallas_util.VMEM_LIMIT_BYTES)


def _pallas_forward(q, k, v, g, beta, keep_states: bool, hb: int):
    """q, k [BH, T, dk], v [BH, T, dv]; g, beta [BH, NC, 1, C] float32;
    Hb value heads a grid step -> (o [BH, T, dv], states [BH, NC, dk,
    dv] float32 or None)."""
    bh, t, dk = q.shape
    dv = v.shape[2]
    nc, c = g.shape[1], g.shape[3]
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    rows = lambda d: vmem((hb, c, d), lambda b, i: (b, i, 0))
    chunk = vmem((hb, 1, 1, c), lambda b, i: (b, i, 0, 0))
    state = vmem((hb, 1, dk, dv), lambda b, i: (b, i, 0, 0))
    out_specs = [rows(dv)] + ([state] if keep_states else [])
    out_shape = [jax.ShapeDtypeStruct((bh, t, dv), v.dtype)] + (
        [jax.ShapeDtypeStruct((bh, nc, dk, dv), jnp.float32)]
        if keep_states else [])
    out = pl.pallas_call(
        _fwd_kernel,
        grid=(bh // hb, nc),
        in_specs=[rows(dk), rows(dk), rows(dv), chunk, chunk],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_params(),
        interpret=pallas_util.interpret(),
        name="gated_delta_fwd",
    )(q, k, v, g, beta)
    return out[0], (out[1] if keep_states else None)


def _pallas_backward(q, k, v, g, beta, states, do, hb: int):
    bh, t, dk = q.shape
    dv = v.shape[2]
    nc, c = g.shape[1], g.shape[3]
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    last = lambda i: nc - 1 - i
    rows = lambda d: vmem((hb, c, d), lambda b, i: (b, last(i), 0))
    chunk = vmem((hb, 1, 1, c), lambda b, i: (b, last(i), 0, 0))
    state = vmem((hb, 1, dk, dv), lambda b, i: (b, last(i), 0, 0))
    return pl.pallas_call(
        _bwd_kernel,
        grid=(bh // hb, nc),
        in_specs=[rows(dk), rows(dk), rows(dv), chunk, chunk, state,
                  rows(dv)],
        out_specs=[rows(dk), rows(dk), rows(dv), chunk, chunk],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, jnp.float32),
                   jax.ShapeDtypeStruct(beta.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_params(),
        interpret=pallas_util.interpret(),
        name="gated_delta_bwd",
    )(q, k, v, g, beta, states, do)


# -- the same chunk functions under lax.scan (off the chip) -----------------


def _by_chunk(x, c):
    """[BH, T, D] -> [NC, BH, C, D]; [BH, NC, 1, C] -> [NC, BH, 1, C]."""
    if x.ndim == 4:
        return x.transpose(1, 0, 2, 3)
    bh, t, d = x.shape
    return x.reshape(bh, t // c, c, d).transpose(1, 0, 2, 3)


def _by_row(x):
    nc, bh, c, d = x.shape
    return x.transpose(1, 0, 2, 3).reshape(bh, nc * c, d)


def _scan_forward(q, k, v, g, beta, keep_states: bool, hb: int):
    del hb                  # the scan runs every head at once
    c = g.shape[3]
    xs = [_by_chunk(x, c) for x in (q, k, v, g, beta)]

    def step(s, xs):
        o, s_next = jax.vmap(_chunk_forward)(*xs, s)
        return s_next, (o.astype(v.dtype), s)

    s0 = jnp.zeros((q.shape[0], q.shape[2], v.shape[2]), jnp.float32)
    _, (o, states) = lax.scan(step, s0, xs)
    return _by_row(o), (states.transpose(1, 0, 2, 3) if keep_states else None)


def _scan_backward(q, k, v, g, beta, states, do, hb: int):
    del hb
    c = g.shape[3]
    xs = [_by_chunk(x, c) for x in (q, k, v, g, beta)] + [
        states.transpose(1, 0, 2, 3), _by_chunk(do, c)]

    def step(ds, xs):
        dq, dk, dv, dg, db, ds0 = jax.vmap(_chunk_backward)(*xs, ds)
        return ds0, (dq.astype(q.dtype), dk.astype(k.dtype),
                     dv.astype(v.dtype), dg, db)

    ds = jnp.zeros(states.shape[:1] + states.shape[2:], jnp.float32)
    _, (dq, dk, dv, dg, db) = lax.scan(step, ds, xs, reverse=True)
    return (_by_row(dq), _by_row(dk), _by_row(dv), dg.transpose(1, 0, 2, 3),
            db.transpose(1, 0, 2, 3))


_FORWARD = {"pallas": _pallas_forward, "jnp": _scan_forward}
_BACKWARD = {"pallas": _pallas_backward, "jnp": _scan_backward}


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, impl, hb):
    return _FORWARD[impl](q, k, v, g, beta, False, hb)[0]


def _rule_fwd(q, k, v, g, beta, impl, hb):
    o, states = _FORWARD[impl](q, k, v, g, beta, True, hb)
    return o, (q, k, v, g, beta, states)


def _rule_bwd(impl, hb, res, do):
    pallas_util.note_traced("gated_delta.backward", impl)
    with jax.named_scope("gated_delta_bwd"):
        return _BACKWARD[impl](*res, do, hb)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _bwd_vmem_bytes(c: int, hb: int, dk: int, dv: int,
                    itemsize: int) -> int:
    """What one grid step of the backward, the larger of the two, keeps
    in VMEM, from its shapes: q, k, v, do and dq, dk, dv blocks with the
    pipeline's second buffers, the g, beta, dg and dbeta rows (a [1, C]
    float32 row fills an 8 x 128 tile), the saved state and the dS
    scratch, and a head's temporaries, ~16 [C, C] and ~16 [C, d] float32
    arrays, for each of the Hb heads."""
    d = max(dk, dv)
    tile_row = 4 * 8 * pl.cdiv(c, _LANE) * _LANE
    blocks = hb * (itemsize * c * (4 * dk + 3 * dv) + 4 * tile_row
                   + 4 * dk * dv)
    return 2 * blocks + hb * (4 * dk * dv + 16 * 4 * c * (c + d))


def _tiling(bh: int, t: int, dk: int, dv: int, dtype,
            chunk: Optional[int] = None) -> Tuple[int, int]:
    """(C, Hb) for a call of BH rows of T positions: C positions a chunk
    (`chunk` where the caller gives one, else CHUNK_MAX, or the power of
    two that covers a shorter T, at least 16) and Hb value heads a grid
    step: where C fills whole lane tiles, the largest power of two up to
    HEADS_MAX that divides BH and keeps a backward step
    (`_bwd_vmem_bytes`) inside `VMEM_BUDGET_BYTES`; else 1. On the v5e
    at bf16 [2, 8192], 32 value heads of 128, forward + backward: 49.7
    ms at C 64 and one head a step, 36.2 at C 128, **26.0 at C 128 and
    Hb 4**, no faster at Hb 8 or 16 (PERF.md section 5)."""
    c = chunk or min(CHUNK_MAX, max(16, 1 << max(t - 1, 1).bit_length()))
    itemsize = jnp.dtype(dtype).itemsize
    hb = min(HEADS_MAX, bh & -bh) if c % _LANE == 0 else 1
    while hb > 1 and _bwd_vmem_bytes(
            c, hb, dk, dv, itemsize) > pallas_util.VMEM_BUDGET_BYTES:
        hb //= 2
    return c, hb


def gated_delta_rule(q, k, v, g, beta, *, chunk: Optional[int] = None,
                     impl: str = "auto"):
    """The gated delta rule over a sequence, from a zero state.

    q, k: [B, T, Hk, dk] (normalised and scaled by the caller); v: [B,
    T, Hv, dv], Hk dividing Hv (value head h reads key head h // (Hv /
    Hk)); g: [B, T, Hv] log decays (<= 0) and beta: [B, T, Hv], both
    taken in float32. -> o [B, T, Hv, dv] in v's dtype. q, k and v
    should share a dtype: the products take it. impl: "pallas" (the
    kernels; interpreted off the chip), "jnp" (the same chunk functions
    under `lax.scan`) or "auto" (the kernels where `auto` may select
    one: `pallas_util.auto_kernel`). chunk: positions a chunk, a power
    of two; None takes `_tiling`'s from the shapes."""
    if chunk is not None and (chunk & (chunk - 1) or chunk < 2):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    if impl == "auto":
        impl = "pallas" if pallas_util.auto_kernel() else "jnp"
    if impl not in _FORWARD:
        raise ValueError(f"impl must be auto|pallas|jnp, got {impl!r}")
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    if hv % hk:
        raise ValueError(f"{hk} key heads must divide {hv} value heads")
    chunk, hb = _tiling(b * hv, t, dk, dv, v.dtype, chunk)
    pallas_util.note_traced("gated_delta.forward", impl)
    pallas_util.note_traced("gated_delta.chunk", str(chunk))
    pallas_util.note_traced("gated_delta.heads_per_step", str(hb))
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    nc = pl.cdiv(t, chunk)
    pad = nc * chunk - t

    def rows(x):        # [B, T, H, D] -> [B*H, T', D]
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3).reshape(b * hv, nc * chunk, -1)

    def chunks(x):      # [B, T, H] -> [B*H, NC, 1, C] float32
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
        return x.transpose(0, 2, 1).reshape(b * hv, nc, 1, chunk)

    o = _rule(rows(q), rows(k), rows(v), jnp.cumsum(chunks(g), axis=3),
              chunks(beta), impl, hb)
    return o.reshape(b, hv, nc * chunk, dv)[:, :, :t].transpose(0, 2, 1, 3)


def recurrence(q, k, v, g, beta):
    """The rule position by position in float32 (`lax.scan`), shapes as
    `gated_delta_rule`'s: what the kernels are checked against."""
    hk, hv = q.shape[2], v.shape[2]
    q, k = (jnp.repeat(x.astype(jnp.float32), hv // hk, axis=2)
            for x in (q, k))
    v = v.astype(jnp.float32)

    def step(s, xs):                    # s [B, H, dk, dv]
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=HI))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=HI)

    s0 = jnp.zeros(q.shape[:1] + (hv, q.shape[3], v.shape[3]), jnp.float32)
    xs = [jnp.moveaxis(x, 1, 0) for x in
          (q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32))]
    return jnp.moveaxis(lax.scan(step, s0, xs)[1], 0, 1)
