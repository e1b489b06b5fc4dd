"""Fused GRU time loop as a single Pallas TPU kernel.

Same residency design as ops.pallas_lstm (W_hh resident in VMEM, h
carried in VMEM scratch across the sequential grid, per-row [start,
end) step windows for ragged batches) applied to the GRU recurrence —
the cell driving the seq2seq-attention north star's bidirectional
encoder (models/seq2seq_attn.py) and the quick-start text models.

Math matches ops.rnn.gru_step_from_proj exactly:
  h_proj = h @ W_hh;  r = sig(xr+hr);  z = sig(xz+hz)
  n = tanh(xn + r*hn);  h' = (1-z)*n + z*h
Backward recomputes (r, z, n) from the saved h stream and routes the
matmul cotangent through h_proj (the r*hn product term makes the GRU's
dW path different from the LSTM's concatenated-gates form).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops import pallas_util
from paddle_tpu.ops.pallas_lstm import (  # shared plumbing
    _sigmoid, _specs, _step_mask, _time_loop_params, fused_fits_vmem,
    pl, pltpu)


def fits_vmem(b: int, hidden: int, w_itemsize: int) -> bool:
    return fused_fits_vmem(b, hidden, 3, w_itemsize)


def _fwd_kernel(xp_ref, whh_ref, h0_ref, bounds_ref, hs_ref, h_scr,
                *, hidden: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    h = h_scr[...]
    h_proj = lax.dot(h.astype(whh_ref.dtype), whh_ref[...],
                     preferred_element_type=jnp.float32)
    xp = xp_ref[0].astype(jnp.float32)
    r = _sigmoid(xp[:, :hidden] + h_proj[:, :hidden])
    z = _sigmoid(xp[:, hidden:2 * hidden] + h_proj[:, hidden:2 * hidden])
    n = jnp.tanh(xp[:, 2 * hidden:] + r * h_proj[:, 2 * hidden:])
    nh = (1.0 - z) * n + z * h
    m = _step_mask(bounds_ref, t)
    nh = jnp.where(m, nh, h)
    h_scr[...] = nh
    hs_ref[0] = nh.astype(hs_ref.dtype)


def _bwd_kernel(xp_ref, whh_ref, whht_ref, hsp_ref, dhs_ref, h0_ref,
                bounds_ref, dhL_ref,
                dxp_ref, dwhh_ref, dh0_ref, *, hidden: int, steps: int):
    r_id = pl.program_id(0)
    t = steps - 1 - r_id

    @pl.when(r_id == 0)
    def _():
        dh0_ref[...] = dhL_ref[...].astype(jnp.float32)
        dwhh_ref[...] = jnp.zeros_like(dwhh_ref)

    at_t0 = r_id == steps - 1
    hprev = jnp.where(at_t0, h0_ref[...].astype(jnp.float32),
                      hsp_ref[0].astype(jnp.float32))
    h_proj = lax.dot(hprev.astype(whh_ref.dtype), whh_ref[...],
                     preferred_element_type=jnp.float32)
    xp = xp_ref[0].astype(jnp.float32)
    hn = h_proj[:, 2 * hidden:]
    r = _sigmoid(xp[:, :hidden] + h_proj[:, :hidden])
    z = _sigmoid(xp[:, hidden:2 * hidden] + h_proj[:, hidden:2 * hidden])
    n = jnp.tanh(xp[:, 2 * hidden:] + r * hn)

    dh = dhs_ref[0].astype(jnp.float32) + dh0_ref[...]
    dz = dh * (hprev - n)
    dn = dh * (1.0 - z)
    dgn = dn * (1.0 - n * n)
    dr = dgn * hn
    dgz = dz * z * (1.0 - z)
    dgr = dr * r * (1.0 - r)
    m = _step_mask(bounds_ref, t)
    # mask once on the x-side gates; dhp reuses the masked r/z columns
    # and differs only in the n column (dgn*r instead of dgn)
    dxp_full = jnp.where(
        m, jnp.concatenate([dgr, dgz, dgn], axis=-1), 0.0)
    dhp = jnp.concatenate(
        [dxp_full[:, :2 * hidden], dxp_full[:, 2 * hidden:] * r], axis=-1)

    dxp_ref[0] = dxp_full.astype(dxp_ref.dtype)
    dhp_c = dhp.astype(whht_ref.dtype)
    dh_back = (dh * z + lax.dot(dhp_c, whht_ref[...],
                                preferred_element_type=jnp.float32))
    dh0_ref[...] = jnp.where(m, dh_back, dh)
    dwhh_ref[...] += lax.dot_general(
        hprev.astype(whh_ref.dtype), dhp_c,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _fwd(x_proj, w_hh, h0, bounds):
    t, b, g3 = x_proj.shape
    h = g3 // 3
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hidden=h),
        grid=(t,),
        in_specs=[
            _specs((1, b, g3), lambda i: (i, 0, 0)),
            _specs((h, g3), lambda i: (0, 0)),
            _specs((b, h), lambda i: (0, 0)),
            _specs((b, 2), lambda i: (0, 0)),
        ],
        out_specs=_specs((1, b, h), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, b, h), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)],
        compiler_params=_time_loop_params(),
        interpret=pallas_util.interpret(),
        name="fused_gru_fwd",
    )(x_proj, w_hh, h0, bounds)


@jax.custom_vjp
def fused_gru(x_proj, w_hh, h0, bounds):
    """Fused scan: returns (hs [T,B,H] f32, h_last [B,H])."""
    hs = _fwd(x_proj, w_hh, h0, bounds)
    return hs, hs[-1].astype(h0.dtype)


def _fused_fwd(x_proj, w_hh, h0, bounds):
    hs = _fwd(x_proj, w_hh, h0, bounds)
    return (hs, hs[-1].astype(h0.dtype)), (x_proj, w_hh, h0, bounds, hs)


def _fused_bwd(res, cts):
    x_proj, w_hh, h0, bounds, hs = res
    dhs, dh_last = cts
    dxp, dwhh, dh0 = _bwd(x_proj, w_hh, w_hh.T, hs, dhs, h0, bounds,
                          jnp.asarray(dh_last))
    return dxp, dwhh.astype(w_hh.dtype), dh0.astype(h0.dtype), None


def _bwd(x_proj, w_hh, w_hh_t, hs, dhs, h0, bounds, dh_last):
    t, b, g3 = x_proj.shape
    h = g3 // 3

    rev = lambda i: (t - 1 - i, 0, 0)
    rev_prev = lambda i: (jnp.maximum(t - 2 - i, 0), 0, 0)
    const2 = lambda i: (0, 0)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hidden=h, steps=t),
        grid=(t,),
        in_specs=[
            _specs((1, b, g3), rev),        # x_proj
            _specs((h, g3), const2),        # w_hh
            _specs((g3, h), const2),        # w_hh^T
            _specs((1, b, h), rev_prev),    # hs at t-1
            _specs((1, b, h), rev),         # dhs
            _specs((b, h), const2),         # h0
            _specs((b, 2), const2),         # bounds
            _specs((b, h), const2),         # dh_last
        ],
        out_specs=[
            _specs((1, b, g3), rev),
            _specs((h, g3), const2),
            _specs((b, h), const2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, g3), x_proj.dtype),
            jax.ShapeDtypeStruct((h, g3), jnp.float32),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
        ],
        compiler_params=_time_loop_params(),
        interpret=pallas_util.interpret(),
        name="fused_gru_bwd",
    )(x_proj, w_hh, w_hh_t, hs, dhs, h0, bounds, dh_last)


fused_gru.defvjp(_fused_fwd, _fused_bwd)
