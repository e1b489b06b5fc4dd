"""Fused vanilla-RNN time loop (tanh recurrence) — completes the fused
family (pallas_lstm, pallas_gru) for the reference's RecurrentLayer
(reference: gserver/layers/RecurrentLayer.cpp). Same design: W_hh
resident, h in VMEM scratch, per-row [start, end) windows. Backward
needs no recomputation at all: dz = dh * (1 - h_t^2) comes from the
saved output stream."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops import pallas_util
from paddle_tpu.ops.pallas_lstm import (  # shared plumbing
    _specs, _step_mask, _time_loop_params, fused_fits_vmem, pl, pltpu)


def fits_vmem(b: int, hidden: int, w_itemsize: int) -> bool:
    return fused_fits_vmem(b, hidden, 1, w_itemsize)


def _fwd_kernel(xp_ref, whh_ref, h0_ref, bounds_ref, hs_ref, h_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    h = h_scr[...]
    z = xp_ref[0].astype(jnp.float32) + lax.dot(
        h.astype(whh_ref.dtype), whh_ref[...],
        preferred_element_type=jnp.float32)
    nh = jnp.tanh(z)
    m = _step_mask(bounds_ref, t)
    nh = jnp.where(m, nh, h)
    h_scr[...] = nh
    hs_ref[0] = nh.astype(hs_ref.dtype)


def _bwd_kernel(whht_ref, hs_ref, hsp_ref, dhs_ref, h0_ref, bounds_ref,
                dhL_ref, dxp_ref, dwhh_ref, dh0_ref, *, steps: int):
    r = pl.program_id(0)
    t = steps - 1 - r

    @pl.when(r == 0)
    def _():
        dh0_ref[...] = dhL_ref[...].astype(jnp.float32)
        dwhh_ref[...] = jnp.zeros_like(dwhh_ref)

    at_t0 = r == steps - 1
    hprev = jnp.where(at_t0, h0_ref[...].astype(jnp.float32),
                      hsp_ref[0].astype(jnp.float32))
    ht = hs_ref[0].astype(jnp.float32)
    dh = dhs_ref[0].astype(jnp.float32) + dh0_ref[...]
    m = _step_mask(bounds_ref, t)
    dz = jnp.where(m, dh * (1.0 - ht * ht), 0.0)
    dxp_ref[0] = dz.astype(dxp_ref.dtype)
    dz_c = dz.astype(whht_ref.dtype)
    dh_back = lax.dot(dz_c, whht_ref[...],
                      preferred_element_type=jnp.float32)
    dh0_ref[...] = jnp.where(m, dh_back, dh)
    dwhh_ref[...] += lax.dot_general(
        hprev.astype(whht_ref.dtype), dz_c,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@jax.custom_vjp
def fused_simple_rnn(x_proj, w_hh, h0, bounds):
    """Fused scan: returns (hs [T,B,H] f32, h_last [B,H])."""
    hs = _run_fwd(x_proj, w_hh, h0, bounds)
    return hs, hs[-1].astype(h0.dtype)


def _run_fwd(x_proj, w_hh, h0, bounds):
    t, b, h = x_proj.shape
    return pl.pallas_call(
        _fwd_kernel,
        grid=(t,),
        in_specs=[
            _specs((1, b, h), lambda i: (i, 0, 0)),
            _specs((h, h), lambda i: (0, 0)),
            _specs((b, h), lambda i: (0, 0)),
            _specs((b, 2), lambda i: (0, 0)),
        ],
        out_specs=_specs((1, b, h), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, b, h), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)],
        compiler_params=_time_loop_params(),
        interpret=pallas_util.interpret(),
        name="fused_rnn_fwd",
    )(x_proj, w_hh, h0, bounds)


def _fused_fwd(x_proj, w_hh, h0, bounds):
    hs = _run_fwd(x_proj, w_hh, h0, bounds)
    return (hs, hs[-1].astype(h0.dtype)), (x_proj, w_hh, h0, bounds, hs)


def _fused_bwd(res, cts):
    x_proj, w_hh, h0, bounds, hs = res
    dhs, dh_last = cts
    dxp, dwhh, dh0 = _run_bwd(w_hh.T, hs, dhs, h0, bounds,
                              jnp.asarray(dh_last), xp_dtype=x_proj.dtype)
    return dxp, dwhh.astype(w_hh.dtype), dh0.astype(h0.dtype), None


def _run_bwd(w_hh_t, hs, dhs, h0, bounds, dh_last, *, xp_dtype):
    t, b, h = hs.shape

    rev = lambda i: (t - 1 - i, 0, 0)
    rev_prev = lambda i: (jnp.maximum(t - 2 - i, 0), 0, 0)
    const2 = lambda i: (0, 0)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, steps=t),
        grid=(t,),
        in_specs=[
            _specs((h, h), const2),       # w_hh^T
            _specs((1, b, h), rev),       # hs
            _specs((1, b, h), rev_prev),  # hs at t-1
            _specs((1, b, h), rev),       # dhs
            _specs((b, h), const2),       # h0
            _specs((b, 2), const2),       # bounds
            _specs((b, h), const2),       # dh_last
        ],
        out_specs=[
            _specs((1, b, h), rev),
            _specs((h, h), const2),
            _specs((b, h), const2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, h), xp_dtype),
            jax.ShapeDtypeStruct((h, h), jnp.float32),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
        ],
        compiler_params=_time_loop_params(),
        interpret=pallas_util.interpret(),
        name="fused_rnn_bwd",
    )(w_hh_t, hs, hs, dhs, h0, bounds, dh_last)


fused_simple_rnn.defvjp(_fused_fwd, _fused_bwd)
