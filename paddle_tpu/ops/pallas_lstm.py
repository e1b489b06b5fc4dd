"""Fused LSTM time loop as a single Pallas TPU kernel.

Why: the XLA `lax.scan` LSTM round-trips the (h, c) carry and the gate
tensors through HBM every step and pays while-loop overhead per
iteration — an early chip trace showed ~97 us/step where the
recurrence FLOPs justify ~0.1 us (ROADMAP A3; the reference's published
RNN benchmark is benchmark/paddle/rnn/run.sh).
This kernel runs the WHOLE time loop in one pallas_call: W_hh stays
resident in VMEM, (h, c) live in VMEM scratch across grid steps (the
TPU grid is sequential), and only x_proj / hs / cs stream from/to HBM.

Variable-length batches are handled in-kernel: a per-row [start, end)
step window (the runner derives it from `lengths`, reversed scans get
[T-len, T)) selects carry-through semantics exactly like the runner's
masked scan, so the fused path serves the ragged batches real models
feed it.

Backward is a second time-reversed kernel using the same residency
trick: it recomputes the gates from the saved (h, c) streams (cheap —
one small matmul) and accumulates dW_hh in VMEM, using its own output
refs as the carry accumulators; the t-1 streams arrive via clamped
index maps (no shifted copies).

Shapes: x_proj [T, B, 4H] (the hoisted input projection — see
ops.rnn.lstm), w_hh [H, 4H], h0/c0 [B, H], bounds [B, 2] i32. Gate
order i, f, g, o (matches ops.rnn.lstm_step_from_proj). Sized for VMEM
(see fused_fits_vmem): every call states its own `vmem_limit_bytes`,
because the backward's resident set (both W_hh layouts and the f32 dW
accumulator, each double-buffered by the pipeline) passes the
compiler's 16 MiB default scope already at h=512.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_util


def _sigmoid(x):
    return jax.nn.sigmoid(x)


def _step_mask(bounds_ref, t):
    """[B, 1] bool: is step t inside this row's [start, end) window."""
    start = bounds_ref[:, :1]
    end = bounds_ref[:, 1:2]
    return (start <= t) & (t < end)


def _fwd_kernel(xp_ref, whh_ref, h0_ref, c0_ref, bounds_ref,
                hs_ref, cs_ref, h_scr, c_scr, *, hidden: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    h = h_scr[...]
    gates = xp_ref[0].astype(jnp.float32) + lax.dot(
        h.astype(whh_ref.dtype), whh_ref[...],
        preferred_element_type=jnp.float32)
    i = _sigmoid(gates[:, :hidden])
    f = _sigmoid(gates[:, hidden:2 * hidden])
    g = jnp.tanh(gates[:, 2 * hidden:3 * hidden])
    o = _sigmoid(gates[:, 3 * hidden:])
    c = f * c_scr[...] + i * g
    nh = o * jnp.tanh(c)
    m = _step_mask(bounds_ref, t)
    nh = jnp.where(m, nh, h)            # masked steps carry through
    c = jnp.where(m, c, c_scr[...])
    h_scr[...] = nh
    c_scr[...] = c
    hs_ref[0] = nh.astype(hs_ref.dtype)
    cs_ref[0] = c


def _bwd_kernel(xp_ref, whh_ref, whht_ref, hsp_ref, csp_ref, cs_ref,
                dhs_ref, h0_ref, c0_ref, bounds_ref, dhL_ref, dcL_ref,
                dxp_ref, dwhh_ref, dh0_ref, dc0_ref, *,
                hidden: int, steps: int):
    r = pl.program_id(0)  # r-th reversed step; original t = steps-1-r
    t = steps - 1 - r

    @pl.when(r == 0)
    def _():
        # the output refs double as the reverse-time carry accumulators
        dh0_ref[...] = dhL_ref[...].astype(jnp.float32)
        dc0_ref[...] = dcL_ref[...].astype(jnp.float32)
        dwhh_ref[...] = jnp.zeros_like(dwhh_ref)

    # hsp/csp blocks are hs/cs at t-1 (index map clamps t-1 to 0, so at
    # the first original step the loaded block is garbage and the
    # initial state is selected instead)
    at_t0 = r == steps - 1
    hprev = jnp.where(at_t0, h0_ref[...].astype(jnp.float32),
                      hsp_ref[0].astype(jnp.float32))
    cprev = jnp.where(at_t0, c0_ref[...].astype(jnp.float32), csp_ref[0])
    ct = cs_ref[0]
    gates = xp_ref[0].astype(jnp.float32) + lax.dot(
        hprev.astype(whh_ref.dtype), whh_ref[...],
        preferred_element_type=jnp.float32)
    i = _sigmoid(gates[:, :hidden])
    f = _sigmoid(gates[:, hidden:2 * hidden])
    g = jnp.tanh(gates[:, 2 * hidden:3 * hidden])
    o = _sigmoid(gates[:, 3 * hidden:])
    tanh_c = jnp.tanh(ct)

    dh = dhs_ref[0].astype(jnp.float32) + dh0_ref[...]
    do = dh * tanh_c * o * (1.0 - o)
    dc = dc0_ref[...] + dh * o * (1.0 - tanh_c * tanh_c)
    di = dc * g * i * (1.0 - i)
    df = dc * cprev * f * (1.0 - f)
    dg = dc * i * (1.0 - g * g)
    dgates = jnp.concatenate([di, df, dg, do], axis=-1)  # [B, 4H] f32
    m = _step_mask(bounds_ref, t)
    dgates = jnp.where(m, dgates, 0.0)

    dxp_ref[0] = dgates.astype(dxp_ref.dtype)
    dgates_c = dgates.astype(whht_ref.dtype)
    # masked steps are identity: the whole cotangent passes through
    dh_back = lax.dot(dgates_c, whht_ref[...],
                      preferred_element_type=jnp.float32)
    dh0_ref[...] = jnp.where(m, dh_back, dh)
    dc0_ref[...] = jnp.where(m, dc * f, dc0_ref[...])
    # dW_hh += hprev^T @ dgates (contract the batch dim)
    dwhh_ref[...] += lax.dot_general(
        hprev.astype(whh_ref.dtype), dgates_c,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _specs(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


def _time_loop_params():
    """The grid is the time loop: sequential, carries in VMEM."""
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=pallas_util.VMEM_LIMIT_BYTES)


def fused_fits_vmem(b: int, hidden: int, gates: int, w_itemsize: int) -> bool:
    """Residency of the WORST pass (backward) of a fused time loop with
    `gates`*H gate columns: W_hh and W_hh^T (w_itemsize) and the f32 dW
    accumulator stay resident and the pipeline holds two buffers of
    each; around them ~16 [B, gates*H] f32 tiles of streams and gate
    temporaries. LSTM h=512 B=64 bf16 comes to 24 MiB, h=1280 to over
    100 MiB (scan path)."""
    w = hidden * gates * hidden
    resident = 2 * (2 * w * w_itemsize + w * 4)
    tiles = 16 * b * gates * hidden * 4
    return resident + tiles <= pallas_util.VMEM_BUDGET_BYTES


def _fwd(x_proj, w_hh, h0, c0, bounds):
    t, b, g4 = x_proj.shape
    h = g4 // 4
    hs, cs = pl.pallas_call(
        functools.partial(_fwd_kernel, hidden=h),
        grid=(t,),
        in_specs=[
            _specs((1, b, g4), lambda i: (i, 0, 0)),
            _specs((h, g4), lambda i: (0, 0)),
            _specs((b, h), lambda i: (0, 0)),
            _specs((b, h), lambda i: (0, 0)),
            _specs((b, 2), lambda i: (0, 0)),
        ],
        out_specs=[
            _specs((1, b, h), lambda i: (i, 0, 0)),
            _specs((1, b, h), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, h), x_proj.dtype),
            jax.ShapeDtypeStruct((t, b, h), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, h), jnp.float32),
            pltpu.VMEM((b, h), jnp.float32),
        ],
        compiler_params=_time_loop_params(),
        interpret=pallas_util.interpret(),
        name="fused_lstm_fwd",
    )(x_proj, w_hh, h0, c0, bounds)
    return hs, cs


@jax.custom_vjp
def fused_lstm(x_proj, w_hh, h0, c0, bounds):
    """Fused scan: returns (hs [T,B,H], h_last [B,H], c_last [B,H])."""
    hs, cs = _fwd(x_proj, w_hh, h0, c0, bounds)
    return hs, hs[-1], cs[-1].astype(c0.dtype)


def _fused_fwd(x_proj, w_hh, h0, c0, bounds):
    hs, cs = _fwd(x_proj, w_hh, h0, c0, bounds)
    return ((hs, hs[-1], cs[-1].astype(c0.dtype)),
            (x_proj, w_hh, h0, c0, bounds, hs, cs))


def _fused_bwd(res, cts):
    x_proj, w_hh, h0, c0, bounds, hs, cs = res
    dhs, dh_last, dc_last = cts
    dxp, dwhh, dh0, dc0 = _bwd(
        x_proj, w_hh, w_hh.T, hs, cs, dhs, h0, c0, bounds,
        jnp.asarray(dh_last), jnp.asarray(dc_last))
    return (dxp, dwhh.astype(w_hh.dtype), dh0.astype(h0.dtype),
            dc0.astype(c0.dtype), None)


def _bwd(x_proj, w_hh, w_hh_t, hs, cs, dhs, h0, c0, bounds,
         dh_last, dc_last):
    t, b, g4 = x_proj.shape
    h = g4 // 4
    f32 = jnp.float32

    rev = lambda i: (t - 1 - i, 0, 0)
    # the SAME hs/cs arrays shifted one step back — no concat copies;
    # the t-1 index clamps to 0 at the first original step, where the
    # kernel selects h0/c0 instead (see _bwd_kernel)
    rev_prev = lambda i: (jnp.maximum(t - 2 - i, 0), 0, 0)
    const2 = lambda i: (0, 0)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hidden=h, steps=t),
        grid=(t,),
        in_specs=[
            _specs((1, b, g4), rev),          # x_proj
            _specs((h, g4), const2),          # w_hh
            _specs((g4, h), const2),          # w_hh^T
            _specs((1, b, h), rev_prev),      # hs at t-1
            _specs((1, b, h), rev_prev),      # cs at t-1
            _specs((1, b, h), rev),           # cs
            _specs((1, b, h), rev),           # dhs
            _specs((b, h), const2),           # h0
            _specs((b, h), const2),           # c0
            _specs((b, 2), const2),           # bounds
            _specs((b, h), const2),           # dh_last
            _specs((b, h), const2),           # dc_last
        ],
        out_specs=[
            _specs((1, b, g4), rev),
            _specs((h, g4), const2),
            _specs((b, h), const2),
            _specs((b, h), const2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, g4), x_proj.dtype),
            jax.ShapeDtypeStruct((h, g4), f32),
            jax.ShapeDtypeStruct((b, h), f32),
            jax.ShapeDtypeStruct((b, h), f32),
        ],
        compiler_params=_time_loop_params(),
        interpret=pallas_util.interpret(),
        name="fused_lstm_bwd",
    )(x_proj, w_hh, w_hh_t, hs, cs, cs, dhs, h0, c0, bounds,
      dh_last, dc_last)


fused_lstm.defvjp(_fused_fwd, _fused_bwd)


def make_bounds(b: int, t: int, lengths, reverse: bool):
    """Per-row [start, end) step window: forward sequences occupy
    [0, len); time-flipped ones occupy [T-len, T)."""
    if lengths is None:
        lo = jnp.zeros((b, 1), jnp.int32)
        hi = jnp.full((b, 1), t, jnp.int32)
    else:
        ln = lengths.astype(jnp.int32)[:, None]
        lo = (t - ln) if reverse else jnp.zeros((b, 1), jnp.int32)
        hi = jnp.full((b, 1), t, jnp.int32) if reverse else ln
    return jnp.concatenate([lo, hi], axis=1)


def fits_vmem(b: int, hidden: int, w_itemsize: int) -> bool:
    return fused_fits_vmem(b, hidden, 4, w_itemsize)
