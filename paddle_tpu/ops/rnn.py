"""Recurrent cells and scan-based runners.

Replaces the reference's fused recurrent kernels and frame-unrolling
engine — LstmLayer/GatedRecurrentLayer with hand-written CUDA
(reference: gserver/layers/LstmLayer.cpp, cuda/src/hl_cuda_lstm.cu,
operators/math/detail/lstm_kernel.h) and RecurrentGradientMachine's
per-timestep sub-network frames (reference:
gserver/gradientmachines/RecurrentGradientMachine.cpp:530) — with
jax.lax.scan over time-major dense batches: one traced step, XLA fuses the
gate math into the matmuls, autodiff gives BPTT, and remat
(jax.checkpoint) trades FLOPs for memory on long sequences (the reference
had no activation checkpointing; SURVEY §5 long-context).

Layout: inputs [B, T, F] ("batch major"), internally scanned time-major.
Variable lengths are handled by masking: finished steps carry the state
through unchanged — numerically identical to the reference's
sorted-by-length batch shrinking (SequenceToBatch) without the reorder.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.dtypes import default_policy
from paddle_tpu.ops import linalg, pallas_util


class LSTMState(NamedTuple):
    h: jnp.ndarray
    c: jnp.ndarray


def lstm_step_from_proj(params, x_proj_t, state: LSTMState, *,
                        activation=jnp.tanh,
                        gate_activation=jax.nn.sigmoid):
    """One LSTM step given the PRE-PROJECTED input x@W_ih + b [.., 4H].

    The full-sequence runners hoist the input projection out of the scan
    (one [B*T, F]x[F, 4H] MXU-sized matmul instead of T small ones — the
    cuDNN-style layout the reference gets from its fused kernels,
    cuda/src/hl_cuda_lstm.cu); only the h@W_hh recurrence stays serial.
    """
    h, c = state
    gates = x_proj_t + linalg.matmul(h, params["w_hh"])
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i = gate_activation(i)
    f = gate_activation(f)
    g = activation(g)
    o = gate_activation(o)
    new_c = f * c + i * g
    new_h = o * activation(new_c)
    return LSTMState(new_h, new_c)


def lstm_step(params, x_t, state: LSTMState, *, activation=jnp.tanh,
              gate_activation=jax.nn.sigmoid):
    """One LSTM step. params: {w_ih [F,4H], w_hh [H,4H], b [4H]}.

    Gate order i,f,g,o (reference gate math: operators/math/detail/
    lstm_kernel.h; we use the standard non-peephole variant — the
    reference's peephole connections are an option below).
    """
    x_proj = linalg.matmul(x_t, params["w_ih"]) + params["b"]
    return lstm_step_from_proj(params, x_proj, state,
                               activation=activation,
                               gate_activation=gate_activation)


def gru_step_from_proj(params, x_proj_t, h, *, activation=jnp.tanh,
                       gate_activation=jax.nn.sigmoid):
    """One GRU step given the pre-projected input x@W_ih + b [.., 3H]
    (see lstm_step_from_proj for why the runners hoist this)."""
    h_proj = linalg.matmul(h, params["w_hh"])
    xr, xz, xn = jnp.split(x_proj_t, 3, axis=-1)
    hr, hz, hn = jnp.split(h_proj, 3, axis=-1)
    r = gate_activation(xr + hr)
    z = gate_activation(xz + hz)
    n = activation(xn + r * hn)
    return (1.0 - z) * n + z * h


def gru_step(params, x_t, h, *, activation=jnp.tanh,
             gate_activation=jax.nn.sigmoid):
    """One GRU step. params: {w_ih [F,3H], w_hh [H,3H], b [3H]}.

    Gate order r,z,n (reference: operators/math/detail/gru_kernel.h,
    gserver/layers/GatedRecurrentLayer.cpp).
    """
    x_proj = linalg.matmul(x_t, params["w_ih"]) + params["b"]
    return gru_step_from_proj(params, x_proj, h, activation=activation,
                              gate_activation=gate_activation)


def _carry_dtype():
    """Recurrent carries accumulate across T steps — keep them at least f32
    even under a bf16 compute policy (the gate matmuls still run bf16)."""
    return jnp.promote_types(default_policy().accum_dtype, jnp.float32)


def _use_fused_kernel(impl: str, name: str, mod, b: int, w_hh) -> bool:
    """Shared impl dispatch for lstm()/gru()/simple_rnn(): 'pallas'
    forces the fused kernel and fails loudly when it can't apply;
    'auto' takes it on TPU when the shape fits the kernel's VMEM budget
    (at the dtype W_hh reaches the kernel in) and the program lowers
    for one device (pallas_util.auto_kernel); 'xla' keeps the scan."""
    from paddle_tpu.core.errors import enforce

    enforce(impl in ("auto", "pallas", "xla"),
            f"{name} impl must be auto|pallas|xla, got {impl!r}")
    hdim = w_hh.shape[0]
    fits = mod.fits_vmem(b, hdim, w_hh.dtype.itemsize)
    if impl == "pallas":
        enforce(fits,
                f"{name} shape B={b} H={hdim} exceeds the fused kernel's "
                "VMEM budget")
    fused = impl == "pallas" or (
        impl == "auto" and fits and pallas_util.auto_kernel())
    pallas_util.note_traced(f"rnn.{name}", "pallas" if fused else "xla")
    return fused


def _kernel_w_hh(params):
    """W_hh as the fused kernels take it: in the policy's compute dtype,
    the same cast `linalg.matmul` applies on the scan path (bf16 under
    the bf16 policy — half the VMEM-resident bytes of the f32 master)."""
    return params["w_hh"].astype(default_policy().compute_dtype)


def _masked_scan(step_fn, init_state, xs, mask, reverse: bool, unroll: int = 1):
    """Scan over time with per-step carry masking for ragged batches."""

    def body(carry, inp):
        x_t, m_t = inp
        new_carry = step_fn(carry, x_t)
        # keep old state where the sequence has ended; cast back so the
        # carry dtype is loop-invariant even if the step math ran bf16
        merged = jax.tree.map(
            lambda new, old: jnp.where(m_t[:, None], new, old).astype(old.dtype),
            new_carry,
            carry,
        )
        return merged, merged

    final, ys = jax.lax.scan(
        body, init_state, (xs, mask), reverse=reverse, unroll=unroll
    )
    return final, ys


def lstm(params, x, lengths=None, *, initial_state: Optional[LSTMState] = None,
         reverse: bool = False, unroll: int = 1, impl: str = "auto"):
    """Run an LSTM over [B, T, F]; returns (outputs [B,T,H], final LSTMState).

    reverse=True scans right-to-left (for bidirectional stacks) while still
    respecting per-sequence lengths via masking.

    impl: "auto" uses the fused Pallas time-loop kernel
    (ops.pallas_lstm — W_hh and the carries stay VMEM-resident across
    steps instead of round-tripping HBM per step) on TPU when the shape
    fits; variable lengths ride the kernel's ragged [start, end) bounds
    (PL.make_bounds). "pallas" forces it (interpret mode off-TPU, for
    tests); "xla" forces the lax.scan.
    """
    b, t, _ = x.shape
    hdim = params["w_hh"].shape[0]
    if initial_state is None:
        # c is the additive accumulator -> keep it >= f32; h feeds the next
        # step's matmul anyway, so it can live in the compute dtype
        initial_state = LSTMState(
            jnp.zeros((b, hdim), default_policy().compute_dtype),
            jnp.zeros((b, hdim), _carry_dtype()),
        )
    if lengths is None:
        mask = jnp.ones((b, t), bool)
    else:
        mask = jnp.arange(t, dtype=jnp.int32)[None, :] < lengths[:, None]

    # hoist the input projection: ONE [B*T, F]x[F, 4H] matmul feeding the
    # MXU at full tilt; the scan then only carries the h@W_hh recurrence
    x_proj = linalg.matmul(x, params["w_ih"]) + params["b"]  # [B, T, 4H]
    xs = jnp.swapaxes(x_proj, 0, 1)  # [T, B, 4H]

    from paddle_tpu.ops import pallas_lstm as PL

    w_hh = _kernel_w_hh(params)
    if _use_fused_kernel(impl, "lstm", PL, b, w_hh):
        xs_f = jnp.flip(xs, axis=0) if reverse else xs
        bounds = PL.make_bounds(b, t, lengths, reverse)
        hs, h_last, c_last = PL.fused_lstm(
            xs_f, w_hh, initial_state.h, initial_state.c, bounds)
        if reverse:
            hs = jnp.flip(hs, axis=0)
        outputs = jnp.swapaxes(hs, 0, 1)
        if lengths is not None:
            outputs = outputs * mask[..., None].astype(outputs.dtype)
        return outputs, LSTMState(h_last, c_last)

    ms = jnp.swapaxes(mask, 0, 1)

    def step(state, xp_t):
        return lstm_step_from_proj(params, xp_t, state)

    final, ys = _masked_scan(step, initial_state, xs, ms, reverse, unroll)
    outputs = jnp.swapaxes(ys.h, 0, 1)  # [B, T, H]
    # zero out positions past each length so downstream pooling is clean
    outputs = outputs * mask[..., None].astype(outputs.dtype)
    return outputs, final


def gru(params, x, lengths=None, *, initial_state=None, reverse: bool = False,
        unroll: int = 1, impl: str = "auto"):
    """Run a GRU over [B, T, F]; returns (outputs [B,T,H], final h).

    impl: as ops.rnn.lstm — "auto" takes the fused Pallas time-loop
    kernel (ops.pallas_gru) on TPU when the shape fits VMEM."""
    b, t, _ = x.shape
    hdim = params["w_hh"].shape[0]
    if initial_state is None:
        initial_state = jnp.zeros((b, hdim), _carry_dtype())
    if lengths is None:
        mask = jnp.ones((b, t), bool)
    else:
        mask = jnp.arange(t, dtype=jnp.int32)[None, :] < lengths[:, None]
    x_proj = linalg.matmul(x, params["w_ih"]) + params["b"]  # hoisted
    xs = jnp.swapaxes(x_proj, 0, 1)

    from paddle_tpu.ops import pallas_gru as PG
    from paddle_tpu.ops import pallas_lstm as PL

    w_hh = _kernel_w_hh(params)
    if _use_fused_kernel(impl, "gru", PG, b, w_hh):
        xs_f = jnp.flip(xs, axis=0) if reverse else xs
        bounds = PL.make_bounds(b, t, lengths, reverse)
        carry_dtype = initial_state.dtype
        hs, h_last = PG.fused_gru(
            xs_f, w_hh, initial_state.astype(jnp.float32), bounds)
        if reverse:
            hs = jnp.flip(hs, axis=0)
        # match the scan path's dtype contract (carry dtype throughout)
        outputs = jnp.swapaxes(hs, 0, 1).astype(carry_dtype)
        if lengths is not None:
            outputs = outputs * mask[..., None].astype(outputs.dtype)
        return outputs, h_last.astype(carry_dtype)

    ms = jnp.swapaxes(mask, 0, 1)

    def step(h, xp_t):
        return gru_step_from_proj(params, xp_t, h)

    final, ys = _masked_scan(step, initial_state, xs, ms, reverse, unroll)
    outputs = jnp.swapaxes(ys, 0, 1)
    outputs = outputs * mask[..., None].astype(outputs.dtype)
    return outputs, final


def simple_rnn(params, x, lengths=None, *, activation=jnp.tanh,
               reverse: bool = False, impl: str = "auto"):
    """Vanilla RNN h' = act(x W_ih + h W_hh + b) (reference:
    gserver/layers/RecurrentLayer.cpp). The fused Pallas path
    (ops.pallas_rnn) applies for the default tanh activation."""
    b, t, _ = x.shape
    hdim = params["w_hh"].shape[0]
    h0 = jnp.zeros((b, hdim), _carry_dtype())
    if lengths is None:
        mask = jnp.ones((b, t), bool)
    else:
        mask = jnp.arange(t, dtype=jnp.int32)[None, :] < lengths[:, None]
    x_proj = linalg.matmul(x, params["w_ih"]) + params["b"]  # hoisted
    xs = jnp.swapaxes(x_proj, 0, 1)

    from paddle_tpu.core.errors import enforce
    from paddle_tpu.ops import pallas_lstm as PL
    from paddle_tpu.ops import pallas_rnn as PR

    if impl == "pallas":
        enforce(activation is jnp.tanh,
                "the fused simple_rnn kernel supports only tanh")
    # validate impl FIRST (lstm/gru contract: typos always raise), then
    # AND the tanh condition for auto
    w_hh = _kernel_w_hh(params)
    fused = (_use_fused_kernel(impl, "simple_rnn", PR, b, w_hh)
             and activation is jnp.tanh)
    if fused:
        xs_f = jnp.flip(xs, axis=0) if reverse else xs
        bounds = PL.make_bounds(b, t, lengths, reverse)
        hs, h_last = PR.fused_simple_rnn(
            xs_f, w_hh, h0.astype(jnp.float32), bounds)
        if reverse:
            hs = jnp.flip(hs, axis=0)
        outputs = jnp.swapaxes(hs, 0, 1).astype(h0.dtype)
        if lengths is not None:
            outputs = outputs * mask[..., None].astype(outputs.dtype)
        return outputs, h_last.astype(h0.dtype)

    ms = jnp.swapaxes(mask, 0, 1)

    def step(h, xp_t):
        return activation(xp_t + linalg.matmul(h, params["w_hh"]))

    final, ys = _masked_scan(step, h0, xs, ms, reverse)
    outputs = jnp.swapaxes(ys, 0, 1)
    return outputs * mask[..., None].astype(outputs.dtype), final


def bidirectional(run_fn, fwd_params, bwd_params, x, lengths=None, **kw):
    """Concat forward and backward passes (reference:
    trainer_config_helpers/networks.py:1230 bidirectional_lstm)."""
    fwd_out, fwd_state = run_fn(fwd_params, x, lengths, reverse=False, **kw)
    bwd_out, bwd_state = run_fn(bwd_params, x, lengths, reverse=True, **kw)
    return jnp.concatenate([fwd_out, bwd_out], axis=-1), (fwd_state, bwd_state)


def init_lstm_params(rng, in_features: int, hidden: int, dtype=jnp.float32):
    k1, k2 = jax.random.split(rng)
    scale = 1.0 / jnp.sqrt(in_features)
    hscale = 1.0 / jnp.sqrt(hidden)
    b = jnp.zeros((4 * hidden,), dtype)
    # forget-gate bias 1.0: standard trick for trainability
    b = b.at[hidden : 2 * hidden].set(1.0)
    return {
        "w_ih": jax.random.uniform(k1, (in_features, 4 * hidden), dtype, -scale, scale),
        "w_hh": jax.random.uniform(k2, (hidden, 4 * hidden), dtype, -hscale, hscale),
        "b": b,
    }


def init_gru_params(rng, in_features: int, hidden: int, dtype=jnp.float32):
    k1, k2 = jax.random.split(rng)
    scale = 1.0 / jnp.sqrt(in_features)
    hscale = 1.0 / jnp.sqrt(hidden)
    return {
        "w_ih": jax.random.uniform(k1, (in_features, 3 * hidden), dtype, -scale, scale),
        "w_hh": jax.random.uniform(k2, (hidden, 3 * hidden), dtype, -hscale, hscale),
        "b": jnp.zeros((3 * hidden,), dtype),
    }


def init_md_lstm_params(rng, in_features: int, hidden: int,
                        dtype=jnp.float32):
    """2-D MDLSTM parameters: 5 gate chunks (g, i, f_row, f_col, o) —
    the reference's inode/ig/fg×D/og packing at D=2 dimensions
    (reference: gserver/layers/MDLstmLayer.cpp:178 'IG Layer: (Input,
    InputGate, ForgetGates, OutputGate)', init :221-236). One recurrent
    matrix per grid dimension; both forget-gate biases start at 1.0
    (same trainability trick as init_lstm_params)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    scale = 1.0 / jnp.sqrt(in_features)
    hscale = 1.0 / jnp.sqrt(hidden)
    b = jnp.zeros((5 * hidden,), dtype)
    b = b.at[2 * hidden:4 * hidden].set(1.0)
    return {
        "w_ih": jax.random.uniform(k1, (in_features, 5 * hidden), dtype,
                                   -scale, scale),
        "w_row": jax.random.uniform(k2, (hidden, 5 * hidden), dtype,
                                    -hscale, hscale),
        "w_col": jax.random.uniform(k3, (hidden, 5 * hidden), dtype,
                                    -hscale, hscale),
        "b": b,
    }


def md_lstm_cell(z, c_up, c_left):
    """One MDLSTM cell from summed pre-activations z [..., 5H]:

        c = σ(i)·tanh(g) + σ(f_row)·c_up + σ(f_col)·c_left
        h = σ(o)·tanh(c)

    — the reference cell with one forget gate PER DIMENSION
    (reference: gserver/layers/MDLstmLayer.cpp:160-177; its optional
    peephole 'check' connections are omitted — the capability is the
    2-D recurrence, and peepholes have long been dropped from practice).
    """
    hdim = c_up.shape[-1]
    g, i, f_r, f_c, o = (z[..., k * hdim:(k + 1) * hdim]
                         for k in range(5))
    c = (jax.nn.sigmoid(i) * jnp.tanh(g)
         + jax.nn.sigmoid(f_r) * c_up
         + jax.nn.sigmoid(f_c) * c_left)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h, c


def md_lstm(params, x, *, reverse_rows: bool = False,
            reverse_cols: bool = False):
    """2-D multi-dimensional LSTM over a grid: cell (i, j) recurs on its
    row-neighbor (i-1, j) and column-neighbor (i, j-1), with zero
    states beyond the boundary (reference:
    gserver/layers/MDLstmLayer.cpp 'mdlstmemory' at numDims=2 — there a
    per-sample CoordIterator walks cells ONE AT A TIME; reverse_* maps
    its per-dimension `directions`).

    TPU-first restructuring: cells on an anti-diagonal are independent,
    so the scan runs over the H+W-1 diagonals — every cell of a
    diagonal updates in ONE [B·H, H]x[H, 5H] matmul pair (wavefront
    parallelism) instead of H·W serial cell updates, and the input
    projection is hoisted out of the scan entirely (one
    [B·H·W, F]x[F, 5H] MXU call, the same trick the 1-D runners use).
    Grid-skewing turns the diagonals into a static-shape scan: buffer
    slot i of diagonal d holds cell (i, d-i), so the row neighbor is
    slot i-1 and the column neighbor slot i of the PREVIOUS diagonal.

    x: [B, H, W, F] -> h: [B, H, W, hidden].
    """
    if reverse_rows:
        x = x[:, ::-1]
    if reverse_cols:
        x = x[:, :, ::-1]
    b, h, w, f = x.shape
    hdim = params["w_row"].shape[0]
    dt = _carry_dtype()
    xp = (linalg.matmul(x, params["w_ih"]) + params["b"]).astype(dt)
    nd = h + w - 1

    rows = jnp.arange(h, dtype=jnp.int32)[:, None]
    cols = jnp.arange(
        nd, dtype=jnp.int32)[None, :] - rows              # [H, ND] j = d - i
    on_grid = (cols >= 0) & (cols < w)
    # skewed[:, i, d, :] = xp[:, i, d - i, :] (zero off-grid)
    skewed = jnp.take_along_axis(
        xp, jnp.clip(cols, 0, w - 1)[None, :, :, None], axis=2)
    skewed = jnp.where(on_grid[None, :, :, None], skewed, 0.0)

    def diag_step(carry, inp):
        h_prev, c_prev = carry                        # diagonal d-1
        x_d, vd = inp                                 # [B, H, 5H], [H]
        # row neighbor (i-1, j): slot i-1; col neighbor (i, j-1): slot i
        h_up = jnp.pad(h_prev, ((0, 0), (1, 0), (0, 0)))[:, :h]
        c_up = jnp.pad(c_prev, ((0, 0), (1, 0), (0, 0)))[:, :h]
        z = (x_d + linalg.matmul(h_up, params["w_row"])
             + linalg.matmul(h_prev, params["w_col"]))
        h_new, c_new = md_lstm_cell(z, c_up, c_prev)
        # off-grid slots must carry ZERO (they are the boundary states
        # of the next diagonal's edge cells)
        m = vd[None, :, None]
        h_new = jnp.where(m, h_new, 0.0)
        c_new = jnp.where(m, c_new, 0.0)
        return (h_new, c_new), h_new

    zeros = jnp.zeros((b, h, hdim), dt)
    _, ys = jax.lax.scan(
        diag_step, (zeros, zeros),
        (skewed.transpose(2, 0, 1, 3), on_grid.T))    # [ND, B, H, 5H]

    # unskew: out[:, i, j] = ys[i + j, :, i]
    diag_of = rows + jnp.arange(
        w, dtype=jnp.int32)[None, :]            # [H, W]
    out = jnp.take_along_axis(
        ys.transpose(1, 2, 0, 3), diag_of[None, :, :, None], axis=2)
    if reverse_cols:
        out = out[:, :, ::-1]
    if reverse_rows:
        out = out[:, ::-1]
    return out


def init_rnn_params(rng, in_features: int, hidden: int, dtype=jnp.float32):
    k1, k2 = jax.random.split(rng)
    scale = 1.0 / jnp.sqrt(in_features)
    hscale = 1.0 / jnp.sqrt(hidden)
    return {
        "w_ih": jax.random.uniform(k1, (in_features, hidden), dtype, -scale, scale),
        "w_hh": jax.random.uniform(k2, (hidden, hidden), dtype, -hscale, hscale),
        "b": jnp.zeros((hidden,), dtype),
    }
