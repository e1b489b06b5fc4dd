"""Convolution and pooling ops (NHWC, TPU-native layout).

Replaces the reference's conv stack — im2col+GEMM (reference:
paddle/function/GemmConvOp.cpp, function/Im2ColOp.cpp), cuDNN layers
(reference: gserver/layers/CudnnConvLayer.cpp) and Fluid conv ops
(reference: paddle/operators/conv_op.cc) — with
jax.lax.conv_general_dilated, which XLA lowers directly onto the MXU.
Layout is NHWC/HWIO (TPU-preferred), not the reference's NCHW.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.dtypes import Policy, default_policy

IntOr2 = Union[int, Tuple[int, int], Sequence[int]]


def _pair(v: IntOr2) -> Tuple[int, int]:
    if isinstance(v, int):
        return (v, v)
    a, b = v
    return (int(a), int(b))


def _padding(padding, kernel: Tuple[int, int]):
    if isinstance(padding, str):
        return padding  # 'SAME' / 'VALID'
    if (
        isinstance(padding, (tuple, list))
        and len(padding) == 2
        and isinstance(padding[0], (tuple, list))
    ):
        return tuple((int(a), int(b)) for a, b in padding)  # ((t,b),(l,r))
    ph, pw = _pair(padding)
    return ((ph, ph), (pw, pw))


def explicit_pad(h: int, w: int, window: IntOr2, stride: IntOr2,
                 padding, dilation: IntOr2 = 1,
                 ) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """Resolve SAME/VALID/int/((t,b),(l,r)) padding to explicit
    ((top,bot),(left,right)) for the given static input size — XLA's
    SAME formula (pad so that out = ceil(in/stride), low half rounded
    down), using the dilation-effective kernel size."""
    kh, kw = _pair(window)
    dh, dw = _pair(dilation)
    ekh, ekw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    sh, sw = _pair(stride)
    if padding == "VALID":
        return ((0, 0), (0, 0))
    if padding == "SAME":
        oh, ow = -(-h // sh), -(-w // sw)
        th = max((oh - 1) * sh + ekh - h, 0)
        tw = max((ow - 1) * sw + ekw - w, 0)
        return ((th // 2, th - th // 2), (tw // 2, tw - tw // 2))
    pad = _padding(padding, (kh, kw))
    return (tuple(pad[0]), tuple(pad[1]))


def out_hw(h: int, w: int, window: IntOr2, stride: IntOr2, padding,
           dilation: IntOr2 = 1) -> Tuple[int, int]:
    """Static output (H, W) of a conv/pool window — built on explicit_pad,
    the ONE place the padding arithmetic lives (shape inference in
    nn.layers and nn.mixed reuses it; keep in sync with what
    lax.conv/reduce_window actually produce)."""
    kh, kw = _pair(window)
    sh, sw = _pair(stride)
    dh, dw = _pair(dilation)
    ekh, ekw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    (pt, pb), (pl, pr) = explicit_pad(h, w, window, stride, padding, dilation)
    return (h + pt + pb - ekh) // sh + 1, (w + pl + pr - ekw) // sw + 1


def conv2d(
    x,
    kernel,
    *,
    stride: IntOr2 = 1,
    padding="SAME",
    dilation: IntOr2 = 1,
    groups: int = 1,
    bias=None,
    policy: Optional[Policy] = None,
):
    """2-D convolution. x: [N,H,W,C], kernel: [kh,kw,Cin/groups,Cout]."""
    policy = policy or default_policy()
    x = x.astype(policy.compute_dtype)
    kernel = kernel.astype(policy.compute_dtype)
    kh, kw = kernel.shape[0], kernel.shape[1]
    y = lax.conv_general_dilated(
        x,
        kernel,
        window_strides=_pair(stride),
        padding=_padding(padding, (kh, kw)),
        rhs_dilation=_pair(dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=policy.accum_dtype,
    )
    if bias is not None:
        y = y + bias
    return y


def space_to_depth(x, block: IntOr2 = 2):
    """[N,H,W,C] -> [N,H/b1,W/b2,b1*b2*C]; channel order ((di*b2+dj)*C+c)."""
    b1, b2 = _pair(block)
    n, h, w, c = x.shape
    x = x.reshape(n, h // b1, b1, w // b2, b2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // b1, w // b2, b1 * b2 * c)


def depth_to_space(x, block: IntOr2 = 2):
    """Inverse of space_to_depth."""
    b1, b2 = _pair(block)
    n, h, w, cc = x.shape
    c = cc // (b1 * b2)
    x = x.reshape(n, h, w, b1, b2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h * b1, w * b2, c)


def s2d_kernel(kernel, block: IntOr2):
    """Re-lay a conv kernel [kh,kw,C,O] for a space-to-depth-blocked
    input: zero-pad kh/kw up to multiples of the block, then fold the
    intra-block offsets into the input-channel dim (matching
    space_to_depth's channel order)."""
    b1, b2 = _pair(block)
    kh, kw, c, o = kernel.shape
    bkh, bkw = -(-kh // b1) * b1, -(-kw // b2) * b2
    kp = jnp.pad(kernel, ((0, bkh - kh), (0, bkw - kw), (0, 0), (0, 0)))
    kp = kp.reshape(bkh // b1, b1, bkw // b2, b2, c, o)
    kp = kp.transpose(0, 2, 1, 3, 4, 5)
    return kp.reshape(bkh // b1, bkw // b2, b1 * b2 * c, o)


def conv2d_space_to_depth(
    x,
    kernel,
    *,
    stride: IntOr2,
    padding="SAME",
    bias=None,
    policy: Optional[Policy] = None,
):
    """conv2d with stride == block, computed on the space-to-depth
    transform of the input — mathematically IDENTICAL output (the
    kernel is re-laid with s2d_kernel; extra kernel rows are zero).

    Motivation: a small-C large-spatial conv like ResNet's 7x7/s2 stem
    on C_in=3 streams mostly padding — the 8-sublane tile is 5/8
    zeros. No chip run on the current stack has timed it (ROADMAP A3
    decides whether it stays). Blocking 2x2 turns
    [N,224,224,3] into [N,112,112,12] with the same FLOPs. The kernel
    PARAMETER stays in its original [kh,kw,C,O] layout so checkpoints
    and the torch importer are unaffected; the re-lay is a tiny
    device-side reshape fused into the step.
    """
    b1, b2 = _pair(stride)
    kh, kw = kernel.shape[0], kernel.shape[1]
    n, h, w, _ = x.shape
    (pt, pb), (pl, pr) = explicit_pad(h, w, (kh, kw), (b1, b2), padding)
    if h % b1 or w % b2 or pt % b1 or pl % b2:
        # sizes that don't block evenly: fall back to the direct conv
        return conv2d(x, kernel, stride=(b1, b2), padding=padding,
                      bias=bias, policy=policy)
    oh, ow = out_hw(h, w, (kh, kw), (b1, b2), padding)
    kb = s2d_kernel(kernel, (b1, b2))
    xb = space_to_depth(x, (b1, b2))
    plb, plwb = pt // b1, pl // b2
    phb = max(0, oh - plb + kb.shape[0] - 1 - h // b1)
    prb = max(0, ow - plwb + kb.shape[1] - 1 - w // b2)
    return conv2d(xb, kb, stride=1,
                  padding=((plb, phb), (plwb, prb)),
                  bias=bias, policy=policy)


def conv2d_transpose(
    x,
    kernel,
    *,
    stride: IntOr2 = 1,
    padding="SAME",
    bias=None,
    policy: Optional[Policy] = None,
):
    """Transposed conv (reference: gserver/layers/ConvTransLayer.cpp,
    paddle/operators/conv_transpose_op.cc). kernel: [kh,kw,Cin,Cout]."""
    policy = policy or default_policy()
    x = x.astype(policy.compute_dtype)
    kernel = kernel.astype(policy.compute_dtype)
    y = lax.conv_transpose(
        x,
        kernel,
        strides=_pair(stride),
        padding=padding if isinstance(padding, str) else _padding(padding, kernel.shape[:2]),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=policy.accum_dtype,
    )
    if bias is not None:
        y = y + bias
    return y


def depthwise_conv2d(
    x,
    kernel,
    *,
    stride: IntOr2 = 1,
    padding="SAME",
    bias=None,
    policy: Optional[Policy] = None,
):
    """Depthwise conv (reference: function/DepthwiseConvOp.cpp).

    kernel: [kh, kw, 1, C*multiplier]; groups = C.
    """
    channels = x.shape[-1]
    return conv2d(
        x,
        kernel,
        stride=stride,
        padding=padding,
        groups=channels,
        bias=bias,
        policy=policy,
    )


def _max_pool2d_raw(x, window, stride, pad2):
    # init must carry x's EXACT dtype: a bare python int promotes to
    # int64 under x64 and reduce_window rejects the mismatch
    init = (np.array(-np.inf, x.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating)
            else np.array(jnp.iinfo(x.dtype).min, x.dtype))
    wh, ww = window
    sh, sw = stride
    return lax.reduce_window(
        x, init, lax.max, (1, wh, ww, 1), (1, sh, sw, 1),
        ((0, 0), pad2[0], pad2[1], (0, 0))
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _max_pool2d_ts(x, window, stride, pad2):
    """Max pool whose VJP splits gradient equally among tied maxima.

    The default VJP of reduce_window is a select-and-scatter (1.9% of
    the image cell's busy time: ledger, PR 27). This formulation
    expresses the backward as per-offset strided slices + compares +
    dilated pads, which XLA fuses into plain streaming loops (and
    which measured slower: see max_pool2d). At ties it divides the
    cotangent equally among the tied maxima — a symmetric element of
    the subgradient set, where select-and-scatter picks a single
    winner. (No choice matches central differences at a >2-way tie;
    away from ties the two gradients are identical.)
    """
    return _max_pool2d_raw(x, window, stride, pad2)


def _max_pool2d_ts_fwd(x, window, stride, pad2):
    y = _max_pool2d_raw(x, window, stride, pad2)
    return y, (x, y)


def _max_pool2d_ts_bwd(window, stride, pad2, res, dy):
    x, y = res
    wh, ww = window
    sh, sw = stride
    (pt, pb), (pl, pr) = pad2
    n, h, w, c = x.shape
    oh, ow = y.shape[1], y.shape[2]
    neg = np.array(-np.inf, x.dtype)
    xp = (jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)), constant_values=neg)
          if (pt or pb or pl or pr) else x)
    hp, wp = h + pt + pb, w + pl + pr
    # the k-th element of every window, as a y-shaped strided slice
    masks = []
    for i in range(wh):
        for j in range(ww):
            xk = lax.slice(
                xp, (0, i, j, 0),
                (n, i + (oh - 1) * sh + 1, j + (ow - 1) * sw + 1, c),
                (1, sh, sw, 1))
            masks.append(xk == y)
    dty = dy.dtype
    cnt = sum(m.astype(dty) for m in masks)
    # cnt==0 only when the window max is NaN (NaN != NaN): drop that
    # window's gradient instead of spreading dy/0 = inf around it
    g = dy / jnp.maximum(cnt, np.array(1, dty))
    zero = np.array(0, dty)
    acc = None
    for (i, j), m in zip(((i, j) for i in range(wh) for j in range(ww)), masks):
        t = m.astype(dty) * g
        # place t[a,b] at padded-x position (i + a*sh, j + b*sw)
        spread = lax.pad(t, zero, (
            (0, 0, 0),
            (i, hp - i - (oh - 1) * sh - 1, sh - 1),
            (j, wp - j - (ow - 1) * sw - 1, sw - 1),
            (0, 0, 0)))
        acc = spread if acc is None else acc + spread
    dx = acc[:, pt:pt + h, pl:pl + w, :] if (pt or pb or pl or pr) else acc
    return (dx.astype(x.dtype),)


_max_pool2d_ts.defvjp(_max_pool2d_ts_fwd, _max_pool2d_ts_bwd)


def max_pool2d(x, window: IntOr2 = 2, *, stride: Optional[IntOr2] = None,
               padding="VALID", tie_split: bool = False):
    """Max pooling (reference: gserver/layers/PoolLayer.cpp MaxPooling,
    paddle/operators/pool_op.cc).

    tie_split=True (floats only) routes the gradient through the
    select-and-scatter-free custom VJP above; tie_split=False keeps
    XLA's native pick-first semantics AND forward-mode (jvp/jacfwd)
    differentiability, which custom_vjp functions reject. Default
    False: an earlier builder's same-protocol A/B on a v5e (resnet
    bs64: select_and_scatter 28.17 ms vs tie-split 40.18 ms, ROADMAP
    C5) had the custom VJP cost +43% on the full step, so the default
    is the faster formulation; the arm exists for gradient-semantics
    parity (ties split vs pick-first), not speed.
    """
    win = _pair(window)
    strd = _pair(stride if stride is not None else window)
    pad2 = explicit_pad(x.shape[1], x.shape[2], win, strd, padding)
    if tie_split and jnp.issubdtype(x.dtype, jnp.floating):
        return _max_pool2d_ts(x, win, strd, pad2)
    return _max_pool2d_raw(x, win, strd, pad2)


def avg_pool2d(
    x,
    window: IntOr2 = 2,
    *,
    stride: Optional[IntOr2] = None,
    padding="VALID",
    count_include_pad: bool = True,
):
    """Average pooling (reference: AvgPooling in gserver/layers/PoolLayer.cpp)."""
    wh, ww = _pair(window)
    sh, sw = _pair(stride if stride is not None else window)
    pad = padding if isinstance(padding, str) else (
        (0, 0),
        (_pair(padding)[0],) * 2,
        (_pair(padding)[1],) * 2,
        (0, 0),
    )
    summed = lax.reduce_window(
        x, 0.0, lax.add, (1, wh, ww, 1), (1, sh, sw, 1), pad
    )
    if count_include_pad or (isinstance(pad, str) and pad == "VALID"):
        return summed / (wh * ww)
    ones = jnp.ones_like(x)
    counts = lax.reduce_window(
        ones, 0.0, lax.add, (1, wh, ww, 1), (1, sh, sw, 1), pad
    )
    return summed / counts


def global_avg_pool2d(x):
    return jnp.mean(x, axis=(1, 2))


def spp(x, pyramid_height: int = 3, pool_type: str = "max"):
    """Spatial pyramid pooling (reference: gserver/layers/SpatialPyramidPoolLayer.cpp).

    Returns [N, sum_l 4^l * C] features over a pyramid of bin grids.
    """
    n, h, w, c = x.shape
    outs = []
    for level in range(pyramid_height):
        bins = 2**level
        # Split H and W into `bins` near-equal windows via resize-free pooling.
        ys = jnp.linspace(0, h, bins + 1).astype(jnp.int32)
        xs = jnp.linspace(0, w, bins + 1).astype(jnp.int32)
        for i in range(bins):
            for j in range(bins):
                patch = x[:, ys[i] : max(int(ys[i + 1]), int(ys[i]) + 1),
                          xs[j] : max(int(xs[j + 1]), int(xs[j]) + 1), :]
                if pool_type == "max":
                    outs.append(jnp.max(patch, axis=(1, 2)))
                else:
                    outs.append(jnp.mean(patch, axis=(1, 2)))
    return jnp.concatenate(outs, axis=-1)


def pad(x, paddings, value: float = 0.0):
    """Pad op (reference: function/PadOp.cpp, operators/pad_op.cc)."""
    return jnp.pad(x, paddings, constant_values=value)


def crop(x, offsets, shape):
    """Crop op (reference: function/CropOp.cpp, operators/crop_op.cc)."""
    return lax.dynamic_slice(x, offsets, shape)


def im2col(x, window: IntOr2, *, stride: IntOr2 = 1, padding="VALID"):
    """Extract patches: [N,H,W,C] -> [N,Ho,Wo,C*kh*kw] (CHANNEL-major:
    reshape the last dim as (C, kh, kw) — the ordering
    conv_general_dilated_patches produces).

    Reference: function/Im2ColOp.cpp / gserver BlockExpandLayer. On TPU you
    rarely want this (XLA handles conv directly); provided for block_expand
    parity.
    """
    kh, kw = _pair(window)
    patches = lax.conv_general_dilated_patches(
        x.transpose(0, 3, 1, 2),
        (kh, kw),
        _pair(stride),
        padding if isinstance(padding, str) else _padding(padding, (kh, kw)),
    )
    # patches: [N, C*kh*kw, Ho, Wo] -> [N, Ho, Wo, C*kh*kw]
    return patches.transpose(0, 2, 3, 1)


def roi_pool(x, rois, output_size: Tuple[int, int], spatial_scale: float = 1.0):
    """ROI max pooling (reference: gserver/layers/ROIPoolLayer.cpp).

    x: [N,H,W,C]; rois: [R,5] = (batch_idx, x1, y1, x2, y2) in input scale.
    Returns [R, oh, ow, C]. Static-shape implementation via per-bin masking.
    """
    n, h, w, c = x.shape
    oh, ow = output_size
    ys = jnp.arange(h, dtype=jnp.float32)
    xs = jnp.arange(w, dtype=jnp.float32)

    def one_roi(roi):
        b = roi[0].astype(jnp.int32)
        x1, y1, x2, y2 = roi[1] * spatial_scale, roi[2] * spatial_scale, roi[3] * spatial_scale, roi[4] * spatial_scale
        roi_h = jnp.maximum(y2 - y1 + 1.0, 1.0)
        roi_w = jnp.maximum(x2 - x1 + 1.0, 1.0)
        bin_h = roi_h / oh
        bin_w = roi_w / ow
        img = x[b]  # [H,W,C]

        def one_bin(i, j):
            y_lo = y1 + i * bin_h
            y_hi = y1 + (i + 1) * bin_h
            x_lo = x1 + j * bin_w
            x_hi = x1 + (j + 1) * bin_w
            ymask = (ys >= jnp.floor(y_lo)) & (ys < jnp.ceil(y_hi))
            xmask = (xs >= jnp.floor(x_lo)) & (xs < jnp.ceil(x_hi))
            mask = ymask[:, None] & xmask[None, :]
            masked = jnp.where(mask[:, :, None], img, -jnp.inf)
            val = jnp.max(masked, axis=(0, 1))
            return jnp.where(jnp.isfinite(val), val, 0.0)

        rows = [jnp.stack([one_bin(i, j) for j in range(ow)]) for i in range(oh)]
        return jnp.stack(rows)

    return jax.vmap(one_roi)(rois.astype(jnp.float32))


def conv3d(x, kernel, *, stride=1, padding="SAME", bias=None,
           policy: Optional[Policy] = None):
    """3-D convolution (reference: gserver/layers/Conv3DLayer.cpp,
    operators/conv3d variants). x: [N,D,H,W,C], kernel: [kd,kh,kw,Cin,Cout]."""
    policy = policy or default_policy()
    x = x.astype(policy.compute_dtype)
    kernel = kernel.astype(policy.compute_dtype)
    s = (stride,) * 3 if isinstance(stride, int) else tuple(stride)
    if isinstance(padding, str):
        pad = padding
    else:
        p = (padding,) * 3 if isinstance(padding, int) else tuple(padding)
        pad = [(q, q) for q in p]
    y = lax.conv_general_dilated(
        x, kernel, window_strides=s, padding=pad,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        preferred_element_type=policy.accum_dtype,
    )
    if bias is not None:
        y = y + bias
    return y


def _pool3d(x, window, stride, padding, init, op):
    w = (window,) * 3 if isinstance(window, int) else tuple(window)
    s = w if stride is None else (
        (stride,) * 3 if isinstance(stride, int) else tuple(stride))
    dims = (1, *w, 1)
    strides = (1, *s, 1)
    if isinstance(padding, str):
        pad = padding
    else:
        p = (padding,) * 3 if isinstance(padding, int) else tuple(padding)
        pad = ((0, 0), *[(q, q) for q in p], (0, 0))
    return lax.reduce_window(x, init, op, dims, strides, pad)


def max_pool3d(x, window=2, *, stride=None, padding="VALID"):
    """3-D max pooling (reference: gserver/layers/Pool3DLayer.cpp).
    x: [N,D,H,W,C]."""
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).min
    return _pool3d(x, window, stride, padding, init, lax.max)


def avg_pool3d(x, window=2, *, stride=None, padding="VALID"):
    """3-D average pooling. x: [N,D,H,W,C]. Padding is excluded from the
    divisor (reference Pool3DLayer's exclusive average)."""
    summed = _pool3d(x, window, stride, padding, 0.0, lax.add)
    w = (window,) * 3 if isinstance(window, int) else tuple(window)
    no_pad = padding == "VALID" or (
        not isinstance(padding, str) and all(
            p == 0 for p in ((padding,) * 3 if isinstance(padding, int)
                             else tuple(padding))))
    if no_pad:
        return summed / float(np.prod(w))
    counts = _pool3d(jnp.ones(x.shape[1:-1], x.dtype)[None, ..., None],
                     window, stride, padding, 0.0, lax.add)
    return summed / counts


def maxout(x, groups: int):
    """Maxout over channel groups (reference:
    gserver/layers/MaxOutLayer.cpp): [..., C] -> [..., C/groups], max over
    each group of `groups` consecutive channels."""
    c = x.shape[-1]
    if c % groups != 0:
        raise ValueError(f"channels {c} not divisible by groups {groups}")
    return x.reshape(*x.shape[:-1], c // groups, groups).max(-1)


def block_expand(x, block: IntOr2, *, stride: IntOr2 = None, padding="VALID"):
    """Image -> sequence of flattened blocks (reference:
    gserver/layers/BlockExpandLayer.cpp, function/BlockExpandOp.cpp):
    sweep a block window over [N, H, W, C] and emit one timestep per
    position. Returns [N, Ho*Wo, bh*bw*C] — feed it to sequence ops/RNNs
    (the OCR pattern the reference built this for).
    """
    bh, bw = _pair(block)
    s = _pair(stride if stride is not None else block)
    patches = im2col(x, (bh, bw), stride=s, padding=padding)
    n, ho, wo, d = patches.shape
    return patches.reshape(n, ho * wo, d)


def bilinear_interp(x, out_hw: Tuple[int, int], *,
                    align_corners: bool = False):
    """Bilinear resize of [N, H, W, C] (reference:
    gserver/layers/BilinearInterpLayer.cpp, operators/bilinear_interp_op).
    align_corners=False matches the reference's pixel-center ratio
    convention for upsampling."""
    import jax.image

    n, h, w, c = x.shape
    oh, ow = out_hw
    if align_corners and oh > 1 and ow > 1:
        # corner-aligned sampling grid via explicit gather weights
        ys = jnp.linspace(0.0, h - 1.0, oh)
        xs = jnp.linspace(0.0, w - 1.0, ow)
        y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
        x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, w - 1)
        y1 = jnp.minimum(y0 + 1, h - 1)
        x1 = jnp.minimum(x0 + 1, w - 1)
        wy = (ys - y0).astype(x.dtype)[None, :, None, None]
        wx = (xs - x0).astype(x.dtype)[None, None, :, None]
        top = x[:, y0][:, :, x0] * (1 - wx) + x[:, y0][:, :, x1] * wx
        bot = x[:, y1][:, :, x0] * (1 - wx) + x[:, y1][:, :, x1] * wx
        return top * (1 - wy) + bot * wy
    return jax.image.resize(x, (n, oh, ow, c), method="bilinear")


def nearest_interp(x, out_hw: Tuple[int, int]):
    """Nearest-neighbor resize of [N, H, W, C]."""
    import jax.image

    n, h, w, c = x.shape
    return jax.image.resize(x, (n, out_hw[0], out_hw[1], c),
                            method="nearest")


def rotate90(x, *, reverse: bool = False):
    """Rotate each [H, W] feature map 90 degrees counter-clockwise
    (reference: gserver/layers/RotateLayer.cpp; reverse=True rotates
    clockwise, its backward). x: [N, H, W, C] -> [N, W, H, C]."""
    if reverse:
        return jnp.flip(jnp.swapaxes(x, 1, 2), axis=2)
    return jnp.flip(jnp.swapaxes(x, 1, 2), axis=1)


def max_pool2d_with_index(x, window: IntOr2 = 2, *,
                          stride: Optional[IntOr2] = None,
                          padding="VALID"):
    """Max pooling that also returns each maximum's FLAT spatial index
    (h*W + w per channel) — the unpooling mask (reference:
    operators/pool_with_index_op.cc, gserver MaxPoolWithMaskLayer).

    x: [N,H,W,C]. Returns (pooled [N,OH,OW,C], idx int32 [N,OH,OW,C]).
    Built on im2col (one XLA patches op); out-of-image window cells are
    masked by INDEX ARITHMETIC (0 <= i*s - pad + r < H) so padded cells
    can never win the argmax — same semantics as max_pool2d's -inf/int-
    min padding, preserving integer dtypes.
    """
    n, h, w, c = x.shape
    wh, ww = _pair(window)
    sh, sw = _pair(stride if stride is not None else window)
    patches = im2col(x, (wh, ww), stride=(sh, sw), padding=padding)
    oh, ow = patches.shape[1], patches.shape[2]
    # im2col flattens channel-major: [..., C * wh * ww]
    vals = patches.reshape(n, oh, ow, c, wh * ww)
    (ph0, _), (pw0, _) = explicit_pad(h, w, (wh, ww), (sh, sw), padding)
    # absolute source coordinates of every window cell: [OH/OW, wh*ww]
    r = jnp.arange(wh * ww, dtype=jnp.int32) // ww
    s = jnp.arange(wh * ww, dtype=jnp.int32) % ww
    abs_h = jnp.arange(
        oh, dtype=jnp.int32)[:, None] * sh - ph0 + r[None, :]   # [OH, K]
    abs_w = jnp.arange(
        ow, dtype=jnp.int32)[:, None] * sw - pw0 + s[None, :]   # [OW, K]
    valid = ((abs_h >= 0) & (abs_h < h))[None, :, None, None, :] & \
        ((abs_w >= 0) & (abs_w < w))[None, None, :, None, :]
    fill = (jnp.array(-jnp.inf, x.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating)
            else jnp.array(jnp.iinfo(x.dtype).min, x.dtype))
    masked = jnp.where(valid, vals, fill)
    pooled = jnp.max(masked, axis=-1)
    best = jnp.argmax(masked, axis=-1)                # window-local flat
    flat = (jnp.take_along_axis(
        jnp.broadcast_to(abs_h[None, :, None, None, :],
                         (n, oh, ow, c, wh * ww)),
        best[..., None], axis=-1)[..., 0] * w +
        jnp.take_along_axis(
            jnp.broadcast_to(abs_w[None, None, :, None, :],
                             (n, oh, ow, c, wh * ww)),
            best[..., None], axis=-1)[..., 0]).astype(jnp.int32)
    return pooled, flat


def max_unpool2d(pooled, idx, out_hw: Tuple[int, int]):
    """Scatter pooled values back to their argmax positions (reference:
    the unpool consumer of pool_with_index; zeros elsewhere).

    pooled/idx: [N,OH,OW,C] from max_pool2d_with_index; out_hw: (H, W).
    Returns [N, H, W, C]. Overlapping windows that selected the SAME
    cell carry the same max — .at[].set writes it once (an .add would
    multiply it by the number of selecting windows).
    """
    n, oh, ow, c = pooled.shape
    h, w = out_hw
    flat_vals = pooled.reshape(n, oh * ow, c)
    flat_idx = idx.reshape(n, oh * ow, c)

    def scatter_one(vals, ids):                     # [K], [K] -> [H*W]
        return jnp.zeros((h * w,), vals.dtype).at[ids].set(vals)

    out = jax.vmap(                                  # over batch
        jax.vmap(scatter_one, in_axes=(1, 1), out_axes=1)  # over channel
    )(flat_vals, flat_idx)                           # [N, H*W, C]
    return out.reshape(n, h, w, c)
