"""Operations and bytes of one flash-attention backward call, from shapes.

Causal attention inside a window, the pairs `flash.forward` counts: a
query sees min(t + 1, window) keys. Each (query, key) pair costs five
matmul terms of 2*head_dim FLOPs: the score and g vT recomputed, and
dv, dk and dq accumulated. That is the floor whatever a kernel
recomputes beyond it (two kernels that each rebuild the score and g vT
run seven), so a share of this roofline cannot pass 100%. The least
traffic is q, k, v, o and g read once and dq, dk and dv written once
(k and v at the full head count, as the forward's).
"""

from __future__ import annotations


def backward(*, batch: int, heads: int, seq: int, head_dim: int, window,
             bytes_per_value: int):
    """-> (FLOPs, bytes) of one call."""
    w = seq if window is None else min(window, seq)
    pairs = w * (w + 1) // 2 + (seq - w) * w
    flops = batch * heads * pairs * 5 * 2 * head_dim
    nbytes = 8 * batch * heads * seq * head_dim * bytes_per_value
    return flops, nbytes
