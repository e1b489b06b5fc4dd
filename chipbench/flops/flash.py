"""Operations and bytes of one flash-attention forward call, from shapes.

Causal attention inside a window: a query sees min(t + 1, window) keys,
and each (query, key) pair costs 2*head_dim FLOPs for the score and
2*head_dim for the weighted sum. The least traffic is q, k and v read
once and the output written once (k and v at the full head count: the
program expands grouped KV heads before the kernel).
"""

from __future__ import annotations


def forward(*, batch: int, heads: int, seq: int, head_dim: int, window,
            bytes_per_value: int):
    """-> (FLOPs, bytes) of one call."""
    w = seq if window is None else min(window, seq)
    pairs = w * (w + 1) // 2 + (seq - w) * w
    flops = batch * heads * pairs * 4 * head_dim
    nbytes = 4 * batch * heads * seq * head_dim * bytes_per_value
    return flops, nbytes
