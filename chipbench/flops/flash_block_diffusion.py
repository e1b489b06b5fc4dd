"""Operations and bytes of one flash-attention call under the
block-diffusion mask, forward and backward, from shapes.

The kernel sees 2L positions a sequence (the noised copy, then the
clean copy). With blk(i) = (i mod L) // Bd the mask admits, a head and
sequence: L Bd noised-noised pairs (own block), (L^2 - L Bd) / 2
noised-clean (earlier blocks) and (L^2 + L Bd) / 2 clean-clean (own and
earlier): L^2 + L Bd. A pair costs 2 * head_dim FLOPs for the score and
2 * head_dim for the weighted sum forward, and five matmul terms of 2 *
head_dim backward (score and g vT recomputed; dv, dk, dq), whatever a
kernel recomputes beyond that. The least traffic is q, k, v read once
and the output written once forward; q, k, v, o, g read and dq, dk, dv
written backward (k and v at the full head count, as `flash.py`).
"""

from __future__ import annotations


def pairs(length: int, block_length: int) -> int:
    """Admitted pairs a head and sequence of 2 * `length` positions."""
    return length * length + length * block_length


def forward(*, batch: int, heads: int, positions: int, head_dim: int,
            block_length: int, bytes_per_value: int):
    """-> (FLOPs, bytes) of one call over `positions` = 2L positions."""
    n = batch * heads * pairs(positions // 2, block_length)
    return (n * 4 * head_dim,
            4 * batch * heads * positions * head_dim * bytes_per_value)


def backward(*, batch: int, heads: int, positions: int, head_dim: int,
             block_length: int, bytes_per_value: int):
    """-> (FLOPs, bytes) of one call."""
    n = batch * heads * pairs(positions // 2, block_length)
    return (n * 5 * 2 * head_dim,
            8 * batch * heads * positions * head_dim * bytes_per_value)
