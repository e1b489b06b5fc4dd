"""Operations and bytes of one call of the gated delta rule's kernels,
from shapes: the layer's own mathematics, not the chunk size or
whatever a chunked form recomputes.

Forward: a position of a value head decays its state (dk x dv), reads
it against the key (S^T k), writes the rank-one update (k u^T) and reads
it against the query (S^T q): 6 dk dv FLOPs for the three products. The
least traffic is q, k and v read once (q and k at the value heads' count:
the program repeats the key heads before the kernel), the decay and
beta (float32) read once and the output written once.

Backward: twice the forward's products, 12 dk dv FLOPs a position and
value head; the least traffic is the forward's inputs and the output's
gradient read once, and the gradients of q, k, v (in their dtype), of
the decay and of beta (float32) written once.
"""

from __future__ import annotations


def forward(*, rows: int, seq: int, dk: int, dv: int, bytes_per_value: int):
    """-> (FLOPs, bytes) of one forward call over `rows` (batch x value
    heads) sequences of `seq` positions."""
    positions = rows * seq
    flops = 6 * dk * dv * positions
    nbytes = positions * (bytes_per_value * (2 * dk + 2 * dv) + 2 * 4)
    return flops, nbytes


def backward(*, rows: int, seq: int, dk: int, dv: int, bytes_per_value: int):
    """-> (FLOPs, bytes) of one backward call."""
    positions = rows * seq
    flops = 12 * dk * dv * positions
    nbytes = positions * (bytes_per_value * (2 * dk + dv + dv)   # q k v do
                          + 2 * 4                                # g beta
                          + bytes_per_value * (2 * dk + dv)      # dq dk dv
                          + 2 * 4)                               # dg dbeta
    return flops, nbytes
