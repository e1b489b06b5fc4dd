"""Operations and bytes of one grouped matrix product of a dropless
expert layer, from shapes.

`rows` rows, ordered by expert, each multiplied by its expert's [k, n]
matrix: 2 * rows * k * n FLOPs, whichever of the three products of a
call it is (rows @ w, grad @ w.T, rows.T @ grad). The least traffic is
the rows in and the rows out once, and the matrices of the groups
touched once (read, or written for the weight gradient). An expert
layer's forward is three such products at [dim, expert_dim]:
2 * rows * dim * expert_dim * 3.
"""

from __future__ import annotations


def product(*, rows: float, k: int, n: int, groups: int,
            bytes_per_value: int):
    """-> (FLOPs, bytes) of one product."""
    return (2.0 * rows * k * n,
            (groups * k * n + rows * (k + n)) * bytes_per_value)


def layer_forward(*, rows: float, dim: int, expert_dim: int):
    """FLOPs of gate, up and down for `rows` rows."""
    return 2.0 * rows * dim * expert_dim * 3
