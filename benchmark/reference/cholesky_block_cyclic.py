"""Plain reference: the Cholesky factorization of a real symmetric positive
definite matrix and the 2D block-cyclic placement of its tiles, in numpy
float64. No jax, no code of ``dlaf_tpu``.

(a) ``cholesky_unblocked``: ``A = L L^T`` the straightforward way, LAPACK
``dpotf2``'s mathematics in its right-looking order. Column ``j``: ``l_jj =
sqrt(a_jj)``, the column below it divided by ``l_jj``, and that column's outer
product taken off the trailing matrix at once. Nothing is blocked: no panel,
no triangular solve with a block, no transposed panel, no look-ahead, nothing
the system under test shares.

(b) ``owner`` / ``local_slot`` / ``local_tiles``: the block-cyclic map written
from ScaLAPACK's definition (the Users' Guide's "block cyclic data
distribution"; ``INDXG2P`` / ``INDXG2L`` with block size 1 on tile indices).
On a ``Pr x Qc`` process grid whose rank ``(sr, sc)`` holds tile ``(0, 0)``,
tile ``(i, j)`` of an ``nb`` tiling lives on rank

    ((i + sr) mod Pr, (j + sc) mod Qc)      at local slot (i div Pr, j div Qc).

``local_tiles(a, nb, grid, rank)`` is what that rank must hold: an array of
``ceil(nt_r / Pr) x ceil(nt_c / Qc)`` slots of ``nb x nb`` (every rank the
same count, as the system stores them), a tile at the slot the map gives it,
zero where a rank has no tile for a slot and in the part of an edge tile past
the matrix.

Departures from upstream (DLA-Future ``matrix/distribution.h``,
``util_distribution.h``): none. Upstream's rank of a tile and local index of a
tile are these two formulas; upstream stores a rank's tiles as separate
allocations, so "slots padded to a uniform count" is a statement about this
file's return value, not about upstream.
"""

from __future__ import annotations

import numpy as np


def cholesky_unblocked(a: np.ndarray, dtype=np.float64) -> np.ndarray:
    """The lower factor of ``a`` (its lower triangle is read), the upper
    triangle zero. ``dtype`` is the precision everything is computed in:
    float64, or float32 to show what a factor of that grade reads on the
    cell's checks (PERF.md)."""
    a = np.tril(np.array(a, dtype=dtype))
    n = a.shape[0]
    for j in range(n):
        if not a[j, j] > 0:
            raise np.linalg.LinAlgError(
                f"leading minor of order {j + 1} is not positive definite")
        a[j, j] = np.sqrt(a[j, j])
        a[j + 1:, j] /= a[j, j]
        col = a[j + 1:, j]
        # the rank-1 update of the trailing matrix; only its lower triangle
        # is the matrix, the rest is cut off below
        a[j + 1:, j + 1:] -= np.outer(col, col)
    return np.tril(a)


def owner(i: int, j: int, grid, source=(0, 0)):
    """The rank ``(pr, pc)`` that holds tile ``(i, j)``."""
    return (i + source[0]) % grid[0], (j + source[1]) % grid[1]


def local_slot(i: int, j: int, grid):
    """The slot of tile ``(i, j)`` among its owner's tiles."""
    return i // grid[0], j // grid[1]


def local_tiles(a: np.ndarray, nb: int, grid, rank, source=(0, 0)):
    """The tiles of ``a`` that ``rank`` holds, ``(slots_r, slots_c, nb,
    nb)``: every tile of the ``nb`` tiling is asked for its owner and its
    slot, one by one, and copied where it belongs."""
    m, n = a.shape
    nt_r, nt_c = -(-m // nb), -(-n // nb)
    out = np.zeros((-(-nt_r // grid[0]), -(-nt_c // grid[1]), nb, nb),
                   dtype=a.dtype)
    for i in range(nt_r):
        for j in range(nt_c):
            if owner(i, j, grid, source) != tuple(rank):
                continue
            tile = a[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
            li, lj = local_slot(i, j, grid)
            out[li, lj, :tile.shape[0], :tile.shape[1]] = tile
    return out
