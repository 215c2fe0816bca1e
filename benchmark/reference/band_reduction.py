"""Plain reference: a real symmetric matrix reduced to band form by
Householder panels, in numpy float64. No jax, no code of ``dlaf_tpu``.

The mathematics of upstream's ``eigensolver/reduction_to_band`` written the
straightforward way: panel ``k`` is the ``band`` columns ``k band ..
(k + 1) band`` below the band (rows from ``(k + 1) band`` on); it is
factorized column by column (LAPACK ``dgeqr2``: reflector ``j`` is
``H_j = I - tau_j v_j v_j^T`` with ``v_j[j] = 1`` and, ``dlarfg``'s sign,
``beta = -sign(alpha) |x|``, ``tau = (beta - alpha) / beta``, tail ``x[1:] /
(alpha - beta)``; a column whose tail is zero gets ``tau = 0``), and every
reflector is applied to the trailing matrix from both sides as soon as it
is formed: ``A <- H_j A H_j``. No T factor, no blocking, no ``W``/``X``
products: nothing the system under test shares.

The result is laid out as the system's (and LAPACK's): the band (diagonals
``0 .. band`` of the lower triangle; the R factors of the panels are its
outermost part) with the reflector tails stored below it, the upper triangle
the mirror of the reduced matrix, and ``taus[k, j]`` the scalar of reflector
``j`` of panel ``k`` (zero where a panel has fewer than ``band`` reflectors).
"""

from __future__ import annotations

import numpy as np


def larfg(x: np.ndarray):
    """``(beta, tau, tail)`` of the reflector that maps ``x`` to ``beta
    e_0`` (LAPACK ``dlarfg`` without its rescaling against underflow)."""
    alpha, rest = x[0], x[1:]
    sigma = rest @ rest
    if sigma == 0.0:
        return alpha, x.dtype.type(0), np.zeros_like(rest)
    beta = -np.copysign(np.sqrt(alpha * alpha + sigma), alpha)
    return beta, (beta - alpha) / beta, rest / (alpha - beta)


def reduce_to_band(a: np.ndarray, band: int, dtype=np.float64):
    """``(out, taus)``: ``out`` holds the band and, below it, the reflector
    tails; ``taus`` has shape ``(ceil(n / band) - 1, band)``. ``dtype`` is
    the precision everything is computed in: float64, or float32 to show
    what a reduction of that grade reads on the cell's checks (PERF.md)."""
    a = np.array(a, dtype=dtype)
    n = a.shape[0]
    npan = max(-(-n // band) - 1, 0)
    taus = np.zeros((npan, band), dtype=dtype)
    tails = []                      # (row of the unit entry, column, tail)
    for k in range(npan):
        top = (k + 1) * band        # first row below the band in this panel
        for j in range(min(band, n - top)):
            col, row = k * band + j, top + j
            beta, tau, tail = larfg(a[row:, col].copy())
            taus[k, j] = tau
            if tau != 0.0:
                v = np.concatenate((np.ones(1, dtype=dtype), tail))
                # A <- H A H on the rows / columns the reflector touches
                a[row:, :] -= tau * np.outer(v, v @ a[row:, :])
                a[:, row:] -= tau * np.outer(a[:, row:] @ v, v)
            a[row, col] = a[col, row] = beta
            a[row + 1:, col] = a[col, row + 1:] = 0.0
            tails.append((row, col, tail))
    for row, col, tail in tails:
        a[row + 1:, col] = tail
    return a, taus


def band_of(out: np.ndarray, band: int) -> np.ndarray:
    """The symmetric band matrix ``B`` as a dense array, read from diagonals
    ``0 .. band`` of the lower triangle of ``out`` and nothing else (what
    lies below the band, the stored reflector tails, does not enter)."""
    n = out.shape[0]
    b = np.zeros((n, n))
    for r in range(min(band, n - 1) + 1):
        d = np.diagonal(out, -r)
        idx = np.arange(n - r)
        b[idx + r, idx] = d
        b[idx, idx + r] = d
    return b


def apply_q(out: np.ndarray, taus: np.ndarray, band: int, x: np.ndarray,
            adjoint: bool = False) -> np.ndarray:
    """``Q x`` (``Q^T x`` with ``adjoint``) for ``Q = H_0 H_1 ...`` over
    all panels' reflectors in the order they were formed, applied one
    reflector at a time from the stored tails and taus."""
    x = np.array(x, dtype=np.float64)
    n = out.shape[0]
    order = [(k, j) for k in range(taus.shape[0])
             for j in range(min(band, n - (k + 1) * band))]
    for k, j in (order if adjoint else reversed(order)):
        tau = taus[k, j]
        if tau == 0.0:
            continue
        row = (k + 1) * band + j
        tail = out[row + 1:, k * band + j]
        w = x[row] + tail @ x[row + 1:]
        x[row] -= tau * w
        x[row + 1:] -= tau * np.outer(tail, w)
    return x
