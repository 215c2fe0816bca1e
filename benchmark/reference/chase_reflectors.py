"""Plain reference: the bulge chase's Householder reflectors applied to a
block of vectors, in numpy float64. No jax, no code of ``dlaf_tpu``.

The published semantics of upstream's ``eigensolver/bt_band_to_tridiag``
written the straightforward way. The chase that took the band matrix ``B``
(``band`` sub-diagonals) to the tridiagonal ``T`` left its reflectors in the
uniform layout ``v[s, t, :]``, ``tau[s, t]``: sweep ``s`` (column ``s`` of
the band) made reflector ``t`` of its bulge chase on the rows

    [s + 1 + t band, s + 1 + (t + 1) band)   clipped to n,

``H = I - tau v v^H`` with ``v[0] = 1``; ``tau = 0`` is the identity (every
sweep is padded to the same number of steps, and a reflector of one row does
nothing). With ``T = Q^H B Q`` the eigenvectors of ``B`` are ``Q E`` for the
eigenvectors ``E`` of ``T``, and ``Q E`` is

    for s = n_sweeps - 1 .. 0:  for t = 0 .. n_steps - 1:
        seg = E[rows of (s, t)];  seg -= conj(tau[s, t]) v (v^H seg)

one rank-1 update a reflector: no T factor, no grouping of sweeps, no
staircase, nothing the system under test shares. The reflectors of one sweep
touch disjoint rows; those of different sweeps overlap and do not commute.
"""

from __future__ import annotations

import numpy as np


def apply_q(v: np.ndarray, tau: np.ndarray, e: np.ndarray, band: int,
            dtype=np.float64, block: int = 64) -> np.ndarray:
    """``Q E`` for the ``(n, m)`` block of vectors ``e``, a block of
    ``block`` columns at a time (the columns are independent; a block keeps
    the working set in cache). ``dtype`` is the precision everything is
    computed in: float64, or float32 to show what an application of that
    grade reads on the cell's checks (PERF.md)."""
    v = np.asarray(v, dtype=dtype)
    tau = np.asarray(tau, dtype=dtype)
    out = np.array(e, dtype=dtype)
    n = out.shape[0]
    n_sweeps, n_steps = tau.shape
    for c0 in range(0, out.shape[1], block):
        cols = out[:, c0:c0 + block]
        for s in range(n_sweeps - 1, -1, -1):
            for t in range(n_steps):
                if tau[s, t] == 0:
                    continue
                r0 = s + 1 + t * band
                r1 = min(r0 + band, n)
                if r1 <= r0:
                    break
                vec = v[s, t, :r1 - r0]
                seg = cols[r0:r1]
                seg -= np.conj(tau[s, t]) * np.outer(vec, np.conj(vec) @ seg)
    return out


def lower_band(a: np.ndarray, band: int) -> np.ndarray:
    """Lower band storage ``(band + 1, n)`` of the Hermitian ``a``: row
    ``r`` holds sub-diagonal ``r``, zero past its ``n - r`` entries (the
    inverse of :func:`dense_band` on a band matrix)."""
    n = a.shape[0]
    out = np.zeros((band + 1, n), dtype=a.dtype)
    for r in range(min(band, n - 1) + 1):
        out[r, :n - r] = np.diagonal(a, -r)
    return out


def dense_band(band_storage: np.ndarray) -> np.ndarray:
    """The Hermitian ``(n, n)`` matrix of lower band storage ``(band + 1,
    n)``: row ``r`` holds sub-diagonal ``r`` (``A[j + r, j]`` at column
    ``j``)."""
    rows, n = band_storage.shape
    a = np.zeros((n, n), dtype=band_storage.dtype)
    for r in range(min(rows, n)):
        diag = band_storage[r, :n - r]
        a += np.diag(diag, -r)
        if r:
            a += np.diag(np.conj(diag), r)
    return a


def tridiagonal(d: np.ndarray, e: np.ndarray) -> np.ndarray:
    """The symmetric tridiagonal matrix of diagonal ``d`` and off-diagonal
    ``e``."""
    return np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
