"""Panel Householder QR with a TPU-trustworthy precision path.

The framework's panel factorizations (reduction_to_band's reflector
panels — its sole consumer; the QR T-factor algorithm takes already-
computed reflectors and is unaffected) ride XLA's ``geqrf`` primitive by
default off-TPU (LAPACK — f64-grade) and this module's ``householder_qr``
on TPU.

History: built while chasing the session-4d red2band ~1e-5 TPU check
failures, as the prime-suspect replacement for geqrf. The probes on the v5e
then EXONERATED geqrf — its expansion is
f64-grade on device (backward error ~2e-14 at every red2band panel
shape); the real culprit was the ozaki peel's use of the emulated-f64
``round`` (see ``tile_ops/ozaki.py _peel_slices``). The sweep earned the
TPU default anyway on throughput: red2band 4096/512/band128 scan measured
74.9 GF/s under it vs 49.3 under the geqrf expansion (+52%, equal
7e-14-grade residuals, post-peel-fix, 2026-08-02 v5e) — XLA's expansion
pays per-block dispatch this single fused loop avoids.

``householder_qr`` is the classical column Householder sweep (LAPACK
``geqrf``'s own algorithm — reference tile op ``dlaf/lapack/tile.h``
geqrf wrapper) in plain jnp elementwise / reduction / outer-product ops.
One ``lax.fori_loop`` iteration per column keeps the compile cost O(1) in
the panel width; the per-column work is a masked column norm (``m``
elements), ``v^H a`` as ONE multiply-and-sum pass over the panel and one
rank-1 update of the trailing columns — ``m*k`` elements each, the same
flop count as any Householder QR. A width-``k`` panel costs ``k``
sequential steps; red2band panels are ``k = band`` (128-512) on ``m`` up
to the matrix size.

``v^H a`` is an elementwise product summed over the rows, in the panel's
own dtype, and NOT ``conj(v) @ a``: a product with one output row has no
MXU shape, the TPU compiler already turns the f32 / c64 ``dot_general``
into that multiply-and-reduce, and for the emulated f64 it expands it into
five nested loops, ~350 kernels a column: 296 of a column's 316 us on
(8192, 128) panels, 52% of a reduction to band at N=8192 (4.58 s a call,
2026-10 v5e). As a sum it is one kernel of 6.5 us, a column 25.8 us (9.3
the loop's own overhead, 6.7 the rank-1 update, 0.8 the norm) and that
reduction 2.21 s a call at equal residuals (PERF.md, PR 34;
``tests/test_qr_panel.py`` keeps ``dot_general`` out of the loop body).

``panel_qr`` is the drop-in ``geqrf`` replacement used by the algorithm
layer: it dispatches per the ``qr_panel`` config knob ("auto" = the
householder sweep on TPU, the LAPACK-backed primitive elsewhere).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax

__all__ = ["householder_qr", "panel_qr", "rebuild_q"]


def _qr_panel_impl() -> str:
    """"geqrf" (XLA primitive) or "householder" (this module); "auto"
    resolves householder on TPU — a pure PERFORMANCE choice: red2band
    panels measured +52% under this sweep vs the geqrf expansion at
    equal (7e-14) accuracy — and geqrf (= LAPACK) elsewhere."""
    from ..config import get_configuration, resolve_platform_auto

    return resolve_platform_auto(
        get_configuration().qr_panel, knob="qr_panel",
        tpu_choice="householder", other_choice="geqrf",
        detail="the jnp householder sweep measured 74.9 GF/s vs 49.3 for "
               "XLA's geqrf expansion on red2band 4096 scan at equal "
               "7e-14-grade residuals — 2026-08-02 v5e; 25.8 us a column "
               "on (8192, 128) f64 panels since v^H a is a multiply-and-"
               "sum (316 as a dot_general) — 2026-10-03 v5e")


@functools.partial(jnp.vectorize, signature="(m,k)->(m,k),(p)")
def householder_qr(a):
    """Column Householder QR of a panel ``a``, in ``geqrf``'s output
    convention: R in the upper triangle (diagonal = the real beta
    values), the reflector tails strictly below it, and ``taus`` of
    shape (min(m, k),) with ``H_j = I - tau_j v_j v_j^H``
    (``v_j[j] = 1``). Matches LAPACK ``*larfg``'s sign choice
    (``beta = -sign(Re alpha) * ||x||``), zero-tail columns produce
    ``tau = 0`` exactly as LAPACK does; wide panels (m < k — the ragged
    final panel of a reduction) reduce min(m, k) columns like geqrf.

    Scope note (documented like tile_ops/ozaki.py): no lassq-style
    rescaling against overflow of ``sum |x|^2`` — on TPU the f64
    emulation is range-limited to f32's exponents anyway, and panels here
    are slices of already well-scaled matrices.
    """
    m, k = a.shape
    kk = min(m, k)                      # columns that get a reflector
    dtype = a.dtype
    cplx = jnp.issubdtype(dtype, jnp.complexfloating)
    rows = jnp.arange(m)
    cols = jnp.arange(k)
    taus0 = jnp.zeros((kk,), dtype=dtype)

    def body(j, carry):
        a, taus = carry
        col = lax.dynamic_slice_in_dim(a, j, 1, axis=1)[:, 0]   # (m,)
        alpha = lax.dynamic_slice_in_dim(col, j, 1)[0]
        below = rows > j
        tail = jnp.where(below, col, jnp.zeros_like(col))
        sigma = jnp.sum(jnp.abs(tail) ** 2)                     # real
        alphr = jnp.real(alpha)
        norm2 = jnp.abs(alpha) ** 2 + sigma
        beta_r = -jnp.sign(jnp.where(alphr == 0, jnp.ones_like(alphr),
                                     alphr)) * jnp.sqrt(norm2)
        # tau = 0 (null reflector, column already reduced): zero tail and,
        # for complex, a real diagonal entry
        null = (sigma == 0) & ((jnp.imag(alpha) == 0) if cplx else True)
        beta = beta_r.astype(dtype)
        tau = jnp.where(null, jnp.zeros((), dtype),
                        ((beta - alpha) / beta).astype(dtype))
        denom = alpha - beta
        scale = jnp.where(null, jnp.zeros((), dtype), 1.0 / denom)
        v = jnp.where(below, col * scale, jnp.zeros_like(col))
        v = jnp.where(rows == j, jnp.ones((), dtype), v)        # v_j = 1
        v = jnp.where(rows < j, jnp.zeros((), dtype), v)
        # apply H^H = I - conj(tau) v v^H to the trailing columns (cols >
        # j) — LAPACK zgeqr2 applies the ADJOINT reflector there while
        # storing tau itself for Q = H_1 ... H_k (real: conj is identity).
        # Earlier columns hold stored reflectors; later rows of col j are
        # written as the stored tail below.
        vha = jnp.sum(jnp.conj(v)[:, None] * a, axis=0)          # (k,)
        upd = jnp.conj(tau) * v[:, None] * vha[None, :]
        a = a - jnp.where(cols[None, :] > j, upd, jnp.zeros_like(upd))
        # column j: R above (rows < j untouched), beta on the diagonal
        # (alpha when null), stored tail below
        dcol = jnp.where(rows < j, col,
                         jnp.where(rows == j,
                                   jnp.where(null, alpha, beta),
                                   jnp.where(null, col, col * scale)))
        a = lax.dynamic_update_slice_in_dim(a, dcol[:, None], j, axis=1)
        taus = jnp.where(jnp.arange(kk) == j, tau, taus)
        return a, taus

    a, taus = lax.fori_loop(0, kk, body, (a, taus0))
    return a, taus


def rebuild_q(vfull, taus):
    """Host-side (numpy, true f64) accumulation of the first ``k`` columns
    of ``Q = H_0 H_1 ... H_{k-1}`` from stored reflectors — the
    verification oracle of the unit tests: any precision loss in ``vfull``/
    ``taus`` shows up as backward error against the input panel."""
    import numpy as np

    v = np.asarray(vfull)
    taus = np.asarray(taus)
    m, k = v.shape
    q = np.eye(m, k, dtype=v.dtype)
    for j in reversed(range(len(taus))):
        w = np.zeros(m, dtype=v.dtype)
        w[j] = 1.0
        w[j + 1:] = v[j + 1:, j]
        q -= taus[j] * np.outer(w, np.conj(w) @ q)
    return q


def panel_qr(a):
    """Drop-in ``geqrf`` replacement for panel factorizations: returns
    ``(vfull, taus)`` with R in ``vfull``'s upper triangle and reflector
    tails below. Dispatches per config ``qr_panel`` (see
    :func:`_qr_panel_impl`); both routes share output convention, so call
    sites are route-agnostic."""
    if _qr_panel_impl() == "householder":
        return householder_qr(a)
    from jax._src.lax.linalg import geqrf

    return geqrf(a)
