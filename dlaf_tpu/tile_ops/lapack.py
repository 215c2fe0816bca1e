"""tpu_lapack — LAPACK tile operations.

TPU-native counterpart of the reference's ``lapack/tile.h:66-645`` (tile-level
``potrf/hegst/laset/lacpy/lange/lantr/larft/stedc/laed4`` dispatched to
lapackpp on CPU, cuSOLVER + custom CUDA kernels on GPU — the custom-kernel
table in SURVEY.md §2/L5). Device ops are pure jnp functions (XLA has native
Cholesky and TriangularSolve; ``lacpy``/``laset`` are trivial masked ops — the
reference needed hand-written CUDA for those, ``src/lapack/gpu/{lacpy,laset}.cu``);
the symmetric-tridiagonal eigensolver leaf (``stedc``) stays a host kernel
exactly as the reference keeps it on CPU (``eigensolver/impl.h:46-72``).

``larft`` replaces the reference's gemv-loop T-factor accumulation
(``factorization/qr/t_factor_impl.h``) with a closed form: for forward
columnwise reflectors, ``T^{-1} = diag(1/tau) + strict_upper(V^H V)``, so T
comes from ONE gemm (MXU) plus ``ceil(log2 k)`` masked doubling steps of two
small products each for its inverse — the TPU-idiomatic formulation.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from .. import obs
from .blas import _diag_of, _embed_diag, hermitian_from, tri_mask, trsm


def laset(uplo: str, alpha, beta, shape, dtype):
    """Fresh block: off-diagonal ``alpha``, diagonal ``beta``, over the
    ``uplo`` region (reference ``tile::laset``; custom CUDA kernel
    ``src/lapack/gpu/laset.cu``)."""
    m, n = shape[-2], shape[-1]
    a = jnp.full(shape, alpha, dtype=dtype)
    a = a + (beta - alpha) * jnp.eye(m, n, dtype=dtype)
    return tri_mask(a, uplo)


def lacpy(uplo: str, a, b):
    """Copy the ``uplo`` region of ``a`` onto ``b`` (reference ``tile::lacpy``;
    custom CUDA kernel ``src/lapack/gpu/lacpy.cu``)."""
    if uplo == "G":
        return a
    keep = "U" if uplo == "L" else "L"
    return tri_mask(a, uplo) + tri_mask(b, keep, k=-1)


def lange(norm: str, a):
    """General-block norm: 'M' (max abs), '1', 'I', 'F'
    (reference ``tile::lange``)."""
    aa = jnp.abs(a)
    if norm == "M":
        return jnp.max(aa, axis=(-2, -1)) if a.size else jnp.zeros(a.shape[:-2], a.dtype)
    if norm == "1":
        return jnp.max(jnp.sum(aa, axis=-2), axis=-1)
    if norm == "I":
        return jnp.max(jnp.sum(aa, axis=-1), axis=-1)
    if norm == "F":
        return jnp.sqrt(jnp.sum(aa * aa, axis=(-2, -1)))
    raise ValueError(f"bad norm {norm!r}")


def lantr(norm: str, uplo: str, diag: str, a):
    """Triangular-block norm (reference ``tile::lantr``)."""
    t = tri_mask(a, uplo)
    if diag == "U":
        t = t - _embed_diag(_diag_of(t), t.shape, t.dtype) + jnp.eye(a.shape[-1], dtype=a.dtype)
    return lange(norm, t)


def potrf(uplo: str, a):
    """Cholesky factor of an SPD/HPD block stored in ``uplo``
    (reference ``tile::potrf``). The factor lands in the ``uplo`` triangle;
    the opposite triangle of ``a`` passes through unchanged (LAPACK in-place
    semantics). Lowers to XLA's native blocked Cholesky on TPU."""
    af = hermitian_from(a, uplo)
    if uplo == "L":
        f = lax.linalg.cholesky(af)
        return tri_mask(f, "L") + tri_mask(a, "U", k=-1)
    f = jnp.conj(jnp.swapaxes(lax.linalg.cholesky(af), -1, -2))
    return tri_mask(f, "U") + tri_mask(a, "L", k=-1)


def potrf_info(uplo: str, a):
    """``potrf`` plus an info value (reference ``tile::potrfInfo``, which
    surfaces the LAPACK/cusolver info instead of asserting): returns
    ``(factor, info)`` with info = 0 on success, nonzero on a failed
    factorization. Unlike LAPACK, info's value does NOT identify the exact
    failing column: XLA backends mark failures by NaN-ing the factor (CPU
    NaNs all of it, TPU's blocked form NaNs from the failing block on), so
    nonzero info is the 1-based index of the first non-finite diagonal —
    a success/failure signal first, a column locator only as far as the
    backend preserves the prefix."""
    f = potrf(uplo, a)
    diag = _diag_of(tri_mask(f, uplo) if uplo != "G" else f)
    bad = ~jnp.isfinite(diag.real) if jnp.iscomplexobj(diag) else ~jnp.isfinite(diag)
    idx = jnp.argmax(bad, axis=-1)
    info = jnp.where(jnp.any(bad, axis=-1), idx + 1, 0)
    return f, info


def laed4(d, z, rho):
    """Secular-equation roots of the rank-one update
    ``D + rho z z^T`` (reference ``tile::laed4`` -> LAPACK ``dlaed4``, the
    D&C merge's per-eigenvalue kernel). Host-side like the reference (it
    keeps laed4 on the CPU even for the GPU backend); delegates to the
    framework's secular solver (native C++ safeguarded Newton, numpy
    bisection fallback — ``eigensolver/tridiag_solver.py``), which also
    provides the device-fused variant for large merges. Returns the k
    updated eigenvalues (ascending)."""
    from ..eigensolver.tridiag_solver import _secular_roots_host

    d = np.asarray(d, dtype=np.float64)
    anchor, offset = _secular_roots_host(d, np.asarray(z, dtype=np.float64),
                                         float(rho))
    return d[anchor] + offset


def hegst(itype: int, uplo: str, a, b):
    """Tile-level generalized-to-standard transform (reference
    ``tile::hegst`` / custom GPU port ``gpu/cusolver/hegst.h``):

    itype=1: ``A := inv(L) A inv(L)^H`` (uplo='L', B = L) or
             ``A := inv(U^H) A inv(U)`` (uplo='U').

    Composed from two XLA triangular solves on the Hermitianized block —
    no custom kernel needed on TPU.
    """
    if itype != 1:
        raise NotImplementedError("hegst itype=2,3 not used by the pipeline")
    af = hermitian_from(a, uplo)
    if uplo == "L":
        t = trsm("L", "L", "N", "N", b, af)         # inv(L) A
        out = trsm("R", "L", "C", "N", b, t)        # ... inv(L)^H
    else:
        t = trsm("L", "U", "C", "N", b, af)         # inv(U)^H A
        out = trsm("R", "U", "N", "N", b, t)        # ... inv(U)
    return _restore_other_triangle(out, a, uplo)


def _restore_other_triangle(update, orig, uplo: str):
    if uplo == "G":
        return update
    other = "U" if uplo == "L" else "L"
    return tri_mask(update, uplo) + tri_mask(orig, other, k=-1)


def larft(v, tau):
    """T factor of a block of forward, columnwise Householder reflectors
    (reference ``tile::larft`` and the distributed T-factor algorithm
    ``factorization/qr/t_factor_impl.h:42-347``).

    ``v``: (m, k) reflectors (unit lower trapezoidal, implicit ones NOT
    required — v's upper triangle is ignored); ``tau``: (k,).
    Uses ``T^{-1} = diag(1/tau) + strict_upper(V^H V)``; zero taus produce
    zero rows/cols in T (null reflectors), as LAPACK does. A zero-tau
    column's stored sub-diagonal is ignored (treated as the null reflector
    it represents) so the closed form matches LAPACK dlarft even when the
    caller left stale data in that column.

    ``T^{-1}`` is inverted by recursive 2x2 block doubling (the recursive
    ``trtri`` scheme) on the whole (k, k) matrix, with masks instead of
    slices: ``X`` starts as the inverse of the diagonal, ``diag(tau)``,
    and is block diagonal with blocks of size ``w``; for each diagonal
    block ``[A1 C; 0 A2]`` of size ``2 w`` the inverse is
    ``[X1, -X1 C X2; 0, X2]``, and ``X - X B X``, with ``B`` the ``C``
    blocks masked out of ``V^H V``, forms exactly that. ``ceil(log2 k)``
    steps of two (k, k) products (7 at k = 128) where a substitution is k
    sequential steps; ragged last blocks and leading batch dimensions need
    nothing else. Counts ``dlaf_larft_doublings_total{k}``: the steps
    emitted, per EXECUTED call (``obs.traced_step_count()``).
    """
    k = tau.shape[-1]
    vlow = tri_mask(v, "L", k=-1)
    # null reflectors (tau==0) must not route cross terms through the Gram:
    # zero their stored sub-diagonal before forming V^H V
    vlow = jnp.where((tau == 0)[..., None, :], jnp.zeros_like(vlow), vlow)
    vv = vlow + jnp.eye(v.shape[-2], k, dtype=v.dtype)
    s = jnp.conj(jnp.swapaxes(vv, -1, -2)) @ vv            # V^H V, one gemm
    tau_safe = jnp.where(tau == 0, jnp.ones_like(tau), tau)
    t = _embed_diag(tau_safe, s.shape, s.dtype)
    i = np.arange(k)
    w, steps = 1, 0
    while w < k:
        blk = i // (2 * w)
        upper = (blk[:, None] == blk[None, :]) & (i[:, None] % (2 * w) < w) \
            & (i[None, :] % (2 * w) >= w)
        b = jnp.where(upper, s, jnp.zeros_like(s))     # strictly upper
        t = t - t @ (b @ t)
        w, steps = 2 * w, steps + 1
    if obs.metrics_active():
        obs.counter("dlaf_larft_doublings_total", k=str(k)).inc(
            steps * obs.traced_step_count())
    nz = (tau != 0)
    mask = nz[..., :, None] & nz[..., None, :]
    return jnp.where(mask, t, jnp.zeros_like(t))


# ---------------------------------------------------------------------------
# Host kernels (reference keeps these on CPU too)
# ---------------------------------------------------------------------------

def stedc(d: np.ndarray, e: np.ndarray):
    """Host symmetric-tridiagonal eigensolver used for D&C leaf solves
    (reference ``tile::stedc`` -> LAPACK stedc / cusolver syevd wrapper
    ``src/cusolver/stedc.cu``). Returns (eigenvalues, eigenvectors)."""
    import scipy.linalg as sla

    d = np.asarray(d, dtype=np.float64)
    e = np.asarray(e, dtype=np.float64)
    if d.size == 1:
        return d.copy(), np.ones((1, 1))
    w, v = sla.eigh_tridiagonal(d, e)
    return w, v
