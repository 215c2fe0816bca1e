"""Mixed-precision f64/c128 panel factorization for TPU: half-precision seed
plus one Newton step.

On TPU, f64 is compiler-emulated (double-double over f32), which makes the
*latency-bound* panel ops of a blocked factorization disproportionately slow:
a 256x256 ``lax.linalg.cholesky`` costs ~16 ms in f64 but ~1.8 ms in f32 on a
v5e, while the flops it performs are trivial. These helpers recover f64-grade
panel results from f32 factorizations plus one Newton-type correction whose
heavy lifting is a handful of small *gemms* (which ARE fast in emulated f64,
being throughput- not latency-bound):

* :func:`potrf_refined`:  ``L32 = chol(f32(A))``, then
  ``L = L32 + L32 * phi(Linv32 E Linv32^T)`` with ``E = A - L32 L32^T`` in
  f64 and ``phi`` = strict lower + half diagonal. One Newton step leaves a
  residual that grows with the block's conditioning (measured ``~6e-16 *
  kappa`` at n=256), so the fast path is gated on a cheap in-program
  condition estimate (:func:`cond_limit`); blocks over the limit take the
  native branch.
* :func:`tri_inv_refined`: explicit ``L^-1`` from the f32 inverse plus one
  Newton iteration ``X <- X + X(I - L X)`` in f64, so a panel solve
  ``P L^-H`` becomes a *gemm* instead of an emulated-f64 triangular solve.

Robustness: the ``lax.cond`` fallback to the native f64 path triggers when
the f32 seed fails outright (non-finite results: block not positive definite
at f32 precision) OR when the condition estimate exceeds :func:`cond_limit`
— the slow-but-sure branch only executes when taken.

The reference has no analog (its panels run on native-f64 hardware); this is
TPU-specific redesign, used by the ``cholesky_trailing="ozaki"`` fast path
together with :mod:`dlaf_tpu.tile_ops.ozaki`.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["potrf_refined", "potrf_inv_refined", "tri_inv_refined",
           "cond_limit"]


def cond_limit() -> float:
    """Conditioning guard for the fast path, as a limit on the squared
    diagonal ratio ``(max diag(L32) / min diag(L32))^2`` (a cheap in-program
    condition estimate of the block: empirically ``residual ~ 3.5e-14 *
    estimate`` for one Newton step, so the default 100 keeps residuals at
    the ``60 n eps`` budget for tile-sized blocks). Blocks estimated worse
    than this take the native emulated-f64 branch.

    Config field ``mixed_cond_limit`` (env ``DLAF_MIXED_COND_LIMIT``,
    CLI ``--dlaf:mixed-cond-limit``) — a real Configuration field so a
    change invalidates registered program caches (the limit is baked into
    compiled ``lax.cond`` guards at trace time)."""
    from ..config import get_configuration

    return float(get_configuration().mixed_cond_limit)


def _seed_dtype(dtype):
    """Half-precision seed dtype: f32 for f64, c64 for c128."""
    return jnp.complex64 if jnp.dtype(dtype).kind == "c" else jnp.float32


def _phi_lower(m):
    """Strict lower triangle plus half the diagonal — the projector that
    maps the Hermitian correction equation onto lower-triangular space. The
    diagonal of the (Hermitian) correction is real up to rounding; its real
    part is taken so the factor's diagonal stays exactly real."""
    d = jnp.diagonal(m, axis1=-2, axis2=-1)
    d = jnp.real(d) if jnp.iscomplexobj(m) else d
    n = m.shape[-1]
    return jnp.tril(m, -1) + 0.5 * d[..., None] * jnp.eye(n, dtype=m.dtype)


def _herm_from_tril(a):
    """Full Hermitian block from its stored lower triangle (real
    diagonal enforced for complex dtypes)."""
    lo = jnp.tril(a, -1)
    d = jnp.diagonal(a, axis1=-2, axis2=-1)
    d = jnp.real(d).astype(a.dtype) if jnp.iscomplexobj(a) else d
    n = a.shape[-1]
    return lo + jnp.conj(jnp.swapaxes(lo, -1, -2)) \
        + d[..., None] * jnp.eye(n, dtype=a.dtype)


def _diag_ratio_sq(tri32):
    """Squared max/min ratio of the (f32) triangular factor's diagonal —
    the conditioning estimate behind :func:`cond_limit`. Non-positive or
    non-finite diagonals map to +inf (forces the native branch)."""
    d = jnp.abs(jnp.diagonal(tri32, axis1=-2, axis2=-1))
    est = (jnp.max(d) / jnp.min(d)) ** 2
    good = jnp.isfinite(est) & (jnp.min(d) > 0)
    return jnp.where(good, est, jnp.inf)


def _chol_inv_seed_recursive(a, base: int):
    """(chol(a), inv(chol(a))) in the seed dtype via TRACE-TIME recursive
    block decomposition: leaves call the native kernels at ``base`` size;
    every upper level composes with gemms only —

        L = [[L11, 0], [A21 L11^-H, chol(A22 - L21 L21^H)]]
        L^-1 = [[L11^-1, 0], [-L22^-1 L21 L11^-1, L22^-1]]

    — so the loop-based XLA triangular solves disappear above the leaves
    and the sequential latency is leaf chols + MXU gemms (config
    ``mixed_seed="recursive"``; a latency attack on the panel chain)."""
    n = a.shape[-1]
    if n <= base:
        l = lax.linalg.cholesky(a)
        linv = lax.linalg.triangular_solve(
            l, jnp.eye(n, dtype=a.dtype), left_side=True, lower=True)
        return l, linv
    h = n // 2
    l11, i11 = _chol_inv_seed_recursive(a[:h, :h], base)
    l21 = a[h:, :h] @ jnp.conj(i11).T
    s = a[h:, h:] - l21 @ jnp.conj(l21).T
    l22, i22 = _chol_inv_seed_recursive(s, base)
    i21 = -(i22 @ l21) @ i11
    ztop = jnp.zeros((h, n - h), dtype=a.dtype)
    l = jnp.concatenate([jnp.concatenate([l11, ztop], axis=1),
                         jnp.concatenate([l21, l22], axis=1)], axis=0)
    linv = jnp.concatenate([jnp.concatenate([i11, ztop], axis=1),
                            jnp.concatenate([i21, i22], axis=1)], axis=0)
    return l, linv


def _chol_lower_native(a):
    """Lower Cholesky factor of the Hermitian block ``a`` in its own
    (f64/c128) precision as a plain left-looking column loop — the
    slow-but-sure branch behind the ``lax.cond`` guards below.

    Not ``lax.linalg.cholesky``: the TPU compiler refuses XLA's f64
    cholesky expansion in a program partitioned over several devices
    ("A tuple parameter that is being flattened shouldn't have frontend
    attributes", v5e 2x2, jax 0.9.0), and this branch sits inside every
    distributed f64 step. One ``fori_loop`` iteration per column: an
    (n, n) matvec in emulated f64, executed only when the guard fails.
    A non-positive pivot gives ``sqrt`` = NaN, which spreads to every
    later column (the ``potrf_info`` contract)."""
    n = a.shape[-1]
    idx = jnp.arange(n)

    def column(j, l):
        done = jnp.where(idx < j, l[j, :], jnp.zeros_like(l[j, :]))
        c = a[:, j] - l @ jnp.conj(done)
        d = jnp.sqrt(jnp.real(c[j])).astype(a.dtype)
        return l.at[:, j].set(jnp.where(idx >= j, c / d, jnp.zeros_like(c)))

    return lax.fori_loop(0, n, column, jnp.zeros_like(a))


def _refined_seed(a):
    """Shared seed+Newton factor body: f32/c64 cholesky seed, its seed
    inverse, and the one-Newton-step refined f64 factor. Returns
    ``(refined_l, linv0, l32)`` — the fused and non-fused entry points
    build on the same refinement so they cannot diverge."""
    from ..config import get_configuration

    cfg = get_configuration()
    sd = _seed_dtype(a.dtype)
    if cfg.mixed_seed == "recursive":
        l32, linv32 = _chol_inv_seed_recursive(a.astype(sd),
                                               int(cfg.mixed_seed_base))
    else:
        l32 = lax.linalg.cholesky(a.astype(sd))
        linv32 = lax.linalg.triangular_solve(
            l32, jnp.eye(a.shape[-1], dtype=sd), left_side=True, lower=True)
    l0 = jnp.tril(l32).astype(a.dtype)
    linv0 = jnp.tril(linv32).astype(a.dtype)
    e = a - l0 @ jnp.conj(l0).T
    m = (linv0 @ e) @ jnp.conj(linv0).T
    return l0 + l0 @ _phi_lower(m), linv0, l32


def _potrf_refined_l(a):
    """Lower-Cholesky of an f64/c128 block via half-precision seed + one
    Newton step (Hermitian-correct: conjugate transposes throughout)."""
    refined, _, l32 = _refined_seed(a)

    def native(_):
        return _chol_lower_native(a)

    ok = (jnp.all(jnp.isfinite(refined))
          & (_diag_ratio_sq(l32) <= cond_limit()))
    return lax.cond(ok, lambda r: r, native, refined)


def potrf_refined(uplo: str, a):
    """f64/complex128 Cholesky factor of the HPD block ``a`` (``uplo``
    triangle read, other triangle of the *result* zeroed). 2D blocks; the
    seed runs at f32/c64 and one Hermitian Newton step recovers full
    precision.

    uplo='L': returns lower ``L`` with ``L L^H`` = the Hermitian matrix
    rebuilt from the stored lower triangle; uplo='U': returns upper ``U``
    with ``U^H U = a`` (``U = conj(L).T`` of the factorization of the
    Hermitian rebuild of ``conj(a).T``'s lower storage).
    """
    if uplo == "L":
        sym = _herm_from_tril(a)
        return _potrf_refined_l(sym)
    sym = _herm_from_tril(jnp.conj(a).T)   # upper storage, transposed problem
    return jnp.conj(_potrf_refined_l(sym)).T


def _potrf_inv_refined_l(a):
    """(L, L^-1) fused: the f32 seed solves are shared, so one panel step
    pays ONE latency-bound f32 cholesky + ONE f32 triangular solve instead
    of two solves (potrf_refined already computes the f32 inverse for its
    Newton step; the separate tri_inv_refined re-solved it)."""
    n = a.shape[-1]
    l, linv0, l32 = _refined_seed(a)
    eye = jnp.eye(n, dtype=a.dtype)
    # Newton inverse of the REFINED factor, seeded by the f32 inverse:
    # seed error is f32-rounding + the l0 -> l drift (~f64-grade), so one
    # step lands at the same residual tri_inv_refined reaches
    x = linv0 + linv0 @ (eye - l @ linv0)

    def native(_):
        ln = _chol_lower_native(a)
        return ln, lax.linalg.triangular_solve(ln, eye, left_side=True,
                                               lower=True)

    ok = (jnp.all(jnp.isfinite(l)) & jnp.all(jnp.isfinite(x))
          & (_diag_ratio_sq(l32) <= cond_limit()))
    return lax.cond(ok, lambda lx: lx, native, (l, x))


def potrf_inv_refined(uplo: str, a):
    """Fused (factor, explicit inverse) of the HPD block ``a`` — same
    contracts as :func:`potrf_refined` + :func:`tri_inv_refined` of its
    result, sharing the half-precision seed solves. uplo='L': ``(L, L^-1)``
    lower; uplo='U': ``(U, U^-1)`` upper (transposed problem)."""
    if uplo == "L":
        return _potrf_inv_refined_l(_herm_from_tril(a))
    l, linv = _potrf_inv_refined_l(_herm_from_tril(jnp.conj(a).T))
    return jnp.conj(l).T, jnp.conj(linv).T


def tri_inv_refined(l, *, lower: bool = True):
    """Explicit f64 inverse of a triangular block: f32 solve + one Newton
    step ``X <- X + X(I - L X)`` (two small f64 gemms). Non-finite f32 seed
    falls back to the native emulated-f64 triangular solve."""
    n = l.shape[-1]
    sd = _seed_dtype(l.dtype)
    eye32 = jnp.eye(n, dtype=sd)
    l32 = l.astype(sd)
    x32 = lax.linalg.triangular_solve(l32, eye32, left_side=True, lower=lower)
    tri = jnp.tril if lower else jnp.triu
    x0 = tri(x32).astype(l.dtype)
    lt = tri(l)
    refined = x0 + x0 @ (jnp.eye(n, dtype=l.dtype) - lt @ x0)

    def native(_):
        return lax.linalg.triangular_solve(lt, jnp.eye(n, dtype=l.dtype),
                                           left_side=True, lower=lower)

    # Newton on the inverse needs ||I - L X0|| < 1, which fails for badly
    # conditioned blocks long before anything overflows — same guard
    ok = (jnp.all(jnp.isfinite(refined))
          & (_diag_ratio_sq(l32) <= cond_limit()))
    return lax.cond(ok, lambda r: r, native, refined)
