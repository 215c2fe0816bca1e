"""Tile-kernel correctness vs numpy/scipy.

Mirrors the reference's ``test/unit/test_blas_tile/`` and
``test_lapack_tile/`` suites: every op, all four scalar types, square and
rectangular blocks, batched forms.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dlaf_tpu.tile_ops import blas as tb
from dlaf_tpu.tile_ops import lapack as tl

DTYPES = [np.float32, np.float64, np.complex64, np.complex128]


def rand(rng, shape, dtype):
    a = rng.standard_normal(shape)
    if np.dtype(dtype).kind == "c":
        a = a + 1j * rng.standard_normal(shape)
    return a.astype(dtype)


def _tol(dtype):
    eps = np.finfo(np.dtype(dtype).type(0).real.dtype).eps
    return dict(rtol=200 * eps, atol=200 * eps)


def np_op(a, op):
    return {"N": a, "T": a.T, "C": a.conj().T}[op]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("opa,opb", [("N", "N"), ("T", "N"), ("N", "C"), ("C", "T")])
def test_gemm(dtype, opa, opb):
    rng = np.random.default_rng(0)
    m, n, k = 7, 5, 6
    a = rand(rng, (k, m) if opa != "N" else (m, k), dtype)
    b = rand(rng, (n, k) if opb != "N" else (k, n), dtype)
    c = rand(rng, (m, n), dtype)
    out = tb.gemm(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c),
                  alpha=2.0, beta=0.5, op_a=opa, op_b=opb)
    expect = 2.0 * np_op(a, opa) @ np_op(b, opb) + 0.5 * c
    np.testing.assert_allclose(np.asarray(out), expect, **_tol(dtype))


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
def test_gemm_batched(dtype):
    rng = np.random.default_rng(1)
    a = rand(rng, (4, 3, 6, 5), dtype)
    b = rand(rng, (4, 3, 5, 7), dtype)
    out = np.asarray(tb.gemm(jnp.asarray(a), jnp.asarray(b)))
    for i in range(4):
        for j in range(3):
            np.testing.assert_allclose(out[i, j], a[i, j] @ b[i, j], **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("side,uplo", [("L", "L"), ("L", "U"), ("R", "L")])
def test_hemm(dtype, side, uplo):
    rng = np.random.default_rng(2)
    n, m = 6, 6
    a = rand(rng, (n, n), dtype)
    b = rand(rng, (n, m), dtype)
    c = rand(rng, (n, m), dtype)
    # reference semantics: only the uplo triangle of a is read
    afull = np.tril(a, -1) + np.tril(a, -1).conj().T + np.diag(np.real(np.diag(a))) \
        if uplo == "L" else np.triu(a, 1) + np.triu(a, 1).conj().T + np.diag(np.real(np.diag(a)))
    expect = 1.5 * (afull @ b if side == "L" else b @ afull) + 0.5 * c
    out = tb.hemm(side, uplo, jnp.asarray(a), jnp.asarray(b), jnp.asarray(c),
                  alpha=1.5, beta=0.5)
    np.testing.assert_allclose(np.asarray(out), expect, **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("uplo,op", [("L", "N"), ("U", "N"), ("L", "C")])
def test_herk(dtype, uplo, op):
    rng = np.random.default_rng(3)
    n, k = 6, 4
    a = rand(rng, (n, k) if op == "N" else (k, n), dtype)
    c = rand(rng, (n, n), dtype)
    if np.dtype(dtype).kind == "c":
        # zherk assumes the imaginary part of C's diagonal is zero
        np.fill_diagonal(c, np.real(np.diag(c)))
    out = np.asarray(tb.herk(uplo, op, jnp.asarray(a), jnp.asarray(c),
                             alpha=0.5, beta=2.0))
    oa = a if op == "N" else a.conj().T
    expect_full = 0.5 * (oa @ oa.conj().T) + 2.0 * c
    if uplo == "L":
        np.testing.assert_allclose(np.tril(out), np.tril(expect_full), **_tol(dtype))
        np.testing.assert_allclose(np.triu(out, 1), np.triu(c, 1), **_tol(dtype))
    else:
        np.testing.assert_allclose(np.triu(out), np.triu(expect_full), **_tol(dtype))
        np.testing.assert_allclose(np.tril(out, -1), np.tril(c, -1), **_tol(dtype))
    if np.dtype(dtype).kind == "c":
        assert np.allclose(np.imag(np.diag(out)), 0)


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_her2k(dtype, uplo):
    rng = np.random.default_rng(4)
    n, k = 5, 3
    a = rand(rng, (n, k), dtype)
    b = rand(rng, (n, k), dtype)
    c = rand(rng, (n, n), dtype)
    alpha = 1.5 - 0.5j if np.dtype(dtype).kind == "c" else 1.5
    out = np.asarray(tb.her2k(uplo, "N", jnp.asarray(a), jnp.asarray(b),
                              jnp.asarray(c), alpha=alpha, beta=0.5))
    expect = alpha * a @ b.conj().T + np.conj(alpha) * b @ a.conj().T + 0.5 * c
    if uplo == "L":
        np.testing.assert_allclose(np.tril(out), np.tril(expect), **_tol(dtype))
    else:
        np.testing.assert_allclose(np.triu(out), np.triu(expect), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("side,uplo,op,diag",
                         [("L", "L", "N", "N"), ("L", "U", "T", "N"),
                          ("R", "L", "C", "N"), ("L", "L", "N", "U")])
def test_trmm(dtype, side, uplo, op, diag):
    rng = np.random.default_rng(5)
    n, m = 6, 4
    adim = n if side == "L" else m
    a = rand(rng, (adim, adim), dtype)
    b = rand(rng, (n, m), dtype)
    t = np.tril(a) if uplo == "L" else np.triu(a)
    if diag == "U":
        np.fill_diagonal(t, 1.0)
    expect = 2.0 * (np_op(t, op) @ b if side == "L" else b @ np_op(t, op))
    out = tb.trmm(side, uplo, op, diag, jnp.asarray(a), jnp.asarray(b), alpha=2.0)
    np.testing.assert_allclose(np.asarray(out), expect, **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("side,uplo,op,diag",
                         [("L", "L", "N", "N"), ("L", "L", "C", "N"),
                          ("L", "U", "T", "N"), ("R", "L", "C", "N"),
                          ("R", "U", "N", "U")])
def test_trsm(dtype, side, uplo, op, diag):
    rng = np.random.default_rng(6)
    n, m = 6, 4
    adim = n if side == "L" else m
    a = rand(rng, (adim, adim), dtype)
    a = a + adim * np.eye(adim, dtype=dtype)  # well-conditioned
    b = rand(rng, (n, m), dtype)
    out = np.asarray(tb.trsm(side, uplo, op, diag, jnp.asarray(a), jnp.asarray(b),
                             alpha=2.0))
    t = np.tril(a) if uplo == "L" else np.triu(a)
    if diag == "U":
        np.fill_diagonal(t, 1.0)
    ot = np_op(t, op)
    residual = (ot @ out if side == "L" else out @ ot) - 2.0 * b
    np.testing.assert_allclose(residual, np.zeros_like(b), **_tol(dtype))


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
@pytest.mark.parametrize("side", ["L", "R"])
@pytest.mark.parametrize("uplo", ["L", "U"])
@pytest.mark.parametrize("op", ["N", "T", "C"])
def test_trsm_recursive_matches_native(monkeypatch, dtype, side, uplo, op):
    """The recursive blocked solve (large-n memory/MXU path) must agree with
    the native lowering on every side/uplo/op combo."""
    monkeypatch.setattr(tb, "TRSM_RECURSE_MIN", 48)
    rng = np.random.default_rng(11)
    n, m = 160, 96  # non-power-of-two, crosses several recursion levels
    adim = n if side == "L" else m
    a = rand(rng, (adim, adim), dtype)
    a = a + adim * np.eye(adim, dtype=dtype)
    b = rand(rng, (n, m), dtype)
    out = np.asarray(tb.trsm(side, uplo, op, "N", jnp.asarray(a),
                             jnp.asarray(b), alpha=0.5))
    t = np.tril(a) if uplo == "L" else np.triu(a)
    ot = np_op(t, op)
    residual = (ot @ out if side == "L" else out @ ot) - 0.5 * b
    np.testing.assert_allclose(residual, np.zeros_like(b), **_tol(dtype))


# -- lapack tile ops --------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("uplo", ["L", "U", "G"])
def test_laset_lacpy(dtype, uplo):
    rng = np.random.default_rng(7)
    a = np.asarray(tl.laset(uplo, 2.0, 5.0, (4, 6), dtype))
    full = np.full((4, 6), 2.0) + 3.0 * np.eye(4, 6)
    expect = {"G": full, "L": np.tril(full), "U": np.triu(full)}[uplo]
    np.testing.assert_allclose(a, expect.astype(dtype))

    src = rand(rng, (5, 5), dtype)
    dst = rand(rng, (5, 5), dtype)
    out = np.asarray(tl.lacpy(uplo, jnp.asarray(src), jnp.asarray(dst)))
    if uplo == "G":
        np.testing.assert_allclose(out, src)
    elif uplo == "L":
        np.testing.assert_allclose(np.tril(out), np.tril(src))
        np.testing.assert_allclose(np.triu(out, 1), np.triu(dst, 1))
    else:
        np.testing.assert_allclose(np.triu(out), np.triu(src))
        np.testing.assert_allclose(np.tril(out, -1), np.tril(dst, -1))


@pytest.mark.parametrize("norm", ["M", "1", "I", "F"])
def test_lange(norm):
    rng = np.random.default_rng(8)
    a = rng.standard_normal((5, 7))
    expect = {"M": np.max(np.abs(a)), "1": np.max(np.abs(a).sum(0)),
              "I": np.max(np.abs(a).sum(1)), "F": np.linalg.norm(a)}[norm]
    np.testing.assert_allclose(float(tl.lange(norm, jnp.asarray(a))), expect, rtol=1e-14)


def test_lantr():
    rng = np.random.default_rng(9)
    a = rng.standard_normal((5, 5))
    t = np.tril(a)
    np.testing.assert_allclose(float(tl.lantr("M", "L", "N", jnp.asarray(a))),
                               np.max(np.abs(t)), rtol=1e-14)
    tu = np.tril(a, -1) + np.eye(5)
    np.testing.assert_allclose(float(tl.lantr("F", "L", "U", jnp.asarray(a))),
                               np.linalg.norm(tu), rtol=1e-14)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_potrf(dtype, uplo):
    rng = np.random.default_rng(10)
    n = 6
    x = rand(rng, (n, n), dtype)
    spd = x @ x.conj().T + n * np.eye(n, dtype=dtype)
    out = np.asarray(tl.potrf(uplo, jnp.asarray(spd)))
    if uplo == "L":
        f = np.tril(out)
        np.testing.assert_allclose(f @ f.conj().T, spd, **_tol(dtype))
        np.testing.assert_allclose(np.triu(out, 1), np.triu(spd, 1), **_tol(dtype))
    else:
        f = np.triu(out)
        np.testing.assert_allclose(f.conj().T @ f, spd, **_tol(dtype))
        np.testing.assert_allclose(np.tril(out, -1), np.tril(spd, -1), **_tol(dtype))


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_hegst(dtype, uplo):
    rng = np.random.default_rng(11)
    n = 6
    x = rand(rng, (n, n), dtype)
    a = x @ x.conj().T + n * np.eye(n, dtype=dtype)  # Hermitian PD
    y = rand(rng, (n, n), dtype)
    bfull = y @ y.conj().T + n * np.eye(n, dtype=dtype)
    bf = np.linalg.cholesky(bfull) if uplo == "L" else np.linalg.cholesky(bfull).conj().T
    out = np.asarray(tl.hegst(1, uplo, jnp.asarray(a), jnp.asarray(bf)))
    if uplo == "L":
        expect = np.linalg.solve(bf, a) @ np.linalg.inv(bf).conj().T
        np.testing.assert_allclose(np.tril(out), np.tril(expect), **_tol(dtype))
    else:
        expect = np.linalg.solve(bf.conj().T, a) @ np.linalg.inv(bf)
        np.testing.assert_allclose(np.triu(out), np.triu(expect), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
def test_larft_matches_reflector_product(dtype):
    rng = np.random.default_rng(12)
    m, k = 8, 4
    v = rand(rng, (m, k), dtype)
    v = np.tril(v, -1) + np.eye(m, k, dtype=dtype)
    # proper Householder taus: tau = 2 / (v^H v) makes each I - tau v v^H unitary
    taus = np.array([2.0 / np.real(np.vdot(v[:, i], v[:, i])) for i in range(k)],
                    dtype=dtype)
    t = np.asarray(tl.larft(jnp.asarray(v), jnp.asarray(taus)))
    q_block = np.eye(m, dtype=dtype) - v @ t @ v.conj().T
    q_prod = np.eye(m, dtype=dtype)
    for i in range(k):
        q_prod = q_prod @ (np.eye(m, dtype=dtype)
                           - taus[i] * np.outer(v[:, i], v[:, i].conj()))
    np.testing.assert_allclose(q_block, q_prod, **_tol(dtype))
    assert np.allclose(np.tril(t, -1), 0)


def test_larft_zero_tau():
    rng = np.random.default_rng(13)
    v = np.tril(rng.standard_normal((6, 3)), -1) + np.eye(6, 3)
    taus = np.array([0.5, 0.0, 0.25])
    t = np.asarray(tl.larft(jnp.asarray(v), jnp.asarray(taus)))
    assert np.allclose(t[1, :], 0) and np.allclose(t[:, 1], 0)
    assert np.isfinite(t).all()


def test_larft_zero_tau_stale_column_wy_identity():
    """Interior tau==0 with a NONZERO stored sub-diagonal in that column:
    LAPACK dlarft treats the column as a null reflector; the closed form
    must not route cross terms through it (round-1 advisor finding). The
    check is the full WY identity against the explicit reflector product."""
    rng = np.random.default_rng(113)
    m, k = 8, 4
    v = np.tril(rng.standard_normal((m, k)), -1) + np.eye(m, k)
    taus = np.array([2.0 / np.dot(v[:, i], v[:, i]) for i in range(k)])
    taus[1] = 0.0  # interior null reflector, stale column data left in v
    t = np.asarray(tl.larft(jnp.asarray(v), jnp.asarray(taus)))
    q_block = np.eye(m) - v @ t @ v.T
    q_prod = np.eye(m)
    for i in range(k):
        q_prod = q_prod @ (np.eye(m) - taus[i] * np.outer(v[:, i], v[:, i]))
    np.testing.assert_allclose(q_block, q_prod, rtol=1e-12, atol=1e-12)


def _larft_case(rng, m, k, dtype, nulls):
    """Reflectors as a QR panel leaves them (unit diagonal, stale data in
    a null reflector's column) with proper taus, ``2 / (v^H v)``; ``nulls``
    zeroes none, every third from the second (interior), or the back half
    (the chase's trailing block of null reflectors)."""
    v = rand(rng, (m, k), dtype)
    v = np.tril(v, -1) + np.eye(m, k, dtype=dtype)
    taus = np.array([2.0 / np.real(np.vdot(v[:, i], v[:, i]))
                     for i in range(k)], dtype=dtype)
    if nulls == "interior":
        taus[1::3] = 0
    elif nulls == "trailing":
        taus[k // 2:] = 0
    return v, taus


def _larft_by_substitution(v, taus):
    """T by scipy's triangular solve of the same ``T^-1 = diag(1/tau) +
    strict_upper(V^H V)``, null reflectors' columns ignored, their rows and
    columns of T zero."""
    import scipy.linalg as sla

    m, k = v.shape
    vv = np.where(taus == 0, 0, np.tril(v, -1)) + np.eye(m, k)
    tinv = np.triu(vv.conj().T @ vv, 1) \
        + np.diag(1.0 / np.where(taus == 0, 1, taus))
    t = sla.solve_triangular(tinv, np.eye(k), lower=False)
    nz = taus != 0
    return np.where(nz[:, None] & nz[None, :], t, 0)


@pytest.mark.parametrize("nulls", ["none", "interior", "trailing"])
@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
@pytest.mark.parametrize("k", [1, 2, 3, 5, 64, 127, 128])
def test_larft_doubling_inverse(k, dtype, nulls):
    """The masked doubling inverse (ISSUE 40) at every depth of its
    recursion, ragged last blocks included: the WY identity against the
    explicit reflector product, and T against a substitution of the same
    ``T^-1`` to 1e-13 relative. k = 128 at the chase's (255, 128)."""
    rng = np.random.default_rng(40 * k + len(nulls))
    m = 255 if k == 128 else k + 9
    v, taus = _larft_case(rng, m, k, dtype, nulls)
    t = np.asarray(tl.larft(jnp.asarray(v), jnp.asarray(taus)))
    want = _larft_by_substitution(v, taus)
    assert np.abs(t - want).max() <= 1e-13 * np.abs(want).max()
    assert np.all(np.tril(t, -1) == 0)
    q_prod = np.eye(m, dtype=dtype)
    for i in range(k):
        q_prod = q_prod - taus[i] * np.outer(q_prod @ v[:, i], v[:, i].conj())
    vv = np.where(taus == 0, 0, np.tril(v, -1)) + np.eye(m, k)
    np.testing.assert_allclose(np.eye(m) - vv @ t @ vv.conj().T, q_prod,
                               rtol=1e-12, atol=1e-12)


def test_larft_batched_matches_per_item():
    """A leading batch dimension: the products broadcast, so each item's T
    is the one a call of its own returns."""
    rng = np.random.default_rng(401)
    cases = [_larft_case(rng, 40, 24, np.float64, nulls)
             for nulls in ("none", "interior", "trailing")]
    v = np.stack([c[0] for c in cases])
    taus = np.stack([c[1] for c in cases])
    t = np.asarray(tl.larft(jnp.asarray(v), jnp.asarray(taus)))
    for j, (vj, tj) in enumerate(cases):
        one = np.asarray(tl.larft(jnp.asarray(vj), jnp.asarray(tj)))
        np.testing.assert_allclose(t[j], one, rtol=1e-14, atol=1e-14)
        want = _larft_by_substitution(vj, tj)
        assert np.abs(t[j] - want).max() <= 1e-13 * np.abs(want).max()


def test_stedc_vs_scipy():
    rng = np.random.default_rng(14)
    n = 12
    d = rng.standard_normal(n)
    e = rng.standard_normal(n - 1)
    w, v = tl.stedc(d, e)
    tri = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    np.testing.assert_allclose(v @ np.diag(w) @ v.T, tri, atol=1e-12)
    assert np.all(np.diff(w) >= 0)


def test_axpy_gemv_trmv():
    rng = np.random.default_rng(15)
    a = rng.standard_normal((4, 4))
    x = rng.standard_normal(4)
    y = rng.standard_normal(4)
    np.testing.assert_allclose(np.asarray(tb.axpy(x, y, alpha=2.5)),
                               y + 2.5 * x, atol=1e-14)
    np.testing.assert_allclose(np.asarray(tb.gemv(a, x, y, alpha=2.0, beta=-1.0)),
                               2.0 * a @ x - y, atol=1e-13)
    np.testing.assert_allclose(np.asarray(tb.gemv(a, x, op_a="T", alpha=1.0)),
                               a.T @ x, atol=1e-13)
    t = np.tril(a)
    np.testing.assert_allclose(np.asarray(tb.trmv("L", "N", "N", a, x)),
                               t @ x, atol=1e-13)
    tu = np.tril(a, -1) + np.eye(4)
    np.testing.assert_allclose(np.asarray(tb.trmv("L", "C", "U", a, x)),
                               tu.T @ x, atol=1e-13)


def test_potrf_info():
    rng = np.random.default_rng(16)
    x = rng.standard_normal((5, 5))
    spd = x @ x.T + 5 * np.eye(5)
    f, info = tl.potrf_info("L", jnp.asarray(spd))
    assert int(info) == 0
    np.testing.assert_allclose(np.tril(np.asarray(f)) @ np.tril(np.asarray(f)).T,
                               spd, atol=1e-10)
    # indefinite input: info = 1-based first failing column, factor has NaNs
    bad = np.diag([1.0, -1.0, 1.0, 1.0, 1.0])
    f2, info2 = tl.potrf_info("L", jnp.asarray(bad))
    assert int(info2) >= 1


def test_laed4_secular_roots():
    rng = np.random.default_rng(17)
    k = 8
    d = np.sort(rng.standard_normal(k))
    z = rng.standard_normal(k)
    z /= np.linalg.norm(z)
    rho = 0.7
    lam = tl.laed4(d, z, rho)
    # roots of the rank-one-updated matrix == eigvals of D + rho z z^T
    w = np.linalg.eigvalsh(np.diag(d) + rho * np.outer(z, z))
    np.testing.assert_allclose(np.sort(lam), w, atol=1e-10)
