"""tile_ops.qr_panel: the TPU-trustworthy panel Householder QR.

Strategy mirrors the reference's tile-op tests (``test/unit/lapack/
test_lapack_tile.cpp``): factor random panels, rebuild Q explicitly from
the stored reflectors, and check backward error + orthogonality against
the dtype's own grade; plus agreement with the LAPACK-backed ``geqrf``
primitive (this suite runs on CPU where geqrf IS LAPACK), LAPACK edge
semantics (zero-tail columns -> tau = 0), and the config wire-in
(``qr_panel`` knob routing both forms through the same call sites).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dlaf_tpu.tile_ops.qr_panel import (householder_qr, panel_qr,
                                         rebuild_q)


@pytest.mark.parametrize("shape", [(64, 16), (33, 16), (16, 16), (257, 32)])
@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
def test_householder_qr_backward_error(shape, dtype):
    rng = np.random.default_rng(sum(shape))
    a = rng.standard_normal(shape)
    if np.issubdtype(dtype, np.complexfloating):
        a = a + 1j * rng.standard_normal(shape)
    a = a.astype(dtype)
    vfull, taus = householder_qr(jnp.asarray(a))
    r = np.triu(np.asarray(vfull)[: shape[1]])
    q = rebuild_q(vfull, taus)
    m, k = shape
    assert np.linalg.norm(a - q @ r) / np.linalg.norm(a) < 50 * k * 2.3e-16
    assert np.linalg.norm(np.conj(q.T) @ q - np.eye(k)) < 50 * k * 2.3e-16
    # R's diagonal is real for complex inputs (LAPACK larfg convention)
    if np.issubdtype(dtype, np.complexfloating):
        assert np.abs(np.imag(np.diagonal(r))).max() < 1e-13


@pytest.mark.parametrize("shape,dtype", [((64, 16), np.float64),
                                         ((48, 12), np.complex128),
                                         ((16, 16), np.float64)])
def test_matches_lapack_geqrf(shape, dtype):
    """Same algorithm, same sign convention as LAPACK: V and taus agree to
    roundoff (this suite's geqrf is LAPACK — conftest pins CPU)."""
    from jax._src.lax.linalg import geqrf

    rng = np.random.default_rng(7)
    a = rng.standard_normal(shape)
    if np.issubdtype(dtype, np.complexfloating):
        a = a + 1j * rng.standard_normal(shape)
    a = jnp.asarray(a.astype(dtype))
    v1, t1 = householder_qr(a)
    v2, t2 = geqrf(a)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=0, atol=1e-12)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2),
                               rtol=0, atol=1e-13)


def test_zero_tail_column_gives_zero_tau():
    """A column with zero tail is already reduced: tau = 0, diagonal kept
    (LAPACK dlarfg semantics — red2band relies on this for its padded
    scan rows)."""
    a = np.eye(8, 4)
    a[0, 0] = 3.0
    vfull, taus = householder_qr(jnp.asarray(a))
    # column 0 tail is zero -> tau_0 = 0 and alpha kept with its sign
    assert np.asarray(taus)[0] == 0.0
    assert np.asarray(vfull)[0, 0] == 3.0
    # remaining identity columns likewise reduce with tau = 0
    assert np.all(np.asarray(taus) == 0.0)
    np.testing.assert_array_equal(np.asarray(vfull), a)


def test_all_zero_panel():
    vfull, taus = householder_qr(jnp.zeros((12, 4), jnp.float64))
    assert np.all(np.asarray(taus) == 0.0)
    assert np.all(np.asarray(vfull) == 0.0)


def test_batched_via_vectorize():
    rng = np.random.default_rng(3)
    a = rng.standard_normal((3, 32, 8))
    vb, tb = householder_qr(jnp.asarray(a))
    assert vb.shape == (3, 32, 8) and tb.shape == (3, 8)
    v0, t0 = householder_qr(jnp.asarray(a[1]))
    np.testing.assert_array_equal(np.asarray(vb)[1], np.asarray(v0))
    np.testing.assert_array_equal(np.asarray(tb)[1], np.asarray(t0))


def test_wide_panel_matches_lapack():
    """m < k (the ragged final panel of a reduction): min(m, k) reflectors
    and taus, exactly geqrf's convention."""
    from jax._src.lax.linalg import geqrf

    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.standard_normal((8, 16)))
    v1, t1 = householder_qr(a)
    v2, t2 = geqrf(a)
    assert t1.shape == t2.shape == (8,)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=0, atol=1e-13)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2),
                               rtol=0, atol=1e-13)


def test_panel_qr_routes_by_config(monkeypatch):
    """The knob actually selects the implementation: each route's output
    is bit-identical to calling that implementation directly (the
    householder sweep is deterministic, so exact equality proves the
    dispatch — a knob lookup regression cannot hide behind roundoff-level
    agreement of the two algorithms)."""
    from jax._src.lax.linalg import geqrf

    from dlaf_tpu import config

    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.standard_normal((40, 8)))
    direct = {"geqrf": geqrf(a), "householder": householder_qr(a)}
    try:
        for route in ("geqrf", "householder"):
            monkeypatch.setenv("DLAF_QR_PANEL", route)
            config.initialize()
            v, t = panel_qr(a)
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(direct[route][0]))
            np.testing.assert_array_equal(np.asarray(t),
                                          np.asarray(direct[route][1]))
    finally:
        monkeypatch.delenv("DLAF_QR_PANEL")
        config.initialize()


def test_red2band_residual_parity_under_householder(monkeypatch):
    """End-to-end wire-in: reduction_to_band under qr_panel=householder
    matches the geqrf route's band eigenvalues to f64 grade (the exact
    check the session-4d miniapp arms run on silicon)."""
    from dlaf_tpu import config
    from dlaf_tpu.common.index2d import GlobalElementSize, TileElementSize
    from dlaf_tpu.eigensolver.reduction_to_band import reduction_to_band
    from dlaf_tpu.matrix.matrix import Matrix
    from test_reduction_to_band import band_dense

    n, nb, band = 96, 32, 16

    def fn(i, j):
        return np.cos(0.001 * (i * 31 + j * 17)) \
            + np.cos(0.001 * (j * 31 + i * 17))

    ref = Matrix.from_element_fn(fn, GlobalElementSize(n, n),
                                 TileElementSize(nb, nb), dtype=np.float64)
    a = ref.to_numpy()
    w_ref = np.linalg.eigvalsh(a)
    try:
        for route in ("householder", "geqrf"):
            monkeypatch.setenv("DLAF_QR_PANEL", route)
            config.initialize()
            red = reduction_to_band(ref, band_size=band)
            w = np.linalg.eigvalsh(band_dense(red, n))
            resid = np.abs(w - w_ref).max() / np.abs(w_ref).max()
            assert resid < 100 * n * 2.3e-16, (route, resid)
    finally:
        monkeypatch.delenv("DLAF_QR_PANEL")
        config.initialize()


_DTYPES = [np.float32, np.float64, np.complex64, np.complex128]


def _panel(kind, dtype, rng):
    """Panels that stress the column step's ``v^H a`` sum: ``parallel``,
    trailing columns within 1e-6 of multiples of column 0, so the reflector
    cancels them almost to nothing; ``orthogonal``, trailing columns
    orthogonal to column 0's reflector, so every ``v^H a_c`` is a sum of
    O(1) terms that cancels to roundoff; ``tall``, a (4096, 128) panel (the
    sum's length on the chip-sized reductions)."""
    cplx = np.issubdtype(dtype, np.complexfloating)

    def normal(*shape):
        x = rng.standard_normal(shape)
        return x + 1j * rng.standard_normal(shape) if cplx else x

    if kind == "tall":
        return normal(4096, 128).astype(dtype)
    m, k = 512, 32
    x = normal(m)
    if kind == "parallel":
        a = np.outer(x, normal(k)) + 1e-6 * normal(m, k)
        a[:, 0] = x
        return a.astype(dtype)
    # the reflector of column 0 (LAPACK's larfg), in float64
    beta = -np.sign(x[0].real if x[0].real != 0 else 1.0) * np.linalg.norm(x)
    v = x / (x[0] - beta)
    v[0] = 1.0
    a = normal(m, k)
    a -= np.outer(v, np.conj(v) @ a) / np.vdot(v, v)
    a[:, 0] = x
    return a.astype(dtype)


@pytest.mark.parametrize("dtype", _DTYPES)
def test_column_loop_holds_no_dot_general(dtype):
    """``v^H a`` is a multiply and a sum over the rows: XLA expands an
    emulated-f64 ``dot_general`` with one output row into five loops a
    column on the TPU (2.4 s of a 4.6 s reduction at N=8192, PERF.md, PR
    34), so the loop body must stay free of it at every dtype."""
    import jax

    from dlaf_tpu.analysis import depgraph

    x = jnp.zeros((64, 16), dtype)
    eqns = list(depgraph.iter_eqns(jax.make_jaxpr(householder_qr)(x)))
    # ONE fori_loop (a scan in the jaxpr: static trip count), not unrolled
    # or split: the benchmark finds the sweep by its trip count
    loops = [e for _, e in eqns if e.primitive.name in ("scan", "while")]
    assert [e.params.get("length") for e in loops] == [16]
    body = {e.primitive.name for path, e in eqns if path}
    assert "reduce_sum" in body and "dot_general" not in body
    text = jax.jit(householder_qr).lower(x).as_text()
    assert text.count("stablehlo.while") == 1 and "dot_general" not in text


@pytest.mark.parametrize("dtype", _DTYPES)
@pytest.mark.parametrize("kind", ["parallel", "orthogonal", "tall"])
def test_sweep_under_cancellation_and_height(kind, dtype):
    """Backward error and orthogonality, computed by numpy in float64 /
    complex128 from the stored reflectors, at the dtype's own grade."""
    rng = np.random.default_rng(34)
    a = _panel(kind, dtype, rng)
    k = a.shape[1]
    wide = np.complex128 if np.iscomplexobj(a) else np.float64
    eps = np.finfo(dtype).eps
    vfull, taus = householder_qr(jnp.asarray(a))
    assert vfull.dtype == dtype and taus.dtype == dtype
    vfull = np.asarray(vfull).astype(wide)
    q = rebuild_q(vfull, np.asarray(taus).astype(wide))
    r = np.triu(vfull[:k])
    a = a.astype(wide)
    assert np.linalg.norm(a - q @ r) / np.linalg.norm(a) < 50 * k * eps
    assert np.linalg.norm(np.conj(q.T) @ q - np.eye(k)) < 50 * k * eps
    if kind == "parallel":
        # the cancelled columns are left at the perturbation's size
        assert np.abs(r[1:, 1:]).max() < 1e-3 * np.abs(r[0]).max()
