"""Tests for the emulated-f64 MXU gemm (tile_ops.ozaki) and the
mixed-precision panel helpers (tile_ops.mixed), plus the cholesky_trailing
="ozaki" fast path end to end.

Verification style follows the reference's analytic approach
(``test/unit/test_blas_tile``): known inputs, error budgets scaled to the
operand magnitudes.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dlaf_tpu.tile_ops.ozaki import matmul_f64, syrk_f64
from dlaf_tpu.tile_ops.mixed import potrf_refined, tri_inv_refined

EPS = np.finfo(np.float64).eps


def _scaled_err(got, ref, a, b):
    scale = (np.abs(a).max(axis=-1)[..., :, None]
             * np.abs(b).max(axis=-2)[..., None, :] * a.shape[-1])
    return (np.abs(got - ref) / np.maximum(scale, 1e-300)).max()


class TestOzakiMatmul:
    def test_accuracy_f64_grade(self):
        rng = np.random.default_rng(7)
        a = rng.standard_normal((96, 200))
        b = rng.standard_normal((200, 64))
        got = np.asarray(matmul_f64(a, b))
        assert _scaled_err(got, a @ b, a, b) < 4 * EPS

    @pytest.mark.parametrize("m,k,n", [(32, 64, 16), (8, 16, 8), (1, 4, 1),
                                       (100, 7, 33)])
    def test_pathological_row_col_scales(self, m, k, n):
        # full f64 exponent range is a CPU-path guarantee; on TPU the X64
        # emulation (f32 pairs) caps all f64 magnitudes at ~1e38 (see
        # module docstring) — tests run on CPU
        rng = np.random.default_rng(8)
        a = rng.standard_normal((m, k))
        b = rng.standard_normal((k, n))
        a[0] *= 2.0**180
        a[-1] *= 2.0**-170
        b[:, 0] *= 2.0**120
        got = np.asarray(matmul_f64(a, b))
        assert _scaled_err(got, a @ b, a, b) < 4 * EPS

    def test_near_dbl_max_rows_stay_finite(self):
        # scale handling must not overflow on its own: finite inputs with
        # near-DBL_MAX magnitudes give finite, correct results as long as
        # the true product is representable
        a = np.full((4, 4), 1e308)
        got = np.asarray(matmul_f64(a, np.eye(4)))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, a, rtol=1e-15)

    def test_zero_rows_and_batch(self):
        rng = np.random.default_rng(9)
        a = rng.standard_normal((2, 3, 24, 40))
        b = rng.standard_normal((2, 3, 40, 8))
        a[..., 0, :] = 0.0
        got = np.asarray(matmul_f64(a, b))
        assert np.isfinite(got).all()
        assert _scaled_err(got, a @ b, a, b) < 4 * EPS

    def test_fewer_slices_tracks_bound(self):
        rng = np.random.default_rng(10)
        a = rng.standard_normal((48, 48))
        b = rng.standard_normal((48, 48))
        err6 = np.abs(np.asarray(matmul_f64(a, b, slices=6)) - a @ b).max()
        err8 = np.abs(np.asarray(matmul_f64(a, b, slices=8)) - a @ b).max()
        assert err8 < err6          # more slices -> strictly more mantissa
        assert err6 < 48 * 2.0**-40  # ~2^-42 relative to ~unit row scales

    def test_deep_contraction_chunks_exactly(self):
        # k * 2^12 == 2^31 at k = 2^19: a single int32 dot accumulation
        # would wrap (round-1 advisor finding — reachable via blas.contract
        # flattening several contracted dims); the chunked _dot_i8 path
        # must stay exact
        k = 1 << 19
        a = np.ones((1, k))
        b = np.ones((k, 1))
        got = np.asarray(matmul_f64(a, b))
        np.testing.assert_allclose(got, [[float(k)]], rtol=1e-15)

    def test_syrk_matches_matmul(self):
        rng = np.random.default_rng(11)
        a = rng.standard_normal((56, 72))
        got = np.asarray(syrk_f64(a))
        assert _scaled_err(got, a @ a.T, a, np.swapaxes(a, -1, -2)) < 4 * EPS
        assert np.allclose(got, got.T)  # symmetry by construction


# ---------------------------------------------------------------------------
# the plain reference: numpy integers, one f64 fold in the product's order
# ---------------------------------------------------------------------------

def _peeled(x, axis, s):
    """Row (``axis=-1``) or column (``axis=-2``) scales of ``x`` and its
    ``s`` slices as int64 numpy arrays (the library's own peel: what is
    under test is how the slices are multiplied and folded)."""
    from dlaf_tpu.tile_ops import ozaki as oz

    sc = oz._scale(jnp.asarray(x), axis=axis)
    return np.asarray(sc), [np.asarray(t, np.int64) for t in
                            oz._peel_slices(oz._normalize(jnp.asarray(x),
                                                          sc), s)]


def reference_matmul(a, b, s):
    """``a @ b`` as the slice product defines it: the shift-group sums
    ``G_d = sum_t I_t J_{d-t}`` as exact int64 products, folded in float64
    in the order ``d = 0..s-1`` at ``2^-7(d+2)`` (exact: a power of two),
    then scaled back. The fold is the only rounding, so every form of the
    product must give these bits."""
    from dlaf_tpu.tile_ops import ozaki as oz

    sa, ia = _peeled(a, -1, s)
    sb, ib = _peeled(b, -2, s)
    acc = None
    for d in range(s):
        g = sum(ia[t] @ ib[d - t] for t in range(d + 1))
        term = g.astype(np.float64) * oz._group_scale(d)
        acc = term if acc is None else acc + term
    return oz._apply_scales(acc, sa, sb)


def reference_syrk(a, s):
    """``a @ a^T`` in the syrk's own algebra (``ozaki._mirror``): the
    integers ``2 g_d + D_d`` (half pairs ``t < d - t``, the diagonal pair
    on even shifts) folded at half the group scale, the f64 accumulator
    mirrored once, then scaled."""
    from dlaf_tpu.tile_ops import ozaki as oz

    sa, ia = _peeled(a, -1, s)
    acc = None
    for d in range(s):
        p = sum(2 * (ia[t] @ np.swapaxes(ia[d - t], -1, -2))
                for t in range(d // 2 + 1) if t != d - t)
        if d % 2 == 0:
            p = p + ia[d // 2] @ np.swapaxes(ia[d // 2], -1, -2)
        term = p.astype(np.float64) * oz._group_scale(d, half=True)
        acc = term if acc is None else acc + term
    acc = acc + np.swapaxes(acc, -1, -2)
    return oz._apply_scales(acc, sa, np.swapaxes(sa, -1, -2))


def _complex(re, im):
    out = np.empty(re.shape, np.complex128)
    out.real, out.imag = re, im
    return out


def reference_matmul_c128(a, b, s):
    """:func:`ozaki.matmul_c128`'s four real products, each the reference."""
    ar, ai, br, bi = a.real, a.imag, b.real, b.imag
    return _complex(reference_matmul(ar, br, s) - reference_matmul(ai, bi, s),
                    reference_matmul(ar, bi, s) + reference_matmul(ai, br, s))


def reference_herk_c128(a, s):
    """:func:`ozaki.herk_c128`: two real syrks and one real product."""
    ar, ai = a.real, a.imag
    m = reference_matmul(ai, np.swapaxes(ar, -1, -2), s)
    return _complex(reference_syrk(ar, s) + reference_syrk(ai, s),
                    m - np.swapaxes(m, -1, -2))


def _under_dot(monkeypatch, dot, fn, *args):
    """``fn(*args)`` as a host array with ``ozaki_dot`` set to ``dot``."""
    from dlaf_tpu import config

    monkeypatch.setenv("DLAF_OZAKI_DOT", dot)
    config.initialize()
    try:
        return np.asarray(fn(*args))
    finally:
        monkeypatch.delenv("DLAF_OZAKI_DOT")
        config.initialize()


def _assert_reference_bits(monkeypatch, dot, s, a, b=None):
    """The product (``b`` given) or the syrk of ``a`` at ``s`` slices on
    the ``dot`` route equals the plain reference bit for bit."""
    if b is None:
        got = _under_dot(monkeypatch, dot, lambda x: syrk_f64(x, slices=s),
                         jnp.asarray(a))
        want = reference_syrk(a, s)
    else:
        got = _under_dot(monkeypatch, dot,
                         lambda x, y: matmul_f64(x, y, slices=s),
                         jnp.asarray(a), jnp.asarray(b))
        want = reference_matmul(a, b, s)
    assert got.shape == want.shape
    assert np.array_equal(got, want)


def _rows_over_decades(rng, shape, lo=-6, hi=6):
    return rng.standard_normal(shape) \
        * 10.0 ** rng.integers(lo, hi, shape[:-1] + (1,))


#: ``(m, k, n)`` of each form of the slice product at K = 40
#: (``ozaki._sequenced_form``), and the syrk's ``(m, k)``
REFERENCE_FORMS = {
    "ragged": (48, 40, 56),         # bulk: both output sides wider than k
    "groups": (56, 40, 40),         # panel: one side k wide
    "slices_a_wide": (56, 40, 24),  # deep, A the wide operand
    "slices_b_wide": (24, 40, 56),  # deep, B the wide operand
    "syrk": (48, 40, None),
}


class TestPlainReference:
    """Every form of the slice product against :func:`reference_matmul`
    and the syrk against :func:`reference_syrk`, bitwise, on both dot
    routes, in 2-D and under ``jnp.vectorize`` batching. Cheap (one small
    compile each), so ``quick``: every case stays in the default tier."""

    @pytest.mark.quick
    @pytest.mark.parametrize("batch", [(), (2,)], ids=["2d", "batched"])
    @pytest.mark.parametrize("dot", ["int8", "bf16"])
    @pytest.mark.parametrize("s", [6, 7, 8])
    @pytest.mark.parametrize("form", list(REFERENCE_FORMS))
    def test_form_equals_reference(self, form, s, dot, batch, monkeypatch):
        from dlaf_tpu.tile_ops.ozaki import _sequenced_form

        m, k, n = REFERENCE_FORMS[form]
        rng = np.random.default_rng([s, len(batch)])
        a = _rows_over_decades(rng, batch + (m, k))
        if n is None:
            _assert_reference_bits(monkeypatch, dot, s, a)
            return
        assert _sequenced_form(m, n, k, s) == form.split("_")[0]
        b = np.swapaxes(_rows_over_decades(rng, batch + (n, k)), -1, -2)
        _assert_reference_bits(monkeypatch, dot, s, a, b)

    @pytest.mark.quick
    @pytest.mark.parametrize("dot", ["int8", "bf16"])
    @pytest.mark.parametrize("s", [7, 8])
    @pytest.mark.parametrize("op", ["matmul_ragged", "matmul_groups",
                                    "matmul_slices", "herk"])
    def test_c128_equals_reference(self, op, s, dot, monkeypatch):
        """``matmul_c128`` / ``herk_c128`` against the same reference
        composed the way they compose it."""
        from dlaf_tpu.tile_ops.ozaki import herk_c128, matmul_c128

        form = {"matmul_ragged": "ragged", "matmul_groups": "groups",
                "matmul_slices": "slices_a_wide", "herk": "syrk"}[op]
        m, k, n = REFERENCE_FORMS[form]
        rng = np.random.default_rng([s, 128])

        def cplx(shape):
            return _rows_over_decades(rng, shape, -4, 4) \
                + 1j * _rows_over_decades(rng, shape, -4, 4)

        a = cplx((m, k))
        if n is None:
            got = _under_dot(monkeypatch, dot,
                             lambda x: herk_c128(x, slices=s), jnp.asarray(a))
            want = reference_herk_c128(a, s)
        else:
            b = cplx((k, n))
            got = _under_dot(monkeypatch, dot,
                             lambda x, y: matmul_c128(x, y, slices=s),
                             jnp.asarray(a), jnp.asarray(b))
            want = reference_matmul_c128(a, b, s)
        assert got.dtype == np.complex128
        assert np.array_equal(got, want)


class TestContract:
    """blas.contract: the einsum->slice-product factorization must equal
    jnp.einsum for every pattern the algorithms use, real and complex."""

    PATTERNS = [
        ("rab,cbd->rcad", (3, 4, 5), (2, 5, 6)),    # triangular/bt trailing
        ("rcab,cbd->rad", (3, 2, 4, 5), (2, 5, 6)),  # red2band W partial
        ("rab,rad->bd", (3, 4, 5), (3, 4, 6)),       # red2band M partial
        ("rad,cbd->rcab", (3, 4, 6), (2, 5, 6)),     # red2band her2k-like
        ("tb,tbm->tm", (4, 5), (4, 5, 6)),           # bt sweeps (batched)
        ("rab,rcad->cbd", (3, 4, 5), (3, 2, 4, 6)),  # bt_b2t W2 partial
        ("xb,cbd->cxd", (4, 5), (2, 5, 6)),          # bt_b2t T apply
    ]

    @pytest.mark.parametrize("sub,shx,shy", PATTERNS)
    @pytest.mark.parametrize("cplx", [False, True])
    def test_matches_einsum_on_mxu_path(self, sub, shx, shy, cplx,
                                        monkeypatch):
        monkeypatch.setenv("DLAF_F64_GEMM", "mxu")
        monkeypatch.setenv("DLAF_F64_GEMM_MIN_DIM", "2")
        import dlaf_tpu.config as config
        config.initialize()
        try:
            from dlaf_tpu.tile_ops.blas import contract
            rng = np.random.default_rng(hash(sub) % 2**31)
            x = rng.standard_normal(shx)
            y = rng.standard_normal(shy)
            if cplx:
                x = x + 1j * rng.standard_normal(shx)
                y = y + 1j * rng.standard_normal(shy)
            got = np.asarray(contract(sub, x, y))
            np.testing.assert_allclose(got, np.einsum(sub, x, y),
                                       rtol=1e-12, atol=1e-12)
        finally:
            monkeypatch.delenv("DLAF_F64_GEMM")
            monkeypatch.delenv("DLAF_F64_GEMM_MIN_DIM")
            config.initialize()

    @pytest.mark.parametrize("which", ["x", "y"])
    def test_mixed_real_complex_native_fallback(self, which):
        # native (non-mxu) branch with one real and one complex operand:
        # preferred_element_type must follow result_type, not x.dtype
        # (round-1 advisor finding — f64 preferred type on a complex
        # contraction is invalid/lossy)
        from dlaf_tpu.tile_ops.blas import contract
        rng = np.random.default_rng(99)
        x = rng.standard_normal((4, 5))
        y = rng.standard_normal((5, 6))
        if which == "x":
            x = x + 1j * rng.standard_normal((4, 5))
        else:
            y = y + 1j * rng.standard_normal((5, 6))
        got = np.asarray(contract("ab,bd->ad", jnp.asarray(x), jnp.asarray(y)))
        np.testing.assert_allclose(got, x @ y, rtol=1e-12, atol=1e-12)

    def test_knob_validation_rejects_typo(self):
        import dlaf_tpu.config as config
        with pytest.raises(ValueError, match="f64_gemm"):
            config.initialize(config.Configuration(f64_gemm="MXU"))
        config.initialize()


class TestComplex128:
    def test_matmul_c128(self):
        from dlaf_tpu.tile_ops.ozaki import matmul_c128
        rng = np.random.default_rng(13)
        a = rng.standard_normal((48, 80)) + 1j * rng.standard_normal((48, 80))
        b = rng.standard_normal((80, 32)) + 1j * rng.standard_normal((80, 32))
        got = np.asarray(matmul_c128(a, b))
        err = np.abs(got - a @ b).max()
        scale = np.abs(a).max() * np.abs(b).max() * 80
        assert err / scale < 8 * EPS

    def test_herk_c128(self):
        from dlaf_tpu.tile_ops.ozaki import herk_c128
        rng = np.random.default_rng(14)
        a = rng.standard_normal((40, 64)) + 1j * rng.standard_normal((40, 64))
        got = np.asarray(herk_c128(a))
        ref = a @ a.conj().T
        assert np.abs(got - ref).max() / (np.abs(a).max() ** 2 * 64) < 8 * EPS
        # Hermitian with exactly-real diagonal by construction
        assert np.abs(np.imag(np.diagonal(got))).max() == 0.0

    def test_blas_herk_complex_under_knob(self, monkeypatch):
        monkeypatch.setenv("DLAF_F64_GEMM", "mxu")
        monkeypatch.setenv("DLAF_F64_GEMM_MIN_DIM", "8")
        import dlaf_tpu.config as config
        config.initialize()
        try:
            from dlaf_tpu.tile_ops import blas as tb
            rng = np.random.default_rng(15)
            a = rng.standard_normal((32, 48)) + 1j * rng.standard_normal((32, 48))
            c = rng.standard_normal((32, 32)) + 1j * rng.standard_normal((32, 32))
            got = np.asarray(tb.herk("L", "N", a, c, alpha=-1.0))
            full = -a @ a.conj().T + c
            ref = np.tril(full) + np.triu(c, 1)
            ref = ref - np.diag(1j * np.imag(np.diag(ref)))
            np.testing.assert_allclose(got, ref, rtol=1e-12, atol=1e-11)
        finally:
            monkeypatch.delenv("DLAF_F64_GEMM")
            monkeypatch.delenv("DLAF_F64_GEMM_MIN_DIM")
            config.initialize()


class TestF64GemmKnob:
    """f64_gemm="mxu" reroutes the level-3 tile ops through the int8 path
    framework-wide; config changes must invalidate cached programs."""

    def _with_knob(self, monkeypatch, min_dim="8"):
        monkeypatch.setenv("DLAF_F64_GEMM", "mxu")
        monkeypatch.setenv("DLAF_F64_GEMM_MIN_DIM", min_dim)
        import dlaf_tpu.config as config
        config.initialize()
        return config

    def test_blas_ops_route_and_match(self, monkeypatch):
        config = self._with_knob(monkeypatch)
        try:
            from dlaf_tpu.tile_ops import blas as tb
            rng = np.random.default_rng(5)
            a = rng.standard_normal((64, 48))
            b = rng.standard_normal((48, 32))
            c = rng.standard_normal((64, 32))
            got = np.asarray(tb.gemm(a, b, c, alpha=2.0, beta=1.0))
            np.testing.assert_allclose(got, 2.0 * (a @ b) + c,
                                       rtol=1e-13, atol=1e-12)
            h = rng.standard_normal((64, 64))
            got = np.asarray(tb.herk("L", "N", a, h, alpha=-1.0))
            ref = np.tril(-a @ a.T + h) + np.triu(h, 1)
            np.testing.assert_allclose(got, ref, rtol=1e-13, atol=1e-12)
        finally:
            monkeypatch.delenv("DLAF_F64_GEMM")
            monkeypatch.delenv("DLAF_F64_GEMM_MIN_DIM")
            config.initialize()

    def test_small_dims_stay_native(self, monkeypatch):
        config = self._with_knob(monkeypatch, min_dim="128")
        try:
            from dlaf_tpu.tile_ops.blas import _mxu_f64
            import jax.numpy as jnp2
            a = jnp2.zeros((64, 64), jnp2.float64)
            assert not _mxu_f64(a, a, dims=(64, 64, 64))
            b = jnp2.zeros((256, 256), jnp2.float64)
            assert _mxu_f64(b, b, dims=(256, 256, 256))
            f = jnp2.zeros((256, 256), jnp2.float32)
            assert not _mxu_f64(f, f, dims=(256, 256, 256))
        finally:
            monkeypatch.delenv("DLAF_F64_GEMM")
            monkeypatch.delenv("DLAF_F64_GEMM_MIN_DIM")
            config.initialize()

    @pytest.mark.parametrize("uplo", ["L", "U"])
    @pytest.mark.parametrize("dtype", [np.float64, np.complex128])
    def test_distributed_cholesky_under_knob(self, uplo, dtype, monkeypatch,
                                             devices8):
        """Distributed path: int8-MXU trailing contraction (real AND complex
        compositions) + mixed-precision panels (real, via f64_trsm)."""
        monkeypatch.setenv("DLAF_F64_TRSM", "mixed")
        config = self._with_knob(monkeypatch)
        try:
            from dlaf_tpu.algorithms.cholesky import cholesky
            from dlaf_tpu.comm.grid import Grid
            from dlaf_tpu.common.index2d import (GlobalElementSize,
                                                 TileElementSize)
            from dlaf_tpu.matrix.matrix import Matrix
            from dlaf_tpu.miniapp.generators import hpd_element_fn

            n, nb = 64, 16
            mat = Matrix.from_element_fn(
                hpd_element_fn(n, dtype), GlobalElementSize(n, n),
                TileElementSize(nb, nb), dtype=dtype, grid=Grid(2, 4))
            out = cholesky(uplo, mat)
            f = out.to_numpy()
            a = mat.to_numpy()
            tri = np.tril(f) if uplo == "L" else np.triu(f)
            rec = tri @ tri.conj().T if uplo == "L" else tri.conj().T @ tri
            resid = np.linalg.norm(rec - a) / np.linalg.norm(a)
            assert resid < 60 * n * EPS
        finally:
            monkeypatch.delenv("DLAF_F64_GEMM")
            monkeypatch.delenv("DLAF_F64_GEMM_MIN_DIM")
            monkeypatch.delenv("DLAF_F64_TRSM")
            config.initialize()

    def test_config_change_clears_registered_caches(self):
        import dlaf_tpu.config as config

        calls = []

        class FakeCached:
            def cache_clear(self):
                calls.append("cleared")

        fake = FakeCached()
        config.register_program_cache(fake)
        try:
            config.initialize()
            base = len(calls)
            cfg = config.Configuration(f64_gemm="mxu")
            config.initialize(cfg)      # differs -> must clear
            assert len(calls) == base + 1
            config.initialize(cfg)      # identical -> no clear
            assert len(calls) == base + 1
            config.initialize()         # back to defaults -> clear again
            assert len(calls) == base + 2
        finally:
            config._PROGRAM_CACHES.remove(fake)
            config.initialize()


class TestMixedPanel:
    @staticmethod
    def _spd(n, seed, cond_boost=0.0):
        rng = np.random.default_rng(seed)
        q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        ev = np.linspace(1.0, 10.0 + cond_boost, n)
        return (q * ev) @ q.T

    @pytest.mark.parametrize("uplo", ["L", "U"])
    def test_potrf_refined_f64_grade(self, uplo):
        a = self._spd(96, 3)
        fac = np.asarray(potrf_refined(uplo, jnp.asarray(a)))
        rec = fac @ fac.T if uplo == "L" else fac.T @ fac
        assert np.linalg.norm(rec - a) / np.linalg.norm(a) < 96 * 4 * EPS
        # opposite triangle zeroed
        off = np.triu(fac, 1) if uplo == "L" else np.tril(fac, -1)
        assert np.all(off == 0)

    def test_potrf_refined_cond_guard_falls_back(self):
        # kappa ~ 1e8: one Newton step cannot reach the 60 n eps budget
        # (residual ~ 6e-16 * kappa), so the conditioning guard must route
        # to the native branch and keep the residual at f64 grade
        n = 128
        rng = np.random.default_rng(12)
        q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        ev = np.geomspace(1e-8, 1.0, n)
        a = (q * ev) @ q.T
        a = (a + a.T) / 2
        fac = np.asarray(potrf_refined("L", jnp.asarray(a)))
        resid = np.linalg.norm(fac @ fac.T - a) / np.linalg.norm(a)
        assert resid < 60 * n * EPS

    @pytest.mark.parametrize("uplo", ["L", "U"])
    def test_potrf_refined_complex128(self, uplo):
        rng = np.random.default_rng(17)
        x = rng.standard_normal((80, 80)) + 1j * rng.standard_normal((80, 80))
        a = x @ x.conj().T + 80 * np.eye(80)
        fac = np.asarray(potrf_refined(uplo, jnp.asarray(a)))
        rec = fac @ fac.conj().T if uplo == "L" else fac.conj().T @ fac
        assert np.linalg.norm(rec - a) / np.linalg.norm(a) < 80 * 8 * EPS
        d = np.diagonal(fac)
        assert np.abs(np.imag(d)).max() == 0.0   # factor diagonal stays real

    def test_tri_inv_refined_complex128(self):
        rng = np.random.default_rng(18)
        l = np.tril(rng.standard_normal((64, 64))
                    + 1j * rng.standard_normal((64, 64))) + 8 * np.eye(64)
        inv = np.asarray(tri_inv_refined(jnp.asarray(l), lower=True))
        # complex rounding carries a ~2x larger constant than the real case
        assert np.linalg.norm(inv @ l - np.eye(64)) < 64 * 32 * EPS

    def test_potrf_refined_fallback_on_f32_failure(self):
        # PD in f64 but singular at f32: the off-diagonal rounds to 1.0
        a = np.array([[1.0, 1.0 - 5e-9], [1.0 - 5e-9, 1.0]])
        fac = np.asarray(potrf_refined("L", jnp.asarray(a)))
        assert np.isfinite(fac).all()
        assert np.linalg.norm(fac @ fac.T - a) < 1e-14

    def test_tri_inv_refined(self):
        rng = np.random.default_rng(4)
        l = np.tril(rng.standard_normal((64, 64))) + 8 * np.eye(64)
        inv = np.asarray(tri_inv_refined(jnp.asarray(l), lower=True))
        assert np.linalg.norm(inv @ l - np.eye(64)) < 64 * 8 * EPS
        u = l.T
        invu = np.asarray(tri_inv_refined(jnp.asarray(u), lower=False))
        assert np.linalg.norm(invu @ u - np.eye(64)) < 64 * 8 * EPS

    @pytest.mark.parametrize("uplo", ["L", "U"])
    @pytest.mark.parametrize("cplx", [False, True])
    def test_potrf_inv_refined_fused(self, uplo, cplx):
        """The fused (factor, inverse) step must match potrf_refined's
        factor contract AND deliver an f64-grade explicit inverse."""
        from dlaf_tpu.tile_ops.mixed import potrf_inv_refined

        n = 96
        if cplx:
            rng = np.random.default_rng(23)
            x = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
            a = x @ x.conj().T + n * np.eye(n)
        else:
            a = self._spd(n, 7)
        fac, inv = (np.asarray(z)
                    for z in potrf_inv_refined(uplo, jnp.asarray(a)))
        rec = fac @ fac.conj().T if uplo == "L" else fac.conj().T @ fac
        assert np.linalg.norm(rec - a) / np.linalg.norm(a) < n * 8 * EPS
        assert np.linalg.norm(inv @ fac - np.eye(n)) < n * 32 * EPS
        tri = np.tril if uplo == "L" else np.triu
        assert np.all(fac == tri(fac)) and np.all(inv == tri(inv))

    @pytest.mark.parametrize("uplo", ["L", "U"])
    @pytest.mark.parametrize("n", [96, 256, 100])  # incl. odd split sizes
    def test_recursive_seed_matches_xla_seed(self, uplo, n, monkeypatch):
        """mixed_seed="recursive" (trace-time block recursion, gemm-only
        above the leaves) must deliver the same f64-grade contracts as the
        native XLA seed."""
        import dlaf_tpu.config as config
        from dlaf_tpu.tile_ops.mixed import potrf_inv_refined

        a = self._spd(n, n + 5)
        monkeypatch.setenv("DLAF_MIXED_SEED", "recursive")
        monkeypatch.setenv("DLAF_MIXED_SEED_BASE", "32")
        config.initialize()
        try:
            fac, inv = (np.asarray(z)
                        for z in potrf_inv_refined(uplo, jnp.asarray(a)))
        finally:
            monkeypatch.delenv("DLAF_MIXED_SEED")
            monkeypatch.delenv("DLAF_MIXED_SEED_BASE")
            config.initialize()
        rec = fac @ fac.T if uplo == "L" else fac.T @ fac
        assert np.linalg.norm(rec - a) / np.linalg.norm(a) < n * 8 * EPS
        assert np.linalg.norm(inv @ fac - np.eye(n)) < n * 32 * EPS

    def test_recursive_seed_complex_and_fallback(self, monkeypatch):
        import dlaf_tpu.config as config
        from dlaf_tpu.tile_ops.mixed import potrf_inv_refined

        monkeypatch.setenv("DLAF_MIXED_SEED", "recursive")
        config.initialize()
        try:
            n = 80
            rng = np.random.default_rng(41)
            x = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
            a = x @ x.conj().T + n * np.eye(n)
            fac, inv = (np.asarray(z)
                        for z in potrf_inv_refined("L", jnp.asarray(a)))
            assert (np.linalg.norm(fac @ fac.conj().T - a)
                    / np.linalg.norm(a) < n * 8 * EPS)
            assert np.linalg.norm(inv @ fac - np.eye(n)) < n * 64 * EPS
            # ill-conditioned block: guard must still route to native
            q, _ = np.linalg.qr(rng.standard_normal((128, 128)))
            ev = np.geomspace(1e-8, 1.0, 128)
            b = (q * ev) @ q.T
            b = (b + b.T) / 2
            fb, _ = (np.asarray(z)
                     for z in potrf_inv_refined("L", jnp.asarray(b)))
            assert (np.linalg.norm(fb @ fb.T - b) / np.linalg.norm(b)
                    < 60 * 128 * EPS)
        finally:
            monkeypatch.delenv("DLAF_MIXED_SEED")
            config.initialize()

    def test_potrf_inv_refined_cond_fallback(self):
        from dlaf_tpu.tile_ops.mixed import potrf_inv_refined

        n = 128
        rng = np.random.default_rng(29)
        q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        ev = np.geomspace(1e-8, 1.0, n)
        a = (q * ev) @ q.T
        a = (a + a.T) / 2
        fac, inv = (np.asarray(z)
                    for z in potrf_inv_refined("L", jnp.asarray(a)))
        assert np.linalg.norm(fac @ fac.T - a) / np.linalg.norm(a) < 60 * n * EPS
        assert np.isfinite(inv).all()


class TestCholeskyOzakiPath:
    @pytest.mark.parametrize("uplo", ["L", "U"])
    def test_local_complex128(self, uplo, monkeypatch):
        """trailing='ozaki' with complex128: herk_c128 trailing + complex
        mixed panels (c64 seed)."""
        monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", "ozaki")
        import dlaf_tpu.config as config
        config.initialize()
        try:
            from dlaf_tpu.algorithms.cholesky import cholesky
            from dlaf_tpu.common.index2d import (GlobalElementSize,
                                                 TileElementSize)
            from dlaf_tpu.matrix.matrix import Matrix
            from dlaf_tpu.miniapp.generators import hpd_element_fn

            n, nb = 192, 64
            mat = Matrix.from_element_fn(
                hpd_element_fn(n, np.complex128), GlobalElementSize(n, n),
                TileElementSize(nb, nb), dtype=np.complex128)
            out = cholesky(uplo, mat)
            f = out.to_numpy()
            a = mat.to_numpy()
            tri = np.tril(f) if uplo == "L" else np.triu(f)
            rec = tri @ tri.conj().T if uplo == "L" else tri.conj().T @ tri
            resid = np.linalg.norm(rec - a) / np.linalg.norm(a)
            assert resid < 60 * n * EPS
        finally:
            monkeypatch.delenv("DLAF_CHOLESKY_TRAILING")
            config.initialize()

    @pytest.mark.parametrize("n,nb,uplo", [(256, 64, "L"), (256, 64, "U"),
                                           (150, 64, "L")])
    def test_local_residual(self, n, nb, uplo, monkeypatch):
        monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", "ozaki")
        import dlaf_tpu.config as config
        config.initialize()
        try:
            from dlaf_tpu.algorithms.cholesky import cholesky
            from dlaf_tpu.common.index2d import (GlobalElementSize,
                                                 TileElementSize)
            from dlaf_tpu.matrix.matrix import Matrix
            from dlaf_tpu.miniapp.generators import hpd_element_fn

            mat = Matrix.from_element_fn(
                hpd_element_fn(n, np.float64), GlobalElementSize(n, n),
                TileElementSize(nb, nb), dtype=np.float64)
            out = cholesky(uplo, mat)
            f = out.to_numpy()
            a = mat.to_numpy()
            tri = np.tril(f) if uplo == "L" else np.triu(f)
            rec = tri @ tri.T if uplo == "L" else tri.T @ tri
            resid = np.linalg.norm(rec - a) / np.linalg.norm(a)
            assert resid < 60 * n * EPS
            # untouched triangle passes through
            other = np.triu(mat.to_numpy(), 1) if uplo == "L" \
                else np.tril(mat.to_numpy(), -1)
            got_other = np.triu(f, 1) if uplo == "L" else np.tril(f, -1)
            np.testing.assert_array_equal(got_other, other)
        finally:
            monkeypatch.delenv("DLAF_CHOLESKY_TRAILING")
            config.initialize()

    def test_non_f64_falls_back(self, monkeypatch):
        # f32 input under trailing="ozaki" must still work (static fallback)
        monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", "ozaki")
        import dlaf_tpu.config as config
        config.initialize()
        try:
            from dlaf_tpu.algorithms.cholesky import cholesky
            from dlaf_tpu.common.index2d import (GlobalElementSize,
                                                 TileElementSize)
            from dlaf_tpu.matrix.matrix import Matrix
            from dlaf_tpu.miniapp.generators import hpd_element_fn

            n = 128
            mat = Matrix.from_element_fn(
                hpd_element_fn(n, np.float32), GlobalElementSize(n, n),
                TileElementSize(64, 64), dtype=np.float32)
            out = cholesky("L", mat)
            f = np.tril(out.to_numpy())
            resid = np.linalg.norm(f @ f.T - mat.to_numpy())
            assert resid / np.linalg.norm(mat.to_numpy()) < 60 * n * 1.2e-7
        finally:
            monkeypatch.delenv("DLAF_CHOLESKY_TRAILING")
            config.initialize()


class TestBf16DotRoute:
    """ozaki_dot="bf16": slice contractions over the native bf16 MXU path
    must be BIT-IDENTICAL to the int8 route (7-bit slices are exact in
    bf16; f32 accumulation is integer-exact while k*2^12 <= 2^24, int32
    chunk sums beyond)."""

    @pytest.mark.parametrize("m,k", [(64, 48), (33, 256), (16, 5000)])
    def test_matmul_bitwise_equal(self, m, k, monkeypatch):
        rng = np.random.default_rng(5)
        a = rng.standard_normal((m, k)) * 10.0 ** rng.integers(-6, 6, (m, 1))
        b = rng.standard_normal((k, m)) * 10.0 ** rng.integers(-6, 6, (1, m))
        from dlaf_tpu import config

        ref = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b)))
        monkeypatch.setenv("DLAF_OZAKI_DOT", "bf16")
        config.initialize()
        try:
            got = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b)))
        finally:
            monkeypatch.delenv("DLAF_OZAKI_DOT")
            config.initialize()
        assert got.tobytes() == ref.tobytes()

    def test_syrk_bitwise_equal(self, monkeypatch):
        rng = np.random.default_rng(6)
        a = rng.standard_normal((96, 128))
        from dlaf_tpu import config

        ref = np.asarray(syrk_f64(jnp.asarray(a)))
        monkeypatch.setenv("DLAF_OZAKI_DOT", "bf16")
        config.initialize()
        try:
            got = np.asarray(syrk_f64(jnp.asarray(a)))
        finally:
            monkeypatch.delenv("DLAF_OZAKI_DOT")
            config.initialize()
        assert got.tobytes() == ref.tobytes()


class TestConcatGroupRoute:
    """One k-concatenated dot per shift group: the concatenated contraction
    is exactly the sum of the per-pair contractions, in exact integer
    arithmetic on both dot routes (int8 i32-accumulated, bf16
    f32-chunk-accumulated), so the product keeps the plain reference's
    bits (:func:`reference_matmul`, :func:`reference_syrk`)."""

    @pytest.mark.parametrize("dot", ["int8", "bf16"])
    @pytest.mark.parametrize("m,k,s", [(64, 48, 7), (33, 256, 8),
                                       (16, 5000, 6)])
    def test_matmul_bitwise_equal(self, m, k, s, dot, monkeypatch):
        rng = np.random.default_rng(12)
        a = rng.standard_normal((m, k)) * 10.0 ** rng.integers(-6, 6, (m, 1))
        b = rng.standard_normal((k, m)) * 10.0 ** rng.integers(-6, 6, (1, m))
        _assert_reference_bits(monkeypatch, dot, s, a, b)

    @pytest.mark.parametrize("dot", ["int8", "bf16"])
    @pytest.mark.parametrize("s", [7, 8])
    def test_syrk_bitwise_equal(self, s, dot, monkeypatch):
        rng = np.random.default_rng(13)
        a = rng.standard_normal((96, 128)) * 10.0 ** rng.integers(-4, 4,
                                                                  (96, 1))
        _assert_reference_bits(monkeypatch, dot, s, a)

    def test_accuracy_f64_grade_under_concat(self):
        # same budget as TestOzaki.test_accuracy_f64_grade, on a panel
        # product (the padded group scan)
        rng = np.random.default_rng(14)
        a = rng.standard_normal((40, 64))
        b = rng.standard_normal((64, 40))
        got = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b)))
        ref = a @ b
        scale = (np.abs(a).max(axis=-1)[:, None]
                 * np.abs(b).max(axis=-2)[None, :] * a.shape[-1])
        assert (np.abs(got - ref) / scale).max() < 4 * EPS

    def test_distributed_cholesky_mxu_under_concat(self, monkeypatch,
                                                   devices8):
        """The distributed mxu trailing einsums route through the same
        matmul/syrk entry points — different contraction shapes (batched
        tile axes) than the local arms above."""
        from dlaf_tpu import config

        monkeypatch.setenv("DLAF_F64_GEMM", "mxu")
        monkeypatch.setenv("DLAF_F64_GEMM_MIN_DIM", "8")
        monkeypatch.setenv("DLAF_F64_TRSM", "mixed")
        config.initialize()
        try:
            from dlaf_tpu.algorithms.cholesky import cholesky
            from dlaf_tpu.comm.grid import Grid
            from dlaf_tpu.common.index2d import (GlobalElementSize,
                                                 TileElementSize)
            from dlaf_tpu.matrix.matrix import Matrix
            from dlaf_tpu.miniapp.generators import hpd_element_fn

            n, nb = 64, 16
            mat = Matrix.from_element_fn(
                hpd_element_fn(n, np.float64), GlobalElementSize(n, n),
                TileElementSize(nb, nb), dtype=np.float64, grid=Grid(2, 4))
            f = cholesky("L", mat).to_numpy()
            a = mat.to_numpy()
            tri = np.tril(f)
            resid = np.linalg.norm(tri @ tri.T - a) / np.linalg.norm(a)
            assert resid < 60 * n * EPS
        finally:
            for k in ("DLAF_F64_GEMM", "DLAF_F64_GEMM_MIN_DIM",
                      "DLAF_F64_TRSM"):
                monkeypatch.delenv(k)
            config.initialize()


class TestScanAccumRoute:
    """The sequenced schedule (O(1) live partials: barriers between the
    ragged groups of a bulk product, lax.scan'd zero-padded groups for
    panel products and the syrk, a scan over the wide operand's slices
    for deep products) keeps the plain reference's bits — padded columns
    and blocks are int8 zeros, which contribute exactly nothing on either
    dot route, and the groups fold in the reference's order with its
    scales."""

    @pytest.mark.parametrize("dot", ["int8", "bf16"])
    @pytest.mark.parametrize("m,k,s", [(64, 48, 7), (33, 256, 8),
                                       (16, 700, 6)])
    def test_matmul_bitwise_equal(self, m, k, s, dot, monkeypatch):
        rng = np.random.default_rng(21)
        a = rng.standard_normal((m, k)) * 10.0 ** rng.integers(-6, 6, (m, 1))
        b = rng.standard_normal((k, m)) * 10.0 ** rng.integers(-6, 6, (1, m))
        _assert_reference_bits(monkeypatch, dot, s, a, b)

    #: deep products, ``k > min(m, n)`` (ISSUE 36): A the wide operand, B
    #: the wide operand, square, and a batch under ``jnp.vectorize``
    DEEP = [(96, 160, 8, ()), (8, 160, 96, ()), (24, 200, 24, ()),
            (72, 300, 16, (3,))]

    @pytest.mark.parametrize("dot", ["int8", "bf16"])
    @pytest.mark.parametrize("s", [7, 8])
    @pytest.mark.parametrize("m,k,n,batch", DEEP)
    def test_deep_matmul_bitwise_equal(self, m, k, n, batch, s, dot,
                                       monkeypatch):
        """The scan over the wide operand's slices (``I_t`` against the
        narrow operand's slices shifted into ``s`` blocks, summed in one
        int32 carry) keeps the reference's bits."""
        from dlaf_tpu.tile_ops.ozaki import _sequenced_form

        assert _sequenced_form(m, n, k, s) == "slices"
        rng = np.random.default_rng(36)
        a = rng.standard_normal(batch + (m, k)) \
            * 10.0 ** rng.integers(-6, 6, batch + (m, 1))
        b = rng.standard_normal((k, n)) * 10.0 ** rng.integers(-6, 6, (1, n))
        _assert_reference_bits(monkeypatch, dot, s, a, b)

    def test_deep_matmul_past_int32_keeps_the_group_scan(self, monkeypatch):
        """Where a group sum could pass int32 (``s k 2^12 >= 2^31``) the
        slices form's int32 carry is not exact: the product keeps the
        group scan, whose dots chunk into f64, and the reference's bits.
        The adversarial rows of ``test_concat_syrk_int32_wrap_window``
        put the last group's sum past ``INT32_MIN``."""
        from dlaf_tpu.tile_ops import ozaki

        m, k, n, s = 8, (1 << 16) + 8, 8, 8
        assert ozaki._sequenced_form(m, n, k, s) == "groups"
        assert ozaki._sequenced_form(m, n, k - 16, s) == "slices"

        def refuse(*args, **kw):
            raise AssertionError("the slices form ran past int32")

        monkeypatch.setattr(ozaki, "_scan_slices", refuse)
        a = np.ones((m, k))
        a[:, 0] = 129.0 / 128.0
        _assert_reference_bits(monkeypatch, "int8", s, a, -a.T)

    @pytest.mark.parametrize("dot", ["int8", "bf16"])
    @pytest.mark.parametrize("s", [7, 8])
    def test_syrk_bitwise_equal(self, s, dot, monkeypatch):
        rng = np.random.default_rng(22)
        a = rng.standard_normal((96, 128)) * 10.0 ** rng.integers(-4, 4,
                                                                  (96, 1))
        _assert_reference_bits(monkeypatch, dot, s, a)

    @pytest.mark.quick
    @pytest.mark.parametrize("dot", ["int8", "bf16"])
    @pytest.mark.parametrize("which", ["bulk", "panel", "syrk"])
    def test_batched_bitwise_equal(self, which, dot, monkeypatch):
        """Under ``jnp.vectorize`` batching (a stack of tiles, as the
        step builders hand them over) the forms of the sequenced schedule
        (barriers between ragged groups of a bulk product, the padded scan
        of a panel product and of the syrk) keep the reference's bits."""
        rng = np.random.default_rng(24)
        a = rng.standard_normal((3, 72, 56)) \
            * 10.0 ** rng.integers(-4, 4, (3, 72, 1))
        n = 64 if which == "bulk" else 24
        b = rng.standard_normal((56, n))        # broadcast over the batch
        _assert_reference_bits(monkeypatch, dot, 7, a,
                               None if which == "syrk" else b)

    def test_accuracy_under_jit(self):
        """The scan schedule composes with jit and stays f64-grade."""
        import jax

        rng = np.random.default_rng(23)
        a = rng.standard_normal((64, 96))
        got = np.asarray(jax.jit(
            lambda x: syrk_f64(x, slices=8))(jnp.asarray(a)))
        np.testing.assert_allclose(got, a @ a.T, rtol=1e-14, atol=1e-12)


def test_concat_syrk_int32_wrap_window():
    """The concat syrk's elementwise pair sum (g + g.T + diag) must not
    wrap int32 in the window where s*k*2^12 >= 2^31 but the half-concat
    depth stays below _dot_i8's own f64-chunking threshold. Adversarial
    rows: a decoy max of 129/128 makes every unit element normalize to
    64/129, whose base-128 expansion has balanced digits of EXACTLY
    +-64 at every level — so each pair dot reaches ~2^28 and a 4-pair
    half-group sum crosses 2^31 on the unguarded path."""
    # 65543 unit columns: the d=7 half-group sum reaches
    # -2*4*4096*65543 = -(2^31) - 229376, strictly past INT32_MIN
    # (65536 columns land at exactly -2^31, which still represents)
    k = (1 << 16) + 8
    a = np.ones((8, k))
    a[:, 0] = 129.0 / 128.0
    got = np.asarray(syrk_f64(jnp.asarray(a), slices=8))
    ref = a @ a.T
    np.testing.assert_allclose(got, ref, rtol=1e-12)


#: the four cells' own products as ``(m, k, n)`` and the form the sequenced
#: schedule gives each (``ozaki.py:_sequenced_form``; s = 7 on a TPU)
CELL_PRODUCTS = [
    # chol_d_n4096_1x1: panel / strip products one block wide and deep
    ((3840, 256, 256), "groups"), ((256, 256, 3840), "groups"),
    ((256, 256, 256), "groups"),
    # chol_d_n16384_1x1: the same at nb = 512; its chunked bulk products
    ((15872, 512, 512), "groups"), ((512, 512, 512), "groups"),
    ((8192, 512, 4096), "ragged"),
    # trsm_d_n8192_2x2: pivot products and the deferred bulk product
    ((4096, 256, 256), "groups"), ((4096, 256, 4096), "ragged"),
    ((3840, 256, 4096), "ragged"),
    # red2band_d_n8192_1x1: the rank-2b update's X V^H and V X^H ...
    ((8192, 128, 8192), "ragged"), ((4096, 128, 8192), "ragged"),
    ((1024, 128, 1024), "ragged"),
    # ... and the deep ones: W = A (V T) by row chunk and whole, M = V^H W
    ((4096, 8192, 128), "slices"), ((7168, 7168, 128), "slices"),
    ((1024, 1024, 128), "slices"), ((128, 8192, 128), "slices"),
    ((128, 1024, 128), "slices"),
]


@pytest.mark.quick
@pytest.mark.parametrize("shape,form", CELL_PRODUCTS,
                         ids=["x".join(map(str, sh)) for sh, _ in
                              CELL_PRODUCTS])
def test_sequenced_form_of_the_cells_products(shape, form):
    """One rule on the shape: bulk products (``min(m, n) > k``) ragged,
    products one block wide and one block deep (``k == min(m, n)``) the
    scan over padded groups, deep products (``k > min(m, n)``) the scan
    over the wide operand's slices. Only the reduction's W and M are
    deep: the other three cells' programs keep every form they had."""
    from dlaf_tpu.tile_ops.ozaki import _sequenced_form

    m, k, n = shape
    assert _sequenced_form(m, n, k, 7) == form
    assert _sequenced_form(n, m, k, 7) == form      # symmetric in (m, n)


@pytest.fixture()
def metrics_on(tmp_path):
    """The metrics sink on for the test (the ``dlaf_ozaki_*`` counters
    count only then); after it an empty registry, so no later test reads
    these counts, and the default configuration again."""
    from dlaf_tpu import config, obs

    config.initialize(config.Configuration(
        metrics_path=str(tmp_path / "metrics.jsonl")))
    yield
    obs._reset_for_tests()
    config.initialize()


def _dot_depths(fn, *args):
    """Contraction depth of every dot in ``fn``'s lowered program, read
    from the text (operand types and ``contracting_dims`` of each
    ``stablehlo.dot_general``), in program order."""
    import re

    import jax

    text = jax.jit(fn).lower(*args).as_text()
    depths = []
    for line in text.splitlines():
        if "stablehlo.dot_general" not in line:
            continue
        lhs_axis = int(re.search(r"contracting_dims = \[(\d+)\]", line)[1])
        lhs = re.search(r": \(tensor<([0-9x]+)x[a-z]", line)[1]
        depths.append(int(lhs.split("x")[lhs_axis]))
    return depths


def _syrk_pairs(s):
    """(half pairs, diagonal pairs) of the syrk's ``s`` shift groups."""
    return (sum((d + 1) // 2 for d in range(s)),
            sum(d % 2 == 0 for d in range(s)))


class TestRaggedGroups:
    """Ragged shift groups (ISSUE 28): a group's dot has its real depth,
    sliced from one concatenation per operand. The product is ragged for
    bulk products (both output dimensions wider than the contraction) and
    keeps the zero-padded ``lax.scan`` for panel products and the syrk,
    where every group has the widest depth:
    ``s * s * k`` deep in all for the product (49 k at s = 7 for 28 k
    real), ``s (h + 1) k`` for the syrk (28 k for 16 k real). Deep
    products (ISSUE 36: the contraction deeper than the narrower output
    side) scan ONE dot of depth ``k`` whose narrow side is ``s`` blocks
    wide: the same 49 k slots, seven steps."""

    K = 40
    BULK = (48, 56)         # (m, n): both wider than K
    PANELS = [(40, 56), (56, 40), (40, 40)]     # one side K wide
    DEEP = [(24, 56), (56, 24), (24, 24)]       # one side narrower than K

    @staticmethod
    def _matmul_depths(m, k, n, s):
        return _dot_depths(lambda x, y: matmul_f64(x, y, slices=s),
                           jnp.zeros((m, k)), jnp.zeros((k, n)))

    @pytest.mark.quick
    @pytest.mark.parametrize("dot", ["int8", "bf16"])
    @pytest.mark.parametrize("s", [6, 7, 8])
    def test_bulk_matmul_dots_have_their_real_depth(self, s, dot,
                                                    monkeypatch):
        from dlaf_tpu import config

        monkeypatch.setenv("DLAF_OZAKI_DOT", dot)
        config.initialize()
        try:
            depths = self._matmul_depths(self.BULK[0], self.K,
                                         self.BULK[1], s)
        finally:
            monkeypatch.delenv("DLAF_OZAKI_DOT")
            config.initialize()
        # s (s + 1) / 2 * k deep in all: 28 k at s = 7
        assert sorted(depths) == [(d + 1) * self.K for d in range(s)]

    @pytest.mark.quick
    @pytest.mark.parametrize("m,n", PANELS)
    def test_panel_matmul_scans_one_padded_body(self, m, n):
        """A product one block wide keeps ONE dot of the widest depth in
        a scan body."""
        import jax

        from dlaf_tpu.analysis import depgraph

        depths = self._matmul_depths(m, self.K, n, 7)
        jaxpr = jax.make_jaxpr(lambda x, y: matmul_f64(x, y, slices=7))(
            jnp.zeros((m, self.K)), jnp.zeros((self.K, n)))
        scans = sum(eqn.primitive.name == "scan"
                    for _, eqn in depgraph.iter_eqns(jaxpr))
        assert depths == [7 * self.K] and scans == 1

    @pytest.mark.quick
    @pytest.mark.parametrize("dot", ["int8", "bf16"])
    @pytest.mark.parametrize("m,n", DEEP)
    def test_deep_matmul_scans_the_wide_operands_slices(self, m, n, dot,
                                                        monkeypatch):
        """A deep product's one scan body holds ONE dot ``K`` deep: a slice
        of the wide operand as it was peeled, (m, K), against the narrow
        operand's ``s`` shifted blocks, (K, s n), or with B the wide one
        (s m, K) against (K, n). No operand of depth ``s K`` exists."""
        import re

        import jax

        from dlaf_tpu import config
        from dlaf_tpu.analysis import depgraph

        monkeypatch.setenv("DLAF_OZAKI_DOT", dot)
        config.initialize()
        try:
            def fn(x, y):
                return matmul_f64(x, y, slices=7)

            args = jnp.zeros((m, self.K)), jnp.zeros((self.K, n))
            depths = _dot_depths(fn, *args)
            text = jax.jit(fn).lower(*args).as_text()
            jaxpr = jax.make_jaxpr(fn)(*args)
        finally:
            monkeypatch.delenv("DLAF_OZAKI_DOT")
            config.initialize()
        scans = sum(eqn.primitive.name == "scan"
                    for _, eqn in depgraph.iter_eqns(jaxpr))
        assert depths == [self.K] and scans == 1
        assert not re.search(rf"tensor<[0-9x]*{7 * self.K}x", text)
        out = (m, 7 * n) if m > n else (7 * m, n)
        assert f"-> tensor<{out[0]}x{out[1]}x" in text

    @pytest.mark.quick
    @pytest.mark.parametrize("s", [6, 7, 8])
    def test_syrk_dot_depths(self, s):
        depths = _dot_depths(lambda x: syrk_f64(x, slices=s),
                             jnp.zeros((self.BULK[0], self.K)))
        halves, diagonals = _syrk_pairs(s)
        if s == 7:
            assert (halves, diagonals) == (12, 4)   # 16 k; padded: 28 k
        # one body: widest half pair + diagonal
        assert sorted(depths) == [self.K, (s // 2) * self.K]

    @pytest.mark.quick
    def test_sequenced_schedule_orders_groups_by_a_barrier(self):
        """One ``optimization_barrier`` between consecutive groups of a
        bulk product (what it buys is the TPU compiler's to show:
        tests/test_chip_compile.py)."""
        import jax

        from dlaf_tpu.analysis import depgraph

        jaxpr = jax.make_jaxpr(lambda x, y: matmul_f64(x, y, slices=7))(
            jnp.zeros((self.BULK[0], self.K)),
            jnp.zeros((self.K, self.BULK[1])))
        barriers = sum(eqn.primitive.name == "optimization_barrier"
                       for _, eqn in depgraph.iter_eqns(jaxpr))
        assert barriers == 6

    @pytest.mark.quick
    @pytest.mark.parametrize("s", [7, 8])
    @pytest.mark.parametrize("which", ["bulk", "panel", "syrk", "deep"])
    def test_mac_counter_real_and_zero_by_hand(self, which, s, metrics_on):
        """``dlaf_ozaki_macs_total{route, kind}``: per traced 2D product
        ``real`` is ``m n k`` times the slice pairs it multiplies
        (product: s (s + 1) / 2; syrk: the half pairs and the diagonal
        pairs); ``zero`` is the padding of the two padded scans (product:
        s (s - 1) / 2 slots; syrk: ``s (s // 2 + 1)`` emitted less the
        real pairs) and 0 everywhere else; all under the route label
        ``scan``, but a deep product counts the same slots under a label
        of its own, ``scan_slices``."""
        from dlaf_tpu import obs

        label = "scan_slices" if which == "deep" else "scan"
        real, zero = (obs.registry().counter("dlaf_ozaki_macs_total",
                                             route=label, kind=kind)
                      for kind in ("real", "zero"))
        base = real.snapshot()["value"], zero.snapshot()["value"]
        k = self.K
        m, n = {"panel": self.PANELS[0], "deep": self.DEEP[0]}.get(
            which, self.BULK)
        a = jnp.asarray(np.random.default_rng(28).standard_normal((m, k)))
        if which == "syrk":
            syrk_f64(a, slices=s)
            pairs = sum(_syrk_pairs(s))
            out, padded = m * m, s * (s // 2 + 1) - pairs
        else:
            b = jnp.asarray(
                np.random.default_rng(29).standard_normal((k, n)))
            matmul_f64(jnp.stack([a, a]), b, slices=s)  # batched: one trace
            pairs = s * (s + 1) // 2
            out, padded = m * n, s * (s - 1) // 2
        if which == "bulk":
            padded = 0
        assert real.snapshot()["value"] - base[0] == out * k * pairs
        assert zero.snapshot()["value"] - base[1] == out * k * padded


class TestSyrkMirrorOnce:
    """The syrk mirrors ONCE per call: it folds the un-mirrored
    half ``2 g_d + D_d`` of each shift group at half the group scale and
    forms ``C + C^T`` from the f64 accumulator after the group loop — no
    (m, m) transpose per shift group (ISSUE 26: 7 int32 transposes a step
    on the chip's scan route). Cheap (no compile over a second), so
    ``quick``: every case stays in the default tier (conftest's stride)."""

    @pytest.mark.quick
    @pytest.mark.parametrize("s", [7, 8])
    def test_one_square_transpose_none_in_scan(self, s):
        """jaxpr pin: exactly one transpose of an (m, m) array, and none
        inside a ``scan`` body (m differs from k and from every padded
        concat depth, so the dots' operand transposes are not square)."""
        import jax

        from dlaf_tpu.analysis import depgraph

        m, k = 40, 24
        jaxpr = jax.make_jaxpr(lambda x: syrk_f64(x, slices=s))(
            jnp.zeros((m, k)))
        square = [path for path, eqn in depgraph.iter_eqns(jaxpr)
                  if eqn.primitive.name == "transpose"
                  and eqn.outvars[0].aval.shape[-2:] == (m, m)]
        assert len(square) == 1, square
        assert not any(frame[0] == "scan" for frame in square[0])
        scans = [eqn for _, eqn in depgraph.iter_eqns(jaxpr)
                 if eqn.primitive.name == "scan"]
        assert len(scans) == 1

    @pytest.mark.quick
    @pytest.mark.parametrize("s", [7, 8])
    def test_accuracy_and_exact_symmetry(self, s, monkeypatch):
        """Rows scaled over ten orders of magnitude: the error against
        numpy stays within the syrk budget relative to ``|a||a|^T``, and
        the accumulator handed to ``_apply_scales`` is EXACTLY symmetric
        (``x + y`` and ``y + x`` round alike)."""
        from dlaf_tpu.tile_ops import ozaki as oz

        rng = np.random.default_rng(26)
        a = rng.standard_normal((56, 72)) \
            * 10.0 ** rng.uniform(-5, 5, (56, 1))
        seen = []
        plain = oz._apply_scales
        monkeypatch.setattr(oz, "_apply_scales", lambda acc, sa, sb: (
            seen.append(np.asarray(acc)), plain(acc, sa, sb))[1])
        got = np.asarray(syrk_f64(jnp.asarray(a), slices=s))
        budget = 4 * EPS if s == 8 else 2.0 ** (-7 * s + 4)
        assert _scaled_err(got, a @ a.T, a, a.T) < budget
        assert len(seen) == 1
        assert seen[0].tobytes() == seen[0].T.copy().tobytes()

    @pytest.mark.quick
    def test_mirror_counter_one_per_traced_call(self, metrics_on):
        """``dlaf_ozaki_mirror_total{route="scan"}``: one count per traced
        ``syrk_f64`` call, whatever the slice count (the per-group form
        would have read ``s``)."""
        from dlaf_tpu import obs

        counter = obs.registry().counter("dlaf_ozaki_mirror_total",
                                         route="scan")
        base = counter.snapshot()["value"]
        a = jnp.asarray(np.random.default_rng(27).standard_normal((24, 16)))
        syrk_f64(a, slices=7)
        assert counter.snapshot()["value"] - base == 1
        syrk_f64(a, slices=8)
        syrk_f64(jnp.stack([a, a]), slices=8)     # batched: one trace
        assert counter.snapshot()["value"] - base == 3


class TestPeelBoundaryRegression:
    """Regression net for the round-4 peel-corruption class (commit
    0807ec7): the TPU f64-emulation's `round` mis-rounds tie+epsilon
    values (measured on-silicon: round(17.5000005) = 19), the one-unit
    overshoot pushed the next residual*scale outside int8, and the
    f32->s8 saturation rail then pinned every later slice — shipping a
    ~2^-8 decomposition error through three rounds of green CPU tests.
    The hardened peel (native f32 round + subtracting the STORED slice
    value) is platform-independent code; these properties pin its two
    invariants at exactly the boundary values that broke, so any future
    peel change that reopens the class fails HERE, not on silicon.
    (On the chip the peel is held by chip_smoke.py's f64 residuals.)
    """

    def _reconstruct(self, sl):
        from dlaf_tpu.tile_ops.ozaki import SLICE_BITS

        return sum(sl[t].astype(np.float64) * 2.0 ** (-SLICE_BITS * (t + 1))
                   for t in range(sl.shape[0]))

    @pytest.mark.parametrize("eps", [0.0, 5e-7, -5e-7, 1e-9, -1e-9])
    def test_tie_epsilon_values_stay_inside_rail(self, eps):
        """Every first-slice tie (k+1/2)/128 plus the measured corruption
        epsilons: all 8 slices inside the +-65 rail (|I|<=64 plus at most
        one absorbable overshoot unit — NOT pinned at the +-127 cast
        rail), and the stored slices reconstruct xn to the 56-bit
        budget."""
        import jax

        from dlaf_tpu.tile_ops import ozaki as oz

        ks = np.arange(-64, 64)
        xn_host = np.clip((ks + 0.5 + eps) / 128.0, -0.5, 0.5)
        slices = jax.jit(lambda v: jnp.stack(oz._peel_slices(v, 8)))(
            jnp.asarray(xn_host))
        sl = np.asarray(slices, dtype=np.int64)
        assert np.abs(sl).max() <= 65, \
            f"slice outside rail: {np.abs(sl).max()} (saturation cascade)"
        err = np.abs(self._reconstruct(sl) - xn_host).max()
        assert err < 2.0 ** -53, f"reconstruction off budget: {err}"

    def test_slice_residual_consistency_random(self):
        """Random normalized blocks: slice/residual consistency means the
        stored int8 values alone reconstruct xn to the budget — whatever
        unit choices the platform's rounding made along the way."""
        import jax

        from dlaf_tpu.tile_ops import ozaki as oz

        rng = np.random.default_rng(23)
        xn_host = rng.uniform(-0.5, 0.5, size=(64, 64))
        slices = jax.jit(lambda v: jnp.stack(oz._peel_slices(v, 8)))(
            jnp.asarray(xn_host))
        sl = np.asarray(slices, dtype=np.int64)
        assert np.abs(sl).max() <= 65
        err = np.abs(self._reconstruct(sl) - xn_host).max()
        # 8 slices x 7 bits = 56 kept bits; the dropped residual is
        # < 2^-57 of the normalized scale
        assert err < 2.0 ** -56, f"reconstruction off budget: {err}"


# ---------------------------------------------------------------------------
# the one-pass peel of a deep product's wide operand (ozaki._peel_stack)
# ---------------------------------------------------------------------------

def _peel_check():
    """``scripts/peel_chip_check.py``: the blocks these tests share with
    the check the chip runs, and the host's binary64 chain."""
    import importlib
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    return importlib.import_module("peel_chip_check")


def _adversarial_block(shape, seed):
    return _peel_check().adversarial_block(shape, seed)


class TestOnePassPeel:
    """The wide operand of a deep product (``_sequenced_form`` ``"slices"``)
    is peeled by ``_peel_stack``: one pass over the block, the residual as
    f32 planes kept across the ``s`` steps, each slice written once into
    its place in the stack. It must give ``jnp.stack(_peel_slices(xn, s))``
    bit for bit. On a TPU that is a Pallas kernel on the two f32 planes
    its f64 carries; on this CPU, where f64 is IEEE binary64, it is the
    chain itself. The oracle of the kernel's arithmetic is the chain in
    binary64 on pairs whose sum binary64 holds exactly: there every
    residual is exact. ``scripts/peel_chip_check.py`` holds the kernel to
    the chain on the chip. Cheap (the whole class runs in ~25 s), so
    ``quick``: every case stays in the default tier (conftest's stride)."""

    SHAPES = [(255, 512), (300, 40), (40, 300)]

    @pytest.mark.quick
    @pytest.mark.parametrize("s", [7, 8])
    @pytest.mark.parametrize("shape", SHAPES,
                             ids=["x".join(map(str, sh)) for sh in SHAPES])
    def test_one_pass_stack_is_the_chains_bits(self, shape, s):
        import jax

        from dlaf_tpu.tile_ops import ozaki as oz

        xn = jnp.asarray(_adversarial_block(shape, seed=shape[0] + s))
        one = np.asarray(jax.jit(lambda v: oz._peel_stack(v, s))(xn))
        chain = np.asarray(jax.jit(
            lambda v: jnp.stack(oz._peel_slices(v, s)))(xn))
        assert one.dtype == np.int8 and one.shape == (s,) + shape
        assert np.abs(chain).max() <= 65
        np.testing.assert_array_equal(one, chain)

    @pytest.mark.quick
    @pytest.mark.parametrize("planes", ["split", "hand_built"])
    @pytest.mark.parametrize("s", [7, 8])
    @pytest.mark.parametrize("shape", SHAPES,
                             ids=["x".join(map(str, sh)) for sh in SHAPES])
    def test_two_plane_peel_is_the_exact_chain(self, shape, s, planes):
        """``_peel_planes``, the kernel's arithmetic, on double-f32 pairs
        (``split``: ``_planes`` of a binary64 array that two f32 planes
        hold; ``hand_built``: exact ties of the pair and un-normalized
        pairs beside ties at every slice's scale) gives the chain's slices
        in binary64, the library's and the host's alike."""
        from dlaf_tpu.tile_ops import ozaki as oz

        check = _peel_check()
        hi, lo = check.double_f32_pairs(shape, seed=shape[1] + s)
        x = hi.astype(np.float64) + lo
        want = check.host_chain(x, s)
        np.testing.assert_array_equal(
            np.stack(oz._peel_slices(jnp.asarray(x), s)), want)
        pair = oz._planes(jnp.asarray(x)) if planes == "split" \
            else (jnp.asarray(hi), jnp.asarray(lo))
        got = []
        oz._peel_planes(pair, s, lambda t, i: got.append(i))
        np.testing.assert_array_equal(np.stack(got), want)

    #: the kernel's blocking: strips of a block (640 = 5 x 128 columns),
    #: edge blocks on both axes (300 rows by 256, 1100 columns by 1024),
    #: and a block narrower than a strip that is not one (255 columns)
    KERNEL_SHAPES = [(255, 640), (300, 1100), (128, 255)]

    @pytest.mark.quick
    @pytest.mark.parametrize("shape", KERNEL_SHAPES,
                             ids=["x".join(map(str, sh))
                                  for sh in KERNEL_SHAPES])
    def test_one_pass_kernel_blocks_in_interpret_mode(self, shape):
        """The TPU's Pallas kernel, run in interpret mode on hand-built
        double-f32 pairs, writes the chain's slices in binary64, element
        for element, whatever the grid, the strips and the edge blocks."""
        from dlaf_tpu.tile_ops import ozaki as oz

        check = _peel_check()
        hi, lo = check.double_f32_pairs(shape, seed=sum(shape))
        got = np.asarray(oz._peel_stack_tpu(jnp.asarray(hi), jnp.asarray(lo),
                                            7, interpret=True))
        np.testing.assert_array_equal(
            got, check.host_chain(hi.astype(np.float64) + lo, 7))

    @pytest.mark.quick
    def test_chip_check_rehearses_on_the_cpu(self):
        """``scripts/peel_chip_check.py``'s comparisons run end to end at
        small shapes (the kernel in interpret mode) and find nothing that
        differs."""
        res = _peel_check().check(shapes=[(255, 640), (40, 300)],
                                  products=[(96, 200, 24), (24, 200, 96),
                                            (24, 200, 24)],
                                  interpret=True)
        assert len(res["stack"]) == 8 and len(res["exact"]) == 4
        assert len(res["product"]) == 3
        assert not any(v for part in res.values() for v in part.values()), res

    @pytest.mark.quick
    @pytest.mark.parametrize("s", [7, 8])
    @pytest.mark.parametrize("side", ["a_wide", "b_wide"])
    def test_slices_form_keeps_the_chains_bits(self, side, s, monkeypatch):
        """``_matmul_f64_2d``'s ``"slices"`` form with the one-pass peel
        gives the bits it gave with the wide operand peeled by the chain,
        with A or B the wide operand, rows and columns over twelve
        decades and adversarial entries beside them."""
        from dlaf_tpu.tile_ops import ozaki as oz

        m, k, n = (96, 200, 24) if side == "a_wide" else (24, 200, 96)
        assert oz._sequenced_form(m, n, k, s) == "slices"
        rng = np.random.default_rng(45 + s)
        a = _adversarial_block((m, k), 1) \
            + rng.standard_normal((m, k)) * 10.0 ** rng.integers(-6, 6,
                                                                 (m, 1))
        b = _adversarial_block((k, n), 2) \
            + rng.standard_normal((k, n)) * 10.0 ** rng.integers(-6, 6,
                                                                 (1, n))
        got = np.asarray(matmul_f64(a, b, slices=s))
        monkeypatch.setattr(oz, "_peel_stack",
                            lambda xn, s: jnp.stack(oz._peel_slices(xn, s)))
        want = np.asarray(matmul_f64(a, b, slices=s))
        assert got.tobytes() == want.tobytes()

    #: (product, (m, k, n) or the syrk's (m, k)) and which operand of it
    #: the one pass peels: only a deep product's wide one
    COUNTS = {"a_wide": ((24, 40, 8), "a"), "b_wide": ((8, 40, 24), "b"),
              "panel": ((40, 40, 56), None), "bulk": ((48, 40, 56), None),
              "syrk": ((24, 40), None)}

    @pytest.mark.quick
    @pytest.mark.parametrize("which", list(COUNTS))
    def test_peeled_counter_by_hand(self, which, metrics_on):
        """``dlaf_ozaki_peeled_total{form}`` per traced 2D product: the
        wide operand's elements under ``one_pass``, every other operand's
        under ``chain``; a Cholesky- or solve-shaped product (one block
        deep, or bulk) and the syrk read ``one_pass`` 0."""
        from dlaf_tpu import obs

        one, chain = (obs.registry().counter("dlaf_ozaki_peeled_total",
                                             form=form)
                      for form in ("one_pass", "chain"))
        base = one.snapshot()["value"], chain.snapshot()["value"]
        shape, wide = self.COUNTS[which]
        rng = np.random.default_rng(7)
        if which == "syrk":
            syrk_f64(jnp.asarray(rng.standard_normal(shape)), slices=7)
            sizes = {"a": shape[0] * shape[1]}
        else:
            m, k, n = shape
            matmul_f64(jnp.asarray(rng.standard_normal((m, k))),
                       jnp.asarray(rng.standard_normal((k, n))), slices=7)
            sizes = {"a": m * k, "b": k * n}
        want_one = sizes.get(wide, 0)
        assert one.snapshot()["value"] - base[0] == want_one
        assert chain.snapshot()["value"] - base[1] \
            == sum(sizes.values()) - want_one
