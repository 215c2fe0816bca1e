"""The route the chip runs, run on the CPU.

The driver's tier-1 is ``JAX_PLATFORMS=cpu``, where every platform-keyed
"auto" knob picks its CPU arm: loop trailing, native f64 products, no
look-ahead, eight slices. The benchmark's cells run the other arm. Here
the public entry points RUN (not compile) under the knob resolution of a
TPU (``as_on_tpu``, tests/conftest.py): ozaki trailing, ``mixed`` panel
solves, look-ahead and comm look-ahead on, bf16 slice dots, seven
slices. Each result is held to
a numpy float64 reference at the benchmark's own tolerance for a TPU
(``c n 2^-47``: c = 60 factor/solve, 200 eigen — BENCHMARK.json
``guarantee``), and ``test_knob_resolves`` writes down, knob by knob,
what "on a TPU" means.

f64 and c128 only: no Pallas kernel takes them, so nothing here needs
interpret mode. What a TPU resolves that this file CANNOT run on the CPU
(ROADMAP D14):

* ``panel_impl`` / ``step_impl`` = "fused": f32/bf16 Pallas kernels, which
  the entries build for the chip (``interpret = default_backend() !=
  "tpu"``) once the backend answers "tpu"; the CPU cannot execute them.
  tests/test_chip_compile.py compiles them for a described v5e,
  tests/test_pallas_panel.py and test_fused_step.py run them interpreted
  under the explicit knob.
* ``secular_device_min_k`` auto = 4096, ``trsm_rhs_chunk`` /
  ``red2band_trail_chunk`` auto (dims >= 8192), ``dist_step_mode`` auto =
  scan at 32 steps: each binds at sizes far beyond a tier-1 case.
  ``bt_b2t_group`` auto asks the device itself, not the backend's name.

On the 2x2 grid the block is 128 = ``f64_gemm_min_dim``: below it the
distributed builders keep native products (route policy), and the mesh
would not run the cells' route.
"""

import numpy as np
import pytest
import scipy.linalg as sla

import dlaf_tpu.config as C
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.algorithms.gen_to_std import gen_to_std
from dlaf_tpu.algorithms.triangular import (triangular_multiply,
                                            triangular_solve)
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.eigensolver.eigensolver import eigensolver, gen_eigensolver
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.tile_ops import blas as tb
from dlaf_tpu.tile_ops import ozaki as oz
from dlaf_tpu.tile_ops import qr_panel

EPS_TPU = 2.0 ** -47          # f64 on a TPU is double-f32 emulation
GRIDS = [pytest.param(None, id="1x1"), pytest.param((2, 2), id="2x2")]
F64_C128 = [pytest.param(np.float64, id="f64"),
            pytest.param(np.complex128, id="c128")]


def _grid(shape):
    return Grid(*shape) if shape else None


def _M(a, nb, grid):
    return Matrix.from_global(a, TileElementSize(nb, nb), grid=grid)


def _randn(rng, shape, dtype):
    x = rng.standard_normal(shape)
    if np.dtype(dtype).kind == "c":
        x = x + 1j * rng.standard_normal(shape)
    return x.astype(dtype)


def _hpd(n, dtype, seed):
    """The benchmark's input: ``(G + G^H)/2 + n I`` (benchmark/ops)."""
    g = _randn(np.random.default_rng(seed), (n, n), dtype)
    return ((g + g.conj().T) / 2 + n * np.eye(n)).astype(dtype)


def _tri(n, uplo, dtype, seed):
    """The benchmark's triangle: off-diagonal normal, diagonal ``2 n``."""
    t = _randn(np.random.default_rng(seed), (n, n), dtype)
    t = np.tril(t, -1) if uplo == "L" else np.triu(t, 1)
    t[np.diag_indices(n)] = 2.0 * n
    return t


def _fro(x):
    return float(np.linalg.norm(x))


@pytest.fixture
def oz_route(as_on_tpu, monkeypatch):
    """What the traces under ``as_on_tpu`` peel and contract: the slice
    counts asked of ``_peel_slices`` and the number of bf16 slice dots.
    A case that expects the mxu route asserts on it, so a stale program
    cache (a trace made under the CPU's resolution) cannot pass."""
    seen = {"slices": set(), "bf16_dots": 0}
    peel, dot = oz._peel_slices, oz._dot_bf16

    def spy_peel(xn, s):
        seen["slices"].add(int(s))
        return peel(xn, s)

    def spy_dot(ia, ib):
        seen["bf16_dots"] += 1
        return dot(ia, ib)

    monkeypatch.setattr(oz, "_peel_slices", spy_peel)
    monkeypatch.setattr(oz, "_dot_bf16", spy_dot)
    return seen


def _assert_chip_products(seen):
    assert seen["slices"] == {7}, seen
    assert seen["bf16_dots"] > 0, seen


# ---------------------------------------------------------------------------
# cholesky: the route of chol_d_n4096_1x1
# ---------------------------------------------------------------------------

#: (even, ragged) per grid and dtype: n = 256 / 200 at nb = 64 on one
#: device; on the mesh nb = 128 (module docstring). complex128 costs four
#: real products a product, so it gets one block column fewer.
CHOL_SIZES = {
    (None, "f"): [(256, 64), (200, 64)],
    (None, "c"): [(192, 64), (136, 64)],
    ((2, 2), "f"): [(384, 128), (200, 128)],
    ((2, 2), "c"): [(256, 128), (200, 128)],
}


@pytest.mark.parametrize("size", [0, 1], ids=["even", "ragged"])
@pytest.mark.parametrize("grid_shape", GRIDS)
@pytest.mark.parametrize("dtype", F64_C128)
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_cholesky(uplo, dtype, grid_shape, size, oz_route, devices8):
    n, nb = CHOL_SIZES[grid_shape, np.dtype(dtype).kind][size]
    a = _hpd(n, dtype, seed=n + nb)
    out = cholesky(uplo, _M(a, nb, _grid(grid_shape))).to_numpy()
    _assert_chip_products(oz_route)
    if uplo == "L":
        f = np.tril(out)
        resid = _fro(f @ f.conj().T - a) / _fro(a)
        np.testing.assert_array_equal(np.triu(out, 1), np.triu(a, 1))
    else:
        f = np.triu(out)
        resid = _fro(f.conj().T @ f - a) / _fro(a)
        np.testing.assert_array_equal(np.tril(out, -1), np.tril(a, -1))
    assert resid <= 60 * n * EPS_TPU, resid


# ---------------------------------------------------------------------------
# triangular solve / multiply: the route of trsm_d_n8192_2x2
# ---------------------------------------------------------------------------

def _op(t, op):
    return t if op == "N" else t.conj().T


def _solve_case(grid_shape):
    """``(n, m, nb)`` of B; A is square on the solved side."""
    return (256, 128, 64) if grid_shape is None else (256, 256, 128)


@pytest.mark.parametrize("grid_shape", GRIDS)
@pytest.mark.parametrize("op", ["N", "C"])
@pytest.mark.parametrize("uplo", ["L", "U"])
@pytest.mark.parametrize("side", ["L", "R"])
def test_triangular_solve(side, uplo, op, grid_shape, oz_route, devices8):
    """op "C" on complex128, "N" on float64. One device: the solve is one
    whole-matrix native solve on every platform (``f64_trsm``: "whole-
    matrix local solves stay native either way"), so no slice product is
    expected there; on the mesh the pivot chain is the mixed route and
    the bulk updates are slice products."""
    dtype = np.complex128 if op == "C" else np.float64
    n, m, nb = _solve_case(grid_shape)
    adim = n if side == "L" else m
    t = _tri(adim, uplo, dtype, seed=3)
    b = _randn(np.random.default_rng(4), (n, m), dtype)
    grid = _grid(grid_shape)
    alpha = 2.0
    x = triangular_solve(side, uplo, op, "N", alpha, _M(t, nb, grid),
                         _M(b, nb, grid)).to_numpy()
    if grid_shape is None:
        assert oz_route["bf16_dots"] == 0, oz_route
    else:
        _assert_chip_products(oz_route)
    tt = _op(t, op)
    res = (tt @ x if side == "L" else x @ tt) - alpha * b
    assert _fro(res) / (_fro(t) * _fro(x)) <= 60 * adim * EPS_TPU


@pytest.mark.parametrize("grid_shape", GRIDS)
@pytest.mark.parametrize("uplo", ["L", "U"])
@pytest.mark.parametrize("side", ["L", "R"])
def test_triangular_multiply(side, uplo, grid_shape, oz_route, devices8):
    n, m, nb = _solve_case(grid_shape)
    adim = n if side == "L" else m
    rng = np.random.default_rng(5)
    t = rng.standard_normal((adim, adim))
    t = np.tril(t) if uplo == "L" else np.triu(t)
    b = rng.standard_normal((n, m))
    grid = _grid(grid_shape)
    out = triangular_multiply(side, uplo, "N", "N", 0.5, _M(t, nb, grid),
                              _M(b, nb, grid)).to_numpy()
    _assert_chip_products(oz_route)
    expect = 0.5 * (t @ b if side == "L" else b @ t)
    assert _fro(out - expect) / (_fro(t) * _fro(b)) <= 60 * adim * EPS_TPU


# ---------------------------------------------------------------------------
# gen_to_std and the eigensolvers
# ---------------------------------------------------------------------------

def _eig_case(grid_shape):
    return (192, 64) if grid_shape is None else (256, 128)


@pytest.mark.parametrize("grid_shape", GRIDS)
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_gen_to_std(uplo, grid_shape, oz_route, devices8):
    """``hegst_impl`` resolves "twosolve" on a TPU: two triangular solves
    against the factor of B (numpy's here), so one device runs two
    whole-matrix native solves and no slice product, like
    ``test_triangular_solve``."""
    n, nb = _eig_case(grid_shape)
    a, b = _hpd(n, np.float64, 6), _hpd(n, np.float64, 7)
    grid = _grid(grid_shape)
    low = np.linalg.cholesky(b)
    bf = _M(low if uplo == "L" else low.conj().T, nb, grid)
    out = gen_to_std(uplo, _M(a, nb, grid), bf).to_numpy()
    if grid_shape is None:
        assert oz_route["bf16_dots"] == 0, oz_route
    else:
        _assert_chip_products(oz_route)
    # inv(L) A inv(L)^H, the same matrix for either stored triangle
    expect = np.linalg.solve(low, np.linalg.solve(low, a).conj().T).conj().T
    pick = np.tril if uplo == "L" else np.triu
    assert _fro(pick(out) - pick(expect)) / _fro(expect) \
        <= 60 * n * EPS_TPU
    other = (lambda x: np.triu(x, 1)) if uplo == "L" \
        else (lambda x: np.tril(x, -1))
    np.testing.assert_array_equal(other(out), other(a))


def _check_eigenpairs(a, b, res, n):
    """The benchmark's three numbers (benchmark/ops/eigensolver.py), on the
    whole eigenvector matrix instead of eight probes of it."""
    lam = np.asarray(res.eigenvalues, dtype=np.float64)
    q = np.asarray(res.eigenvectors.to_numpy(), dtype=np.float64)
    tol = 200 * n * EPS_TPU
    bq = q if b is None else b @ q
    assert _fro(a @ q - bq * lam) / (_fro(a) * _fro(q)) <= tol
    assert _fro(q.T @ bq - np.eye(n)) / np.sqrt(n) <= tol
    ref = sla.eigh(a, b, eigvals_only=True)
    assert np.abs(lam - ref).max() / np.abs(ref).max() <= tol


@pytest.mark.parametrize("grid_shape", GRIDS)
def test_eigensolver(grid_shape, oz_route, devices8):
    n, nb = _eig_case(grid_shape)
    a = _hpd(n, np.float64, 8)
    res = eigensolver("L", _M(a, nb, _grid(grid_shape)))
    _assert_chip_products(oz_route)
    _check_eigenpairs(a, None, res, n)


@pytest.mark.parametrize("grid_shape", GRIDS)
def test_gen_eigensolver(grid_shape, oz_route, devices8):
    n, nb = _eig_case(grid_shape)
    a, b = _hpd(n, np.float64, 9), _hpd(n, np.float64, 10)
    grid = _grid(grid_shape)
    res = gen_eigensolver("L", _M(a, nb, grid), _M(b, nb, grid))
    _assert_chip_products(oz_route)
    _check_eigenpairs(a, b, res, n)


# ---------------------------------------------------------------------------
# what "on a TPU" means, knob by knob
# ---------------------------------------------------------------------------

def _through_entry(knob, entry):
    """A knob resolved inside its entry point and nowhere else: run the
    entry at a tiny size and read what ``resolve_platform_auto`` returned
    for it."""
    def resolve(monkeypatch):
        got = {}
        real = C.resolve_platform_auto

        def spy(value, *, knob, **kw):
            got[knob] = real(value, knob=knob, **kw)
            return got[knob]

        with monkeypatch.context() as m:
            m.setattr(C, "resolve_platform_auto", spy)
            entry()
        return got[knob]
    return resolve


def _tiny_cholesky():
    return cholesky("L", _M(_hpd(8, np.float64, 1), 4, None))


def _tiny_gen_to_std():
    return gen_to_std("L", _M(_hpd(8, np.float64, 2), 4, None),
                      _tiny_cholesky())


def _direct(fn):
    return lambda monkeypatch: fn()


#: knob -> (how it is resolved, on a TPU, elsewhere)
KNOBS = {
    "cholesky_trailing": (_through_entry("cholesky_trailing",
                                         _tiny_cholesky), "ozaki", "loop"),
    "cholesky_lookahead": (_direct(C.resolved_cholesky_lookahead),
                           True, False),
    "comm_lookahead": (_direct(C.resolved_comm_lookahead), True, False),
    "dc_level_batch": (_direct(C.resolved_dc_level_batch), True, False),
    "bt_lookahead": (_direct(C.resolved_bt_lookahead), True, False),
    "f64_gemm": (_direct(C.resolved_f64_gemm), "mxu", "native"),
    "f64_trsm": (_direct(C.resolved_f64_trsm), "mixed", "native"),
    "panel_impl": (_direct(C.resolved_panel_impl), "fused", "xla"),
    "step_impl": (_direct(C.resolved_step_impl), "fused", "xla"),
    "ozaki_dot": (_direct(oz._slice_dot_impl), "bf16", "int8"),
    "qr_panel": (_direct(qr_panel._qr_panel_impl), "householder", "geqrf"),
    "hegst_impl": (_through_entry("hegst_impl", _tiny_gen_to_std),
                   "twosolve", "blocked"),
    "f64_gemm_slices": (_direct(tb._oz_slices), 7, 8),
}


@pytest.mark.parametrize("knob", list(KNOBS))
def test_knob_resolves(knob, request, monkeypatch):
    """The default configuration, first as this process resolves it, then
    as a TPU process does."""
    resolve, on_tpu, elsewhere = KNOBS[knob]
    assert getattr(C.initialize(), knob) in ("auto", 0)
    assert resolve(monkeypatch) == elsewhere
    request.getfixturevalue("as_on_tpu")
    assert resolve(monkeypatch) == on_tpu
