"""Slow-tier distributed tests at realistic tile counts (>= 8 tiles per
rank on the 2x4 mesh) — the regime where telescoped-scan segment windows,
slot alignment, and the blocked HEGST's deferred trailing solve actually
interact (VERDICT r3 item 6; reference analog: the 6-rank suites'
size/grid sweeps, ``test/unit/factorization/test_cholesky.cpp:41-74``).

The toy-size suites (n <= 32) sweep grids/offsets broadly; these pin a few
deep configurations: n=512 with nb=32 gives nt=16 -> 8x4 = 32 tiles per
rank, so every telescoped segment boundary (chunks of ceil(16/8)=2 panels)
falls inside live data.

Grid shapes/orderings are ROTATED across the suite instead of
cross-producted (ADVICE r5 item 1): every test/config runs under exactly
ONE of 2x4 row-major / 4x2 row-major / 2x4 col-major, assigned
round-robin at import time in source order (:func:`_next_grid`), so the
slow tier stays ~flat (21 deep tests, not 63) while all three shapes —
tall, wide, col-major fill — keep coverage somewhere in the suite (the
module-bottom assertion pins that all three were actually assigned). A
deep-tier slot-alignment or owner-mapping bug specific to one shape
still fails here rather than on silicon; it just fails in the one test
carrying that shape.

Marked ``slow`` — excluded from ``-m quick``; run with the full suite or
``-m slow``.
"""

import itertools

import numpy as np
import pytest
import scipy.linalg as sla

import dlaf_tpu.config as config
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.algorithms.gen_to_std import gen_to_std
from dlaf_tpu.algorithms.triangular import (triangular_multiply,
                                            triangular_solve)
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.eigensolver.reduction_to_band import reduction_to_band
from dlaf_tpu.matrix.matrix import Matrix

pytestmark = pytest.mark.slow

N, NB = 512, 32          # nt=16: 8 row x 4 col slots per rank on the 2x4

#: The three deep-tier grid shapes (reference analog: the 6-rank fixtures
#: sweep 3x2 row-major / 2x3 col-major / split-comm sets per test,
#: ``test/include/dlaf_test/comm_grids/grids_6_ranks.h:12-58``).
_GRIDS = {"2x4r": (2, 4, "row-major"),
          "4x2r": (4, 2, "row-major"),
          "2x4c": (2, 4, "col-major")}
_CYCLE = itertools.cycle(sorted(_GRIDS))
_ASSIGNED = []


def _next_grid() -> str:
    """Round-robin grid id, drawn once per test/config at import time
    (decorator evaluation order == source order, so the assignment is
    deterministic and independent of collection order)."""
    gid = next(_CYCLE)
    _ASSIGNED.append(gid)
    return gid


def rotated(values):
    """Pair each of a test's own param configs with the next grid id."""
    return [(*v, _next_grid()) if isinstance(v, tuple)
            else (v, _next_grid()) for v in values]


def _grid(gid: str, devices8) -> Grid:
    rows, cols, ordering = _GRIDS[gid]
    return Grid(rows, cols, ordering=ordering)


def hpd(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n))
    return x @ x.T + n * np.eye(n)


def set_step_mode(monkeypatch, mode):
    monkeypatch.setenv("DLAF_DIST_STEP_MODE", mode)
    config.initialize()


@pytest.fixture(autouse=True)
def _restore_config():
    yield
    config.initialize()


@pytest.mark.parametrize("trailing,gid", rotated(["loop", "scan"]))
def test_cholesky_deep(trailing, gid, devices8, monkeypatch):
    """Distributed Cholesky (unrolled + telescoped scan) at 32 tiles/rank
    against scipy."""
    grid = _grid(gid, devices8)
    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", trailing)
    config.initialize()
    a = hpd(N, seed=1)
    out = cholesky("L", Matrix.from_global(a, TileElementSize(NB, NB),
                                           grid=grid)).to_numpy()
    np.testing.assert_allclose(np.tril(out), sla.cholesky(a, lower=True),
                               atol=1e-8 * N)


@pytest.mark.parametrize("mode,combo,gid", rotated([
    (m, c) for m in ("unrolled", "scan")
    for c in (("L", "L", "N"), ("R", "U", "C"))]))
def test_triangular_solve_deep(mode, combo, gid, devices8, monkeypatch):
    """Forward (LLN) and backward (RUC) distributed solves, both step
    formulations, at 32 tiles/rank — exercises the telescoped windows'
    bottom- and top-sliced forms with live data at every boundary."""
    grid = _grid(gid, devices8)
    side, uplo, op = combo
    set_step_mode(monkeypatch, mode)
    rng = np.random.default_rng(2)
    a = np.tril(rng.standard_normal((N, N))) + N * np.eye(N)
    if uplo == "U":
        a = a.T
    b = rng.standard_normal((N, N))
    ts = TileElementSize(NB, NB)
    am = Matrix.from_global(a, ts, grid=grid)
    bm = Matrix.from_global(b, ts, grid=grid)
    x = triangular_solve(side, uplo, op, "N", 1.0, am, bm).to_numpy()
    opa = a.conj().T if op == "C" else a
    ref = (sla.solve_triangular(opa, b, lower=(uplo == "L") != (op == "C"))
           if side == "L" else
           sla.solve_triangular(opa.T, b.T,
                                lower=(uplo == "U") != (op == "C")).T)
    np.testing.assert_allclose(x, ref, atol=1e-9 * N)


@pytest.mark.parametrize("mode,combo,gid", rotated([
    (m, c) for m in ("unrolled", "scan")
    for c in (("L", "L", "N"), ("R", "L", "C"))]))
def test_triangular_multiply_deep(mode, combo, gid, devices8, monkeypatch):
    grid = _grid(gid, devices8)
    side, uplo, op = combo
    set_step_mode(monkeypatch, mode)
    rng = np.random.default_rng(3)
    a = np.tril(rng.standard_normal((N, N)))
    b = rng.standard_normal((N, N))
    ts = TileElementSize(NB, NB)
    am = Matrix.from_global(a, ts, grid=grid)
    bm = Matrix.from_global(b, ts, grid=grid)
    out = triangular_multiply(side, uplo, op, "N", 1.0, am, bm).to_numpy()
    opa = a.conj().T if op == "C" else a
    ref = opa @ b if side == "L" else b @ opa
    np.testing.assert_allclose(out, ref, atol=1e-10 * N)


@pytest.mark.parametrize("mode,gid", rotated(["unrolled", "scan"]))
def test_hegst_blocked_deep(mode, gid, devices8, monkeypatch):
    """Distributed HEGST at 32 tiles/rank: the blocked form's deferred
    trailing solves span many panel fan-ins at nt=16 (unrolled mode);
    scan mode exercises the twosolve reroute through the telescoped
    triangular solver."""
    grid = _grid(gid, devices8)
    set_step_mode(monkeypatch, mode)
    a = hpd(N, seed=4)
    bf = sla.cholesky(hpd(N, seed=5), lower=True)
    ts = TileElementSize(NB, NB)
    am = Matrix.from_global(a, ts, grid=grid)
    lm = Matrix.from_global(bf, ts, grid=grid)
    out = gen_to_std("L", am, lm).to_numpy()
    linv = sla.solve_triangular(bf, np.eye(N), lower=True)
    ref = linv @ a @ linv.conj().T
    np.testing.assert_allclose(np.tril(out), np.tril(ref), atol=1e-8 * N)


@pytest.mark.parametrize("mode,gid", rotated(["unrolled", "scan"]))
def test_red2band_deep(mode, gid, devices8, monkeypatch):
    """Distributed reduction to band (band < block size) at 8 tiles/rank
    with nb=64: the telescoped red2band segments cover live panels; must
    match the local reduction exactly (same reflector schedule)."""
    grid = _grid(gid, devices8)
    set_step_mode(monkeypatch, mode)
    nb, band = 64, 32
    rng = np.random.default_rng(6)
    x = rng.standard_normal((N, N))
    a = (x + x.T) / 2
    local = reduction_to_band(Matrix.from_global(a, TileElementSize(nb, nb)),
                              band_size=band)
    dist = reduction_to_band(
        Matrix.from_global(a, TileElementSize(nb, nb), grid=grid),
        band_size=band)
    np.testing.assert_allclose(dist.matrix.to_numpy(),
                               local.matrix.to_numpy(), atol=1e-10 * N)
    np.testing.assert_allclose(np.asarray(dist.taus),
                               np.asarray(local.taus), atol=1e-11 * N)


@pytest.mark.parametrize("gid", [_next_grid()])
def test_cholesky_deep_complex(gid, devices8, monkeypatch):
    """Complex128 distributed Cholesky at 32 tiles/rank, scan mode — the
    deep tier's one complex configuration (the toy suites sweep complex
    broadly; this pins the telescoped windows x complex tile-op
    interaction at realistic tile counts)."""
    grid = _grid(gid, devices8)
    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", "scan")
    config.initialize()
    rng = np.random.default_rng(10)
    x = rng.standard_normal((N, N)) + 1j * rng.standard_normal((N, N))
    a = x @ x.conj().T + N * np.eye(N)
    out = cholesky("L", Matrix.from_global(a, TileElementSize(NB, NB),
                                           grid=grid)).to_numpy()
    np.testing.assert_allclose(np.tril(out), sla.cholesky(a, lower=True),
                               atol=1e-8 * N)


@pytest.mark.parametrize("gid", [_next_grid()])
def test_bt_r2b_deep(gid, devices8, monkeypatch):
    """Distributed bt_reduction_to_band in scan mode at npan=31 (n=512,
    nb=64, band=16): the telescoped reverse-sweep windows take NONZERO
    slot offsets here (the toy suites' npan <= 8 yield one full-window
    segment), so the window-relative rolled-panel math is exercised with
    base > 0. Must match the local back-transform."""
    from dlaf_tpu.eigensolver.back_transform import bt_reduction_to_band

    grid = _grid(gid, devices8)
    set_step_mode(monkeypatch, "scan")
    nb, band = 64, 16
    rng = np.random.default_rng(9)
    x = rng.standard_normal((N, N))
    a = (x + x.T) / 2
    c = rng.standard_normal((N, N))
    red_local = reduction_to_band(Matrix.from_global(a,
                                                     TileElementSize(nb, nb)),
                                  band_size=band)
    q_local = np.asarray(bt_reduction_to_band(red_local, c))
    red_dist = reduction_to_band(
        Matrix.from_global(a, TileElementSize(nb, nb), grid=grid),
        band_size=band)
    cm = Matrix.from_global(c, TileElementSize(nb, nb), grid=grid)
    q_dist = bt_reduction_to_band(red_dist, cm).to_numpy()
    np.testing.assert_allclose(q_dist, q_local, atol=1e-10 * N)


@pytest.mark.parametrize("gid", [_next_grid()])
def test_eigensolver_deep(gid, devices8, monkeypatch):
    """Full distributed eigensolver pipeline at n=512, nb=64: residual
    and orthogonality at 8+ tiles/rank (scan step mode — the hardware
    configuration for large tile counts)."""
    from dlaf_tpu.eigensolver.eigensolver import eigensolver

    grid = _grid(gid, devices8)
    set_step_mode(monkeypatch, "scan")
    nb = 64
    rng = np.random.default_rng(7)
    x = rng.standard_normal((N, N))
    a = (x + x.T) / 2
    res = eigensolver("L", Matrix.from_global(a, TileElementSize(nb, nb),
                                              grid=grid))
    w = np.asarray(res.eigenvalues)
    q = res.eigenvectors.to_numpy()
    assert np.all(np.diff(w) >= 0)
    resid = np.linalg.norm(a @ q - q * w[None, :]) / np.linalg.norm(a)
    assert resid < 1e-12 * N
    assert np.linalg.norm(q.T @ q - np.eye(N)) < 1e-12 * N


@pytest.mark.parametrize("gid", [_next_grid()])
def test_eigensolver_deep_mxu_mixed(gid, devices8, monkeypatch):
    """The hardware-session knob configuration (f64_gemm=mxu,
    f64_trsm=mixed, scan step modes) at 8+ tiles/rank — the exact config
    the TPU session runs, validated deep on the CPU mesh so session
    minutes never discover an interaction bug. Uses the emulated-f64
    accuracy budget (the mxu path is f64-grade by construction; the
    mixed panels are Newton-refined)."""
    from dlaf_tpu.eigensolver.eigensolver import eigensolver

    grid = _grid(gid, devices8)
    set_step_mode(monkeypatch, "scan")
    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", "scan")
    monkeypatch.setenv("DLAF_F64_GEMM", "mxu")
    monkeypatch.setenv("DLAF_F64_TRSM", "mixed")
    config.initialize()
    nb = 64
    rng = np.random.default_rng(11)
    x = rng.standard_normal((N, N))
    a = (x + x.T) / 2
    res = eigensolver("L", Matrix.from_global(a, TileElementSize(nb, nb),
                                              grid=grid))
    w = np.asarray(res.eigenvalues)
    q = res.eigenvectors.to_numpy()
    resid = np.linalg.norm(a @ q - q * w[None, :]) / np.linalg.norm(a)
    assert resid < 1e-11 * N
    assert np.linalg.norm(q.T @ q - np.eye(N)) < 1e-11 * N


@pytest.mark.parametrize("gid", [_next_grid()])
def test_cholesky_deep_mxu_accum_scan(gid, devices8, monkeypatch):
    """Distributed Cholesky under the full TPU product route (mxu gemms,
    mixed panels) — the slice product's O(1)-live-partials schedule, the
    N=16384 OOM fix, through the REAL distributed path (shard_map +
    contract + trsm_panel), not just the 2D tile ops the bitwise unit
    tests hold to the plain reference."""
    grid = _grid(gid, devices8)
    monkeypatch.setenv("DLAF_F64_GEMM", "mxu")
    monkeypatch.setenv("DLAF_F64_TRSM", "mixed")
    config.initialize()
    a = hpd(N, seed=4)
    out = np.tril(cholesky(
        "L", Matrix.from_global(a, TileElementSize(NB, NB),
                                grid=grid)).to_numpy())
    np.testing.assert_allclose(out, sla.cholesky(a, lower=True),
                               atol=1e-8 * N)


@pytest.mark.parametrize("gid", [_next_grid()])
def test_slot_alignment_net_has_teeth(gid, devices8, monkeypatch):
    """Sabotage check (VERDICT r3 item 6): shift the telescoped segment
    windows one slot late (`uniform_slot_start + 1`) and assert the deep
    Cholesky result actually corrupts — proving these tests would catch a
    real off-by-one in the slot-window math, not just pass vacuously."""
    import importlib

    grid = _grid(gid, devices8)
    # the algorithms package re-exports the cholesky FUNCTION under the
    # submodule's name; import_module returns the module itself
    chol_mod = importlib.import_module("dlaf_tpu.algorithms.cholesky")

    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", "scan")
    config.initialize()
    a = hpd(N, seed=8)
    ts = TileElementSize(NB, NB)
    good = cholesky("L", Matrix.from_global(a, ts, grid=grid)).to_numpy()
    np.testing.assert_allclose(np.tril(good), sla.cholesky(a, lower=True),
                               atol=1e-8 * N)

    monkeypatch.setattr(chol_mod, "uniform_slot_start",
                        lambda k, p: k // p + 1)
    chol_mod._dist_cholesky_cached.cache_clear()
    try:
        bad = cholesky("L", Matrix.from_global(a, ts, grid=grid)).to_numpy()
        assert not np.allclose(np.tril(bad), sla.cholesky(a, lower=True),
                               atol=1e-8 * N), \
            "sabotaged slot windows produced a correct result — the deep " \
            "distributed tests have no teeth"
    finally:
        monkeypatch.undo()
        chol_mod._dist_cholesky_cached.cache_clear()


@pytest.mark.parametrize("gid", [_next_grid()])
def test_slot_alignment_net_has_teeth_triangular(gid, devices8, monkeypatch):
    """Same sabotage for the telescoped triangular solve's own
    uniform_slot_start binding (each builder imports the bound into its
    namespace, so the Cholesky check does not cover it)."""
    import importlib

    grid = _grid(gid, devices8)
    tri_mod = importlib.import_module("dlaf_tpu.algorithms.triangular")
    set_step_mode(monkeypatch, "scan")
    rng = np.random.default_rng(12)
    a = np.tril(rng.standard_normal((N, N))) + N * np.eye(N)
    b = rng.standard_normal((N, N))
    ts = TileElementSize(NB, NB)
    am = Matrix.from_global(a, ts, grid=grid)
    bm = Matrix.from_global(b, ts, grid=grid)
    good = triangular_solve("L", "L", "N", "N", 1.0, am, bm).to_numpy()
    ref = sla.solve_triangular(a, b, lower=True)
    np.testing.assert_allclose(good, ref, atol=1e-9 * N)

    monkeypatch.setattr(tri_mod, "uniform_slot_start",
                        lambda k, p: k // p + 1)
    tri_mod._dist_solve_cached.cache_clear()
    try:
        bad = triangular_solve("L", "L", "N", "N", 1.0, am, bm).to_numpy()
        assert not np.allclose(bad, ref, atol=1e-9 * N), \
            "sabotaged solve windows produced a correct result"
    finally:
        monkeypatch.undo()
        tri_mod._dist_solve_cached.cache_clear()


# coverage pin for the rotation itself: every one of the three deep grid
# shapes must have been assigned to at least one test above — if an edit
# drops below 3 configs or breaks the cycle, the import fails loudly
assert set(_ASSIGNED) == set(_GRIDS), sorted(set(_ASSIGNED))
assert len(_ASSIGNED) == 21, len(_ASSIGNED)
