"""Fused Pallas panel factorization (``panel_impl``, docs/pallas_panel.md).

Interpret-mode parity suite for the ``tpu_lapack`` panel shim
(tile_ops/pallas_panel.py): kernel-level fused-vs-XLA parity within the
documented ulp bounds, end-to-end route parity across dtype x uplo x
{local, 2x2 dist}, the ``potrf_info`` NaN/failure contract, the bitwise
``cholesky_lookahead``/``comm_lookahead``/``with_info`` contracts WITHIN
the fused route, the ``site="panel"`` degradation accounting (incl. the
DLAF_STRICT raise and ``inject.disable_pallas``), and the jaxpr pins the
acceptance criteria name: a fused-route panel step emits exactly ONE
``pallas_call`` for the potrf and ONE for the strip solve, and the
comm-lookahead independence pins hold under ``panel_impl="fused"``.
"""

import os

import numpy as np
import pytest
import scipy.linalg as sla

import jax
import jax.numpy as jnp

import dlaf_tpu.config as C
from dlaf_tpu import health, obs
from dlaf_tpu.analysis import depgraph
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.tile_ops import blas as tb
from dlaf_tpu.tile_ops import lapack as tl
from dlaf_tpu.tile_ops import pallas_panel as ppan

#: Documented parity bounds (docs/pallas_panel.md): the fused route is a
#: different factorization order + explicit-inverse solve application,
#: both backward-stable — parity vs the XLA route is c*n*eps with c~8
#: for the well-conditioned HPD test blocks (measured ~1e-7 rel at
#: n<=64 f32), NOT bitwise.
ULP_C = 8.0


def _bound(n, dtype):
    return ULP_C * n * float(jnp.finfo(jnp.dtype(dtype)).eps)


@pytest.fixture(autouse=True)
def _reset():
    yield
    for k in ("DLAF_PANEL_IMPL", "DLAF_METRICS_PATH",
              "DLAF_CHOLESKY_LOOKAHEAD", "DLAF_COMM_LOOKAHEAD",
              "DLAF_CHOLESKY_TRAILING", "DLAF_DIST_STEP_MODE"):
        os.environ.pop(k, None)
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


def hpd(n, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n))
    return (x @ x.T + n * np.eye(n)).astype(dtype)


def kernel_count(impl, op):
    return obs.registry().counter("dlaf_panel_kernel_total", impl=impl,
                                  op=op).snapshot()["value"]


# ---------------------------------------------------------------------------
# Kernel-level parity (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,rtol", [(np.float32, None),
                                        (jnp.bfloat16, 0.06)])
@pytest.mark.parametrize("uplo", ["L", "U"])
@pytest.mark.parametrize("m", [8, 24, 64])
def test_fused_potrf_parity(uplo, m, dtype, rtol):
    a = jnp.asarray(hpd(m), dtype=dtype)
    f = ppan.fused_potrf(uplo, a, interpret=True)
    assert f.dtype == a.dtype
    ref = tl.potrf(uplo, a.astype(jnp.float32))
    tol = rtol if rtol is not None else _bound(m, np.float32)
    err = float(jnp.max(jnp.abs(f.astype(jnp.float32) - ref))
                / jnp.max(jnp.abs(ref)))
    assert err < tol, (uplo, m, err, tol)


def test_fused_potrf_passthrough_triangle():
    """LAPACK storage semantics: the opposite triangle passes through."""
    a = jnp.asarray(hpd(16))
    garbage = a + jnp.triu(jnp.full((16, 16), 7.0, jnp.float32), 1)
    f = ppan.fused_potrf("L", garbage, interpret=True)
    np.testing.assert_array_equal(np.triu(np.asarray(f), 1),
                                  np.triu(np.asarray(garbage), 1))


@pytest.mark.parametrize("combo", [("R", "L", "C", "N"), ("L", "U", "C", "N"),
                                   ("L", "L", "N", "N"), ("R", "U", "N", "U"),
                                   ("L", "L", "T", "U"), ("R", "L", "T", "N")])
@pytest.mark.parametrize("batched", [False, True])
def test_fused_panel_solve_parity(combo, batched):
    side, uplo, op, diag = combo
    na = 32
    rng = np.random.default_rng(3)
    t = np.tril(rng.standard_normal((na, na))).astype(np.float32) \
        + na * np.eye(na, dtype=np.float32)
    if uplo == "U":
        t = t.T.copy()
    t = jnp.asarray(t)
    shape = (3, na, na) if batched else \
        ((40, na) if side == "R" else (na, 40))
    b = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    out = ppan.fused_panel_solve(side, uplo, op, diag, t, b,
                                 interpret=True)
    ref = tb.trsm_panel(side, uplo, op, diag, t, b)
    err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert err < _bound(na, np.float32), (combo, err)


def test_fused_panel_solve_alpha():
    na = 16
    t = jnp.asarray(np.eye(na, dtype=np.float32) * 2)
    b = jnp.asarray(np.ones((na, na), np.float32))
    out = ppan.fused_panel_solve("R", "L", "N", "N", t, b, alpha=4.0,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), 2.0, rtol=1e-6)


def test_fused_potrf_nan_on_failure():
    """A non-positive pivot NaNs the diagonal from the failing column on
    — the potrf_info prefix contract (column 3 fails here, 1-based)."""
    a = np.diag([4.0, 9.0, -1.0, 2.0, 5.0, 1.0, 1.0, 1.0]
                ).astype(np.float32)
    f = np.asarray(ppan.fused_potrf("L", jnp.asarray(a), interpret=True))
    d = np.diagonal(f)
    assert np.isfinite(d[:2]).all(), d
    assert not np.isfinite(d[2:]).any(), d
    _, info = tl.potrf_info("L", ppan.fused_potrf("L", jnp.asarray(a),
                                                  interpret=True))
    assert int(info) == 3


# ---------------------------------------------------------------------------
# End-to-end route parity + knob contracts
# ---------------------------------------------------------------------------

def _factor(uplo, a, nb, grid=None, **kw):
    return cholesky(uplo, Matrix.from_global(a, TileElementSize(nb, nb),
                                             grid=grid), **kw)


@pytest.mark.parametrize("uplo", ["L", "U"])
@pytest.mark.parametrize("grid_shape", [None, (2, 2)])
def test_cholesky_route_parity(uplo, grid_shape, devices8, monkeypatch):
    """Fused vs XLA route pinned within the documented bound across
    uplo x {local, 2x2 dist} (f32; bf16 rides its own test below — the
    CPU XLA route has no bf16 LAPACK cholesky to compare against)."""
    n, nb = 48, 8
    a = hpd(n, seed=1)
    grid = Grid(*grid_shape) if grid_shape else None
    outs = {}
    for impl in ("xla", "fused"):
        monkeypatch.setenv("DLAF_PANEL_IMPL", impl)
        C.initialize()
        outs[impl] = np.asarray(_factor(uplo, a, nb, grid=grid).storage)
    scale = np.abs(outs["xla"]).max()
    assert np.abs(outs["fused"] - outs["xla"]).max() / scale \
        < _bound(n, np.float32)


@pytest.mark.parametrize("grid_shape", [None, (2, 2)])
def test_cholesky_bf16_fused(grid_shape, devices8, monkeypatch):
    """bf16 end-to-end on the fused route (the kernels compute in f32
    and cast back) against the f32 reference factor."""
    n, nb = 48, 8
    a = hpd(n, seed=1)
    a16 = jnp.asarray(a, dtype=jnp.bfloat16)
    monkeypatch.setenv("DLAF_PANEL_IMPL", "fused")
    C.initialize()
    grid = Grid(*grid_shape) if grid_shape else None
    out = _factor("L", a16, nb, grid=grid)
    ref = sla.cholesky(np.asarray(a16, dtype=np.float32)
                       + 0.0, lower=True)
    got = np.tril(np.asarray(out.to_numpy(), dtype=np.float32))
    assert np.abs(got - ref).max() / np.abs(ref).max() < 0.06


def test_info_agrees_on_failure(devices8, monkeypatch):
    """with_info under panel_impl fused/xla: zero agrees with zero on an
    SPD input; on a non-SPD input both routes report a failing column
    inside the truly-failing tile (the exact column is backend-prefix
    dependent — tile_ops/lapack.potrf_info's documented contract)."""
    n, nb = 32, 8
    good = hpd(n, seed=2)
    bad = good.copy()
    bad[18, 18] = -1000.0        # fails inside tile 2 (cols 17..24)
    for grid in (None, Grid(2, 2)):
        infos = {}
        for impl in ("xla", "fused"):
            monkeypatch.setenv("DLAF_PANEL_IMPL", impl)
            C.initialize()
            _, i0 = _factor("L", good, nb, grid=grid, with_info=True)
            assert int(i0) == 0, impl
            _, i1 = _factor("L", bad, nb, grid=grid, with_info=True)
            infos[impl] = int(i1)
        for impl, iv in infos.items():
            assert 17 <= iv <= 24, (impl, infos)


@pytest.mark.parametrize("trailing", ["loop", "scan"])
@pytest.mark.parametrize("grid_shape", [None, (2, 2)])
def test_lookahead_bitwise_under_fused(trailing, grid_shape, devices8,
                                       monkeypatch):
    """cholesky_lookahead (and comm_lookahead, dist) stay transparent on
    the fused route — the knobs only reorder emission of the same
    deterministic kernels. Local: BITWISE. On the 2x2 grid the pin was
    bitwise too and it was the pin that was wrong, not the program: the
    pipelined order emits the next panel column's update as its own
    ``rab,db->rad`` einsum ahead of the bulk ``rab,cdb->rcad`` one, and
    the installed XLA:CPU rounds those two f32 dots differently (1 ulp,
    on the ``panel_impl=xla`` route as well; f64 still agrees bitwise,
    tests/test_cholesky.py). So the distributed f32 case is held to a
    few ulps of the factor's scale."""
    n, nb = 48, 8
    a = hpd(n, seed=4)
    grid = Grid(*grid_shape) if grid_shape else None
    monkeypatch.setenv("DLAF_PANEL_IMPL", "fused")
    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", trailing)
    outs = {}
    for la in ("0", "1"):
        monkeypatch.setenv("DLAF_CHOLESKY_LOOKAHEAD", la)
        monkeypatch.setenv("DLAF_COMM_LOOKAHEAD", la)
        C.initialize()
        outs[la] = np.asarray(_factor("L", a, nb, grid=grid).storage)
    if grid is None:
        assert outs["0"].tobytes() == outs["1"].tobytes()
    else:
        scale = np.abs(outs["0"]).max()
        assert np.abs(outs["0"] - outs["1"]).max() / scale \
            <= 16 * float(np.finfo(np.float32).eps)


def test_with_info_bitwise_under_fused(devices8, monkeypatch):
    """The factor is bitwise identical with with_info on or off on the
    fused route (info is a pure extra output)."""
    a = hpd(32, seed=5)
    monkeypatch.setenv("DLAF_PANEL_IMPL", "fused")
    C.initialize()
    for grid in (None, Grid(2, 2)):
        plain = np.asarray(_factor("L", a, 8, grid=grid).storage)
        f, info = _factor("L", a, 8, grid=grid, with_info=True)
        assert int(info) == 0
        assert np.asarray(f.storage).tobytes() == plain.tobytes()


# ---------------------------------------------------------------------------
# Degradation accounting (site="panel")
# ---------------------------------------------------------------------------

def _metrics_on(tmp_path, **cfg):
    path = str(tmp_path / "panel.jsonl")
    C.initialize(C.Configuration(metrics_path=path, **cfg))
    return path


def fallback_count(reason):
    return obs.registry().counter(health.FALLBACK_COUNTER, site="panel",
                                  reason=reason).snapshot()["value"]


def test_unsupported_dtype_counted(tmp_path):
    """Explicit panel_impl="fused" with f64 input: the XLA landing is a
    COUNTED degradation; result stays correct."""
    _metrics_on(tmp_path, panel_impl="fused")
    a = hpd(32, dtype=np.float64, seed=6)
    before = fallback_count("unsupported_dtype")
    out = _factor("L", a, 8).to_numpy()
    assert fallback_count("unsupported_dtype") >= before + 1
    np.testing.assert_allclose(np.tril(out), sla.cholesky(a, lower=True),
                               atol=1e-10 * 32)


def test_auto_policy_uncounted(tmp_path):
    """auto off-TPU resolves xla by POLICY — no fallback counted."""
    _metrics_on(tmp_path, panel_impl="auto")
    before = fallback_count("unsupported_dtype")
    _factor("L", hpd(16, seed=7), 8)
    assert fallback_count("unsupported_dtype") == before


def test_disable_pallas_counted(tmp_path):
    """inject.disable_pallas forces the fused route off: counted at
    site="panel", factor still correct via the XLA route."""
    from dlaf_tpu.health import inject

    _metrics_on(tmp_path, panel_impl="fused")
    a = hpd(32, seed=8)
    before = fallback_count("injected_off")
    with inject.disable_pallas():
        out = _factor("L", a, 8).to_numpy()
    assert fallback_count("injected_off") >= before + 1
    np.testing.assert_allclose(np.tril(out),
                               sla.cholesky(a, lower=True), atol=1e-4)


def test_disable_pallas_strict_raises(tmp_path):
    from dlaf_tpu.health import inject
    from dlaf_tpu.health.errors import DegradationError

    _metrics_on(tmp_path, panel_impl="fused", strict=True)
    with inject.disable_pallas():
        with pytest.raises(DegradationError):
            _factor("L", hpd(16, seed=9), 8)


def test_kernel_counters(tmp_path, devices8):
    """Trace-time dlaf_panel_kernel_total{impl,op}: the fused dist build
    counts one potrf per step and one solve per non-final step; the xla
    route counts under impl="xla"."""
    _metrics_on(tmp_path, panel_impl="fused")
    n, nb = 48, 8          # nt = 6
    a = hpd(n, seed=10)
    base_potrf = kernel_count("fused", "potrf")
    base_solve = kernel_count("fused", "solve")
    _factor("L", a, nb, grid=Grid(2, 2))
    assert kernel_count("fused", "potrf") - base_potrf == 6
    assert kernel_count("fused", "solve") - base_solve == 5
    _metrics_on(tmp_path, panel_impl="xla")
    base_x = kernel_count("xla", "potrf")
    _factor("U", a, nb, grid=Grid(2, 2))
    assert kernel_count("xla", "potrf") - base_x == 6


def test_kernel_counters_cover_mixed_route(tmp_path, monkeypatch):
    """The documented counter contract: impl="xla" covers the native AND
    mixed/ozaki XLA panel chains — the f64 ozaki trailing (mixed fused
    factor+inverse panels) must count its potrf/solve steps too."""
    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", "ozaki")
    _metrics_on(tmp_path)
    n, nb = 32, 8          # nt = 4
    a = hpd(n, dtype=np.float64, seed=12)
    base_p = kernel_count("xla", "potrf")
    base_s = kernel_count("xla", "solve")
    _factor("L", a, nb)
    assert kernel_count("xla", "potrf") - base_p == 4
    assert kernel_count("xla", "solve") - base_s == 3


# ---------------------------------------------------------------------------
# jaxpr pins (acceptance criteria)
# ---------------------------------------------------------------------------

def _pallas_positions(eqns):
    return depgraph.positions(eqns, "pallas_call")


def _count_pallas(jaxpr_body):
    n = 0
    for eqns in (jaxpr_body,):
        for e in eqns:
            n += sum(1 for _ in _iter_pallas(e))
    return n


def _iter_pallas(eqn):
    if eqn.primitive.name == "pallas_call":
        yield eqn
    for _, sub in depgraph.subjaxprs(eqn):
        for e in sub.eqns:
            yield from _iter_pallas(e)


def test_fused_step_emits_one_kernel_per_panel_op(devices8):
    """jaxpr pin: the fused-route dist program holds exactly ONE
    pallas_call per potrf (nt) and ONE per strip solve (nt-1) — 2*nt-1
    total — where the XLA route holds none (its panel chain is the
    cholesky/triangular_solve op pair per step)."""
    from dlaf_tpu.algorithms.cholesky import _build_dist_cholesky

    C.initialize()
    grid = Grid(2, 2)
    mat = Matrix.from_global(hpd(24), TileElementSize(4, 4), grid=grid)
    nt = 6

    def eqns(panel_fused):
        fn = _build_dist_cholesky(mat.dist, grid.mesh, "L", False, True,
                                  panel_fused=panel_fused)
        return depgraph.shard_map_body(fn, mat.storage)

    fused = eqns(True)
    total = sum(1 for e in fused for _ in _iter_pallas(e))
    assert total == 2 * nt - 1, total
    xla = eqns(False)
    assert sum(1 for e in xla for _ in _iter_pallas(e)) == 0
    assert any(depgraph.positions(xla, "cholesky")), \
        "xla route lost its cholesky op — pin is stale"


@pytest.mark.parametrize("uplo", ["L", "U"])
def test_comm_overlap_pin_under_fused(uplo, devices8):
    """The PR-4 lookahead independence pin holds with panel_impl=fused:
    step k+1's transposed-panel all_gather is emitted before, and is
    independent of, step k's bulk product."""
    from dlaf_tpu.algorithms.cholesky import _build_dist_cholesky

    C.initialize()
    grid = Grid(2, 2)
    mat = Matrix.from_global(hpd(24), TileElementSize(4, 4), grid=grid)
    fn = _build_dist_cholesky(mat.dist, grid.mesh, uplo, False, True,
                              lookahead=True, comm_la=True,
                              panel_fused=True)
    eqns = depgraph.shard_map_body(fn, mat.storage)
    ag = depgraph.positions(eqns, "all_gather")
    bulk = depgraph.positions(eqns, depgraph.is_bulk_dot)
    assert len(ag) >= 2 and bulk
    assert ag[1] < bulk[0], (ag, bulk)
    assert not depgraph.depends_on(eqns, ag[1], depgraph.is_bulk_dot)
