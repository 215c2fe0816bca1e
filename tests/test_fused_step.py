"""Fused Cholesky STEP kernel (``step_impl``, docs/pallas_panel.md).

Interpret-mode exactness suite for the fused step route
(tile_ops/pallas_panel.py ``fused_step`` / ``fused_factor_solve``):
kernel-vs-composed-ops parity within the documented c*n*eps bound across
uplo x {f32, bf16}, the ``potrf_info`` NaN-prefix contract preserved
(the fused kernel's factor is bitwise the fused_potrf ladder's), the
bitwise ``cholesky_lookahead``/``comm_lookahead``/``with_info``
contracts WITHIN the fused-step route, the ``site="step"`` degradation
accounting (unsupported dtype / VMEM budget / ``inject.disable_route``,
strict-raising), the ``dlaf_step_kernel_total{impl}`` trace-time
counter, and the jaxpr pins: ONE pallas_call per strip-bearing step on
the fused-step route, with the PR-4 comm-overlap independence pins
holding under ``step_impl=fused``.

This sandbox has no chip, so interpret mode is the numerical validation
path here (tests/test_chip_compile.py holds the kernels to the v5e's
compiler, chip_smoke.py to the chip) — these pins are load-bearing,
mirroring tests/test_pallas_panel.py's discipline for the panel route.
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

#: Documented parity bound (docs/pallas_panel.md "Fused step kernel"):
#: the fused step is the same micro-block potrf ladder + explicit-
#: inverse solve + one-dot trailing slab, each backward-stable — parity
#: vs the composed op chain is c*n*eps with c~8 for well-conditioned
#: HPD test blocks, NOT bitwise.
ULP_C = 8.0


def _bound(n, dtype):
    return ULP_C * n * float(jnp.finfo(jnp.dtype(dtype)).eps)


@pytest.fixture(autouse=True)
def _reset():
    yield
    for k in ("DLAF_STEP_IMPL", "DLAF_STEP_VMEM_LIMIT", "DLAF_PANEL_IMPL",
              "DLAF_METRICS_PATH", "DLAF_CHOLESKY_LOOKAHEAD",
              "DLAF_COMM_LOOKAHEAD", "DLAF_CHOLESKY_TRAILING",
              "DLAF_DIST_STEP_MODE"):
        os.environ.pop(k, None)
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


def hpd(n, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n))
    return (x @ x.T + n * np.eye(n)).astype(dtype)


# ---------------------------------------------------------------------------
# Kernel-level parity (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,rtol", [(np.float32, None),
                                        (jnp.bfloat16, 0.06)])
@pytest.mark.parametrize("uplo", ["L", "U"])
@pytest.mark.parametrize("d,m", [(8, 24), (4, 10), (16, 16), (8, 3)])
def test_fused_step_parity(uplo, d, m, dtype, rtol):
    """3-op kernel (potrf + strip solve + trailing slab) vs the composed
    chain: diag/panel/slab all within the documented bound, and the
    slab's not-yet-factored cells pass through bitwise."""
    w = min(d, m)
    a = jnp.asarray(hpd(d + m, seed=2), dtype=dtype)
    blk = a[:d, :d]
    if uplo == "L":
        strip, slab = a[d:, :d], a[d:, d:d + w]
    else:
        strip, slab = a[:d, d:], a[d:d + w, d:]
    diag, panel, nslab = ppan.fused_step(uplo, blk, strip, slab,
                                         interpret=True)
    assert (diag.dtype, panel.dtype, nslab.dtype) == (a.dtype,) * 3
    f32 = jnp.float32
    dr = tl.potrf(uplo, blk.astype(f32))
    pr = (tb.trsm("R", "L", "C", "N", dr, strip.astype(f32))
          if uplo == "L" else
          tb.trsm("L", "U", "C", "N", dr, strip.astype(f32)))
    if uplo == "L":
        mask = np.arange(m)[:, None] >= np.arange(w)[None, :]
        sr = np.asarray(slab, np.float32) - np.where(
            mask, np.asarray(pr @ jnp.conj(pr[:w]).T), 0)
    else:
        mask = np.arange(w)[:, None] <= np.arange(m)[None, :]
        sr = np.asarray(slab, np.float32) - np.where(
            mask, np.asarray(jnp.conj(pr[:, :w]).T @ pr), 0)
    tol = rtol if rtol is not None else _bound(d + m, np.float32)
    for got, ref, name in ((diag, dr, "diag"), (panel, pr, "panel"),
                           (nslab, sr, "slab")):
        err = float(np.abs(np.asarray(got, np.float32) - np.asarray(ref)
                           ).max() / max(np.abs(np.asarray(ref)).max(),
                                         1e-30))
        assert err < tol, (uplo, d, m, name, err, tol)
    # pass-through: unmasked slab cells are bitwise the input's
    sm = np.where(mask, np.asarray(slab), np.asarray(nslab))
    np.testing.assert_array_equal(sm, np.asarray(slab))


@pytest.mark.parametrize("uplo", ["L", "U"])
@pytest.mark.parametrize("batched", [False, True])
def test_fused_factor_solve_parity(uplo, batched):
    """2-op kernel (potrf + strip solve, no slab — the dist builders'
    form, where the trailing update needs the post-collective panel)."""
    d, m, r = 8, 20, 3
    a = jnp.asarray(hpd(d * (r + 1), seed=3))
    blk = a[:d, :d]
    if batched:
        strip = jnp.stack([a[(i + 1) * d:(i + 2) * d, :d] if uplo == "L"
                           else a[:d, (i + 1) * d:(i + 2) * d]
                           for i in range(r)])
    else:
        strip = a[d:d + m, :d] if uplo == "L" else a[:d, d:d + m]
    diag, pan = ppan.fused_factor_solve(uplo, blk, strip, interpret=True)
    dr = tl.potrf(uplo, blk)
    if batched:
        pr = (tb.trsm_panel("R", "L", "C", "N", dr, strip) if uplo == "L"
              else tb.trsm_panel("L", "U", "C", "N", dr, strip))
    else:
        pr = (tb.trsm("R", "L", "C", "N", dr, strip) if uplo == "L"
              else tb.trsm("L", "U", "C", "N", dr, strip))
    bound = _bound(d * (r + 1), np.float32)
    for got, ref in ((diag, dr), (pan, pr)):
        err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
        assert err < bound, (uplo, batched, err)


def test_fused_step_nan_prefix_info_contract():
    """The fused step's factor block is BITWISE the fused_potrf ladder's
    — a non-positive pivot NaNs the diagonal from the failing column on,
    so the potrf_info prefix contract carries over unchanged."""
    bad = np.diag([4.0, 9.0, -1.0, 2.0, 5.0, 1.0, 1.0, 1.0]
                  ).astype(np.float32)
    strip = np.ones((16, 8), np.float32)
    slab = np.ones((16, 8), np.float32)
    diag, _, _ = ppan.fused_step("L", jnp.asarray(bad), jnp.asarray(strip),
                                 jnp.asarray(slab), interpret=True)
    ref = ppan.fused_potrf("L", jnp.asarray(bad), interpret=True)
    assert np.asarray(diag).tobytes() == np.asarray(ref).tobytes()
    _, info = tl.potrf_info("L", diag)
    assert int(np.asarray(info).ravel()[0]) == 3


def test_step_vmem_bytes_model():
    """The VMEM budget model (docs/pallas_panel.md): pad-size squares of
    the resident diag+factor (2x), the 4 double-buffered grid blocks
    (8x), and the three f32 scratch squares."""
    s = 128
    assert ppan.step_vmem_bytes(s, np.float32) == s * s * (10 * 4 + 12)
    assert ppan.step_vmem_bytes(s, jnp.bfloat16) == s * s * (10 * 2 + 12)
    # sub-pad block edges price at the padded kernel size
    assert ppan.step_vmem_bytes(8, np.float32) == \
        ppan.step_vmem_bytes(128, np.float32)
    # the default budget admits the product nb=256 f32 step kernel
    assert ppan.step_vmem_bytes(256, np.float32) \
        <= C.Configuration().step_vmem_limit


# ---------------------------------------------------------------------------
# End-to-end route parity + knob contracts
# ---------------------------------------------------------------------------

def _factor(uplo, a, nb, grid=None, **kw):
    return cholesky(uplo, Matrix.from_global(a, TileElementSize(nb, nb),
                                             grid=grid), **kw)


@pytest.mark.parametrize("trailing", ["loop", "biggemm", "scan"])
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_local_route_parity(uplo, trailing, devices8, monkeypatch):
    """Fused-step vs composed route pinned within the documented bound
    across uplo x trailing (local, f32; n%nb != 0 exercises the ragged
    final block)."""
    n, nb = 21, 8
    a = hpd(n, seed=1)
    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", trailing)
    outs = {}
    for impl in ("xla", "fused"):
        monkeypatch.setenv("DLAF_STEP_IMPL", impl)
        C.initialize()
        outs[impl] = np.asarray(_factor(uplo, a, nb).storage)
    scale = np.abs(outs["xla"]).max()
    assert np.abs(outs["fused"] - outs["xla"]).max() / scale \
        < _bound(n, np.float32)


@pytest.mark.parametrize("trailing", ["loop", "scan"])
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_dist_route_parity(uplo, trailing, devices8, monkeypatch):
    """Fused-step vs composed route on the 2x2 dist builders (unrolled
    and scan step modes)."""
    n, nb = 24, 8
    a = hpd(n, seed=6)
    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", trailing)
    outs = {}
    for impl in ("xla", "fused"):
        monkeypatch.setenv("DLAF_STEP_IMPL", impl)
        C.initialize()
        outs[impl] = np.asarray(_factor(uplo, a, nb,
                                        grid=Grid(2, 2)).storage)
    scale = np.abs(outs["xla"]).max()
    assert np.abs(outs["fused"] - outs["xla"]).max() / scale \
        < _bound(n, np.float32)


def test_local_bf16_fused_step(monkeypatch):
    """bf16 end-to-end on the fused-step route (the kernel computes in
    f32 and casts back) against the f32 reference factor."""
    n, nb = 24, 8
    a16 = jnp.asarray(hpd(n, seed=1), dtype=jnp.bfloat16)
    monkeypatch.setenv("DLAF_STEP_IMPL", "fused")
    # the final (strip-less) step has no fused-step kernel; its potrf
    # rides the panel route, which must also be fused for bf16 on CPU
    monkeypatch.setenv("DLAF_PANEL_IMPL", "fused")
    C.initialize()
    out = _factor("L", a16, nb)
    ref = sla.cholesky(np.asarray(a16, dtype=np.float32) + 0.0,
                       lower=True)
    got = np.tril(np.asarray(out.to_numpy(), dtype=np.float32))
    assert np.abs(got - ref).max() / np.abs(ref).max() < 0.06


@pytest.mark.parametrize("trailing", ["loop", "scan"])
@pytest.mark.parametrize("grid_shape", [None, (2, 2)])
def test_lookahead_bitwise_under_fused_step(trailing, grid_shape,
                                            devices8, monkeypatch):
    """cholesky_lookahead (and comm_lookahead, dist) stay BITWISE
    transparent on the fused-step route — the fused branch always uses
    the split-trailing structure, so the knobs only change carry-vs-
    re-read of identical values."""
    n, nb = 24, 8
    a = hpd(n, seed=4)
    grid = Grid(*grid_shape) if grid_shape else None
    monkeypatch.setenv("DLAF_STEP_IMPL", "fused")
    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", trailing)
    outs = {}
    for la in ("0", "1"):
        monkeypatch.setenv("DLAF_CHOLESKY_LOOKAHEAD", la)
        monkeypatch.setenv("DLAF_COMM_LOOKAHEAD", la)
        C.initialize()
        outs[la] = np.asarray(_factor("L", a, nb, grid=grid).storage)
    assert outs["0"].tobytes() == outs["1"].tobytes()


def test_with_info_bitwise_under_fused_step(devices8, monkeypatch):
    """The factor is bitwise identical with with_info on or off on the
    fused-step route (info is a pure extra output over the same
    kernels)."""
    a = hpd(24, seed=5)
    monkeypatch.setenv("DLAF_STEP_IMPL", "fused")
    C.initialize()
    for grid in (None, Grid(2, 2)):
        plain = np.asarray(_factor("L", a, 8, grid=grid).storage)
        f, info = _factor("L", a, 8, grid=grid, with_info=True)
        assert int(info) == 0
        assert np.asarray(f.storage).tobytes() == plain.tobytes()


def test_composes_with_fused_panel(monkeypatch):
    """step_impl=fused + panel_impl=fused: the final (strip-less) step
    still routes its potrf through the fused panel kernel; parity
    holds."""
    n, nb = 21, 8
    a = hpd(n, seed=9)
    monkeypatch.setenv("DLAF_STEP_IMPL", "fused")
    monkeypatch.setenv("DLAF_PANEL_IMPL", "fused")
    C.initialize()
    out = np.asarray(_factor("L", a, nb).to_numpy())
    ref = sla.cholesky(a, lower=True)
    assert np.abs(np.tril(out) - ref).max() / np.abs(ref).max() \
        < _bound(n, np.float32)


# ---------------------------------------------------------------------------
# Degradation accounting (site="step") + counters
# ---------------------------------------------------------------------------

def _metrics_on(tmp_path, **cfg):
    path = str(tmp_path / "step.jsonl")
    C.initialize(C.Configuration(metrics_path=path, **cfg))
    return path


def fallback_count(reason):
    return obs.registry().counter(health.FALLBACK_COUNTER, site="step",
                                  reason=reason).snapshot()["value"]


def step_count(impl):
    return obs.registry().counter("dlaf_step_kernel_total",
                                  impl=impl).snapshot()["value"]


def test_unsupported_dtype_counted(tmp_path):
    """Explicit step_impl="fused" with f64 input: the composed-chain
    landing is a COUNTED degradation; result stays correct."""
    _metrics_on(tmp_path, step_impl="fused")
    a = hpd(32, dtype=np.float64, seed=6)
    before = fallback_count("unsupported_dtype")
    out = _factor("L", a, 8).to_numpy()
    assert fallback_count("unsupported_dtype") >= before + 1
    np.testing.assert_allclose(np.tril(out), sla.cholesky(a, lower=True),
                               atol=1e-10 * 32)


def test_vmem_budget_counted(tmp_path):
    """Explicit step_impl="fused" over a starved step_vmem_limit: the
    budget overflow is a COUNTED degradation (reason="vmem_budget") and
    the factorization lands on the composed chain, still correct."""
    _metrics_on(tmp_path, step_impl="fused", step_vmem_limit=1024)
    a = hpd(32, seed=7)
    before = fallback_count("vmem_budget")
    out = _factor("L", a, 8).to_numpy()
    assert fallback_count("vmem_budget") >= before + 1
    np.testing.assert_allclose(np.tril(out),
                               sla.cholesky(a, lower=True), atol=1e-4)


def test_auto_policy_uncounted(tmp_path):
    """auto off-TPU resolves xla by POLICY — no fallback counted."""
    _metrics_on(tmp_path, step_impl="auto")
    before = fallback_count("unsupported_dtype")
    _factor("L", hpd(16, seed=7), 8)
    assert fallback_count("unsupported_dtype") == before


def test_disable_route_counted(tmp_path):
    """inject.disable_route("pallas") forces the fused step off: counted
    at site="step", factor still correct via the composed chain."""
    from dlaf_tpu.health import inject

    _metrics_on(tmp_path, step_impl="fused")
    a = hpd(32, seed=8)
    before = fallback_count("injected_off")
    with inject.disable_route("pallas"):
        out = _factor("L", a, 8).to_numpy()
    assert fallback_count("injected_off") >= before + 1
    np.testing.assert_allclose(np.tril(out),
                               sla.cholesky(a, lower=True), atol=1e-4)


def test_disable_route_strict_raises(tmp_path):
    from dlaf_tpu.health import inject
    from dlaf_tpu.health.errors import DegradationError

    _metrics_on(tmp_path, step_impl="fused", strict=True)
    with inject.disable_route("pallas"):
        with pytest.raises(DegradationError):
            _factor("L", hpd(16, seed=9), 8)


def test_step_kernel_counter(tmp_path, devices8):
    """Trace-time dlaf_step_kernel_total{impl}: one count per emitted
    strip-bearing step — nt-1 = 3 for n=32 nb=8 on the local unrolled
    and dist unrolled builders, under the impl the route resolved."""
    n, nb = 32, 8
    a = hpd(n, seed=10)
    for grid in (None, Grid(2, 2)):
        _metrics_on(tmp_path, step_impl="fused")
        base = step_count("fused")
        _factor("L", a, nb, grid=grid)
        assert step_count("fused") - base == 3, grid
        _metrics_on(tmp_path, step_impl="xla")
        base_x = step_count("xla")
        _factor("U", a, nb, grid=grid)
        assert step_count("xla") - base_x == 3, grid


# ---------------------------------------------------------------------------
# jaxpr pins (acceptance criteria)
# ---------------------------------------------------------------------------

def _iter_pallas(eqn):
    if eqn.primitive.name == "pallas_call":
        yield eqn
    for _, sub in depgraph.subjaxprs(eqn):
        for e in sub.eqns:
            yield from _iter_pallas(e)


def test_one_pallas_call_per_step(devices8):
    """jaxpr pin: the fused-step dist program holds exactly ONE
    pallas_call per strip-bearing step (nt-1) — the panel potrf and
    strip solve fused into one kernel where the fused-panel route
    needed two — plus the final step's standalone potrf when the panel
    route is also fused (2*nt-1 -> nt)."""
    from dlaf_tpu.algorithms.cholesky import _build_dist_cholesky

    C.initialize()
    grid = Grid(2, 2)
    mat = Matrix.from_global(hpd(24), TileElementSize(4, 4), grid=grid)
    nt = 6

    def count(panel_fused, step_fused):
        fn = _build_dist_cholesky(mat.dist, grid.mesh, "L", False, True,
                                  panel_fused=panel_fused,
                                  step_fused=step_fused)
        eqns = depgraph.shard_map_body(fn, mat.storage)
        return sum(1 for e in eqns for _ in _iter_pallas(e))

    assert count(panel_fused=False, step_fused=True) == nt - 1
    assert count(panel_fused=True, step_fused=True) == nt
    assert count(panel_fused=True, step_fused=False) == 2 * nt - 1


@pytest.mark.parametrize("uplo", ["L", "U"])
def test_comm_overlap_pin_under_fused_step(uplo, devices8):
    """The PR-4 lookahead independence pin holds with step_impl=fused:
    step k+1's transposed-panel all_gather is emitted before, and is
    independent of, step k's bulk product."""
    from dlaf_tpu.algorithms.cholesky import _build_dist_cholesky

    C.initialize()
    grid = Grid(2, 2)
    mat = Matrix.from_global(hpd(24), TileElementSize(4, 4), grid=grid)
    fn = _build_dist_cholesky(mat.dist, grid.mesh, uplo, False, True,
                              lookahead=True, comm_la=True,
                              step_fused=True)
    eqns = depgraph.shard_map_body(fn, mat.storage)
    ag = depgraph.positions(eqns, "all_gather")
    bulk = depgraph.positions(eqns, depgraph.is_bulk_dot)
    assert len(ag) >= 2 and bulk
    assert ag[1] < bulk[0], (ag, bulk)
    assert not depgraph.depends_on(eqns, ag[1], depgraph.is_bulk_dot)


# ---------------------------------------------------------------------------
# the committed critpath fixture pair (pre/post, ISSUE 19)
# ---------------------------------------------------------------------------

def test_critpath_fixture_pair_gap_shrinks():
    """The committed fixture pair (tests/fixtures/critpath_prestep/ =
    composed-op step route, tests/fixtures/critpath/ = fused step route;
    same n/nb/grid/f32, same documented 2 ms injection before
    cholesky.step002 — scripts/refresh_devtrace_fixture.py) carries the
    step-gap claim hermetically: each leg's artifact pins its route via
    ``dlaf_step_kernel_total{impl}``, and the fused leg's residual
    boundary gap at the injected step is SMALLER — the one-kernel step
    spans the boundary and absorbs more of the stall."""
    from dlaf_tpu.obs import critpath
    from dlaf_tpu.obs.aggregate import merge_artifacts
    from dlaf_tpu.obs.devtrace import load_trace

    here = os.path.dirname(os.path.abspath(__file__))
    gaps = {}
    for name, impl in (("critpath_prestep", "xla"), ("critpath", "fused")):
        fixdir = os.path.join(here, "fixtures", name)
        records = merge_artifacts([os.path.join(fixdir, "merged.jsonl")])
        counts = {}
        for r in records:
            if r.get("type") == "metrics":
                for m in r["metrics"]:
                    if m["name"] == "dlaf_step_kernel_total":
                        counts[m["labels"]["impl"]] = \
                            counts.get(m["labels"]["impl"], 0) + m["value"]
        # route pin: ONLY the leg's own impl counted, 3 strip-bearing
        # steps x 2 participating artifacts
        assert counts == {impl: 6.0}, (name, counts)
        report = critpath.attribute(
            load_trace(os.path.join(fixdir, "trace.json.gz")), records)
        prog = report["programs"]["cholesky"]
        assert prog["n_steps"] == 4, (name, prog["n_steps"])
        step_gaps = [s.get("gap_after_s", 0.0) for s in prog["steps"]
                     if not s.get("empty")]
        # the injected stall surfaces at the step002 boundary and ONLY
        # there on both legs (same spec -> the pair isolates the route)
        assert max(step_gaps) == step_gaps[1] > 0, (name, step_gaps)
        gaps[name] = step_gaps[1]
    assert gaps["critpath"] < gaps["critpath_prestep"], gaps
