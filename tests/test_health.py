"""Robustness-layer tests (dlaf_tpu.health — ISSUE 3).

Covers: the potrf_info tile contract across dtypes x uplo (pinning the
backend NaN semantics the docstring claims), the in-graph ``with_info``
plumbing through all four cholesky builders (bitwise-identical factors,
no host sync — transfer-guard and jaxpr proofs), the singular-diagonal
detection of the triangular solve and HEGST, the shift-retry
``robust_cholesky`` driver (recovery, exhaustion, spans, counters, the
DLAF_CHECK finite guard), and — via ``health.inject`` — every
degradation path end-to-end: non-SPD -> shift-retry, native-load failure
-> numpy, pallas-off -> XLA, ozaki-off -> plain dot, strict mode ->
raise; each with its ``dlaf_fallback_total`` accounting asserted, local
and distributed.
"""

import os

import jax
import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu import health, obs
from dlaf_tpu.algorithms.cholesky import (_cholesky_local, cholesky)
from dlaf_tpu.algorithms.gen_to_std import gen_to_std
from dlaf_tpu.algorithms.triangular import triangular_solve
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.health import inject
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.tile_ops import lapack as tl

DTYPES = [np.float32, np.float64, np.complex64, np.complex128]


@pytest.fixture(autouse=True)
def health_reset():
    """Leave every test with the suite's default config and no metrics."""
    yield
    os.environ.pop("DLAF_METRICS_PATH", None)
    obs._reset_for_tests()
    health.circuit.reset()            # no tripped breaker leaks between
    C.finalize()                      # tests (docs/robustness.md §3)
    C.initialize()


def _metrics_on(tmp_path, **cfg):
    path = str(tmp_path / "health.jsonl")
    C.initialize(C.Configuration(metrics_path=path, **cfg))
    return path


def hpd_matrix(n, dtype=np.float64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n))
    if np.dtype(dtype).kind == "c":
        x = x + 1j * rng.standard_normal((n, n))
    return (x @ x.conj().T + n * np.eye(n)).astype(dtype)


def Matrix_from(a, nb, grid=None):
    return Matrix.from_global(a, TileElementSize(nb, nb), grid=grid)


def fallback_count(site, reason="native_unavailable"):
    return obs.registry().counter(health.FALLBACK_COUNTER, site=site,
                                  reason=reason).snapshot()["value"]


# ---------------------------------------------------------------------------
# potrf_info tile contract (satellite: pin the docstring's claims)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_potrf_info_semantics(uplo, dtype):
    """SPD -> info 0 with the factor byte-equal to plain potrf; non-SPD ->
    nonzero info = first non-finite diagonal. On CPU, XLA NaNs the WHOLE
    factor (the docstring's claim at tile_ops/lapack.py:84, previously
    untested): even a failure at column 4 reports info == 1."""
    a = hpd_matrix(6, dtype)
    f_ref = np.asarray(tl.potrf(uplo, a))
    f, info = tl.potrf_info(uplo, a)
    assert int(info) == 0
    np.testing.assert_array_equal(np.asarray(f), f_ref)

    bad = a.copy()
    bad[3, 3] = -1000.0          # leading minor fails at column 4 (1-based)
    f2, info2 = tl.potrf_info(uplo, bad)
    d = np.diagonal(np.asarray(f2)).real
    assert int(info2) >= 1
    assert int(info2) == int(np.argmax(~np.isfinite(d))) + 1
    if jax.default_backend() == "cpu":
        # CPU semantics: the whole factor is NaN'd, so the locator
        # degrades to the first column — a success/failure signal first
        assert not np.isfinite(d).any()
        assert int(info2) == 1
    # the pass-through triangle is NOT part of the info signal
    other = np.tril(np.asarray(f2), -1) if uplo == "U" \
        else np.triu(np.asarray(f2), 1)
    assert np.isfinite(other.real).all()


# ---------------------------------------------------------------------------
# with_info plumbing: all four builders, bitwise factors, no host sync
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trailing", ["loop", "biggemm", "scan", "xla"])
def test_with_info_factor_bitwise_local(trailing, monkeypatch):
    monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", trailing)
    C.initialize()
    a = hpd_matrix(13)
    plain = cholesky("L", Matrix_from(a, 4)).to_numpy()
    fac, info = cholesky("L", Matrix_from(a, 4), with_info=True)
    assert int(info) == 0
    np.testing.assert_array_equal(fac.to_numpy(), plain)


@pytest.mark.parametrize("scan", [False, True])
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_with_info_factor_bitwise_distributed(uplo, scan, devices8,
                                              monkeypatch):
    if scan:
        monkeypatch.setenv("DLAF_CHOLESKY_TRAILING", "scan")
    C.initialize()
    grid = Grid(2, 4)
    a = hpd_matrix(16)
    plain = cholesky(uplo, Matrix_from(a, 4, grid)).to_numpy()
    fac, info = cholesky(uplo, Matrix_from(a, 4, grid), with_info=True)
    assert int(info) == 0
    np.testing.assert_array_equal(fac.to_numpy(), plain)


@pytest.mark.parametrize("grid_shape", [None, (2, 2)])
def test_with_info_detects_failing_column(grid_shape, devices8):
    """A non-SPD pivot in the second diagonal tile must report a failing
    column inside that tile (backend NaN prefix bounds the precision to
    the tile's first column), identically local and distributed."""
    a = hpd_matrix(16)
    a[6, 6] = -1e6               # tile 1 spans 1-based columns 5..8
    grid = Grid(*grid_shape) if grid_shape else None
    _, info = cholesky("L", Matrix_from(a, 4, grid), with_info=True)
    assert 5 <= int(info) <= 7


def test_with_info_no_host_sync():
    """The acceptance proof: with_info adds NO host sync to the hot path —
    the call completes under a device->host transfer guard (fetching info
    stays the caller's explicit decision), and the traced program carries
    no callback/infeed/outfeed primitives."""
    a = hpd_matrix(16)
    mat = Matrix_from(a, 4)
    cholesky("L", Matrix_from(a, 4), with_info=True)   # warm the caches
    with jax.transfer_guard_device_to_host("disallow"):
        fac, info = cholesky("L", mat, with_info=True)
    assert isinstance(info, jax.Array)                 # still on device
    assert int(info) == 0                              # fetch AFTER guard

    from dlaf_tpu.analysis import depgraph

    jaxpr = depgraph.trace(
        lambda x: _cholesky_local(x, uplo="L", nb=4, trailing="loop",
                                  with_info=True), a)
    assert not depgraph.callbacks(jaxpr), \
        "hot path grew a host-callback/transfer primitive"


@pytest.mark.parametrize("grid_shape", [None, (2, 2)])
def test_triangular_solve_with_info(grid_shape, devices8):
    n = 8
    a = np.tril(hpd_matrix(n)) + n * np.eye(n)
    b = np.arange(n * 4, dtype=np.float64).reshape(n, 4) / 7.0
    grid = Grid(*grid_shape) if grid_shape else None
    x, info = triangular_solve("L", "L", "N", "N", 1.0,
                               Matrix_from(a, 4, grid),
                               Matrix_from(b, 4, grid), with_info=True)
    assert int(info) == 0
    sing = a.copy()
    sing[5, 5] = 0.0
    x2, info2 = triangular_solve("L", "L", "N", "N", 1.0,
                                 Matrix_from(sing, 4, grid),
                                 Matrix_from(b, 4, grid), with_info=True)
    assert int(info2) == 6       # 1-based first singular global column
    # implicit unit diagonal is never singular
    _, info3 = triangular_solve("L", "L", "N", "U", 1.0,
                                Matrix_from(sing, 4, grid),
                                Matrix_from(b, 4, grid), with_info=True)
    assert int(info3) == 0


def test_gen_to_std_with_info():
    n = 8
    a = hpd_matrix(n, seed=1)
    l = np.tril(hpd_matrix(n)) + n * np.eye(n)
    out, info = gen_to_std("L", Matrix_from(a, 4), Matrix_from(l, 4),
                           with_info=True)
    assert int(info) == 0
    l[2, 2] = 0.0
    out2, info2 = gen_to_std("L", Matrix_from(a, 4), Matrix_from(l, 4),
                             with_info=True)
    assert int(info2) == 3


# ---------------------------------------------------------------------------
# shift_diagonal / robust_cholesky
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("grid_shape", [None, (2, 4)])
def test_shift_diagonal_exact(grid_shape, devices8):
    n = 13                        # non-divisible: exercises the edge tile
    a = hpd_matrix(n)
    grid = Grid(*grid_shape) if grid_shape else None
    shifted = health.shift_diagonal(Matrix_from(a, 4, grid), 2.5)
    np.testing.assert_array_equal(shifted.to_numpy(), a + 2.5 * np.eye(n))


@pytest.mark.parametrize("grid_shape", [None, (2, 4)])
def test_robust_cholesky_recovers(grid_shape, devices8, tmp_path):
    """The non-SPD -> shift-retry -> success path, local AND distributed,
    with the retry spans and counters landing in the JSONL artifact."""
    path = _metrics_on(tmp_path)
    n = 16
    a = hpd_matrix(n)
    indef = a - 2 * n * np.eye(n)          # strongly indefinite
    grid = Grid(*grid_shape) if grid_shape else None
    res = health.robust_cholesky("L", Matrix_from(indef, 4, grid))
    assert res.attempts > 1
    assert res.infos[-1] == 0 and all(i != 0 for i in res.infos[:-1])
    assert res.shifts[0] == 0.0 and res.shifts[-1] > 0
    # the factor factorizes the SHIFTED matrix
    f = np.tril(res.matrix.to_numpy())
    target = indef + res.shifts[-1] * np.eye(n)
    resid = np.linalg.norm(f @ f.T - target) / np.linalg.norm(target)
    assert resid < 60 * n * np.finfo(np.float64).eps
    obs.flush()
    records = obs.read_records(path)
    assert not obs.validate_records(records, require_retries=True)
    attempts = [r for r in records if r.get("type") == "span"
                and r.get("name") == "robust_cholesky.attempt"]
    assert len(attempts) == res.attempts
    assert [r["attrs"]["attempt"] for r in attempts] == \
        list(range(res.attempts))
    assert [r["attrs"]["shift"] for r in attempts] == list(res.shifts)
    assert [r["attrs"]["info"] for r in attempts] == list(res.infos)


def test_robust_cholesky_exhaustion_raises():
    a = hpd_matrix(8)
    a[2, 1] = a[1, 2] = np.nan             # unrecoverable by shifting
    with pytest.raises(health.FactorizationError) as ei:
        health.robust_cholesky("L", Matrix_from(a, 4), max_attempts=2)
    e = ei.value
    assert e.attempts == 2
    assert len(e.shifts) == 2 and e.shifts[0] == 0.0
    assert e.failing_column >= 1
    assert all(i != 0 for i in e.infos)


def test_robust_cholesky_first_try_spd():
    a = hpd_matrix(8)
    res = health.robust_cholesky("L", Matrix_from(a, 4))
    assert res.attempts == 1 and res.shifts == (0.0,) and res.infos == (0,)
    plain = cholesky("L", Matrix_from(a, 4)).to_numpy()
    np.testing.assert_array_equal(res.matrix.to_numpy(), plain)


def test_dlaf_check_finite_guard(tmp_path):
    _metrics_on(tmp_path, check=True)
    a = hpd_matrix(8)
    health.robust_cholesky("L", Matrix_from(a, 4))     # clean input passes
    a[3, 0] = np.nan
    with pytest.raises(health.CheckError) as ei:
        health.robust_cholesky("L", Matrix_from(a, 4))
    assert ei.value.what == "cholesky input" and ei.value.count == 1
    assert obs.registry().counter("dlaf_check_failures_total",
                                  what="cholesky input"
                                  ).snapshot()["value"] == 1


# ---------------------------------------------------------------------------
# fault injection: data corruption
# ---------------------------------------------------------------------------

def test_nan_tile_deterministic_and_detected():
    a = hpd_matrix(16)
    m1 = inject.nan_tile(Matrix_from(a, 4), seed=7)
    m2 = inject.nan_tile(Matrix_from(a, 4), seed=7)
    np.testing.assert_array_equal(m1.to_numpy(), m2.to_numpy())
    assert np.isnan(m1.to_numpy()).sum() == 1
    poisoned = inject.nan_tile(Matrix_from(a, 4), tile=(1, 0),
                               element=(2, 3))
    out = poisoned.to_numpy()
    assert np.isnan(out[6, 3]) and np.isnan(out).sum() == 1
    _, info = cholesky("L", poisoned, with_info=True)
    assert int(info) != 0


def test_corrupt_collective_detected_and_contained(devices8):
    """Poisoning one bcast payload must surface as nonzero info on the
    distributed factorization — and must NOT leak into later runs (the
    injection context clears compiled-program caches both ways)."""
    grid = Grid(2, 4)
    a = hpd_matrix(16)
    with inject.corrupt_collective("bcast", nth=0, seed=3):
        _, info = cholesky("L", Matrix_from(a, 4, grid), with_info=True)
        assert int(info) != 0
    _, clean = cholesky("L", Matrix_from(a, 4, grid), with_info=True)
    assert int(clean) == 0
    # deterministic: the same (nth, seed) poisons the same position
    with inject.corrupt_collective("bcast", nth=0, seed=3):
        _, info2 = cholesky("L", Matrix_from(a, 4, grid), with_info=True)
    assert int(info2) == int(info)


# ---------------------------------------------------------------------------
# fault injection: native-load failure -> numpy (+ bindings cache contract)
# ---------------------------------------------------------------------------

def test_bindings_cached_error_reraise_and_once_log(tmp_path, monkeypatch):
    """The cached-error re-raise path (bindings.get_lib): a failed build is
    cached — the compiler is NOT respawned per call — and the error-level
    log lands exactly once."""
    from dlaf_tpu.native import bindings

    path = _metrics_on(tmp_path)
    calls = []

    def failing_build():
        calls.append(1)
        raise RuntimeError("synthetic toolchain failure")

    monkeypatch.setattr(bindings, "_build", failing_build)
    # point at a nonexistent artifact so the build path always runs
    monkeypatch.setattr(bindings, "_LIB", str(tmp_path / "no-such-lib.so"))
    bindings._reset_for_tests()
    try:
        for _ in range(3):
            with pytest.raises(RuntimeError, match="synthetic"):
                bindings.get_lib()
        assert len(calls) == 1, "cached error must not respawn the build"
    finally:
        bindings._reset_for_tests()
    obs.flush()
    errors = [r for r in obs.read_records(path)
              if r.get("type") == "log" and r.get("level") == "error"
              and r.get("logger") == "native"]
    assert len(errors) == 1


def test_force_native_failure_degrades_to_numpy(tmp_path):
    from dlaf_tpu.eigensolver.band_to_tridiag import (band_to_tridiag,
                                                      band_to_tridiag_numpy)
    from dlaf_tpu.eigensolver.tridiag_solver import (_secular_roots,
                                                     _secular_roots_host)

    _metrics_on(tmp_path)
    band = np.zeros((3, 12))
    band[0] = np.arange(1.0, 13.0)
    band[1, :-1] = 0.5
    band[2, :-2] = 0.1
    d = np.arange(1.0, 7.0)
    z = np.full(6, 0.4)
    with inject.force_native_failure():
        chased = band_to_tridiag(band, 2)
        anchor, mu = _secular_roots_host(d, z, 0.5)
    ref = band_to_tridiag_numpy(band, 2)
    np.testing.assert_allclose(chased.d, ref.d)
    np.testing.assert_allclose(chased.e, ref.e)
    a_ref, m_ref = _secular_roots(d, z, 0.5)
    np.testing.assert_allclose(d[anchor] + mu, d[a_ref] + m_ref, rtol=1e-10)
    assert fallback_count("band_to_tridiag") >= 1
    assert fallback_count("secular") >= 1
    # outside the context the native library loads again
    from dlaf_tpu.native import bindings

    try:
        bindings.get_lib()
    except Exception:
        pytest.skip("no native toolchain in this environment")


def test_strict_mode_raises_instead_of_degrading(tmp_path):
    from dlaf_tpu.eigensolver.band_to_tridiag import band_to_tridiag

    _metrics_on(tmp_path, strict=True)
    band = np.zeros((3, 8))
    band[0] = np.arange(1.0, 9.0)
    with inject.force_native_failure():
        with pytest.raises(health.DegradationError) as ei:
            band_to_tridiag(band, 2)
    assert ei.value.site == "band_to_tridiag"
    assert ei.value.reason == "native_unavailable"


# ---------------------------------------------------------------------------
# fault injection: route degradations (pallas -> XLA, ozaki -> plain dot)
# ---------------------------------------------------------------------------

def test_pallas_off_degrades_to_xla(tmp_path, monkeypatch, devices8):
    """pallas-off -> XLA on the distributed f32 trailing update: with the
    route forced available (interpret mode off-TPU), disabling it via
    injection must register the degradation and still produce a correct
    factor through the einsum route."""
    monkeypatch.setenv("DLAF_FORCE_PALLAS_UPDATE", "1")
    _metrics_on(tmp_path)
    grid = Grid(2, 2)
    n = 8
    a = hpd_matrix(n, np.float32)
    via_pallas = cholesky("L", Matrix_from(a, 4, grid)).to_numpy()
    assert fallback_count("pallas_update", "injected_off") == 0
    with inject.disable_pallas():
        degraded = cholesky("L", Matrix_from(a, 4, grid)).to_numpy()
    assert fallback_count("pallas_update", "injected_off") >= 1
    for out in (via_pallas, degraded):
        f = np.tril(out)
        resid = np.linalg.norm(f @ f.T - a) / np.linalg.norm(a)
        assert resid < 60 * n * np.finfo(np.float32).eps


def test_ozaki_off_degrades_to_plain_dot(tmp_path):
    from dlaf_tpu.tile_ops import blas as tb

    path = str(tmp_path / "oz.jsonl")
    C.initialize(C.Configuration(metrics_path=path, f64_gemm="mxu",
                                 f64_gemm_min_dim=4))
    assert tb.f64_gemm_uses_mxu(np.float64, 8)
    with inject.disable_ozaki():
        assert not tb.f64_gemm_uses_mxu(np.float64, 8)
        # the plain-dot route still factorizes correctly
        a = hpd_matrix(8)
        out = cholesky("L", Matrix_from(a, 4)).to_numpy()
        f = np.tril(out)
        assert np.linalg.norm(f @ f.T - a) / np.linalg.norm(a) < 1e-12
    assert fallback_count("ozaki_gemm", "injected_off") >= 1
    assert tb.f64_gemm_uses_mxu(np.float64, 8)   # restored on exit


# ---------------------------------------------------------------------------
# multihost bring-up timeout
# ---------------------------------------------------------------------------

def test_multihost_timeout_actionable_error(monkeypatch):
    from dlaf_tpu.comm import multihost

    seen = {}

    def fake_initialize(coordinator_address=None, num_processes=None,
                        process_id=None, initialization_timeout=None):
        seen["timeout"] = initialization_timeout
        raise TimeoutError("deadline exceeded waiting for coordinator")

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    with pytest.raises(RuntimeError) as ei:
        multihost.initialize_multihost("10.0.0.1:8476", num_processes=4,
                                       process_id=1, timeout=5,
                                       connect_attempts=1)
    msg = str(ei.value)
    assert "10.0.0.1:8476" in msg and "timeout=5s" in msg
    assert "firewall" in msg and "SAME" in msg
    assert seen["timeout"] == 5
    # single-process worlds stay a no-op (no coordinator required)
    multihost.initialize_multihost(None, num_processes=1)


def test_multihost_connect_retries_transient_failures(monkeypatch,
                                                      tmp_path):
    """The coordinator connect rides the shared policy engine (PR 12):
    a transient bring-up failure retries with backoff and the world
    comes up on a later attempt; a caller bug raises immediately with
    its own message (never retried)."""
    from dlaf_tpu.comm import multihost
    from dlaf_tpu.health import policy as hpolicy

    _metrics_on(tmp_path)     # arm the registry: the counter assertion
                              # below must have teeth, not read a no-op

    calls = []

    def flaky_initialize(coordinator_address=None, num_processes=None,
                         process_id=None, initialization_timeout=None):
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("connection refused")

    slept = []
    monkeypatch.setattr(jax.distributed, "initialize", flaky_initialize)
    monkeypatch.setattr(hpolicy.time, "sleep", slept.append)
    multihost.initialize_multihost("10.0.0.1:8476", num_processes=4,
                                   process_id=1, connect_attempts=3,
                                   connect_backoff_s=0.25)
    assert len(calls) == 3 and len(slept) == 2
    assert slept[0] < slept[1]           # exponential backoff applied
    assert obs.registry().counter("dlaf_retry_total",
                                  site="multihost.connect"
                                  ).snapshot()["value"] == 2  # one per retry

    calls.clear()

    def buggy_initialize(**kw):
        calls.append(1)
        raise ValueError("already initialized")

    monkeypatch.setattr(jax.distributed, "initialize", buggy_initialize)
    with pytest.raises(ValueError, match="already initialized"):
        multihost.initialize_multihost("10.0.0.1:8476", num_processes=4,
                                       process_id=1)
    assert len(calls) == 1               # caller bugs are never retried


# ---------------------------------------------------------------------------
# DLAF_STRICT coverage audit (PR 12 satellite): EVERY report_fallback site
# must have a strict-raise assertion in this file — secular and
# band_to_tridiag are covered by the tests above/below; the rest here. The
# audit test at the end greps the source so a NEW site cannot land without
# extending this block.
# ---------------------------------------------------------------------------

def test_strict_deflate_site_raises(tmp_path):
    from dlaf_tpu.eigensolver.tridiag_solver import _deflation_scan

    _metrics_on(tmp_path, strict=True)
    ds = np.array([1.0, 1.0 + 1e-14, 2.0])
    zs = np.array([0.5, 0.5, 0.5])
    live = np.ones(3, dtype=bool)
    with inject.force_native_failure():
        with pytest.raises(health.DegradationError) as ei:
            _deflation_scan(ds, zs, live, 1e-8)
    assert ei.value.site == "deflate"


def test_strict_pallas_update_site_raises(tmp_path, monkeypatch, devices8):
    monkeypatch.setenv("DLAF_FORCE_PALLAS_UPDATE", "1")
    _metrics_on(tmp_path, strict=True)
    a = hpd_matrix(8, np.float32)
    with inject.disable_pallas():
        with pytest.raises(health.DegradationError) as ei:
            cholesky("L", Matrix_from(a, 4, Grid(2, 2)))
    assert ei.value.site == "pallas_update"
    assert ei.value.reason == "injected_off"


def test_strict_ozaki_gemm_site_raises(tmp_path):
    from dlaf_tpu.tile_ops import blas as tb

    path = str(tmp_path / "strict_oz.jsonl")
    C.initialize(C.Configuration(metrics_path=path, strict=True,
                                 f64_gemm="mxu", f64_gemm_min_dim=4))
    with inject.disable_ozaki():
        with pytest.raises(health.DegradationError) as ei:
            tb.f64_gemm_uses_mxu(np.float64, 8)
    assert ei.value.site == "ozaki_gemm"


def test_strict_panel_site_raises(tmp_path):
    path = str(tmp_path / "strict_panel.jsonl")
    C.initialize(C.Configuration(metrics_path=path, strict=True,
                                 panel_impl="fused"))
    a = hpd_matrix(16, np.float32)
    with inject.disable_pallas():
        with pytest.raises(health.DegradationError) as ei:
            cholesky("L", Matrix_from(a, 4))
    assert ei.value.site == "panel"


def test_strict_step_site_raises(tmp_path):
    path = str(tmp_path / "strict_step.jsonl")
    C.initialize(C.Configuration(metrics_path=path, strict=True,
                                 step_impl="fused", step_vmem_limit=1024))
    a = hpd_matrix(16, np.float32)
    with pytest.raises(health.DegradationError) as ei:
        cholesky("L", Matrix_from(a, 4))
    assert ei.value.site == "step"
    assert ei.value.reason == "vmem_budget"


def test_strict_coverage_audit_no_unlisted_site():
    """The audit itself: every ``report_fallback``/``route_available``
    site literal in dlaf_tpu/ must be in the strict-covered list below
    (each entry has a strict-raise test in this file). A new degradation
    site cannot land without a strict assertion riding along."""
    import re

    covered = {"secular", "deflate", "band_to_tridiag", "pallas_update",
               "ozaki_gemm", "panel", "step"}
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "dlaf_tpu")
    found = set()
    pat = re.compile(
        r"report_fallback\(\s*['\"]([a-z0-9_]+)['\"]"
        r"|route_available\(\s*['\"][a-z0-9_]+['\"]\s*,"
        r"\s*['\"]([a-z0-9_]+)['\"]")
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            src = open(os.path.join(dirpath, fn)).read()
            for m in pat.finditer(src):
                found.add(m.group(1) or m.group(2))
    # registry.py's own "circuit_open" reason-path and docstring mentions
    # are not sites; the regex only matches call-site literals
    assert found, "audit found no degradation sites — regex rotted?"
    assert found <= covered, \
        f"degradation site(s) {sorted(found - covered)} have no strict-" \
        "raise test in tests/test_health.py — add one and list it here"


# ---------------------------------------------------------------------------
# fault injection parity: the PR-6 eigensolver pipeline paths
# (hoisted bt collectives + the level-batched secular route)
# ---------------------------------------------------------------------------

def test_corrupt_all_gather_reaches_bt_chain(devices8, monkeypatch):
    """corrupt_collective("all_gather") must reach the bt_reduction_to_band
    panel gather even when bt_lookahead hoists it ahead of the bulk
    (the drill targets "a collective on the back-transform chain"; the
    hoist must not move the payload out of the corruption's reach) — and
    the poison must NOT leak into later runs."""
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.eigensolver.back_transform import bt_reduction_to_band
    from dlaf_tpu.eigensolver.reduction_to_band import reduction_to_band

    monkeypatch.setenv("DLAF_BT_LOOKAHEAD", "1")
    monkeypatch.setenv("DLAF_DIST_STEP_MODE", "unrolled")
    C.initialize()
    try:
        rng = np.random.default_rng(7)
        n, nb = 24, 4
        x = rng.standard_normal((n, n))
        a = x @ x.T + n * np.eye(n)
        c = rng.standard_normal((n, n))
        grid = Grid(2, 2)

        def run():
            red = reduction_to_band(Matrix.from_global(
                a, TileElementSize(nb, nb), grid=grid))
            return bt_reduction_to_band(red, Matrix.from_global(
                c, TileElementSize(nb, nb), grid=grid)).to_numpy()

        clean = run()
        assert np.isfinite(clean).all()
        with inject.corrupt_collective("all_gather", nth=0, seed=5):
            poisoned = run()
        assert np.isnan(poisoned).any(), \
            "all_gather corruption never reached the hoisted bt gather"
        again = run()
        np.testing.assert_array_equal(again, clean)
    finally:
        monkeypatch.delenv("DLAF_BT_LOOKAHEAD", raising=False)
        monkeypatch.delenv("DLAF_DIST_STEP_MODE", raising=False)
        C.initialize()


def test_level_batched_secular_native_failure(tmp_path, monkeypatch):
    """Batched D&C + injected native failure: every merge's host secular
    solve must degrade to the numpy bisection THROUGH the registry
    (dlaf_fallback_total{site="secular"} counted), and the batched
    decomposition must stay correct."""
    import scipy.linalg as sla

    from dlaf_tpu.eigensolver.tridiag_solver import tridiag_solver

    _metrics_on(tmp_path, dc_level_batch="1")
    rng = np.random.default_rng(9)
    n = 64
    d = rng.standard_normal(n)
    e = rng.standard_normal(n - 1)
    with inject.force_native_failure():
        lam, q = tridiag_solver(d, e, 8, use_device=True)
    assert fallback_count("secular", "native_unavailable") >= 1
    np.testing.assert_allclose(lam, sla.eigvalsh_tridiagonal(d, e),
                               atol=1e-11)
    q = np.asarray(q)
    t = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    assert np.linalg.norm(t @ q - q * lam[None, :]) < 1e-10 * n


def test_level_batched_strict_mode_raises(tmp_path):
    """DLAF_STRICT under the batched route: the first secular degradation
    raises DegradationError instead of silently taking the ~100x numpy
    path (same contract as the serialized walk)."""
    from dlaf_tpu.eigensolver.tridiag_solver import tridiag_solver
    from dlaf_tpu.health.errors import DegradationError

    _metrics_on(tmp_path, dc_level_batch="1", strict=True)
    rng = np.random.default_rng(2)
    d = rng.standard_normal(48)
    e = rng.standard_normal(47)
    with inject.force_native_failure():
        with pytest.raises(DegradationError):
            tridiag_solver(d, e, 8, use_device=True)
