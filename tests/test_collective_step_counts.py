"""The collective counters count per EXECUTED step (ISSUE 27).

``comm.collectives._record`` runs at trace time and a ``lax.scan`` body is
traced once for all its iterations, so inside a scan builder's body it
multiplies by the trip count the builder hands ``obs.scoped_step``. Pinned
here: the scan-form triangular solve of the benchmark's 2x2 configuration
class against hand arithmetic from ``telescope_windows``, the unrolled form's
counts (unchanged by that), the other distributed scan builders scaling with
their step count, and the wrapper's behaviour when JAX traces a body twice.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.linalg

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.algorithms.triangular import triangular_solve
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import (GlobalElementSize, GridSize2D,
                                     TileElementSize)
from dlaf_tpu.matrix.distribution import Distribution
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.matrix.panel import uniform_slot_start
from dlaf_tpu.matrix.tiling import storage_tile_grid
from dlaf_tpu.types import telescope_windows

COUNT = "dlaf_comm_collective_count_total"
BYTES = "dlaf_comm_collective_bytes_total"


@pytest.fixture(autouse=True)
def obs_reset():
    """Leave every test with the suite's default unobserved config."""
    yield
    os.environ.pop("DLAF_METRICS_PATH", None)
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


def _observe(tmp_path, name="m.jsonl", **knobs):
    """Metrics sink on (a new path, so the program caches are dropped and
    the next call traces its program)."""
    C.initialize(C.Configuration(metrics_path=str(tmp_path / name), **knobs))
    assert obs.metrics_active()


def _totals():
    """``(collectives, payload bytes)`` summed over kind and axis."""
    snap = obs.registry().snapshot()
    return tuple(int(sum(m["value"] for m in snap if m["name"] == name))
                 for name in (COUNT, BYTES))


def _trsm_inputs(n, seed=27):
    """The benchmark op's input class: strictly lower triangle of a normal
    matrix with 2n on the diagonal, normal right-hand sides (m = n)."""
    rng = np.random.default_rng(seed)
    t = np.tril(rng.standard_normal((n, n)), -1)
    t[np.diag_indices(n)] = 2.0 * n
    return t, rng.standard_normal((n, n))


def _solve_2x2(t, b, nb):
    grid = Grid(2, 2)
    size = TileElementSize(nb, nb)
    return triangular_solve("L", "L", "N", "N", 1.0,
                            Matrix.from_global(t, size, grid=grid),
                            Matrix.from_global(b, size, grid=grid)).to_numpy()


def _scan_solve_by_hand(n, nb, p=2, q=2):
    """Collectives and bytes of one LLNN scan-form solve of an n x n block
    (float64) on a p x q grid, serial body: per step one ``bcast2d`` of the
    diagonal tile (charged once per axis), the pivot block row of ``B``
    broadcast along the rows (all of this rank's column slots) and ``A``'s
    column panel broadcast along the columns (the segment's row window)."""
    nt = n // nb
    lt_rows, lt_cols = nt // p, nt // q
    tile = nb * nb * 8
    count = nbytes = 0
    for lo, _pos, seg_len in telescope_windows(
            nt, lambda pos, _len: uniform_slot_start(pos, p)):
        window = lt_rows - lo
        count += seg_len * 4
        nbytes += seg_len * tile * (2 + lt_cols + window)
    return count, nbytes


def test_scan_solve_counts_every_executed_step(tmp_path, devices8):
    """2x2 LLNN, n = 512, nb = 32 (nt = 16: two segments of 8 steps): the
    counters equal segment length times the body's collectives, summed over
    the telescoped segments, not one step per segment."""
    n, nb = 512, 32
    _observe(tmp_path, dist_step_mode="scan", cholesky_lookahead="0")
    t, b = _trsm_inputs(n)
    x = _solve_2x2(t, b, nb)
    assert _totals() == _scan_solve_by_hand(n, nb) == (64, 2_097_152)
    np.testing.assert_allclose(
        x, scipy.linalg.solve_triangular(t, b, lower=True),
        rtol=0, atol=1e-15)


def test_unrolled_solve_counts_are_what_they_were(tmp_path, devices8):
    """The unrolled builder emits every step itself and passes through no
    ``scoped_step``: 63 collectives, 1 835 008 bytes at this size, before
    and after the counters learned about trip counts."""
    _observe(tmp_path, dist_step_mode="unrolled")
    t, b = _trsm_inputs(512)
    _solve_2x2(t, b, 32)
    assert _totals() == (63, 1_835_008)


def test_scan_solve_matches_scipy_at_the_configuration_class(devices8):
    """m = n, LLNN, 2x2, scan form, float64 against
    ``scipy.linalg.solve_triangular``: the shape class of
    ``trsm-d-n8192-nb256-2x2`` (``test_triangular.py::
    test_solve_distributed_scan`` covers 2x4 / 4x2 grids with m != n)."""
    C.initialize(C.Configuration(dist_step_mode="scan"))
    n, nb = 512, 32
    t, b = _trsm_inputs(n, seed=28)
    x = _solve_2x2(t, b, nb)
    want = scipy.linalg.solve_triangular(t, b, lower=True)
    assert np.abs(x - want).max() <= 8 * np.finfo(np.float64).eps \
        * np.abs(want).max()
    # the benchmark's own check, at its tolerance for native float64
    w = np.random.default_rng(3).standard_normal((n, 8))
    res = np.linalg.norm(t @ (x @ w) - b @ w) \
        / (np.linalg.norm(t) * np.linalg.norm(x @ w))
    assert res <= 60 * n * 2.0 ** -52


def _trace_scan_builder(algo, steps, nb=8):
    """Trace (``eval_shape``: no compile, no run) the scan form of ``algo``
    with ``steps`` block steps on a 2x2 grid; the counters do the rest."""
    grid = Grid(2, 2)
    n = steps * nb if algo in ("cholesky", "trmm") else (steps + 1) * nb
    dist = Distribution(GlobalElementSize(n, n), TileElementSize(nb, nb),
                        grid_size=GridSize2D(2, 2))
    rows, cols, _, _ = storage_tile_grid(dist)
    tiles = jax.ShapeDtypeStruct((rows, cols, nb, nb), jnp.float64)
    if algo == "cholesky":
        from dlaf_tpu.algorithms.cholesky import _build_dist_cholesky_scan

        jax.eval_shape(_build_dist_cholesky_scan(dist, grid.mesh, "L"),
                       tiles)
    elif algo == "trmm":
        from dlaf_tpu.algorithms.triangular import _build_dist_mult_scan

        jax.eval_shape(
            _build_dist_mult_scan(dist, dist, grid.mesh, "L", "L", "N", "N",
                                  "float64"),
            tiles, tiles, jax.ShapeDtypeStruct((), jnp.float64))
    elif algo == "red2band":
        from dlaf_tpu.eigensolver.reduction_to_band import \
            _build_dist_red2band_scan

        jax.eval_shape(
            _build_dist_red2band_scan(dist, grid.mesh, "float64", nb), tiles)
    else:
        from dlaf_tpu.eigensolver.back_transform import \
            _build_dist_bt_r2b_scan

        taus = jax.ShapeDtypeStruct((steps, nb), jnp.float64)
        jax.eval_shape(_build_dist_bt_r2b_scan(dist, dist, grid.mesh, nb),
                       tiles, taus, tiles)


@pytest.mark.parametrize("algo", ["cholesky", "red2band", "bt_r2b", "trmm"])
def test_scan_builder_counts_scale_with_the_step_count(algo, tmp_path,
                                                       devices8):
    """9 and 16 steps both telescope into two segments (8 + 1, 8 + 8): a
    count per traced body would be the same for both; per executed step it
    is the body's collectives times the step count."""
    counts = {}
    for steps in (9, 16):
        _observe(tmp_path, f"m{steps}.jsonl")
        _trace_scan_builder(algo, steps)
        counts[steps], nbytes = _totals()
        assert nbytes > 0
        obs._reset_for_tests()
    per_step, rest = divmod(counts[9], 9)
    assert per_step > 0 and rest == 0, counts
    assert counts[16] == 16 * per_step, counts


def test_a_body_traced_twice_counts_once(tmp_path):
    """``lax.scan`` traces its body a second time when a weakly typed carry
    comes out with another dtype than it went in with; the wrapper counts
    the first trace only. Nested wrapped scans multiply."""
    from dlaf_tpu.comm import collectives as cc

    _observe(tmp_path)
    traces = []

    def body(carry, _x):
        traces.append(obs.traced_step_count())
        cc._record("bcast", "row", np.zeros((2, 2)))
        return carry + jnp.ones((), jnp.float32), None

    jax.lax.scan(obs.scoped_step("test.scanstep", body, steps=5), 0.0,
                 jnp.arange(5))       # weakly typed float64 in, float32 out
    assert traces == [5, 0]
    assert _totals() == (5, 5 * 32)
    assert obs.traced_step_count() == 1

    def outer(carry, _x):
        inner = obs.scoped_step("test.inner", body, steps=3)
        return jax.lax.scan(inner, carry, jnp.arange(3))[0], None

    jax.lax.scan(obs.scoped_step("test.outer", outer, steps=4),
                 jnp.zeros((), jnp.float32), jnp.arange(4))
    assert traces[2:] == [12]
    assert _totals() == (17, 17 * 32)


def test_scoped_step_is_a_pass_through_when_off():
    C.initialize()
    assert not obs.enabled()

    def body(carry, _x):
        return carry, None

    assert obs.scoped_step("x.scanstep", body, steps=7) is body
    assert obs.traced_step_count() == 1
