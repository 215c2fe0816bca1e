"""The distributed Cholesky as the cell ``chol_d_n4096_2x2`` runs it
(ISSUE 37).

The public entry RUNS here on a 2x2 grid of the CPU's virtual devices under
a TPU's knob resolution (``as_on_tpu``: ``cholesky_trailing`` ozaki,
``cholesky_lookahead`` 1, ``comm_lookahead`` 1, ``f64_trsm`` mixed, seven
slices), at the cell's 16 block steps of the smallest block at which the
DISTRIBUTED route traces slice products (nb = 128 = ``f64_gemm_min_dim``:
below it the distributed builders keep native products, route policy; n =
2048) and at one order nb does not divide, both ``uplo``, once from a
non-zero source rank: the unrolled ``_build_dist_cholesky``, one program a
call.

It is compared with the benchmark's plain reference
(``benchmark/reference/cholesky_block_cyclic.py``: numpy float64, the
unblocked right-looking factorization and the block-cyclic map from
ScaLAPACK's definition; no jax, no code of ``dlaf_tpu``): the factor at the
cell's tolerance ``60 n 2^-47``, and EVERY DEVICE'S SHARD against
``local_tiles`` of the reference for that device's rank, the triangle that
passes through bit for bit the input's. The counters the cell's metrics
read are checked against hand counts from the step structure, and the
scopes against the lowered text.
"""

import functools
import importlib
import importlib.util
import os

import jax
import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import RankIndex2D, TileElementSize
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.obs import scopes, telemetry
from dlaf_tpu.tile_ops import ozaki as oz

chol_mod = importlib.import_module("dlaf_tpu.algorithms.cholesky")

EPS_TPU = 2.0 ** -47
NB = 128
STEPS = 16               # the cell's: 4096 / 256
N = STEPS * NB
N_RAGGED = N - 48        # 16 block steps too, the last tile 80 wide
GRID = (2, 2)
SLICES = 7               # f64_gemm_slices auto on a TPU
ITEM = 8                 # bytes of a float64


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference",
        "cholesky_block_cyclic.py")
    spec = importlib.util.spec_from_file_location("cholesky_block_cyclic",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


@pytest.fixture(autouse=True)
def obs_reset():
    yield
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


@functools.lru_cache(maxsize=None)
def _hpd(n, seed):
    """The benchmark's input: ``(G + G^T)/2 + n I`` (benchmark/ops)."""
    g = np.random.default_rng(seed).standard_normal((n, n))
    a = (g + g.T) / 2 + n * np.eye(n)
    a.setflags(write=False)
    return a


@functools.lru_cache(maxsize=None)
def _reference_factor(n, seed, dtype=np.float64):
    """The plain reference's factor of ``_hpd(n, seed)`` (seven seconds at
    n = 2048: one a matrix, shared by the cases)."""
    low = ref.cholesky_unblocked(_hpd(n, seed), dtype=dtype)
    low.setflags(write=False)
    return low


@pytest.fixture
def route(as_on_tpu, monkeypatch, devices8):
    """Which distributed builder the entry's program cache builds and what
    the traces peel: a case asserts on both, so neither a scan-form program
    nor a route that silently kept native products passes."""
    unrolled, scan = (chol_mod._build_dist_cholesky,
                      chol_mod._build_dist_cholesky_scan)
    peel = oz._peel_slices
    seen = {"builders": [], "kwargs": {}, "slices": set(),
            "grid": Grid(*GRID, devices=list(devices8[:4]))}

    def spy(name, build):
        def built(*args, **kw):
            seen["builders"].append(name)
            seen["kwargs"] = kw
            return build(*args, **kw)
        return built

    def spy_peel(xn, s):
        seen["slices"].add(int(s))
        return peel(xn, s)

    monkeypatch.setattr(chol_mod, "_build_dist_cholesky",
                        spy("unrolled", unrolled))
    monkeypatch.setattr(chol_mod, "_build_dist_cholesky_scan",
                        spy("scan", scan))
    monkeypatch.setattr(oz, "_peel_slices", spy_peel)
    return seen


def _factor(uplo, a, route, source=(0, 0)):
    mat = Matrix.from_global(a, TileElementSize(NB, NB), grid=route["grid"],
                             source_rank=RankIndex2D(*source))
    return cholesky(uplo, mat, donate=True)


def _shards(out):
    """``{rank: the device's (slots_r, slots_c, nb, nb) array}``, the rank
    of a device being its position in the grid's mesh."""
    where = {dev: rank for rank, dev in np.ndenumerate(out.grid.mesh.devices)}
    return {where[s.device]: np.asarray(s.data)
            for s in out.storage.addressable_shards}


# ---------------------------------------------------------------------------
# hand counts (from the step structure, not the builder's own arithmetic)
# ---------------------------------------------------------------------------

def hand_comm(steps, nb, grid, nt_local):
    """Per-axis records of one call's collectives at ``steps`` block steps
    on a ``grid`` whose every rank holds ``nt_local`` tile rows and columns:
    ``(count, bytes, overlapped)``, each ``{axis: n}``.

    Step ``k`` broadcasts its diagonal tile to the whole grid: ONE
    all-reduce over both axes, recorded once on each. Every step but the
    last then solves its panel on the local row slots that hold a tile past
    ``k`` on some rank (from slot ``(k + 1) div P`` on: slot ``l`` holds
    tiles ``l P .. l P + P - 1``), broadcasts those tiles along ``col`` and
    all-gathers them along ``row`` (the transposed panel). With
    ``comm_lookahead`` the chain of every step but the first is emitted
    ahead of the step before's bulk product."""
    count = {"row": 0, "col": 0}
    nbytes = {"row": 0, "col": 0}
    over = {"row": 0, "col": 0}
    tile = nb * nb * ITEM
    for k in range(steps):
        step_count = {"row": 1, "col": 1}           # the diagonal's bcast2d
        step_bytes = {"row": tile, "col": tile}
        if k < steps - 1:
            slots = nt_local - (k + 1) // grid[0]
            step_count["col"] += 1                  # the panel's broadcast
            step_bytes["col"] += slots * tile
            step_count["row"] += 1                  # the transposed panel
            step_bytes["row"] += slots * tile
        for axis in count:
            count[axis] += step_count[axis]
            nbytes[axis] += step_bytes[axis]
            if k > 0:
                over[axis] += step_count[axis]
    return count, nbytes, over


def test_the_hand_count_at_the_cells_sixteen_steps():
    """What ``collectives_per_call`` and ``comm_overlapped_share`` must
    read on the chip: 62 per-axis records a call (46 collectives: 16
    diagonal broadcasts counted twice), 58 of them hoisted."""
    count, _nbytes, over = hand_comm(16, 256, (2, 2), 8)
    assert sum(count.values()) == 62 and count == {"row": 31, "col": 31}
    assert sum(over.values()) == 58
    assert 100.0 * 58 / 62 == pytest.approx(93.548387, abs=1e-6)


# ---------------------------------------------------------------------------
# the route, against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("uplo, n, source", [
    pytest.param("L", N, (0, 0), id="L-16steps"),
    pytest.param("U", N, (0, 0), id="U-16steps"),
    pytest.param("L", N_RAGGED, (0, 0), id="L-16steps-ragged"),
    pytest.param("U", N_RAGGED, (1, 1), id="U-16steps-ragged-source-1-1"),
])
def test_factor_and_every_shard_against_the_reference(uplo, n, source,
                                                      route):
    a, low = _hpd(n, n), _reference_factor(n, n)
    out = _factor(uplo, a, route, source)
    got = out.to_numpy()
    tol = 60 * n * EPS_TPU
    # the factor, gathered
    tri = np.tril(got) if uplo == "L" else np.triu(got).T
    err = np.linalg.norm(tri - low) / np.linalg.norm(low)
    assert err <= tol, err
    # every device's shard is what the reference says its rank must hold:
    # the factor's triangle to the tolerance; the other triangle and the
    # slots no tile maps to bit for bit
    want = low + np.triu(a, 1) if uplo == "L" else low.T + np.tril(a, -1)
    ones = np.ones((n, n))
    passes = np.triu(ones, 1) if uplo == "L" else np.tril(ones, -1)
    shards = _shards(out)
    assert sorted(shards) == [(0, 0), (0, 1), (1, 0), (1, 1)]
    for rank, shard in shards.items():
        mine = ref.local_tiles(want, NB, GRID, rank, source)
        assert shard.shape == mine.shape == (8, 8, NB, NB)
        inside = ref.local_tiles(ones, NB, GRID, rank, source) == 1
        err = np.linalg.norm((shard - mine)[inside]) / np.linalg.norm(mine)
        assert err <= tol, (rank, err)
        untouched = ref.local_tiles(passes, NB, GRID, rank, source) == 1
        np.testing.assert_array_equal(shard[untouched], mine[untouched])
        # past the matrix a slot holds the zeros it was stored with, or, on
        # the diagonal of the short last tile, the identity the potrf was
        # handed (the unrolled builder writes the padded factor back; no
        # caller reads it: ``to_numpy`` and ``tile`` cut it off)
        pad = shard[~inside]
        assert np.all((pad == 0) | (pad == 1)), rank
        assert np.count_nonzero(pad) <= (NB - n % NB) % NB, rank
    assert route["builders"] == ["unrolled"], route
    assert route["kwargs"]["use_mxu"] and route["kwargs"]["use_mixed"]
    assert route["kwargs"]["lookahead"] and route["kwargs"]["comm_la"]
    assert route["slices"] == {SLICES}, route


def test_a_float32_factor_fails_the_tolerance():
    """The comparison is tight enough to catch a lower precision: the
    reference computed in float32 is over the limit on the gathered factor
    and on every rank's tiles."""
    low = _reference_factor(N, N)
    low32 = _reference_factor(N, N, np.float32).astype(np.float64)
    tol = 60 * N * EPS_TPU
    assert np.linalg.norm(low32 - low) / np.linalg.norm(low) > 10 * tol
    for rank in np.ndindex(*GRID):
        mine = ref.local_tiles(low, NB, GRID, rank)
        lower = ref.local_tiles(low32, NB, GRID, rank)
        assert np.linalg.norm(lower - mine) / np.linalg.norm(mine) > 10 * tol


# ---------------------------------------------------------------------------
# counters, spans and scopes of the dispatched program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced(devices8, tmp_path_factory):
    """Two calls with the metrics sink on (one trace, two dispatches) under
    a TPU's knob resolution, once for the cases below: the registry's
    snapshot, the dispatched program's lowered text with and without
    locations, its phase table, and the text a process with observability
    off lowers from the same handle."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    # jax keeps metadata out of the persistent cache's key: with the cache
    # on, this program (an eight-second compile, so it is cached) would be
    # served by whatever tree compiled it first, scopes and all, and the
    # phase table would read ``stale``
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")      # as_on_tpu
        built = []
        unrolled = chol_mod._build_dist_cholesky
        mp.setattr(chol_mod, "_build_dist_cholesky",
                   lambda *a, **kw: built.append(kw) or unrolled(*a, **kw))
        C.initialize(C.Configuration(
            metrics_path=str(tmp_path_factory.mktemp("obs") / "m.jsonl")))
        C._clear_program_caches()
        grid = Grid(*GRID, devices=list(devices8[:4]))
        for _ in range(2):
            mat = Matrix.from_global(_hpd(N, N), TileElementSize(NB, NB),
                                     grid=grid)
            out = cholesky("L", mat, donate=True)
        jax.block_until_ready(out.storage)
        handle = telemetry._HANDLES["cholesky.dist"]
        scoped = handle.fn.lower(*handle.args, **handle.kwargs)
        seen = {"built": built, "snapshot": obs.registry().snapshot(),
                "programs": telemetry.programs(),
                "table": telemetry.phase_table("cholesky.dist"),
                "located": scoped.as_text(debug_info=True),
                "scoped": scoped.as_text()}
        # observability off: the program as the parent lowers it
        obs._reset_for_tests()
        C.finalize()
        C.initialize()
        C._clear_program_caches()
        handle.fn.clear_cache()     # jit's own trace of the scoped program
        assert not obs.metrics_active()
        plain = handle.fn.lower(*handle.args, **handle.kwargs)
        seen["plain_located"] = plain.as_text(debug_info=True)
        seen["plain"] = plain.as_text()
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()
    C.initialize()
    C._clear_program_caches()
    return seen


def _count(traced, name, **labels):
    return sum(m["value"] for m in traced["snapshot"]
               if m["name"] == name
               and all(m["labels"].get(k) == v for k, v in labels.items()))


def _case_unrolled_one_program(traced):
    assert len(traced["built"]) == 1 and traced["built"][0]["comm_la"]
    assert _count(traced, "dlaf_cholesky_steps_total", algo="cholesky_dist",
                  mode="overlapped") == STEPS - 1
    assert _count(traced, "dlaf_cholesky_steps_total", algo="cholesky_dist",
                  mode="serialized") == 1
    assert _count(traced, "dlaf_cholesky_steps_total",
                  algo="cholesky_dist_scan") == 0
    assert _count(traced, "dlaf_entry_programs_total", entry="cholesky") == 2
    assert _count(traced, "dlaf_entry_calls_total", entry="cholesky") == 2
    assert _count(traced, "dlaf_fallback_total") == 0
    assert traced["programs"] == ["cholesky.dist"]


def _case_collective_counts(traced):
    count, _nbytes, _over = hand_comm(STEPS, NB, GRID, 8)
    for axis in ("row", "col"):
        assert _count(traced, "dlaf_comm_collective_count_total",
                      axis=axis) == count[axis]
    assert _count(traced, "dlaf_comm_collective_count_total") == 62
    assert _count(traced, "dlaf_comm_collective_count_total",
                  kind="bcast2d") == 2 * STEPS
    assert _count(traced, "dlaf_comm_collective_count_total",
                  kind="bcast") == STEPS - 1
    assert _count(traced, "dlaf_comm_collective_count_total",
                  kind="all_gather") == STEPS - 1


def _case_collective_bytes(traced):
    _counts, nbytes, _over = hand_comm(STEPS, NB, GRID, 8)
    for axis in ("row", "col"):
        assert _count(traced, "dlaf_comm_collective_bytes_total",
                      axis=axis) == nbytes[axis]


def _case_overlapped(traced):
    _counts, _nbytes, over = hand_comm(STEPS, NB, GRID, 8)
    for axis in ("row", "col"):
        assert _count(traced, "dlaf_comm_overlapped_total",
                      algo="cholesky_dist", axis=axis) == over[axis]
    assert _count(traced, "dlaf_comm_overlapped_total") == 58


def _case_dispatch_span(traced):
    """``stage.cholesky.dispatch`` around each call's one dispatch (the
    span's histogram is named after what the profiler's annotation is)."""
    spans = [m for m in traced["snapshot"]
             if m["name"] == "dlaf_span_seconds"
             and m["labels"].get("span") == "stage.cholesky.dispatch"]
    assert len(spans) == 1 and spans[0]["count"] == 2, spans


def _case_scopes(traced):
    """``cholesky.comm`` beside ``panel`` / ``strip`` / ``bulk`` in the
    lowered text's named locations and in the executable's phase table; a
    hoisted chain's collectives are the NEXT step's ``comm``."""
    want = {"comm", "panel", "strip", "bulk"}
    assert want <= scopes.phases_of_text(traced["located"])
    assert "cholesky.step001.panel/cholesky.comm" in traced["located"]
    assert not traced["table"]["stale"]
    assert want <= set(traced["table"]["counts"])


def _case_scopes_are_metadata_only(traced):
    """The scopes change no instruction: with observability off the
    lowered module is, without locations, the one the sink-on process
    lowers."""
    assert not scopes.phases_of_text(traced["plain_located"])
    assert traced["scoped"] == traced["plain"]


CASES = [_case_unrolled_one_program, _case_collective_counts,
         _case_collective_bytes, _case_overlapped, _case_dispatch_span,
         _case_scopes, _case_scopes_are_metadata_only]


@pytest.mark.parametrize("case", CASES,
                         ids=[c.__name__[len("_case_"):] for c in CASES])
def test_counters_spans_and_scopes(case, traced):
    case(traced)
