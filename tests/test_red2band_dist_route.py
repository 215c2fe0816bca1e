"""The distributed reduction to band as the cell ``red2band_d_n16384_2x2``
runs it: ``reduction_to_band`` of a ``Matrix`` on a 2x2 ``Grid`` takes the
scan-form ``_build_dist_red2band_scan``, one ``shard_map`` program a call.

The public entry RUNS here on a 2x2 grid of the CPU's virtual devices under
a TPU's knob resolution (``as_on_tpu``: the householder sweep, seven-slice
products) with band < nb as published (nb = 128, band = 32), the scan form
asked for (15 panels are under ``dist_step_mode`` auto's 32; the cell's 127
take it unasked) and the slice route's gate at the test's band
(``f64_gemm_min_dim`` is 128, the published band: at band 32 the products
would stay native and the route compared would not be the chip's). At an
order nb divides and at one it does not, once from a non-zero source rank.

It is compared with the benchmark's plain references (numpy float64, no jax,
no code of ``dlaf_tpu``): ``benchmark/reference/band_reduction.py``
elementwise (the band, the stored reflector tails, the taus) and through the
cell's three checks; ``benchmark/reference/cholesky_block_cyclic.py``'s
``local_tiles`` for EVERY DEVICE'S SHARD of the result; every device's copy
of the taus against chip (0, 0)'s. Tolerance ``100 n 2^-47`` of the largest
entry, the cell's own limit (the slice products carry 49 bits; a Householder
reduction's reflectors are good to that times the conditioning of the panels
they were formed from: tens). The counters the cell's metrics read are
checked against hand counts from the step structure, and the scopes against
the lowered and the compiled text.
"""

import functools
import importlib
import importlib.util
import os
import re

import jax
import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import RankIndex2D, TileElementSize
from dlaf_tpu.eigensolver import reduction_to_band
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.obs import scopes, telemetry
from dlaf_tpu.tile_ops import ozaki as oz

r2b = importlib.import_module("dlaf_tpu.eigensolver.reduction_to_band")

EPS_TPU = 2.0 ** -47
NB, BAND = 128, 32       # band < nb, as the published configuration's
N = 512                  # 15 panels, two telescoped bodies
N_RAGGED = 488           # 15 panels too, the last tile 104 wide
GRID = (2, 2)
SLICES = 7               # f64_gemm_slices auto on a TPU
ITEM = 8                 # bytes of a float64
#: the phases of the distributed body: the local body's and the two of
#: its collectives
PHASES = {"gather", "exchange", "panel", "larft", "w", "x", "update"}
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter")


def _load(*parts):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference", *parts)
    spec = importlib.util.spec_from_file_location(parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("band_reduction.py")
cyclic = _load("cholesky_block_cyclic.py")


@pytest.fixture(autouse=True)
def obs_reset():
    yield
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


def _configure(**knobs):
    C.initialize(C.Configuration(dist_step_mode="scan",
                                 f64_gemm_min_dim=BAND, **knobs))
    C._clear_program_caches()


@functools.lru_cache(maxsize=None)
def _sym(n, seed):
    """The benchmark's input: ``(G + G^T)/2`` (benchmark/ops)."""
    g = np.random.default_rng(seed).standard_normal((n, n))
    a = (g + g.T) / 2
    a.setflags(write=False)
    return a


@functools.lru_cache(maxsize=None)
def _reference(n, seed):
    out, taus = ref.reduce_to_band(_sym(n, seed), BAND)
    out.setflags(write=False)
    taus.setflags(write=False)
    return out, taus


@pytest.fixture
def route(as_on_tpu, monkeypatch, devices8):
    """Which distributed builder the entry's program cache builds and what
    the traces peel: a case asserts on both, so neither the unrolled form
    nor a route that silently kept native products passes."""
    scan, unrolled = r2b._build_dist_red2band_scan, r2b._build_dist_red2band
    peel = oz._peel_slices
    seen = {"builders": [], "slices": set(),
            "grid": Grid(*GRID, devices=list(devices8[:4]))}

    def spy(name, build):
        def built(*args, **kw):
            seen["builders"].append(name)
            return build(*args, **kw)
        return built

    def spy_peel(xn, s):
        seen["slices"].add(int(s))
        return peel(xn, s)

    monkeypatch.setattr(r2b, "_build_dist_red2band_scan", spy("scan", scan))
    monkeypatch.setattr(r2b, "_build_dist_red2band",
                        spy("unrolled", unrolled))
    monkeypatch.setattr(oz, "_peel_slices", spy_peel)
    _configure()
    return seen


def _by_rank(grid, arr):
    """``{rank: that device's array}``, the rank of a device being its
    position in the grid's mesh."""
    where = {dev: rank for rank, dev in np.ndenumerate(grid.mesh.devices)}
    return {where[s.device]: np.asarray(s.data)
            for s in arr.addressable_shards}


def _reduce(n, grid, source=(0, 0)):
    mat = Matrix.from_global(_sym(n, n), TileElementSize(NB, NB), grid=grid,
                             source_rank=RankIndex2D(*source))
    return reduction_to_band(mat, band_size=BAND, donate=True)


# ---------------------------------------------------------------------------
# hand counts (from the step structure, not the builder's own arithmetic)
# ---------------------------------------------------------------------------

def hand_counts(n, nb, band, grid):
    """One call's ``(bodies, steps, columns, count, bytes)``, the last two
    ``{axis: n}`` per-axis records of its collectives.

    The panels, ``ceil(n / band) - 1``, run in telescoped segments of
    ``max(8, ceil(panels / 8))`` (the last one ragged); a segment whose
    first panel lies in tile column ``t`` works on the local row slots from
    the first one that holds a tile at or past ``t`` on some rank (slot
    ``l`` holds tiles ``l P .. l P + P - 1``), and neighbouring segments on
    the same slots are one body. A step sweeps ``band`` columns and moves,
    over ``col``: the panel column's broadcast and W's psum, and over
    ``row``: the panel column's all_gather, M's psum (``band x band``) and
    X's all_gather, each of the others ``rows x nb x band``."""
    nt = -(-n // nb)
    ltr = -(-nt // grid[0])
    panels = -(-n // band) - 1
    seg = max(8, -(-panels // 8))
    count = {"row": 0, "col": 0}
    nbytes = {"row": 0, "col": 0}
    offsets = []
    for pos in range(0, panels, seg):
        steps = min(seg, panels - pos)
        first = pos * band // nb
        off = max(0, -(-(first + 1 - grid[0]) // grid[0]))
        if not offsets or offsets[-1] != off:
            offsets.append(off)
        column = (ltr - off) * nb * band * ITEM
        count["col"] += 2 * steps
        nbytes["col"] += 2 * column * steps
        count["row"] += 3 * steps
        nbytes["row"] += (2 * column + band * band * ITEM) * steps
    return len(offsets), panels, panels * band, count, nbytes


def test_the_hand_count_at_the_cells_shape():
    """What the cell's program counts on the chip: 127 panels in eight
    bodies, 16 256 columns, 635 per-axis records a call (five a step), and
    the bytes one chip moves: 1150 MiB over ``col``, 1150 MiB and 127 M
    matrices of 128 KiB over ``row``."""
    bodies, steps, columns, count, nbytes = hand_counts(16384, 512, 128,
                                                        (2, 2))
    assert (bodies, steps, columns) == (8, 127, 16256)
    assert count == {"col": 254, "row": 381}
    mib = 2 ** 20
    assert nbytes == {"col": 1150 * mib, "row": 1150 * mib + 127 * 128 * 1024}


# ---------------------------------------------------------------------------
# the route, against the plain references
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n, source", [
    pytest.param(N, (0, 0), id="15panels"),
    pytest.param(N_RAGGED, (0, 0), id="15panels-ragged"),
    pytest.param(N_RAGGED, (1, 1), id="15panels-ragged-source-1-1"),
])
def test_result_shards_and_taus_against_the_references(n, source, route):
    red = _reduce(n, route["grid"], source)
    assert red.band == BAND
    out = np.asarray(red.matrix.to_numpy())
    taus = np.asarray(red.taus)
    a = _sym(n, n)
    want, want_taus = _reference(n, n)
    tol = 100 * n * EPS_TPU
    # elementwise: the band, the stored reflector tails, the taus
    offset = np.subtract.outer(np.arange(n), np.arange(n))
    band = (offset >= 0) & (offset <= BAND)
    below = offset > BAND
    assert np.abs(out - want)[band].max() <= tol * np.abs(want).max()
    assert np.abs(out - want)[below].max() <= tol          # tails: |v| <= 1
    assert taus.shape == want_taus.shape
    assert np.abs(taus - want_taus).max() <= tol           # 1 <= tau <= 2
    # the cell's three checks: A = Q B Q^H, Q orthogonal, A's eigenvalues
    x = np.random.default_rng(n + 1).standard_normal((n, 8))
    b = ref.band_of(out, BAND)
    ax = a @ x
    qhx = ref.apply_q(out, taus, BAND, x, adjoint=True)
    assert np.linalg.norm(ax - ref.apply_q(out, taus, BAND, b @ qhx)) \
        <= tol * np.linalg.norm(ax)
    assert np.linalg.norm(
        ref.apply_q(out, taus, BAND, ref.apply_q(out, taus, BAND, x),
                    adjoint=True) - x) <= tol * np.linalg.norm(x)
    lam = np.linalg.eigvalsh(a)
    assert np.abs(np.linalg.eigvalsh(b) - lam).max() \
        <= tol * np.abs(lam).max()
    # every device's shard is the block-cyclic map of the gathered result,
    # bit for bit, and every device holds the same taus
    shards = _by_rank(route["grid"], red.matrix.storage)
    assert sorted(shards) == [(0, 0), (0, 1), (1, 0), (1, 1)]
    for rank, shard in shards.items():
        mine = cyclic.local_tiles(out, NB, GRID, rank, source)
        assert shard.shape == mine.shape == (2, 2, NB, NB)
        np.testing.assert_array_equal(shard, mine)
    copies = _by_rank(route["grid"], red.taus)
    assert sorted(copies) == sorted(shards)
    for rank, copy in copies.items():
        np.testing.assert_array_equal(copy, copies[0, 0])
    assert route["builders"] == ["scan"], route
    assert route["slices"] == {SLICES}, route


def test_a_float32_grade_reduction_fails_the_tolerance():
    """The comparison is tight enough to catch a lower precision: the
    reference computed in float32 is over the limit on the band and on the
    cell's similarity check."""
    want, want_taus = _reference(N, N)
    low, low_taus = ref.reduce_to_band(_sym(N, N), BAND, dtype=np.float32)
    low, low_taus = low.astype(np.float64), low_taus.astype(np.float64)
    tol = 100 * N * EPS_TPU
    offset = np.subtract.outer(np.arange(N), np.arange(N))
    band = (offset >= 0) & (offset <= BAND)
    assert np.abs(low - want)[band].max() > 10 * tol * np.abs(want).max()
    x = np.random.default_rng(N + 1).standard_normal((N, 8))
    ax = _sym(N, N) @ x
    qhx = ref.apply_q(low, low_taus, BAND, x, adjoint=True)
    err = np.linalg.norm(
        ax - ref.apply_q(low, low_taus, BAND, ref.band_of(low, BAND) @ qhx))
    assert err > 10 * tol * np.linalg.norm(ax)


# ---------------------------------------------------------------------------
# counters, spans and scopes of the dispatched program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced(devices8, tmp_path_factory):
    """Two calls with the metrics sink on (one trace, two dispatches) under
    a TPU's knob resolution, once for the cases below: the registry's
    snapshot, the dispatched program's lowered text with and without
    locations, its compiled text and phase table, and the text a process
    with observability off lowers from the same handle."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    # jax keeps metadata out of the persistent cache's key: with the cache
    # on, this program would be served by whatever tree compiled it first,
    # scopes and all, and the phase table would read ``stale``
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")      # as_on_tpu
        built = []
        scan = r2b._build_dist_red2band_scan
        mp.setattr(r2b, "_build_dist_red2band_scan",
                   lambda *a, **kw: built.append(a) or scan(*a, **kw))
        _configure(metrics_path=str(tmp_path_factory.mktemp("obs")
                                    / "m.jsonl"))
        grid = Grid(*GRID, devices=list(devices8[:4]))
        for _ in range(2):
            red = _reduce(N, grid)
        jax.block_until_ready(red.matrix.storage)
        handle = telemetry._HANDLES["reduction_to_band.dist"]
        scoped = handle.fn.lower(*handle.args, **handle.kwargs)
        seen = {"built": built, "snapshot": obs.registry().snapshot(),
                "programs": telemetry.programs(),
                "table": telemetry.phase_table("reduction_to_band.dist"),
                "compiled": telemetry.compiled(
                    "reduction_to_band.dist").as_text(),
                "located": scoped.as_text(debug_info=True),
                "scoped": scoped.as_text()}
        # observability off: the program as the parent lowers it
        obs._reset_for_tests()
        C.finalize()
        _configure()
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


def _case_one_program_a_call(traced):
    assert len(traced["built"]) == 1
    assert traced["programs"] == ["reduction_to_band.dist"]
    assert _count(traced, "dlaf_entry_programs_total",
                  entry="reduction_to_band") == 2
    assert _count(traced, "dlaf_entry_calls_total",
                  entry="reduction_to_band") == 2
    assert _count(traced, "dlaf_fallback_total") == 0


def _case_form_counters(traced):
    bodies, steps, columns, _count_, _bytes = hand_counts(N, NB, BAND, GRID)
    assert (bodies, steps, columns) == (2, 15, 480)
    for name, want in (("bodies", bodies), ("steps", steps),
                       ("panel_columns", columns)):
        assert _count(traced, f"dlaf_red2band_{name}_total",
                      form="dist_scan") == want, name
        assert _count(traced, f"dlaf_red2band_{name}_total",
                      form="scan") == 0, name


def _case_collective_counts(traced):
    _b, steps, _c, count, _bytes = hand_counts(N, NB, BAND, GRID)
    for axis in ("row", "col"):
        assert _count(traced, "dlaf_comm_collective_count_total",
                      axis=axis) == count[axis]
    assert _count(traced, "dlaf_comm_collective_count_total") == 5 * steps
    assert _count(traced, "dlaf_comm_collective_count_total",
                  kind="bcast", axis="col") == steps
    assert _count(traced, "dlaf_comm_collective_count_total",
                  kind="all_gather", axis="row") == 2 * steps
    for axis in ("row", "col"):
        assert _count(traced, "dlaf_comm_collective_count_total",
                      kind="all_reduce", axis=axis) == steps


def _case_collective_bytes(traced):
    *_rest, nbytes = hand_counts(N, NB, BAND, GRID)
    assert nbytes == {"col": 1_507_328, "row": 1_630_208}
    for axis in ("row", "col"):
        assert _count(traced, "dlaf_comm_collective_bytes_total",
                      axis=axis) == nbytes[axis]


def _case_dispatch_span(traced):
    """``stage.reduction_to_band.dispatch`` around each call's one
    dispatch."""
    spans = [m for m in traced["snapshot"]
             if m["name"] == "dlaf_span_seconds"
             and m["labels"].get("span", "").startswith(
                 "stage.reduction_to_band.")]
    assert [m["labels"]["span"] for m in spans] == [
        "stage.reduction_to_band.dispatch"]
    assert spans[0]["count"] == 2


def _case_scopes(traced):
    """The seven phases in the lowered text's named locations and in the
    executable's phase table."""
    assert PHASES <= scopes.phases_of_text(traced["located"])
    assert not traced["table"]["stale"]
    assert PHASES <= set(traced["table"]["counts"])


def _case_collectives_are_gather_or_exchange(traced):
    """Every collective instruction of the executable is placed in
    ``gather`` or ``exchange``, and there are some."""
    phases = traced["table"]["phases"]
    placed = {}
    for inst, _op_name, rest in scopes.instructions(traced["compiled"]):
        opcode = re.match(r"\S+ ([a-z\-]+)\(", rest)
        if opcode and opcode.group(1).startswith(COLLECTIVES):
            placed[inst] = phases.get(inst)
    assert placed
    assert set(placed.values()) <= {"gather", "exchange"}, placed
    assert set(placed.values()) == {"gather", "exchange"}


def _case_scopes_are_metadata_only(traced):
    """The scopes change no instruction: with observability off the
    lowered module is, without locations, the one the sink-on process
    lowers."""
    assert not scopes.phases_of_text(traced["plain_located"])
    assert traced["scoped"] == traced["plain"]


CASES = [_case_one_program_a_call, _case_form_counters,
         _case_collective_counts, _case_collective_bytes,
         _case_dispatch_span, _case_scopes,
         _case_collectives_are_gather_or_exchange,
         _case_scopes_are_metadata_only]


@pytest.mark.parametrize("case", CASES,
                         ids=[c.__name__[len("_case_"):] for c in CASES])
def test_counters_spans_and_scopes(case, traced):
    case(traced)
