"""The local reduction to band on the route the chip runs (ISSUE 33).

``red2band_d_n8192_1x1`` (N=8192, nb=512, band=128: 63 panels) runs
``_red2band_local_scan``: on a TPU ``reduction_to_band`` takes the scan-form
builder from 32 panel steps on (``config.resolve_step_mode``). Here the
public entry RUNS on the CPU under a TPU's knob resolution (``as_on_tpu``,
the pattern of tests/test_tpu_route.py and tests/test_chol_scan_route.py)
with band < nb and 32 or more panels, and the result is compared
**elementwise** with the benchmark's plain reference
(``benchmark/reference/band_reduction.py``: numpy float64, unblocked
Householder columns applied two-sidedly one reflector at a time; no jax, no
code of ``dlaf_tpu``): the band, the stored reflector tails and the taus. So
the traced roll, the masks, the telescoped segments and the row chunks are
what is compared. The counters the cell's metrics read are checked against
hand counts.

Tolerance, ``100 n 2^-47`` of the largest entry (the cell's own limit): a
Householder reduction is backward stable, its reflectors are not computed to
working precision but to that times the conditioning of the panels they
were formed from (a random symmetric matrix: tens), and on this route the
two-sided update's products are seven-slice products (49 bits, 2^-47 with
the accumulation). Measured here: 8e-13 on the band and 7e-12 on the tails
at n=528 against a limit of 3.8e-10; a float32-grade reduction errs by 1e-6.
"""

import importlib
import importlib.util
import os

import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.eigensolver import reduction_to_band
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.tile_ops import blas as tb
from dlaf_tpu.tile_ops import ozaki as oz
from dlaf_tpu.types import telescope_segments

r2b = importlib.import_module("dlaf_tpu.eigensolver.reduction_to_band")

EPS_TPU = 2.0 ** -47
NB, BAND = 64, 16        # band < nb, as the published configuration's
SLICES = 7               # f64_gemm_slices auto on a TPU


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference",
        "band_reduction.py")
    spec = importlib.util.spec_from_file_location("band_reduction", path)
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


def _sym(n, seed):
    """The benchmark's input: ``(G + G^T)/2`` (benchmark/ops)."""
    g = np.random.default_rng(seed).standard_normal((n, n))
    return (g + g.T) / 2


@pytest.fixture
def route(as_on_tpu, monkeypatch):
    """Which local builder the entry dispatches, how many slices its
    products peel and what row-chunk widths its bodies resolve: a case
    asserts on all three, so neither a stale program cache nor a route that
    kept native products passes."""
    scan, unrolled = r2b._red2band_local_scan, r2b._red2band_local
    peel, chunk = oz._peel_slices, r2b._trail_chunk
    seen = {"builders": [], "slices": set(), "chunks": set()}

    def spy(name, fn):
        def run(*args, **kw):
            seen["builders"].append(name)
            return fn(*args, **kw)
        return run

    def spy_peel(xn, s):
        seen["slices"].add(int(s))
        return peel(xn, s)

    def spy_chunk(*args):
        seen["chunks"].add(chunk(*args))
        return chunk(*args)

    monkeypatch.setattr(r2b, "_red2band_local_scan", spy("scan", scan))
    monkeypatch.setattr(r2b, "_red2band_local", spy("unrolled", unrolled))
    monkeypatch.setattr(oz, "_peel_slices", spy_peel)
    monkeypatch.setattr(r2b, "_trail_chunk", spy_chunk)
    return seen


def _configure(tmp_path=None, **knobs):
    """The default configuration with the slice route's gate at the test's
    band: ``f64_gemm_min_dim`` is 128, the published band; at band 16 the
    products would stay native and the route compared would not be the
    chip's. Everything else resolves as on a TPU."""
    if tmp_path is not None:
        knobs["metrics_path"] = str(tmp_path / "obs.jsonl")
    C.initialize(C.Configuration(f64_gemm_min_dim=BAND, **knobs))


def _compare(n, out, taus):
    """Elementwise against the plain reference: band, tails, taus."""
    a = _sym(n, seed=n)
    want, want_taus = ref.reduce_to_band(a, BAND)
    tol = 100 * n * EPS_TPU
    scale = np.abs(want).max()
    offset = np.subtract.outer(np.arange(n), np.arange(n))
    band = (offset >= 0) & (offset <= BAND)
    below = offset > BAND
    assert np.abs(out - want)[band].max() <= tol * scale
    assert np.abs(out - want)[below].max() <= tol          # tails: |v| <= 1
    assert taus.shape == want_taus.shape
    assert np.abs(taus - want_taus).max() <= tol           # 1 <= tau <= 2
    # and the reduction it stores is A's: the cell's three checks
    x = np.random.default_rng(n + 1).standard_normal((n, 8))
    b = ref.band_of(out, BAND)
    qhx = ref.apply_q(out, taus, BAND, x, adjoint=True)
    ax = a @ x
    assert np.linalg.norm(ax - ref.apply_q(out, taus, BAND, b @ qhx)) \
        <= tol * np.linalg.norm(ax)
    assert np.linalg.norm(
        ref.apply_q(out, taus, BAND, ref.apply_q(out, taus, BAND, x),
                    adjoint=True) - x) <= tol * np.linalg.norm(x)
    lam = np.linalg.eigvalsh(a)
    assert np.abs(np.linalg.eigvalsh(b) - lam).max() \
        <= tol * np.abs(lam).max()


def _reduce(n):
    red = reduction_to_band(
        Matrix.from_global(_sym(n, seed=n), TileElementSize(NB, NB)),
        band_size=BAND, donate=True)
    assert red.band == BAND
    return np.asarray(red.matrix.to_numpy()), np.asarray(red.taus)


def _case_scan(n, route, tmp_path):
    _configure()
    _compare(n, *_reduce(n))
    assert route["builders"] == ["scan"], route
    assert route["slices"] == {SLICES}, route
    assert route["chunks"] == {0}, route


def _case_chunked(n, route, tmp_path):
    """``red2band_trail_chunk`` forced on (the auto rule binds where every
    dimension is 8192 or more: segment 0 of the cell): W = A (V T) and the
    rank-2b update run through ``_map_row_chunks``, ragged last chunk
    included, in every segment taller than the width."""
    _configure(red2band_trail_chunk=96)
    _compare(n, *_reduce(n))
    assert route["builders"] == ["scan"], route
    assert route["slices"] == {SLICES}, route
    assert 96 in route["chunks"], route


def _case_native_products(n, route, tmp_path, gate=128):
    """The default gate (128): at band 16 the products stay native f64; the
    scan form, its roll and its masks are the same."""
    C.initialize(C.Configuration(f64_gemm_min_dim=gate))
    _compare(n, *_reduce(n))
    assert route["builders"] == ["scan"], route
    assert route["slices"] == set(), route


def _case_gate_reads_band(n, route, tmp_path):
    """The gate between ``band`` and ``2 band``: the update's one product
    is ``2 band`` deep and keeps the route of the two ``band`` deep ones
    it replaced (native: nothing peels), as W and M do."""
    _case_native_products(n, route, tmp_path, gate=2 * BAND)


def _case_unrolled_below_32(n, route, tmp_path):
    """31 panels: the unrolled builder, against the same reference."""
    _configure()
    _compare(n, *_reduce(n))
    assert route["builders"] == ["unrolled"], route
    assert route["slices"] == {SLICES}, route


# ---------------------------------------------------------------------------
# hand counts (independent of the builder's own arithmetic)
# ---------------------------------------------------------------------------

def _hand_macs(n, band, s, chunk=0, chunk_at=0):
    """``(real, zero)`` multiply-accumulates of the slice dots of one call
    of the scan form, per EXECUTED step and chunk. A step on a trailing
    block of m rows makes W = A (V T) ((m, m) x (m, band): m deep and one
    band wide, so s^2 slice-pair slots of depth m are emitted for the s (s
    + 1) / 2 real ones), M = V^H W ((band, m) x (m, band), likewise) and
    the rank-2b update [X / alpha | V] [alpha V | X]^H ((m, 2 band) x (2
    band, m), ONE product since ISSUE 38 with the multiply-accumulates of
    the two it replaced: both outputs wider than the depth, so ragged
    groups, s (s + 1) / 2 times 2 band and no padding). In row chunks of
    ``chunk`` (where shorter than m, and from ``chunk_at`` rows on: the auto
    rule's 8192) W and the update have ``ceil(m / chunk)`` chunks of
    ``chunk`` rows each: the ragged last one starts early and recomputes
    rows. Summed over the route labels of :func:`_hand_macs_by_route`."""
    return tuple(map(sum, zip(*_hand_macs_by_route(n, band, s, chunk,
                                                   chunk_at).values())))


def _hand_macs_by_route(n, band, s, chunk=0, chunk_at=0):
    """``{route label: (real, zero)}`` of :func:`_hand_macs`' products. W
    and M, whose contraction (m) is deeper than their narrower output side
    (band), scan the wide operand's slices since ISSUE 36 and count under
    ``scan_slices``: the padded scan's 28 real and 21 zero slots of m an
    output element. The rank-2b update's one product ``2 band`` deep stays
    under ``scan`` (ragged groups, no padding)."""
    pairs = s * (s + 1) // 2
    panels = -(-n // band) - 1
    deep_real = deep_zero = bulk_real = 0
    off = 0
    for seg in telescope_segments(panels):
        m = (-(-n // band) - off) * band
        chunked = 0 < chunk < m and m >= chunk_at
        rows = -(-m // chunk) * chunk if chunked else m
        deep_real += seg * (rows * band + band * band) * pairs * m   # W, M
        deep_zero += seg * (rows * band + band * band) * (s * s - pairs) * m
        bulk_real += seg * rows * m * pairs * 2 * band    # the update
        off += seg
    return {"scan_slices": (deep_real, deep_zero), "scan": (bulk_real, 0)}


def _counters(name, **labels):
    return sum(m["value"] for m in obs.registry().snapshot()
               if m["name"] == name
               and all(m["labels"].get(k) == v for k, v in labels.items()))


def _case_counters(n, route, tmp_path, chunk=0):
    """One traced ``_red2band_local_scan``: the slice dots' MACs equal the
    sum over executed steps and chunks of the products' shapes, and the
    builder says how many panels, bodies and columns it ran; two calls
    dispatch six programs."""
    _configure(tmp_path, **({"red2band_trail_chunk": chunk} if chunk else {}))
    _reduce(n)
    _reduce(n)
    panels = -(-n // BAND) - 1
    real, zero = _hand_macs(n, BAND, SLICES, chunk)
    assert _counters("dlaf_ozaki_macs_total", kind="real") == real
    assert _counters("dlaf_ozaki_macs_total", kind="zero") == zero
    # W and M scan the wide operand's slices, the rank-2b update stays
    # ragged: each route label reads its own products' hand count
    for label, (r, z) in _hand_macs_by_route(n, BAND, SLICES,
                                             chunk).items():
        assert _counters("dlaf_ozaki_macs_total", route=label,
                         kind="real") == r, label
        assert _counters("dlaf_ozaki_macs_total", route=label,
                         kind="zero") == z, label
    assert _counters("dlaf_ozaki_masked_macs_total") == 0
    assert _counters("dlaf_red2band_steps_total", form="scan") == panels
    assert _counters("dlaf_red2band_bodies_total", form="scan") \
        == len(telescope_segments(panels))
    assert _counters("dlaf_red2band_panel_columns_total", form="scan") \
        == panels * BAND
    assert _counters("dlaf_red2band_steps_total", form="unrolled") == 0
    # the rank-2b update is ONE product a step (two before ISSUE 38),
    # whatever the row chunks
    assert _counters("dlaf_red2band_update_products_total",
                     form="scan") == panels
    assert _counters("dlaf_red2band_update_products_total",
                     form="unrolled") == 0
    assert _counters("dlaf_entry_calls_total",
                     entry="reduction_to_band") == 2
    assert _counters("dlaf_entry_programs_total",
                     entry="reduction_to_band") == 6
    assert _counters("dlaf_fallback_total") == 0
    assert route["builders"] == ["scan", "scan"], route


def _case_counters_chunked(n, route, tmp_path):
    _case_counters(n, route, tmp_path, chunk=96)


def _case_counters_unrolled(n, route, tmp_path):
    """Below 32 panels every panel is a body of its own."""
    _configure(tmp_path)
    _reduce(n)
    panels = -(-n // BAND) - 1
    assert _counters("dlaf_red2band_bodies_total", form="unrolled") == panels
    assert _counters("dlaf_red2band_steps_total", form="unrolled") == panels
    assert _counters("dlaf_red2band_panel_columns_total",
                     form="unrolled") == panels * BAND
    assert _counters("dlaf_red2band_bodies_total", form="scan") == 0
    assert _counters("dlaf_red2band_update_products_total",
                     form="unrolled") == panels
    assert _counters("dlaf_entry_programs_total",
                     entry="reduction_to_band") == 3


CASES = [
    pytest.param(_case_scan, 33 * BAND, id="scan-32panels"),
    pytest.param(_case_scan, 34 * BAND - 5, id="scan-33panels-ragged"),
    pytest.param(_case_chunked, 33 * BAND, id="chunked-32panels"),
    pytest.param(_case_chunked, 34 * BAND - 5,
                 id="chunked-33panels-ragged"),
    pytest.param(_case_native_products, 33 * BAND,
                 id="scan-32panels-native-products"),
    pytest.param(_case_gate_reads_band, 33 * BAND,
                 id="scan-32panels-gate-reads-band"),
    pytest.param(_case_unrolled_below_32, 32 * BAND,
                 id="unrolled-31panels"),
    pytest.param(_case_counters, 33 * BAND, id="counters-hand-count"),
    pytest.param(_case_counters_chunked, 33 * BAND,
                 id="counters-hand-count-chunked"),
    pytest.param(_case_counters_unrolled, 9 * BAND,
                 id="counters-unrolled"),
]


@pytest.mark.parametrize("case, n", CASES)
def test_local_reduction_to_band_on_the_chips_route(case, n, route,
                                                    tmp_path):
    case(n, route, tmp_path)


def _hand_peels(n, band, chunk=0):
    """``(one_pass, chain)`` elements the slice products of one call peel,
    per EXECUTED step and chunk. W = A (V T) peels its wide operand, the
    trailing block's rows (in chunks: ``ceil(m / chunk)`` chunks of
    ``chunk`` rows), in one pass and V T by the chain, once a chunk; M =
    V^H W ((band, m) x (m, band): a square output, whose wide operand is
    the right one) peels W in one pass and V^H by the chain; the rank-2b
    update ``[X / alpha | V] [alpha V | X]^H`` is no deep product: both
    its operands by the chain, the right one once a chunk."""
    one = chain = off = 0
    for seg in telescope_segments(-(-n // band) - 1):
        m = (-(-n // band) - off) * band
        chunks = -(-m // chunk) if 0 < chunk < m else 1
        rows = chunks * chunk if chunks > 1 else m
        one += seg * (rows * m + band * m)
        chain += seg * (chunks * m * band + m * band
                        + rows * 2 * band + chunks * 2 * band * m)
        off += seg
    return one, chain


@pytest.mark.parametrize("chunk", [0, 96], ids=["whole", "chunked"])
def test_peel_counter_reads_the_hand_count(chunk, route, tmp_path):
    """``dlaf_ozaki_peeled_total{form}`` of one traced call: W's and M's
    wide operands under ``one_pass``, the rest under ``chain``."""
    n = 33 * BAND
    _configure(tmp_path, **({"red2band_trail_chunk": chunk} if chunk else {}))
    _reduce(n)
    one, chain = _hand_peels(n, BAND, chunk)
    assert _counters("dlaf_ozaki_peeled_total", form="one_pass") == one
    assert _counters("dlaf_ozaki_peeled_total", form="chain") == chain
    assert route["builders"] == ["scan"], route


def test_hand_count_of_the_cells_shape():
    """N=8192, band=128: what the traced run on the chip has to read. 63
    panels in 8 bodies, 8064 columns; segment 0 (8192 rows) in two row
    chunks of 4096, which divide it, so the chunks add no work."""
    panels = 8192 // 128 - 1
    assert panels == 63
    assert telescope_segments(panels) == (8,) * 7 + (7,)
    assert panels * 128 == 8064
    real, zero = _hand_macs(8192, 128, 7, chunk=4096, chunk_at=8192)
    assert (real, zero) == _hand_macs(8192, 128, 7)
    assert (real, zero) == (18523187314688, 4698207682560)
    # dlaf_red2band_update_products_total{form="scan"}: one a step (the
    # counters case reads it equal to the steps), 126 before ISSUE 38
    assert sum(telescope_segments(panels)) == 63


def test_hand_count_of_the_cells_deep_products():
    """What ``dlaf_ozaki_macs_total{route="scan_slices"}`` has to read on
    the cell: W = A (V T) and M = V^H W of every executed step, ``sum seg
    (m^2 + 128 m) x 128 x 28`` real and the same with 21 zero: all of the
    call's zeros (the rank-2b update is ragged) and 47% of its emitted
    multiply-accumulates."""
    by_route = _hand_macs_by_route(8192, 128, 7, chunk=4096, chunk_at=8192)
    segs = zip((8,) * 7 + (7,), range(8192, 0, -1024))
    slots = sum(seg * (m * m + 128 * m) * 128 for seg, m in segs)
    assert by_route["scan_slices"] == (28 * slots, 21 * slots)
    assert by_route["scan_slices"] == (6264276910080, 4698207682560)
    assert by_route["scan"] == (18523187314688 - 6264276910080, 0)
    emitted = 18523187314688 + 4698207682560
    assert round(100 * 49 * slots / emitted, 1) == 47.2


# ---------------------------------------------------------------------------
# the rank-2b update alone (ISSUE 38)
# ---------------------------------------------------------------------------

def _update_operands(m, band, log2_ratio, seed, dtype=np.float64):
    """(acc, X, V): V as a step's reflector block (unit lower trapezoid,
    entries within 1), X normal at ``2^log2_ratio`` times that, or all
    zero for ``None`` (a dead step under the masks)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        g = rng.standard_normal(shape)
        if np.issubdtype(dtype, np.complexfloating):
            g = g + 1j * rng.standard_normal(shape)
        return g.astype(dtype)

    v = normal(m, band)
    v = np.tril(v / np.abs(v).max(), -1) + np.eye(m, band)
    x = normal(m, band) * (0.0 if log2_ratio is None else 2.0 ** log2_ratio)
    acc = normal(m, m)
    return acc + acc.conj().T, x, v


@pytest.mark.parametrize("cw", [0, 48], ids=["whole", "row-chunks"])
@pytest.mark.parametrize("log2_ratio", [-20, 0, 10, 20, None],
                         ids=["2^-20", "1", "2^10", "2^20", "zero-X"])
def test_the_merged_update_against_longdouble(log2_ratio, cw, route):
    """``[X / alpha | V] [alpha V | X]^H`` on the chip's route (seven
    slices, ragged groups 2 band deep) against numpy's ``longdouble`` ``X
    V^H + V X^H``: within twice the error of the two ``band`` deep
    products on the same data whatever ``|X| / |V|`` (unbalanced, the
    smaller half would lose its low slices: seven orders at 2^20), and
    finite and exact on an all-zero X. The last row chunk starts early
    (160 = 3 x 48 + 16)."""
    _configure()
    m = 160
    acc, x, v = _update_operands(m, BAND, log2_ratio, seed=7)
    want = (x.astype(np.longdouble) @ v.astype(np.longdouble).T
            + v.astype(np.longdouble) @ x.astype(np.longdouble).T)
    zero = np.zeros_like(acc)
    merged = -np.asarray(r2b._rank2b_update(zero, x, v, cw=cw, form="scan"))
    two = np.asarray(tb.mm(x, v.T) + tb.mm(v, x.T))
    assert route["slices"] == {SLICES}, route
    assert np.isfinite(merged).all()
    err_two = np.abs(two - want).max()
    assert np.abs(merged - want).max() <= 2 * err_two
    if log2_ratio is None:
        assert not merged.any()
    else:
        # seven slices: 2^-49 of the operands' row and column scales
        assert err_two <= 64 * 2.0 ** -49 * np.abs(want).max()
    # and the subtraction from a live accumulator
    out = np.asarray(r2b._rank2b_update(acc, x, v, cw=cw, form="scan"))
    assert np.abs(out - (acc - want)).max() <= 2 * err_two + \
        2.0 ** -52 * np.abs(acc).max()


@pytest.mark.parametrize("cw", [0, 48], ids=["whole", "row-chunks"])
def test_the_merged_update_complex_on_the_native_route(cw):
    """complex128 where it runs (the CPU's native products; it does not
    compile for the TPU, PERF.md PR 34): ``conj`` is on ``Q``'s halves, so
    the merged update equals ``acc - X V^H - V X^H`` to rounding, at a
    ratio that needs the balance to be exact."""
    C.initialize()
    acc, x, v = _update_operands(160, BAND, 10, seed=11,
                                 dtype=np.complex128)
    want = acc - x @ v.conj().T - v @ x.conj().T
    out = np.asarray(r2b._rank2b_update(acc, x, v, cw=cw, form="scan"))
    assert out.dtype == np.complex128
    assert np.abs(out - want).max() <= 1e-13 * np.abs(want).max()


@pytest.mark.parametrize("band, peels", [(64, False), (127, False),
                                         (128, True)],
                         ids=["band64", "band127", "band128"])
def test_the_updates_route_is_decided_on_band(band, peels, route):
    """Under the default gate (``f64_gemm_min_dim`` 128) a band of 64..127
    keeps native products though the merged depth ``2 band`` reaches the
    gate; the cell's band, 128, takes the slice route as before."""
    C.initialize()
    acc, x, v = _update_operands(384, band, 0, seed=band)
    out = np.asarray(r2b._rank2b_update(acc, x, v, cw=0, form="scan"))
    assert route["slices"] == ({SLICES} if peels else set()), route
    want = acc - x @ v.T - v @ x.T
    assert np.abs(out - want).max() <= 1e-12 * np.abs(want).max()


@pytest.mark.parametrize("x_scale, v_scale, want", [
    (1.0, 1.0, 1.0), (3.0 * 2 ** 20, 1.0, 2.0 ** 21),
    (2.0 ** -20, 1.0, 2.0 ** -20), (1.0, 2.0 ** 10, 2.0 ** -10),
    (0.0, 1.0, 1.0), (1.0, 0.0, 1.0), (0.0, 0.0, 1.0),
    (np.inf, 1.0, 1.0), (1e300, 1e-300, 1.0)],
    ids=["equal", "3x2^20", "2^-20", "v-2^10", "zero-x", "zero-v",
         "both-zero", "inf", "beyond-f32"])
def test_the_balance_is_an_exact_power_of_two(x_scale, v_scale, want):
    """``_balance``: the power of two nearest the ratio of the operands'
    mean row maxima (here 0.75 ``x_scale / v_scale``) with its exact
    inverse; 1 where an operand is all zero or the ratio is not finite in
    f32 (what the TPU's f64 holds), never inf or nan."""
    x = np.array([[0.25, -1.0], [0.5, 0.125]]) * x_scale
    v = np.array([[1.0, 0.0], [-0.5, 1.0]]) * v_scale
    alpha, inv = (np.asarray(t) for t in r2b._balance(x, v))
    assert alpha.dtype == inv.dtype == np.float64
    assert (float(alpha), float(inv)) == (want, 1.0 / want)


def test_the_balance_reads_rows_not_the_unit_diagonal():
    """A step's V is a unit diagonal over tails of ``1 / sqrt(m)``: its
    largest entry is 1 in ``band`` rows of m, and a balance on the largest
    entries would set every other row's V half four bits under its X half
    (PERF.md section 6, PR 38: 0.05 digits of the chip's residual)."""
    m, band = 4096, 16
    rng = np.random.default_rng(5)
    v = np.tril(rng.standard_normal((m, band)) / np.sqrt(m), -1) \
        + np.eye(m, band)
    x = rng.standard_normal((m, band))
    alpha = float(np.asarray(r2b._balance(x, v)[0]))
    rows = np.abs(x).max(axis=1).mean() / np.abs(v).max(axis=1).mean()
    assert alpha == 2.0 ** np.round(np.log2(rows))
    assert alpha >= 8 * np.abs(x).max() / np.abs(v).max()


def test_the_reference_reduces_and_reconstructs():
    """The plain reference on its own: a band matrix with A's spectrum,
    reflectors that reconstruct A, LAPACK's sign (R's diagonal opposes the
    column it replaced) and tau in [1, 2]."""
    n, band = 61, 8
    a = _sym(n, seed=3)
    out, taus = ref.reduce_to_band(a, band)
    b = ref.band_of(out, band)
    lam = np.linalg.eigvalsh(a)
    np.testing.assert_allclose(np.linalg.eigvalsh(b), lam, atol=1e-12)
    eye = np.eye(n)
    q = ref.apply_q(out, taus, band, eye)
    np.testing.assert_allclose(q.T @ q, eye, atol=1e-13)
    np.testing.assert_allclose(q @ b @ q.T, a, atol=1e-12)
    np.testing.assert_allclose(ref.apply_q(out, taus, band, eye,
                                           adjoint=True), q.T, atol=1e-13)
    live = taus[taus != 0]
    assert live.min() >= 1.0 and live.max() <= 2.0
    # first reflector: beta = -sign(alpha) |x|
    x = a[band:, 0]
    assert out[band, 0] == pytest.approx(-np.sign(x[0]) * np.linalg.norm(x))
    assert np.array_equal(out[np.triu_indices(n, band + 1)],
                          np.zeros(len(np.triu_indices(n, band + 1)[0])))
