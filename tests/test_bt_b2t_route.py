"""The chase's back-transformation on the route the chip runs (ISSUE 39).

``bt_b2t_d_n4096_1x1`` (n = m = 4096, band 128, tile 512) runs
``_bt_b2t_blocked`` with the group a TPU resolves: ``bt_b2t_group`` auto asks
the device itself (``tpu_info.default_device``), so besides ``as_on_tpu``
(tests/conftest.py; the pattern of tests/test_tpu_route.py) the test answers
that question as a chip would: G = band, slice products from
``f64_gemm_min_dim`` on (lowered here to the test's band; 128 = the
published band on the cell). The public entry RUNS on the CPU under that
resolution and is held to the benchmark's plain reference at the cell's
limit for a TPU; what the cell's metrics read is held to hand counts made
from ``n``, ``b`` and ``G`` alone: levels and live / null reflector slots,
programs a call on each branch, the four phases of the program's table, the
host phases' spans and the slice products' multiply-accumulates per EXECUTED
level.
"""

import collections
import importlib
import importlib.util
import os
import re

import jax
import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu import obs, tpu_info
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.eigensolver import bt_band_to_tridiag
from dlaf_tpu.eigensolver.band_to_tridiag import band_to_tridiag
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.obs import telemetry
from dlaf_tpu.tile_ops import ozaki as oz
from dlaf_tpu.types import Device

bt = importlib.import_module("dlaf_tpu.eigensolver.back_transform")

EPS_TPU = 2.0 ** -47
NB = 32
SLICES = 7               # f64_gemm_slices auto on a TPU
PHASES = {"stair", "tfactor", "project", "apply"}
SPANS = ("upload", "to_global", "apply", "to_tiles")
SIZES = [(96, 16), (150, 32)]


def _load(*parts):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        parts[-1].removesuffix(".py"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference", "chase_reflectors.py")
#: ``(levels, live, null)`` from ``n``, ``b``, ``G`` alone, written beside
#: the benchmark's reader of the counters and independent of the library
hand_slots = _load("layer_metrics", "bt_null_reflector_share.py").hand_count


@pytest.fixture(autouse=True)
def obs_reset():
    # an empty registry on the way in too: the cases compare counters with
    # hand counts, and a test that ran before in this process may have left
    # its counts behind
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


@pytest.fixture
def route(as_on_tpu, monkeypatch):
    """The group the blocked program was built with and the slices its
    products peeled: a case asserts on both, so neither the CPU's group of
    64 nor a route that kept native products passes."""
    monkeypatch.setattr(tpu_info, "default_device", lambda: Device.TPU)
    blocked, peel = bt._bt_b2t_blocked, oz._peel_slices
    seen = {"groups": [], "slices": set()}

    def spy_blocked(*args, **kw):
        seen["groups"].append(kw["group"])
        return blocked(*args, **kw)

    def spy_peel(xn, s):
        seen["slices"].add(int(s))
        return peel(xn, s)

    spy_blocked.lower = blocked.lower       # telemetry.compiled lowers it
    monkeypatch.setattr(bt, "_bt_b2t_blocked", spy_blocked)
    monkeypatch.setattr(oz, "_peel_slices", spy_peel)
    return seen


def _configure(tmp_path, b, **knobs):
    C.initialize(C.Configuration(metrics_path=str(tmp_path / "obs.jsonl"),
                                 f64_gemm_min_dim=b, **knobs))


def _chase(n, b):
    g = np.random.default_rng(n + b).standard_normal((n, n))
    return band_to_tridiag(ref.lower_band((g + g.T) / 2, b), b)


def _counters(name, **labels):
    return sum(m["value"] for m in obs.registry().snapshot()
               if m["name"] == name
               and all(m["labels"].get(k) == v for k, v in labels.items()))


def _span_count(name):
    return sum(m["count"] for m in obs.registry().snapshot()
               if m["name"] == "dlaf_span_seconds"
               and m["labels"].get("span") == name)


def hand_macs(n, b, group, m, s=SLICES):
    """``(real, zero)`` of the two slice products a level: ``V^H seg``
    ((G, L) x (L, m), L = b + G - 1 deep: deeper than its narrower side G,
    so the scan over the wide operand's slices, s^2 slots emitted for the
    s (s + 1) / 2 real) and ``V W`` ((L, G) x (G, m): both sides at least
    as wide as the depth G: ragged groups, or the padded group scan where
    ``L == G``), per EXECUTED level."""
    levels = hand_slots(n, b, group)[0]
    pairs = s * (s + 1) // 2
    depth = b + group - 1
    real = levels * pairs * (group * depth * m + depth * group * m)
    zero = levels * (s * s - pairs) * group * depth * m
    return real, zero


@pytest.mark.parametrize("n, b", SIZES)
def test_matrix_branch_on_the_chips_route(n, b, route, tmp_path):
    _configure(tmp_path, b)
    tri = _chase(n, b)
    e = np.random.default_rng(3 * n).standard_normal((n, n))
    want = ref.apply_q(tri.v, tri.tau, e, b)
    for _ in range(2):
        out = bt_band_to_tridiag(
            tri, Matrix.from_global(e, TileElementSize(NB, NB)))
    got = np.asarray(out.to_numpy())
    assert np.linalg.norm(got - want) \
        <= 100 * n * EPS_TPU * np.linalg.norm(want)
    # the route: G = band, seven-slice products
    assert route["groups"] == [b, b], route
    assert route["slices"] == {SLICES}, route
    # one traced program: a call's levels and reflector slots
    levels, live, null = hand_slots(n, b, b)
    assert bt.chase_reflector_slots(n, b, *tri.tau.shape, b) \
        == (levels, live, null)
    assert _counters("dlaf_bt_b2t_levels_total", impl="blocked") == levels
    assert _counters("dlaf_bt_b2t_reflectors_total", impl="blocked",
                     kind="live") == live
    assert _counters("dlaf_bt_b2t_reflectors_total", impl="blocked",
                     kind="null") == null
    assert _counters("dlaf_bt_b2t_levels_total", impl="sweeps") == 0
    assert live + null == levels * b and null > 0.4 * levels * b
    # the slice products count every executed level, not one traced body
    real, zero = hand_macs(n, b, b, n)
    assert _counters("dlaf_ozaki_macs_total", kind="real") == real
    assert _counters("dlaf_ozaki_macs_total", kind="zero") == zero
    # three dispatch sites a call on the Matrix branch, each under its span
    assert _counters("dlaf_entry_calls_total",
                     entry="bt_band_to_tridiag") == 2
    assert _counters("dlaf_entry_programs_total",
                     entry="bt_band_to_tridiag") == 6
    for name in SPANS:
        assert _span_count(f"stage.bt_band_to_tridiag.{name}") == 2, name
    assert _counters("dlaf_fallback_total") == 0
    # the dispatched program is remembered, with exactly the four phases
    assert telemetry.programs() == ["bt_band_to_tridiag.local"]
    table = telemetry.phase_table("bt_band_to_tridiag.local")
    assert not table["stale"]
    assert set(table["counts"]) == PHASES, table["counts"]
    assert all(table["counts"][p] > 0 for p in PHASES)


#: ``(n, b, m)``: 40 columns, and windows no wider than the staircase is
#: tall (``m = L = 2 b - 1`` columns, or one), where T is folded into V too
ARRAY = [pytest.param(n, b, 40, id=f"{n}-{b}") for n, b in SIZES] + [
    pytest.param(n, b, m, id=f"{n}-{b}-{tag}") for n, b in SIZES
    for tag, m in (("L", 2 * b - 1), ("one", 1))]


@pytest.mark.parametrize("n, b, m", ARRAY)
def test_array_branch_is_one_program_a_call(n, b, m, route, tmp_path):
    _configure(tmp_path, b)
    tri = _chase(n, b)
    e = np.random.default_rng(5 * n).standard_normal((n, m))
    got = np.asarray(bt_band_to_tridiag(tri, e))
    want = ref.apply_q(tri.v, tri.tau, e, b)
    assert np.linalg.norm(got - want) \
        <= 100 * n * EPS_TPU * np.linalg.norm(want)
    assert route["groups"] == [b], route
    assert _counters("dlaf_entry_calls_total",
                     entry="bt_band_to_tridiag") == 1
    assert _counters("dlaf_entry_programs_total",
                     entry="bt_band_to_tridiag") == 1
    assert _span_count("stage.bt_band_to_tridiag.upload") == 1
    assert _span_count("stage.bt_band_to_tridiag.apply") == 1
    assert _span_count("stage.bt_band_to_tridiag.to_global") == 0
    assert _span_count("stage.bt_band_to_tridiag.to_tiles") == 0
    assert _counters("dlaf_bt_b2t_levels_total", impl="blocked") \
        == hand_slots(n, b, b)[0]


@pytest.mark.parametrize("n, b", SIZES)
def test_peel_counter_reads_the_hand_count(n, b, route, tmp_path):
    """``dlaf_ozaki_peeled_total{form}`` of one traced call on a window of
    m = 40 columns, per EXECUTED level: ``V^H seg`` ((G, L) x (L, m), L = b
    + G - 1 deep) peels the window ``seg``, its wider side, in one pass and
    ``V^H`` by the chain; ``(V T) W`` ((L, G) x (G, m), ragged) peels both
    by the chain."""
    _configure(tmp_path, b)
    m, group = 40, b
    depth = b + group - 1
    bt_band_to_tridiag(_chase(n, b),
                       np.random.default_rng(n).standard_normal((n, m)))
    levels = hand_slots(n, b, group)[0]
    assert _counters("dlaf_ozaki_peeled_total", form="one_pass") \
        == levels * depth * m
    assert _counters("dlaf_ozaki_peeled_total", form="chain") \
        == levels * (group * depth + depth * group + group * m)
    assert route["groups"] == [b], route


def _f64_dots(text):
    """``Counter`` of ``(lhs, rhs, result)`` shapes of the module's f64
    ``dot_general``s, e.g. ``("31x16", "16x16", "31x16")``."""
    return collections.Counter(re.findall(
        r"stablehlo\.dot_general .*: \(tensor<(\w+)xf64>, "
        r"tensor<(\w+)xf64>\) -> tensor<(\w+)xf64>", text))


@pytest.mark.parametrize("m", [40, 31], ids=["wide", "narrow"])
def test_t_is_folded_into_v_at_every_width(m, as_on_tpu, monkeypatch,
                                           tmp_path):
    """The blocked program at n = 96, b = G = 16 (L = 31) lowered for a TPU
    on the slice route, on a window wider than the staircase is tall (m =
    40) and on one as wide (m = L): its one raw f64 product beside
    ``larft``'s Gram and 2 x 4 doubling dots is ``V T``, (L, G) x (G, G),
    and no f64 product has a (G, m) result (``T (V^H seg)``)."""
    monkeypatch.setattr(tpu_info, "default_device", lambda: Device.TPU)
    n, b = 96, 16
    _configure(tmp_path, b)
    n_sweeps, n_steps = n - 1, -(-(n - 1) // b)

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, np.float64)

    text = bt._bt_b2t_blocked.trace(
        spec(n_sweeps, n_steps, b), spec(n_sweeps, n_steps), spec(n, m),
        b=b, n=n, group=b).lower(lowering_platforms=("tpu",)).as_text()
    dots = _f64_dots(text)
    g, L = f"{b}x{b}", f"{2 * b - 1}x{b}"
    larft_dots = {(f"{b}x{2 * b - 1}", L, g): 1, (g, g, g): 8}
    assert dots == collections.Counter({**larft_dots, (L, g, L): 1}), dots
    assert not any(out == f"{b}x{m}" for *_, out in dots), dots


def test_sweeps_form_counts_a_sweep_a_level(route, tmp_path):
    n, b = SIZES[0]
    _configure(tmp_path, b, bt_b2t_impl="sweeps")
    tri = _chase(n, b)
    e = np.random.default_rng(11).standard_normal((n, n))
    got = np.asarray(bt_band_to_tridiag(tri, e))
    want = ref.apply_q(tri.v, tri.tau, e, b)
    assert np.linalg.norm(got - want) \
        <= 100 * n * EPS_TPU * np.linalg.norm(want)
    levels, live, null = hand_slots(n, b, 0)
    assert route["groups"] == []
    assert _counters("dlaf_bt_b2t_levels_total", impl="sweeps") == levels
    assert _counters("dlaf_bt_b2t_reflectors_total", impl="sweeps",
                     kind="live") == live
    assert _counters("dlaf_bt_b2t_reflectors_total", impl="sweeps",
                     kind="null") == null
    assert set(telemetry.phase_table("bt_band_to_tridiag.local")["counts"]) \
        == {"project", "apply"}


def test_counters_and_spans_are_silent_without_the_metrics_sink(route):
    n, b = SIZES[0]
    C.initialize(C.Configuration(f64_gemm_min_dim=b))
    assert not obs.metrics_active()
    tri = _chase(n, b)
    bt_band_to_tridiag(tri, Matrix.from_global(
        np.eye(n), TileElementSize(NB, NB)))
    assert _counters("dlaf_entry_programs_total") == 0
    assert _counters("dlaf_bt_b2t_levels_total") == 0
    assert telemetry.programs() == []


def test_the_cells_hand_counts():
    """What the cell reads at n = 4096, b = G = 128: 1024 levels, and
    nearly half of the multiplied slots null."""
    levels, live, null = hand_slots(4096, 128, 128)
    assert levels == 1024 and live + null == 1024 * 128
    assert (live, null) == (67551, 63521)
