"""The local Cholesky's step form comes from the step count (ISSUE 31).

On a TPU ``cholesky`` takes the telescoped scan builder from 32 block steps
on (``config.resolve_step_mode``, the resolver of every other builder) and
keeps the unrolled one below: ``chol_d_n16384_1x1`` (N=16384, nb=512: 32
steps) runs ``_cholesky_local_scan``, ``chol_d_n4096_1x1`` (16 steps) the
program it always ran. Here the public entry RUNS on the CPU under a TPU's
knob resolution (``as_on_tpu``, the pattern of tests/test_tpu_route.py) at
32 and 33 steps of the smallest block at which the local route traces
slice products (64), against ``numpy.linalg.cholesky`` at the cell's
tolerance ``60 n 2^-47``; at 31 steps the unrolled builder is asserted and
its lowering pinned to the parent commit's. The counters the cell's
metrics read are checked against hand counts.

ISSUE 32: the scan bodies put a value of its own between their read of a
window of the carry and their write to it (``_carry_window``,
``_scan_bulk_update``). The factor must stay the parent's bit for bit:
``reference_carry_window`` and ``reference_bulk_update`` below keep the
parent's formulation (commit 950257e) of the two helpers, and every form
of the builder is run both ways.
"""

import functools
import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.tile_ops import ozaki as oz

#: the module (``dlaf_tpu.algorithms.cholesky`` the attribute is the entry)
chol_mod = importlib.import_module("dlaf_tpu.algorithms.cholesky")

EPS_TPU = 2.0 ** -47
NB = 64
SLICES = 7               # f64_gemm_slices auto on a TPU

#: sha256 of the StableHLO text of the local program at n = 31 * 64,
#: nb = 64, uplo L, not donated, lowered for the CPU under ``as_on_tpu``
#: on the parent commit (8ec4bd4, jax 0.9.0): the unrolled route below 32
#: steps is the parent's, text for text.
PARENT_LOWERING_NT31 = \
    "895162647d5f8f14e1614553a0fb9d70c627819398d1161b2ec8225c391ba611"


@pytest.fixture(autouse=True)
def obs_reset():
    yield
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


def _hpd(n, seed):
    """The benchmark's input: ``(G + G^T)/2 + n I`` (benchmark/ops)."""
    g = np.random.default_rng(seed).standard_normal((n, n))
    return (g + g.T) / 2 + n * np.eye(n)


def _matrix(a):
    return Matrix.from_global(a, TileElementSize(NB, NB))


@pytest.fixture
def route(as_on_tpu, monkeypatch):
    """Which local builder the entry hands ``_local_cholesky_cached`` and
    what the traces peel: a case asserts on both, so neither a stale
    program cache nor a route that silently kept native products passes."""
    cached, peel = chol_mod._local_cholesky_cached, oz._peel_slices
    seen = {"builders": [], "slices": set(), "cached": cached}

    def spy_cached(local, dist, donate, statics):
        seen["builders"].append(local)
        seen["statics"] = dict(statics)
        return cached(local, dist, donate, statics)

    def spy_peel(xn, s):
        seen["slices"].add(int(s))
        return peel(xn, s)

    monkeypatch.setattr(chol_mod, "_local_cholesky_cached", spy_cached)
    monkeypatch.setattr(oz, "_peel_slices", spy_peel)
    return seen


def _counters(name, **labels):
    return sum(m["value"] for m in obs.registry().snapshot()
               if m["name"] == name
               and all(m["labels"].get(k) == v for k, v in labels.items()))


# ---------------------------------------------------------------------------
# hand counts (independent of the builder's own arithmetic)
# ---------------------------------------------------------------------------

def _segments(nt):
    """Equal chunks of eight steps, the last one ragged
    (``types.telescope_segments`` at its defaults, for nt <= 64)."""
    return [8] * (nt // 8) + ([nt % 8] if nt % 8 else [])


def _hand_macs(nt, nb, s, chunk=None):
    """``(all, masked)`` multiply-accumulates of the slice dots of the
    look-ahead scan form at ``nt`` steps: per executed step a panel product
    (m x nb x nb, a padded scan of s groups at depth s nb), a strip product
    (the same shape) and the bulk — one (m, m) syrk (a padded scan: s
    groups of 3 nb half-pairs + nb diagonal, at s = 7) or, in chunks of
    ``chunk`` columns, ragged products of s (s + 1) / 2 pair depths. Live
    is what the stored lower triangle of the trailing block needs."""
    all_macs = masked = 0
    pad_depth = s * s * nb                   # padded product, per element
    syrk_depth = s * (s // 2 * nb + nb)      # padded syrk, per element
    ragged_depth = s * (s + 1) // 2 * nb
    off = 0
    for seg in _segments(nt):
        m = (nt - off) * nb
        for k in range(seg):
            lo = (k + 1) * nb                # first trailing row / column
            r = m - lo
            # panel: rows below the pivot are live
            all_macs += m * nb * pad_depth
            masked += (m - r) * nb * pad_depth
            # strip: the stored trapezoid of block column k + 1
            live = sum(m - j for j in range(lo, min(lo + nb, m)))
            all_macs += m * nb * pad_depth
            masked += (m * nb - live) * pad_depth
            # bulk of the previous step, past block column k (none pending
            # in the factorization's first body)
            first = off == 0 and k == 0
            if chunk is None or m < 2 * chunk:
                live = 0 if first else sum(m - j for j in range(lo, m))
                all_macs += m * m * syrk_depth
                masked += (m * m - live) * syrk_depth
            else:
                for c0 in range(0, m, chunk):
                    c1 = min(c0 + chunk, m)
                    live = 0 if first else sum(
                        m - j for j in range(max(c0, lo), c1))
                    all_macs += (m - c0) * (c1 - c0) * ragged_depth
                    masked += ((m - c0) * (c1 - c0) - live) * ragged_depth
        off += seg
    return all_macs, masked


# ---------------------------------------------------------------------------
# the route, against the plain reference
# ---------------------------------------------------------------------------

def _run_and_check(uplo, n, route):
    a = _hpd(n, seed=n)
    out = cholesky(uplo, _matrix(a), donate=True).to_numpy()
    ref = np.linalg.cholesky(a)
    got = np.tril(out) if uplo == "L" else np.triu(out).T
    err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert err <= 60 * n * EPS_TPU, err
    # the other triangle passes through
    keep = np.triu(a, 1) if uplo == "L" else np.tril(a, -1)
    other = np.triu(out, 1) if uplo == "L" else np.tril(out, -1)
    np.testing.assert_array_equal(other, keep)
    assert route["builders"] == [chol_mod._cholesky_local_scan], route
    assert route["statics"]["use_mxu"] and route["statics"]["use_mixed"]
    assert route["statics"]["lookahead"]
    assert route["slices"] == {SLICES}, route


def _case_scan(uplo, n, route, tmp_path, monkeypatch):
    _run_and_check(uplo, n, route)


def _case_chunked(uplo, n, route, tmp_path, monkeypatch):
    """The bulk product in block-column chunks (the shape rule binds at
    8192 rows on the chip; here its two constants are set to the test's
    size, the code path is the chip's)."""
    monkeypatch.setattr(chol_mod, "SCAN_BULK_CHUNK", 256)
    monkeypatch.setattr(chol_mod, "SCAN_BULK_CHUNK_AT", 512)
    _run_and_check(uplo, n, route)


def _case_unrolled_below_32(uplo, n, route, tmp_path, monkeypatch):
    """31 steps: the unrolled builder, and the parent's program. The entry
    is stopped at its dispatch (the program is lowered, not compiled)."""
    cached = route["cached"]
    lowered = []

    def stop(local, dist, donate, statics):
        route["builders"].append(local)
        route["statics"] = dict(statics)
        fn = cached(local, dist, donate, statics)
        return lambda x: lowered.append(fn.lower(x).as_text()) or x

    monkeypatch.setattr(chol_mod, "_local_cholesky_cached", stop)
    cholesky(uplo, _matrix(_hpd(n, seed=n)))
    assert route["builders"] == [chol_mod._cholesky_local], route
    assert route["statics"]["trailing"] == "ozaki"
    assert route["slices"] == {SLICES}, route
    sha = hashlib.sha256(lowered[0].encode()).hexdigest()
    assert sha == PARENT_LOWERING_NT31, sha


def _case_one_program_and_step_counts(uplo, n, route, tmp_path, monkeypatch):
    """One program a call, and the run says which builder it took: four
    bodies for 32 overlapped steps."""
    C.initialize(C.Configuration(metrics_path=str(tmp_path / "obs.jsonl")))
    a = _hpd(n, seed=n)
    for _ in range(2):
        out = cholesky(uplo, _matrix(a), donate=True)
    jax.block_until_ready(out.storage)
    assert _counters("dlaf_entry_programs_total", entry="cholesky") == 2
    assert _counters("dlaf_entry_calls_total", entry="cholesky") == 2
    assert _counters("dlaf_cholesky_bodies_total", algo="cholesky_scan") == 4
    assert _counters("dlaf_cholesky_steps_total", algo="cholesky_scan",
                     mode="overlapped") == 32
    assert _counters("dlaf_cholesky_steps_total", algo="cholesky") == 0
    assert _counters("dlaf_fallback_total") == 0


def _case_masked_macs(uplo, n, route, tmp_path, monkeypatch, chunk=None):
    """``dlaf_ozaki_masked_macs_total`` and ``dlaf_ozaki_macs_total``
    against the hand count: per executed step, what the uniform shapes
    compute, and how much of it lies beyond the stored triangle of the
    live block (the panel factorization's own (nb, nb) products are native
    and count nowhere)."""
    C.initialize(C.Configuration(metrics_path=str(tmp_path / "obs.jsonl")))
    if chunk:
        monkeypatch.setattr(chol_mod, "SCAN_BULK_CHUNK", chunk)
        monkeypatch.setattr(chol_mod, "SCAN_BULK_CHUNK_AT", 2 * chunk)
    cholesky(uplo, _matrix(_hpd(n, seed=n)), donate=True)
    want_all, want_masked = _hand_macs(-(-n // NB), NB, SLICES, chunk)
    assert _counters("dlaf_ozaki_macs_total") == want_all
    assert _counters("dlaf_ozaki_masked_macs_total") == want_masked


def _case_masked_macs_chunked(uplo, n, route, tmp_path, monkeypatch):
    _case_masked_macs(uplo, n, route, tmp_path, monkeypatch, chunk=256)


CASES = [
    pytest.param(_case_scan, "L", 32 * NB, id="scan-L-32steps"),
    pytest.param(_case_scan, "U", 32 * NB, id="scan-U-32steps"),
    pytest.param(_case_scan, "L", 32 * NB + 32, id="scan-L-33steps-ragged"),
    pytest.param(_case_scan, "U", 32 * NB + 32, id="scan-U-33steps-ragged"),
    pytest.param(_case_chunked, "L", 32 * NB, id="chunked-L-32steps"),
    pytest.param(_case_chunked, "U", 32 * NB + 32,
                 id="chunked-U-33steps-ragged"),
    pytest.param(_case_unrolled_below_32, "L", 31 * NB,
                 id="unrolled-31steps-parent-lowering"),
    pytest.param(_case_one_program_and_step_counts, "L", 32 * NB,
                 id="one-program-four-bodies"),
    pytest.param(_case_masked_macs, "L", 32 * NB, id="masked-macs-hand-count"),
    pytest.param(_case_masked_macs_chunked, "L", 32 * NB,
                 id="masked-macs-hand-count-chunked"),
]


@pytest.mark.parametrize("case, uplo, n", CASES)
def test_local_cholesky_step_form(case, uplo, n, route, tmp_path,
                                  monkeypatch):
    case(uplo, n, route, tmp_path, monkeypatch)


# ---------------------------------------------------------------------------
# ISSUE 32: the bodies against the parent's formulation, bit for bit
# ---------------------------------------------------------------------------

def reference_carry_window(acc, start, shape):
    """The parent's reads of the carry (950257e): plain slices, fused by
    the compiler into whatever consumes them."""
    return jax.lax.dynamic_slice(acc, start, shape)


def reference_bulk_update(acc, xt, lo, rows, live, *, uplo, chunks,
                          syrk_like):
    """``bulk_update`` as the parent had it (950257e,
    ``algorithms/cholesky.py:529-568``): each chunk subtracted from a slice
    of the carry taken inside the expression that writes it back."""
    if chunks is None:
        with oz.live_outputs(live[0]):
            upd = syrk_like(xt)
        if uplo == "L":
            mask = rows[:, None] >= rows[None, :]
            if lo is not None:
                mask = mask & (rows[None, :] >= lo)
        else:
            mask = rows[:, None] <= rows[None, :]
            if lo is not None:
                mask = mask & (rows[:, None] >= lo)
        return acc - jnp.where(mask, upd, 0)
    for (c0, c1), kept in zip(chunks, live):
        long, short = xt[c0:], xt[c0:c1]
        rl, rs = rows[c0:], rows[c0:c1]
        if uplo == "L":
            with oz.live_outputs(kept):
                upd = chol_mod._oz_product(long, jnp.conj(short).T)
            mask = rl[:, None] >= rs[None, :]
            if lo is not None:
                mask = mask & (rs[None, :] >= lo)
            acc = acc.at[c0:, c0:c1].set(
                acc[c0:, c0:c1] - jnp.where(mask, upd, 0))
        else:
            with oz.live_outputs(kept):
                upd = chol_mod._oz_product(short, jnp.conj(long).T)
            mask = rs[:, None] <= rl[None, :]
            if lo is not None:
                mask = mask & (rs[:, None] >= lo)
            acc = acc.at[c0:c1, c0:].set(
                acc[c0:c1, c0:] - jnp.where(mask, upd, 0))
    return acc


def _hpd_c128(n, seed):
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    return (g + g.conj().T) / 2 + 2 * n * np.eye(n)


@pytest.mark.parametrize("uplo, n, lookahead, with_info, dtype", [
    pytest.param("L", 32 * NB, True, False, "f64", id="L-la"),
    pytest.param("U", 32 * NB, True, False, "f64", id="U-la"),
    pytest.param("L", 32 * NB, False, False, "f64", id="L-serial"),
    pytest.param("U", 32 * NB, False, False, "f64", id="U-serial"),
    pytest.param("L", 32 * NB, True, True, "f64", id="L-la-with-info"),
    pytest.param("L", 32 * NB + 32, True, False, "f64",
                 id="L-la-33steps-padded"),
    pytest.param("U", 16 * NB + 32, False, True, "c128",
                 id="U-serial-17steps-padded-info-c128"),
])
def test_scan_bodies_match_the_parents_bit_for_bit(
        uplo, n, lookahead, with_info, dtype, as_on_tpu, monkeypatch):
    """The route the chip runs (slice products, mixed panels) with the
    chunk rule's constants at 256 / 1024: at nb = 64 the segments' blocks
    have 2048, 1536 and 1024 rows (chunked: 8, 6 and 4 trapezoids a step)
    and 512 (one self-product; 33 steps add a one-step segment of one
    padded block; the complex case has 17 steps: 1088 rows chunked, 576 and
    64 not). Reading the windows as values changes no bit of the factor,
    of ``info`` or of the triangle that passes through."""
    monkeypatch.setattr(chol_mod, "SCAN_BULK_CHUNK", 256)
    monkeypatch.setattr(chol_mod, "SCAN_BULK_CHUNK_AT", 1024)
    a = _hpd(n, seed=n) if dtype == "f64" else _hpd_c128(n, seed=n)

    def factor():
        fn = jax.jit(functools.partial(
            chol_mod._cholesky_local_scan.__wrapped__, uplo=uplo, nb=NB,
            use_mxu=True, use_mixed=True, lookahead=lookahead,
            with_info=with_info))
        out = fn(jnp.asarray(a))
        return [np.asarray(x) for x in (out if with_info else (out,))]

    got = factor()
    windows, updates = [], []
    monkeypatch.setattr(
        chol_mod, "_carry_window",
        lambda *args: windows.append(1) or reference_carry_window(*args))
    monkeypatch.setattr(
        chol_mod, "_scan_bulk_update",
        lambda *args, **kw: updates.append(kw["chunks"] is not None)
        or reference_bulk_update(*args, **kw))
    want = factor()
    # the reference ran, on chunked and unchunked segments
    assert windows and True in updates and False in updates
    nt = -(-n // NB)
    assert len(updates) == -(-nt // 8)      # one traced body a segment
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    if with_info:
        assert got[1] == 0
    ref = np.linalg.cholesky(a)
    low = np.tril(got[0]) if uplo == "L" else np.triu(got[0]).conj().T
    err = np.linalg.norm(low - ref) / np.linalg.norm(ref)
    assert err <= 60 * n * EPS_TPU, err
    keep = np.triu(a, 1) if uplo == "L" else np.tril(a, -1)
    other = np.triu(got[0], 1) if uplo == "L" else np.tril(got[0], -1)
    np.testing.assert_array_equal(other, keep)


@pytest.mark.parametrize("trailing, builder", [
    ("ozaki", "_cholesky_local"), ("loop", "_cholesky_local"),
    ("scan", "_cholesky_local_scan")])
def test_an_explicit_trailing_form_is_kept(trailing, builder, route,
                                           monkeypatch):
    """``cholesky_trailing`` names a form: the step count does not override
    it (32 steps; the entry is stopped at its dispatch)."""
    C.initialize(C.Configuration(cholesky_trailing=trailing))
    monkeypatch.setattr(
        chol_mod, "_local_cholesky_cached",
        lambda local, dist, donate, statics:
        route["builders"].append(local) or (lambda x: x))
    cholesky("L", Matrix.from_global(_hpd(32 * 8, seed=1),
                                     TileElementSize(8, 8)))
    assert route["builders"] == [getattr(chol_mod, builder)]


def test_the_cpu_keeps_its_threshold():
    """Off the TPU the resolver switches at 128 steps: 32 steps of the
    default route stay unrolled (no ``as_on_tpu`` here)."""
    C.initialize()
    assert chol_mod.local_step_form(32) == "unrolled"
    assert chol_mod.local_step_form(128) == "scan"
    assert C.resolve_step_mode(32) == "unrolled"
    assert C.resolve_step_mode(32, platform="tpu") == "scan"
    assert C.resolve_step_mode(31, platform="tpu") == "unrolled"
