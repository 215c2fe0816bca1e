"""Tests for auxiliary components: timer, views, printing, memory helpers,
tpu_info, kernel/band miniapps, scaling scripts."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from dlaf_tpu.common.index2d import GlobalElementIndex, GlobalElementSize, \
    GlobalTileIndex, TileElementSize
from dlaf_tpu.common.timer import PhaseTimer, Timer
from dlaf_tpu.matrix import printing
from dlaf_tpu.matrix.distribution import Distribution
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.matrix.views import SubMatrixView, SubTileSpec


def test_timer():
    t = Timer()
    assert t.elapsed() >= 0
    pt = PhaseTimer()
    with pt.phase("a"):
        pass
    with pt.phase("a"):
        pass
    assert "a" in pt.report() and pt.report()["a"] >= 0


def test_submatrix_view():
    d = Distribution(GlobalElementSize(16, 16), TileElementSize(4, 4))
    v = SubMatrixView(d, GlobalElementIndex(5, 2))
    assert v.begin_tile == GlobalTileIndex(1, 0)
    spec = v.tile_spec(GlobalTileIndex(1, 0))
    assert spec == SubTileSpec(1, 2, 3, 2)
    spec2 = v.tile_spec(GlobalTileIndex(2, 1))
    assert spec2 == SubTileSpec(0, 0, 4, 4)


def test_printing(capsys):
    a = np.arange(4.0).reshape(2, 2)
    mat = Matrix.from_global(a, TileElementSize(2, 2))
    s = printing.print_numpy(mat, name="m")
    assert s.startswith("m = np.array(") and "dtype=np.float64" in s
    ns = {"np": np}
    exec(s, ns)
    np.testing.assert_array_equal(ns["m"], a)
    c = printing.print_csv(mat)
    assert c.splitlines()[0] == "0.0,1.0"


def test_memory_place():
    from dlaf_tpu.matrix import memory as mem

    x = mem.place(np.ones((4, 4)))
    assert x.shape == (4, 4) and hasattr(x, "devices")


def test_tpu_info():
    from dlaf_tpu import tpu_info

    devs = tpu_info.devices()
    assert len(devs) == 8
    assert all(d.platform == "cpu" for d in devs)


def test_effective_eps_platform_calibration(monkeypatch):
    """Residual-check eps: true dtype eps off-TPU; the double-f32
    emulation eps (2^-47, labeled — silicon-calibrated post peel-fix,
    see checks.EMULATED_F64_EPS) for 64-bit dtypes on TPU, where no
    code path can deliver 2^-53-grade results (miniapp/checks.py)."""
    from dlaf_tpu.miniapp import checks

    # CPU backend (this suite): nothing widened, no label
    for dt in (np.float32, np.float64, np.complex128):
        eps, label = checks.effective_eps(dt)
        assert eps == np.finfo(np.dtype(dt).type(0).real.dtype).eps
        assert label == ""

    monkeypatch.setattr(checks, "f64_is_emulated", lambda of=None: True)
    eps, label = checks.effective_eps(np.float64)
    assert eps == checks.EMULATED_F64_EPS and "2^-47" in label
    eps_c, label_c = checks.effective_eps(np.complex128)
    assert eps_c == checks.EMULATED_F64_EPS and label_c == label
    # f32 is native on TPU: untouched even when f64 is emulated
    eps32, label32 = checks.effective_eps(np.float32)
    assert eps32 == np.finfo(np.float32).eps and label32 == ""


def test_miniapp_kernel_and_band():
    from dlaf_tpu.miniapp.miniapp_kernel import run as krun

    res = krun(["--kernel", "gemm", "-m", "32", "--batch", "4", "--nruns", "1"])
    assert len(res) == 1 and res[0]["gflops"] > 0

    from dlaf_tpu.miniapp.miniapp_band_to_tridiag import run as brun

    res = brun(["-m", "64", "-b", "8", "--nruns", "1", "--check-result", "last"])
    assert len(res) == 1


def test_public_api_surface():
    """The reference's free-function layer is reachable from the subpackage
    roots (user-facing API contract)."""
    import numpy as np

    import dlaf_tpu.algorithms as alg
    import dlaf_tpu.eigensolver as eig
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix import Matrix

    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 16))
    a = x @ x.T + 16 * np.eye(16)
    m = Matrix.from_global(a, TileElementSize(4, 4))
    out = alg.cholesky("L", m).to_numpy()
    l = np.tril(out)
    assert np.linalg.norm(l @ l.T - a) < 1e-10 * np.linalg.norm(a)
    res = eig.eigensolver("L", m)
    np.testing.assert_allclose(res.eigenvalues, np.linalg.eigvalsh(a), atol=1e-9)


def test_checkpoint_roundtrip(tmp_path, devices8):
    """Matrix -> orbax checkpoint -> Matrix, local and distributed
    (the application-owned persistence hook; the reference has no
    checkpoint subsystem, SURVEY §5)."""
    import numpy as np

    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.common.index2d import RankIndex2D, TileElementSize
    from dlaf_tpu.matrix import checkpoint
    from dlaf_tpu.matrix.matrix import Matrix

    rng = np.random.default_rng(5)
    a = rng.standard_normal((24, 16))
    m = Matrix.from_global(a, TileElementSize(8, 8))
    checkpoint.save(str(tmp_path / "local"), m)
    m2 = checkpoint.load(str(tmp_path / "local"))
    np.testing.assert_array_equal(m2.to_numpy(), a)

    grid = Grid(2, 4)
    md = Matrix.from_global(a, TileElementSize(8, 8), grid=grid,
                            source_rank=RankIndex2D(1, 2))
    checkpoint.save(str(tmp_path / "dist"), md)
    md2 = checkpoint.load(str(tmp_path / "dist"), grid=grid)
    np.testing.assert_array_equal(md2.to_numpy(), a)
    assert md2.dist.source_rank == RankIndex2D(1, 2)


def test_miniapp_bt_band_to_tridiag():
    from dlaf_tpu.miniapp.miniapp_bt_band_to_tridiag import run as btrun

    res = btrun(["-m", "64", "-b", "8", "--nruns", "1", "--check-result", "last"])
    assert len(res) == 1 and res[0]["gflops"] > 0
    res = btrun(["-m", "64", "-b", "8", "--grid-rows", "2", "--grid-cols", "2",
                 "--nruns", "1", "--check-result", "last"])
    assert len(res) == 1


def test_miniapp_gen_eigensolver_standalone():
    from dlaf_tpu.miniapp.miniapp_gen_eigensolver import run as grun

    res = grun(["-m", "32", "-b", "8", "--nruns", "1", "--check-result", "last"])
    assert len(res) == 1 and res[0]["gflops"] > 0


def test_scaling_scripts():
    out = subprocess.run(
        [sys.executable, "scripts/gen_strong.py", "--miniapp", "cholesky",
         "-m", "1024", "-b", "128", "--grids", "1x1", "2x2"],
        capture_output=True, text=True, check=True, cwd=REPO).stdout
    assert out.count("miniapp_cholesky") == 2 and "--grid-rows 2" in out
    out = subprocess.run(
        [sys.executable, "scripts/gen_weak.py", "--m-per-device", "512",
         "-b", "128", "--grids", "1x1", "2x2"],
        capture_output=True, text=True, check=True, cwd=REPO).stdout
    assert "-m 512" in out and "-m 1024" in out


def test_plot_bench_parses(tmp_path):
    log = tmp_path / "run.log"
    log.write_text("[0] 1.5s 100.0GFlop/s dL (4096, 4096) (256, 256) (2, 2) 8 tpu\n"
                   "[1] 1.0s 150.0GFlop/s dL (4096, 4096) (256, 256) (2, 2) 8 tpu\n")
    out = subprocess.run(
        [sys.executable, "scripts/plot_bench.py", str(log)],
        capture_output=True, text=True, check=True, cwd=REPO).stdout
    assert "best=150.0GF/s" in out and "median=1.5" in out.replace("median=1.5000", "median=1.5")


def test_round_robin():
    from dlaf_tpu.common.round_robin import RoundRobin

    rr = RoundRobin(["a", "b", "c"])
    assert len(rr) == 3
    # nextResource cycles in order, wrapping (common/round_robin.h:24-30)
    assert [rr.next_resource() for _ in range(5)] == ["a", "b", "c", "a", "b"]
    assert rr.current_resource() == "b"  # re-read without advancing
    assert rr.current_resource() == "b"
    assert list(rr) == ["a", "b", "c"]  # pool iteration does not advance
    assert rr.next_resource() == "c"
    import pytest as _pytest
    with _pytest.raises(ValueError):
        RoundRobin([])


def test_profile_dir_hook(tmp_path):
    """--dlaf:profile-dir emits a jax.profiler trace (SURVEY §5 tracing;
    the green-field observability hook the reference lacks)."""
    from dlaf_tpu.miniapp.miniapp_cholesky import run as crun

    out = crun(["-m", "64", "-b", "16", "--nruns", "1",
                f"--dlaf:profile-dir={tmp_path}"])
    assert len(out) == 1
    assert any((tmp_path / p).exists() for p in ("plugins",)) or \
        any(tmp_path.iterdir())


def _load_bench_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_module", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_headline_live_tpu_wins():
    # a live TPU sweep takes the headline directly, no replay fields
    bench = _load_bench_module()
    results = [
        {"variant": "ozaki", "platform": "tpu", "dtype": "float64",
         "gflops": 95.0, "ts": "t1"},
        {"variant": "xla", "platform": "tpu", "dtype": "float64",
         "gflops": 41.0, "ts": "t2"},
    ]
    out = bench.assemble_headline(results, 4096, 256)
    assert out["value"] == 95.0
    assert "[tpu]" in out["metric"] and "ozaki" in out["metric"]
    assert "replayed" not in out and "live_fallback" not in out


def test_bench_headline_ignores_stage_arms():
    # the eigensolver stage arms (tridiag/btr2b — ISSUE 6) measure
    # different flop models; even a faster stage number must never take
    # the cholesky headline
    bench = _load_bench_module()
    results = [
        {"variant": "xla", "platform": "tpu", "dtype": "float64",
         "gflops": 41.0, "ts": "t1"},
        {"variant": "tridiag+dcb1", "platform": "tpu", "dtype": "float64",
         "gflops": 500.0, "workload": "tridiag", "ts": "t2"},
        {"variant": "btr2b+btla1", "platform": "tpu", "dtype": "float64",
         "gflops": 900.0, "workload": "btr2b", "ts": "t3"},
    ]
    out = bench.assemble_headline(results, 4096, 256)
    assert out["value"] == 41.0 and "xla" in out["metric"]


def test_bench_headline_ignores_fpanel_arms():
    # the fused-panel A/B pair (ISSUE 10) is an f32 arm with its own
    # workload label — a (cheap-dtype) faster number must never take the
    # f64 cholesky headline, and the pair must be known to the sweep
    bench = _load_bench_module()
    results = [
        {"variant": "loop", "platform": "tpu", "dtype": "float64",
         "gflops": 41.0, "ts": "t1"},
        {"variant": "fpanel+fp1", "platform": "tpu", "dtype": "float32",
         "gflops": 4000.0, "workload": "fpanel", "ts": "t2"},
    ]
    out = bench.assemble_headline(results, 4096, 256)
    assert out["value"] == 41.0 and "loop" in out["metric"]
    assert "fpanel" in bench.STAGE_BASES


def test_bench_headline_stage_arms_only():
    # every cholesky arm died, only stage arms landed: the headline is
    # None (sweep exits nonzero) — never a mislabeled stage number, never
    # a recorded one
    bench = _load_bench_module()
    results = [
        {"variant": "tridiag+dcb1", "platform": "cpu", "dtype": "float64",
         "gflops": 500.0, "workload": "tridiag", "ts": "t"},
    ]
    assert bench.assemble_headline(results, 4096, 256) is None


def test_bench_headline_labels_an_explicit_cpu_run():
    # CI's explicit JAX_PLATFORMS=cpu arms keep working: the live result
    # stands, labelled with its platform, and nothing else rides along
    bench = _load_bench_module()
    results = [{"variant": "xla", "platform": "cpu", "dtype": "float64",
                "gflops": 13.6, "ts": "t-live"}]
    out = bench.assemble_headline(results, 4096, 256)
    assert out["value"] == 13.6 and "[cpu]" in out["metric"]
    assert set(out) == {"metric", "value", "unit", "vs_baseline"}


def test_bench_refuses_a_platform_that_is_not_the_chip(monkeypatch, capsys):
    """Without an explicit JAX_PLATFORMS=cpu this benchmark measures the
    chip: a run that comes up anywhere else (this test session's CPU)
    exits non-zero before it measures, and prints no number."""
    bench = _load_bench_module()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.expected_platform() == "tpu"
    monkeypatch.setenv("DLAF_BENCH_VARIANT", "loop")
    before = dict(os.environ)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""
    # the refused child wrote none of its arm's knobs into the process
    # (they would steer every later test of this worker)
    assert dict(os.environ) == before
    # the caller's own JAX_PLATFORMS=cpu is the one way onto the CPU
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.expected_platform() == "cpu"
    assert bench.require_platform() == "cpu"


def test_bench_fleet_arm_refuses_the_chip(monkeypatch):
    """The fleet arm's worker processes would each need the one chip: it
    refuses there (before JAX comes up), and the default sweep leaves it
    out on a chip."""
    bench = _load_bench_module()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as exc:
        bench._run_stage_variant("fleet", "fleet", set())
    assert exc.value.code not in (0, None)
    assert "fleet" in bench.MULTIPROCESS_ARMS


def test_bench_failed_arm_fails_the_sweep(monkeypatch, tmp_path, capsys):
    """A child with a non-zero exit makes the sweep exit non-zero, even
    when other arms landed (their live headline is still printed)."""
    import json
    import subprocess

    bench = _load_bench_module()
    monkeypatch.setenv("DLAF_BENCH_OBS_DIR", str(tmp_path))

    def fake_run(cmd, env=None, **kw):
        variant = env["DLAF_BENCH_VARIANT"]
        ok = variant == "xla"
        line = {"variant": variant, "platform": "cpu", "dtype": "float64",
                "n": 4096, "nb": 256, "gflops": 13.6, "t": 0.1, "ts": "t"}
        return subprocess.CompletedProcess(
            cmd, 0 if ok else 1,
            stdout=(json.dumps(line) + "\n").encode() if ok else b"")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setattr(bench, "VARIANT_TIMEOUT_S", 5)
    import dlaf_tpu.algorithms.cholesky  # noqa: F401  (the sweep's import)

    with pytest.raises(SystemExit) as exc:
        bench.sweep("cpu")
    assert exc.value.code == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1])["value"] == 13.6   # live, and still failed


def test_bench_parent_never_creates_a_backend():
    """One process owns a chip: the sweep's parent imports
    dlaf_tpu.algorithms.cholesky for VALID_TRAILING and must not bring a
    JAX backend up by doing so (its children do)."""
    import subprocess

    code = ("import bench, jax\n"
            "from dlaf_tpu.algorithms.cholesky import VALID_TRAILING\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
            "print('clean')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and "clean" in out.stdout, out.stderr[-2000:]


def test_initialize_places_the_compile_cache(monkeypatch):
    """config.initialize() is the one owner of the persistent compile
    cache's place: JAX_COMPILATION_CACHE_DIR set -> nothing is set in
    code; unset -> <checkout>/.jax_cache, derived from the package."""
    import jax

    import dlaf_tpu.config as C

    seen = []
    real_update = jax.config.update

    def spy(name, value):
        seen.append(name)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        C.initialize()
        assert "jax_compilation_cache_dir" not in seen
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        real_update("jax_compilation_cache_dir", None)
        C.initialize()
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache") == \
            C.DEFAULT_COMPILATION_CACHE_DIR
        assert not hasattr(C.Configuration(), "compilation_cache_dir")
    finally:
        real_update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("uplo", ["G", "L"])
def test_max_norm_local_and_distributed(uplo, devices8):
    # auxiliary::norm parity (reference auxiliary/norm/mc.h:29-108):
    # per-tile partial maxima folded locally then max-reduced over both
    # mesh axes; uplo='L' restricts to the stored lower triangle
    from dlaf_tpu.algorithms.norm import max_norm
    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.common.index2d import RankIndex2D, TileElementSize

    rng = np.random.default_rng(7)
    a = rng.standard_normal((13, 13))
    a[11, 2] = 50.0    # strict-lower extreme
    a[1, 12] = -90.0   # strict-upper extreme (excluded under uplo='L')
    expect = np.abs(np.tril(a) if uplo == "L" else a).max()

    local = Matrix.from_global(a, TileElementSize(4, 4))
    assert np.isclose(max_norm(local, uplo), expect)

    dist = Matrix.from_global(a, TileElementSize(4, 4), grid=Grid(2, 4),
                              source_rank=RankIndex2D(1, 2))
    assert np.isclose(max_norm(dist, uplo), expect)

    empty = Matrix.from_global(np.zeros((0, 0)), TileElementSize(4, 4))
    assert max_norm(empty, uplo) == 0.0


def test_telescope_segments_properties():
    from dlaf_tpu.types import telescope_segments

    for steps in [0, 1, 2, 7, 8, 9, 11, 16, 31, 32, 64, 127, 128, 1000]:
        segs = telescope_segments(steps)
        assert sum(segs) == steps
        assert all(s > 0 for s in segs)
        # equal chunks: bounded program count, every chunk >= min size
        assert len(segs) <= 9   # max_segments + ragged tail
        if len(segs) > 1:
            assert all(s_ == segs[0] for s_ in segs[:-1])
            assert segs[-1] <= segs[0]
    assert telescope_segments(8) == (8,)
    assert telescope_segments(16) == (8, 8)
    assert telescope_segments(127) == (16,) * 7 + (15,)
    assert telescope_segments(64) == (8,) * 8


def test_telescope_windows_coalescing():
    """types.telescope_windows — the shared segment builder of every
    telescoped scan formulation: segments cover all steps exactly once in
    order, and adjacent segments with equal window descriptors merge into
    one (no duplicate identically-shaped step programs)."""
    from dlaf_tpu.types import telescope_windows

    # distinct windows: no merging, starts/lengths tile the step range
    segs = telescope_windows(32, lambda pos, _len: pos)
    assert [(s, l) for _, s, l in segs] == [(0, 8), (8, 8), (16, 8),
                                           (24, 8)]
    # slot-window style fn on a 4-rank axis: chunks whose k0 // 4 agree
    # coalesce (e.g. nt=32, chunks of 8 -> windows 0,2,4,6: distinct)
    segs = telescope_windows(32, lambda pos, _len: pos // 16)
    assert [(w, s, l) for w, s, l in segs] == [(0, 0, 16), (1, 16, 16)]
    # constant window: everything merges into ONE scan
    segs = telescope_windows(1000, lambda pos, _len: 0)
    assert segs == [(0, 0, 1000)]
    # length-dependent window (the reverse-sweep/bt form): merging keeps
    # coverage exact and ordered
    segs = telescope_windows(31, lambda pos, ln: (31 - pos - ln) // 8)
    assert sum(l for _, _, l in segs) == 31
    starts = [s for _, s, l in segs]
    assert starts == sorted(starts) and starts[0] == 0
    assert telescope_windows(0, lambda pos, _len: 0) == []


def test_layout_info_offsets_and_min_mem():
    """LayoutInfo parity (reference layout_info.h): tile offsets and
    minimal buffer size for both canonical layouts."""
    from dlaf_tpu.common.index2d import (LocalElementSize, LocalTileIndex,
                                         TileElementSize)
    from dlaf_tpu.matrix.layout_info import col_major_layout, tile_layout

    size = LocalElementSize(10, 7)
    block = TileElementSize(4, 4)
    cm = col_major_layout(size, block, ld=10)
    assert cm.nr_tiles == (3, 2)
    # col-major: vertical neighbor advances by block rows, horizontal by
    # block_cols * ld
    assert cm.tile_offset(LocalTileIndex(1, 0)) == 4
    assert cm.tile_offset(LocalTileIndex(0, 1)) == 4 * 10
    assert cm.tile_offset(LocalTileIndex(2, 1)) == 4 * 10 + 8
    # last element of the last (ragged 2x3) tile fits in min_mem_size
    assert cm.min_mem_size() == cm.tile_offset(LocalTileIndex(2, 1)) \
        + (3 - 1) * 10 + 2
    tl = tile_layout(size, block)
    assert tl.nr_tiles == (3, 2)
    # tile layout: contiguous tiles
    assert tl.tile_size_of(LocalTileIndex(2, 1)) == TileElementSize(2, 3)


def test_matrix_mirror_roundtrip(devices8):
    """MatrixMirror parity (reference matrix_mirror.h): D2H then H2D with
    the same layout reproduces the matrix, distributed included."""
    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix import ops as mops
    from dlaf_tpu.matrix.matrix import Matrix

    rng = np.random.default_rng(5)
    a = rng.standard_normal((13, 13))
    m = Matrix.from_global(a, TileElementSize(4, 4), grid=Grid(2, 4))
    host = mops.mirror_to_host(m)
    np.testing.assert_array_equal(host, a)
    back = mops.mirror_to_device(host * 2, like=m)
    assert back.grid is m.grid and back.block_size == m.block_size
    np.testing.assert_array_equal(back.to_numpy(), a * 2)


def test_permute_array_rows_cols():
    import jax.numpy as jnp

    from dlaf_tpu.algorithms.permutations import permute_array

    a = np.arange(12.0).reshape(3, 4)
    perm = [2, 0, 1]
    np.testing.assert_array_equal(
        np.asarray(permute_array("Row", perm, jnp.asarray(a))), a[perm])
    permc = [3, 2, 1, 0]
    np.testing.assert_array_equal(
        np.asarray(permute_array("Col", permc, jnp.asarray(a))), a[:, permc])


def test_assert_tiers(monkeypatch):
    """3-tier assertion ladder (reference DLAF_ASSERT/_MODERATE/_HEAVY):
    plain asserts always fire; heavy fires only when enabled (the test
    session enables it via conftest)."""
    import dlaf_tpu.common.asserts as asserts

    with pytest.raises(asserts.DlafAssertError, match="boom"):
        asserts.dlaf_assert(False, "boom")
    # heavy is enabled in the suite (conftest sets the env)
    with pytest.raises(asserts.DlafAssertError):
        asserts.dlaf_assert_heavy(False, "heavy fires when enabled")
    asserts.dlaf_assert(True, "no fire")
    asserts.dlaf_assert_moderate(True, "no fire")


def test_sub_panel_view_width(devices8):
    from dlaf_tpu.common.index2d import (GlobalElementIndex,
                                         GlobalElementSize, TileElementSize)
    from dlaf_tpu.matrix.distribution import Distribution
    from dlaf_tpu.matrix.views import SubPanelView

    dist = Distribution(GlobalElementSize(16, 16), TileElementSize(4, 4))
    v = SubPanelView(dist, GlobalElementIndex(4, 12), width=4)
    assert v.begin_tile.row == 1 and v.begin_tile.col == 3
    assert v.cols() == 4
    edge = SubPanelView(dist, GlobalElementIndex(0, 14), width=4)
    assert edge.cols() == 2   # clamped at the matrix edge


# ---------------------------------------------------------------------------
# chip_smoke.py: tiny-N CPU rehearsal of the on-chip check's phases
# ---------------------------------------------------------------------------

def _load_chip_smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke_module", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_single_chip_phases_rehearse_on_cpu(capsys):
    """Phases 1-4 as functions of their sizes: same entry points, same
    host-reference comparisons, at tiny N on the CPU."""
    cs = _load_chip_smoke()
    a, fac = cs.phase_cholesky("cholesky_f64", np.float64, 64, 16, "cpu")
    cs.phase_solve("solve_f64", a, fac, 16, "cpu")
    cs.phase_cholesky("cholesky_f32", np.float32, 64, 16, "cpu")
    cs.phase_eigensolver("eigensolver_f64", 64, 16, "cpu")
    out = capsys.readouterr().out
    for phase in ("cholesky_f64", "solve_f64", "cholesky_f32",
                  "eigensolver_f64"):
        assert f"[{phase}] program=" in out          # compile/run lines
        assert f"[{phase}] dlaf_fallback_total=0" in out
    assert "ok=False" not in out and out.count("check: PASSED") == 3


def test_chip_smoke_multichip_phase_rehearses_on_cpu(devices8, capsys):
    cs = _load_chip_smoke()
    cs.phase_multichip("cpu", devices8[:4], 64, 48, 32, 8)
    out = capsys.readouterr().out
    assert out.count("shard_devices=4") == 3 and "ok=False" not in out
    assert "[multichip_trsm_f64] step_mode=" in out


def test_chip_smoke_refuses_without_a_chip(capsys):
    """The device check fails loudly on the CPU: non-zero, before any
    phase, and no result line on stdout."""
    cs = _load_chip_smoke()
    assert cs.main([]) != 0 and cs.main(["--multichip"]) != 0
    cap = capsys.readouterr()
    assert cap.out == "" and "not a TPU" in cap.err


@pytest.mark.parametrize("value", [1.0, float("nan")])
def test_chip_smoke_failed_comparison_exits_nonzero(value, capsys):
    cs = _load_chip_smoke()
    with pytest.raises(SystemExit) as exc:
        cs._hold("phase", "residual", value, 1e-9)
    assert exc.value.code == 1 and "FAILED" in capsys.readouterr().out
    # the f64 budgets are tight enough that an f32-grade answer fails
    assert cs._tol(cs.C_FACTOR, 4096, np.float64, "tpu") < 2.0 ** -24
    assert cs._tol(cs.C_EIGEN, 4096, np.float64, "tpu") < 2.0 ** -24


def test_mfu_table_peaks_are_keyed_by_device_kind():
    """The one table of published peaks answers to the key the chip
    itself reports; an unknown kind is an error, not a default."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import mfu_table

    assert mfu_table.peaks_for("TPU v5 lite") is mfu_table.CHIPS["v5e"]
    assert mfu_table.peaks_for("TPU v5 lite")["bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        mfu_table.peaks_for("TPU v9 imaginary")
