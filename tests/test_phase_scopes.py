"""The builders' phases as scopes of the compiled programs (ISSUE 35).

Three things are checked here, on the CPU:

* ``obs/scopes.py``, the library's one parser of trace-time scope paths, on
  hand-written paths and compiled text (innermost phase wins, step markers
  are not phases, no scope gives None), and ``critpath.schedule_from_hlo``
  reading through it;
* the four builders the benchmark's cells run (the unrolled and the scan
  local Cholesky, the scan local reduction to band, the distributed scan
  solve), dispatched small through their public entries under a TPU's knob
  resolution: every phase of the vocabulary is in the executable
  (``dlaf_phase_instructions`` non-zero) and the scopes are metadata only
  (the StableHLO without locations is what a process with observability off
  lowers, which is the parent's program);
* ``telemetry.call`` with the metrics sink on: the same arrays, one handle a
  site, no aval built after the first call, nothing remembered with the sink
  off; ``phase_table`` says ``stale`` for an executable without scopes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.obs import critpath, scopes, telemetry


@pytest.fixture(autouse=True)
def obs_reset():
    yield
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


def _sink_on(tmp_path):
    C.initialize(C.Configuration(metrics_path=str(tmp_path / "m.jsonl")))
    assert obs.metrics_active()


# ---------------------------------------------------------------------------
# the parser
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path, want", [
    # the innermost phase wins, a scan body reports step -1
    ("jit(f)/while/body/closed_call/red2band.scanstep/red2band.panel/mul",
     ("red2band", -1, "panel")),
    # a helper jitted once keeps each call site's phase
    ("jit(f)/cholesky.panel/jit(helper)/dot_general",
     ("cholesky", None, "panel")),
    ("jit(f)/cholesky.bulk/jit(helper)/dot_general",
     ("cholesky", None, "bulk")),
    # a chain hoisted into step 3's scope and tagged step 4's
    ("jit(run)/cholesky.step003/cholesky.step004.panel/psum",
     ("cholesky", 4, "panel")),
    ("jit(run)/trsm.step012.bulk/dot_general", ("trsm", 12, "bulk")),
    # an inner phase under an outer one
    ("jit(f)/red2band.scanstep/red2band.w/while/body/red2band.rowchunk/dot",
     ("red2band", -1, "w")),
    ("jit(f)/cholesky.bulk/cholesky.strip/add", ("cholesky", None, "strip")),
    # the bare layout phase
    ("jit(cholesky_local_on_tiles)/layout/transpose", ("", None, "layout")),
    # the distributed Cholesky's collectives inside a panel chain: the
    # innermost phase is ``comm``; a chain hoisted into step 3's scope and
    # tagged step 4's panel is step 4's ``comm``; a scan body's is step -1
    ("jit(run)/cholesky.step000/cholesky.step000.panel/cholesky.comm/psum",
     ("cholesky", 0, "comm")),
    ("jit(run)/cholesky.step003/cholesky.step004.panel/cholesky.comm/psum",
     ("cholesky", 4, "comm")),
    ("jit(run)/while/body/cholesky.scanstep/cholesky.comm/all_gather",
     ("cholesky", -1, "comm")),
])
def test_parse_innermost_phase_wins(path, want):
    assert tuple(scopes.parse(path)) == want


@pytest.mark.parametrize("path, want", [
    ("jit(run)/while/body/trsm.scanstep/mul", ("trsm", -1, None)),
    ("jit(f)/red2band.scanstep/while/body/red2band.rowchunk/dot_general",
     ("red2band", -1, None)),
    ("jit(run)/cholesky.step007/add", ("cholesky", 7, None)),
])
def test_step_markers_are_not_phases(path, want):
    scope = scopes.parse(path)
    assert tuple(scope) == want and scope.phase is None


@pytest.mark.parametrize("path", [
    "jit(f)/while/body/closed_call/mul", "x", "",
    "jit(_cholesky_local)/jit(_where)/select_n", "jit(f)/dot_general",
])
def test_no_scope_is_none(path):
    assert scopes.parse(path) is None


HLO = """HloModule jit_toy, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(toy)/while/body/toy.scanstep/toy.panel/mul" stack_frame_id=3}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(toy)/while/body/toy.scanstep/toy.panel/mul" stack_frame_id=3}
  %copy.2 = f32[4]{0:T(128)} copy(%fusion.1), backend_config={"flag_configs":[]}
  %fusion.3 = f32[4]{0} fusion(%copy.2, %a), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(toy)/while/body/toy.scanstep/toy.larft/dot_general"}
  %add.4 = f32[4]{0} add(%fusion.3, %a), metadata={op_name="jit(toy)/toy.step002.bulk/add"}
  ROOT %neg.5 = f32[4]{0} negate(%add.4), metadata={op_name="jit(toy)/neg"}
}
"""


def test_instructions_of_compiled_text():
    rows = {name: (op_name, scopes.operand_names(rest))
            for name, op_name, rest in scopes.instructions(HLO)}
    assert scopes.module_name(HLO) == "jit_toy"
    assert rows["copy.2"] == ("", ["fusion.1"])
    assert rows["fusion.3"][1] == ["copy.2", "a"]
    assert rows["mul.1"][0].endswith("toy.panel/mul")
    assert rows["neg.5"] == ("jit(toy)/neg", ["add.4"])


def test_schedule_from_hlo_reads_through_the_parser():
    """Any phase token reaches the schedule (the reports fold the ones
    outside ``critpath.PHASES`` into ``other``); an instruction without a
    step marker is left out, as before."""
    sched = critpath.schedule_from_hlo(HLO)
    assert sched["module"] == "jit_toy"
    assert sched["ops"] == {
        "mul.1": ["toy", -1, "panel"], "fusion.1": ["toy", -1, "panel"],
        "fusion.3": ["toy", -1, "larft"], "add.4": ["toy", 2, "bulk"]}
    assert "larft" not in critpath.PHASES


@pytest.mark.parametrize("phase, chain", [
    ("panel", True), ("comm", True), ("strip", True), ("bulk", False),
    ("other", False)])
def test_critpath_enumerates_the_panel_chain(phase, chain):
    """``comm`` (the distributed Cholesky's collectives) is a phase of the
    reports and part of the panel chain, not folded into ``other`` (which
    counts with the bulk): a step whose chain time is all collectives is
    still bound by its panel chain."""
    assert phase in critpath.PHASES
    assert (phase in critpath.PANEL_CHAIN) == chain
    step = {"phases": {phase: 1.0, "bulk": 0.5}, "comm_exposed_s": 0.0,
            "copy_s": 0.0}
    assert critpath._bound_of(step) == ("panel" if chain else "bulk")
    path = critpath._critical_path(
        [{"step": 0, "phases": {"panel": 1.0, phase: 2.0, "bulk": 1.0}}],
        lookahead=True)
    assert f"step000.{phase}" in path["nodes"]


def test_a_hoisted_chains_comm_is_the_next_steps():
    """A chain hoisted into step 3's scope, tagged step 4's panel, with its
    collective under ``cholesky.comm``: the schedule gives it to step 4's
    ``comm``."""
    hlo = HLO.replace("jit(toy)/toy.step002.bulk/add",
                      "jit(toy)/toy.step003/toy.step004.panel/toy.comm/psum")
    assert critpath.schedule_from_hlo(hlo)["ops"]["add.4"] \
        == ["toy", 4, "comm"]


# ---------------------------------------------------------------------------
# the four builders, through their entries
# ---------------------------------------------------------------------------

def _hpd(n, seed=0):
    x = np.random.default_rng(seed).standard_normal((n, n))
    return x @ x.T + n * np.eye(n)


def _cholesky(n, nb):
    from dlaf_tpu.algorithms import cholesky

    def dispatch():
        mat = Matrix.from_global(_hpd(n), TileElementSize(nb, nb))
        return cholesky("L", mat, donate=True).storage
    return dispatch


def _red2band():
    from dlaf_tpu.eigensolver import reduction_to_band

    def dispatch():
        g = np.random.default_rng(3).standard_normal((528, 528))
        mat = Matrix.from_global((g + g.T) / 2, TileElementSize(64, 64))
        return reduction_to_band(mat, band_size=16, donate=True).matrix.storage
    return dispatch


def _trsm(devices):
    from dlaf_tpu.algorithms import triangular_solve

    def dispatch():
        grid = Grid(2, 2, devices=list(devices[:4]))
        n, nb = 256, 8      # 32 block steps: the scan form, as the cell's
        t = np.tril(np.random.default_rng(5).standard_normal((n, n)), -1)
        t[np.diag_indices(n)] = 2.0 * n
        b = np.random.default_rng(6).standard_normal((n, n))
        tm = Matrix.from_global(t, TileElementSize(nb, nb), grid=grid)
        bm = Matrix.from_global(b, TileElementSize(nb, nb), grid=grid)
        return triangular_solve("L", "L", "N", "N", 1.0, tm, bm,
                                donate_b=True).storage
    return dispatch


def _cholesky_dist(devices):
    from dlaf_tpu.algorithms import cholesky

    def dispatch():
        grid = Grid(2, 2, devices=list(devices[:4]))
        mat = Matrix.from_global(_hpd(128), TileElementSize(16, 16),
                                 grid=grid)
        return cholesky("L", mat, donate=True).storage
    return dispatch


BUILDERS = {
    # site, dispatch, phases the program must carry
    "cholesky_unrolled": ("cholesky.local", lambda d: _cholesky(128, 32),
                          {"panel", "strip", "bulk", "layout"}),
    "cholesky_scan": ("cholesky.local_scan", lambda d: _cholesky(256, 8),
                      {"panel", "strip", "bulk", "layout"}),
    "red2band_scan": ("reduction_to_band.local_scan", lambda d: _red2band(),
                      {"panel", "larft", "w", "x", "update"}),
    "trsm_dist_scan": ("triangular_solve.dist", _trsm, {"panel", "bulk"}),
    # ISSUE 37: the unrolled distributed Cholesky (8 steps, look-ahead and
    # the hoisted chains as on a TPU), its collectives the phase ``comm``
    "cholesky_dist": ("cholesky.dist", _cholesky_dist,
                      {"panel", "comm", "strip", "bulk"}),
}


@pytest.fixture
def no_persistent_cache():
    """jax keeps metadata out of the persistent cache's key: the plain
    program, once a compile of 5 s or more under a loaded host wrote it to
    the checkout's cache, would serve the scoped dispatch below (and every
    later run's), and its table would read ``stale``."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.mark.parametrize("which", sorted(BUILDERS))
def test_builder_carries_its_phases_as_metadata_only(which, as_on_tpu,
                                                     no_persistent_cache,
                                                     tmp_path, devices8):
    site, make, phases = BUILDERS[which]
    dispatch = make(devices8)
    # observability off: the program as the parent lowers it
    plain_out = np.asarray(dispatch())
    assert telemetry.programs() == []
    # the metrics sink on: the same entry, scopes and handles live
    _sink_on(tmp_path)
    C._clear_program_caches()
    out = np.asarray(dispatch())
    np.testing.assert_array_equal(out, plain_out)
    assert site in telemetry.programs()
    table = telemetry.phase_table(site)
    assert not table["stale"]
    assert phases <= set(table["counts"]), (which, table["counts"])
    gauges = {m["labels"]["phase"]: m["value"]
              for m in obs.registry().snapshot()
              if m["name"] == "dlaf_phase_instructions"
              and m["labels"]["site"] == site}
    assert all(gauges[p] > 0 for p in phases), gauges
    hbm = {m["labels"]["what"] for m in obs.registry().snapshot()
           if m["name"] == "dlaf_hbm_bytes" and m["labels"]["site"] == site}
    assert {"temp", "code"} <= hbm
    # scopes are locations: without them the module is the plain one
    handle = telemetry._HANDLES[site]
    scoped = handle.fn.lower(*handle.args, **handle.kwargs)
    assert phases <= scopes.phases_of_text(scoped.as_text(debug_info=True))
    obs._reset_for_tests()
    C.finalize()
    C.initialize()
    C._clear_program_caches()
    handle.fn.clear_cache()         # jit's own trace of the scoped program
    assert not obs.metrics_active()
    plain = handle.fn.lower(*handle.args, **handle.kwargs)
    assert not scopes.phases_of_text(plain.as_text(debug_info=True))
    assert scoped.as_text() == plain.as_text()


# ---------------------------------------------------------------------------
# telemetry.call and the handles
# ---------------------------------------------------------------------------

def _toy():
    @jax.jit
    def toy(x, s):
        with obs.named_span("toy.panel"):
            y = x * s
        with obs.named_span("toy.bulk"):
            return y @ y
    return toy


def test_call_remembers_one_handle_a_site(tmp_path, monkeypatch):
    x = jnp.arange(16.0).reshape(4, 4)
    off = np.asarray(telemetry.call("toy", _toy(), x, 2.0))
    assert telemetry.programs() == [] and not telemetry._HANDLES
    _sink_on(tmp_path)
    built = []
    plain_aval = telemetry._aval
    monkeypatch.setattr(telemetry, "_aval",
                        lambda a: built.append(1) or plain_aval(a))
    toy = _toy()
    on = np.asarray(telemetry.call("toy", toy, x, 2.0))
    np.testing.assert_array_equal(on, off)
    assert telemetry.programs() == ["toy"] and len(built) == 2
    for _ in range(3):
        np.testing.assert_array_equal(
            np.asarray(telemetry.call("toy", toy, x, 2.0)), off)
    telemetry.call("toy", toy, jnp.ones((2, 2)), 1.0)     # another shape
    assert len(built) == 2 and telemetry.programs() == ["toy"]
    assert telemetry._HANDLES["toy"].args[0].shape == (4, 4)
    # not a jitted callable: the site is known, nothing can be lowered
    assert telemetry.call("plain", lambda v: v + 1, 1) == 2
    assert "plain" in telemetry._HANDLES
    assert telemetry.programs() == ["toy"]
    assert telemetry.compiled("plain") is None
    assert telemetry.phase_table("never_called") is None


def test_handles_are_bounded_and_cleared_with_the_program_caches(
        tmp_path, monkeypatch):
    _sink_on(tmp_path)
    monkeypatch.setattr(telemetry, "MAX_PROGRAMS", 3)
    toy = _toy()
    for i in range(5):
        telemetry.call(f"site{i}", toy, jnp.ones((2, 2)), 1.0)
    assert telemetry.programs() == ["site2", "site3", "site4"]
    C._clear_program_caches()
    assert telemetry.programs() == []


def test_compiled_and_phase_table_on_demand(tmp_path):
    _sink_on(tmp_path)
    toy = _toy()
    x = jnp.ones((4, 4))
    telemetry.call("toy", toy, x, 2.0)
    handle = telemetry._HANDLES["toy"]
    assert handle.compiled is None and handle.table is None   # nobody asked
    exe = telemetry.compiled("toy")
    assert telemetry.compiled("toy") is exe                   # kept
    np.testing.assert_array_equal(np.asarray(exe(x, 2.0)),
                                  np.asarray(toy(x, 2.0)))
    table = telemetry.phase_table("toy")
    assert telemetry.phase_table("toy") is table
    assert table["module"] == "jit_toy" and not table["stale"]
    assert set(table["counts"]) == {"panel", "bulk"}
    assert set(table["phases"].values()) == {"panel", "bulk"}
    assert not set(table["phases"]) & set(table["operands"])
    gauges = {(m["labels"]["site"], m["labels"]["phase"]): m["value"]
              for m in obs.registry().snapshot()
              if m["name"] == "dlaf_phase_instructions"}
    assert gauges == {("toy", p): float(n)
                      for p, n in table["counts"].items()}


def test_knob_on_serves_the_same_functions(tmp_path):
    """``DLAF_PROGRAM_TELEMETRY``: the AOT path's executable is the one
    ``compiled`` hands out, and the gauges are the same."""
    C.initialize(C.Configuration(metrics_path=str(tmp_path / "k.jsonl"),
                                 program_telemetry=True))
    toy = _toy()
    telemetry.call("toy", toy, jnp.ones((4, 4)), 2.0)
    assert telemetry.programs() == ["toy"]
    exe = telemetry.compiled("toy")
    assert any(entry[1] is exe for entry in telemetry._PROGRAMS.values())
    assert set(telemetry.phase_table("toy")["counts"]) == {"panel", "bulk"}


class _ScopelessExecutable:
    """An executable as an older tree's persistent cache hands it out: the
    same program, its ``op_name`` metadata without our scopes."""

    def as_text(self):
        return HLO.replace("toy.scanstep/toy.panel/", "").replace(
            "toy.scanstep/toy.larft/", "").replace("toy.step002.bulk/", "")

    def memory_analysis(self):
        return None


def test_phase_table_says_stale_on_text_without_scopes(tmp_path):
    _sink_on(tmp_path)
    telemetry.call("toy", _toy(), jnp.ones((4, 4)), 2.0)
    telemetry._HANDLES["toy"].compiled = _ScopelessExecutable()
    table = telemetry.phase_table("toy")
    assert table["stale"] and not table["phases"] and not table["counts"]
    gauges = {m["labels"]["phase"]: m["value"]
              for m in obs.registry().snapshot()
              if m["name"] == "dlaf_phase_instructions"}
    assert gauges == {"panel": 0.0, "bulk": 0.0}
