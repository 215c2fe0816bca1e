"""Tests for ISSUE 7: program telemetry, rank-aware artifacts +
aggregation/Chrome export, and the bench-regression gate.

Covers: the DLAF_PROGRAM_TELEMETRY knob end-to-end (compile walls,
retrace counters, HBM gauges, the ``program`` record type,
``--require-telemetry``), the bitwise no-op contract (knob on == knob
off on the algorithm paths), the ``%r`` per-rank artifact template,
``dlaf_tpu.obs.aggregate`` (skew/imbalance/overlap + Chrome trace), the
schema-validated bench history path, and ``scripts/bench_gate.py``
(clean replay passes, an injected 20 % slowdown trips the gate).
"""

import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.matrix.matrix import Matrix

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")
if SCRIPTS not in sys.path:
    sys.path.insert(0, SCRIPTS)


@pytest.fixture(autouse=True)
def telemetry_reset():
    """Leave every test with the suite's default unobserved config."""
    yield
    for key in ("DLAF_METRICS_PATH", "DLAF_TRACE_DIR", "DLAF_LOG",
                "DLAF_PROGRAM_TELEMETRY"):
        os.environ.pop(key, None)
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


def _hpd(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n))
    return x @ x.T + n * np.eye(n)


def _telemetry_on(tmp_path, name="tele.jsonl"):
    path = str(tmp_path / name)
    C.initialize(C.Configuration(metrics_path=path, program_telemetry=True))
    return path


# ---------------------------------------------------------------------------
# program telemetry (tentpole)
# ---------------------------------------------------------------------------

def test_telemetry_call_records_compile_and_retrace(tmp_path):
    """telemetry.call: one compile record + retrace count per distinct
    program; a second same-shape call reuses the executable; a new shape
    is a retrace. The artifact validates under --require-telemetry."""
    path = _telemetry_on(tmp_path)
    f = jax.jit(lambda x: x * 2.0)
    a = jnp.ones((8, 8))
    out1 = obs.telemetry.call("toy", f, a)
    out2 = obs.telemetry.call("toy", f, a)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    obs.telemetry.call("toy", f, jnp.ones((4, 4)))
    obs.flush()
    recs = obs.read_records(path)
    compiles = [r for r in recs if r.get("type") == "program"
                and r.get("event") == "compile"]
    assert len(compiles) == 2               # 2 shapes -> 2 programs
    for r in compiles:
        assert r["site"] == "toy"
        assert math.isfinite(r["compile_s"]) and r["compile_s"] >= 0
        assert math.isfinite(r["trace_s"])
        assert all(math.isfinite(v) for v in r["hbm"].values())
        assert "peak" in r["hbm"]
    snap = [r for r in recs if r.get("type") == "metrics"][-1]["metrics"]
    retrace = [m for m in snap if m["name"] == "dlaf_retrace_total"]
    assert retrace and retrace[0]["labels"] == {"site": "toy"} \
        and retrace[0]["value"] == 2.0
    hbm = {(m["labels"]["what"]) for m in snap
           if m["name"] == "dlaf_hbm_bytes"}
    assert {"args", "output", "temp", "code", "peak"} <= hbm
    assert obs.validate_file(path, require_telemetry=True) == []


def test_telemetry_off_is_passthrough():
    """Knob off: call() returns the jitted callable's own result and
    builds no program cache, no records, no registry metrics."""
    C.initialize()
    assert not obs.telemetry.active()
    f = jax.jit(lambda x: x + 1)
    out = obs.telemetry.call("toy", f, jnp.zeros((4,)))
    np.testing.assert_array_equal(np.asarray(out), np.ones((4,)))
    assert obs.telemetry._PROGRAMS == {}


def test_program_cache_is_bounded(tmp_path, monkeypatch):
    """The AOT program cache evicts LRU at MAX_PROGRAMS — a long-lived
    telemetry-on process sweeping many shapes must not pin every dead
    executable forever."""
    from dlaf_tpu.obs import telemetry

    _telemetry_on(tmp_path)
    monkeypatch.setattr(telemetry, "MAX_PROGRAMS", 3)
    f = jax.jit(lambda x: x + 1)
    for n in range(1, 6):
        obs.telemetry.call("bounded", f, jnp.zeros((n,)))
    assert len(telemetry._PROGRAMS) == 3
    # the newest shapes survived; re-calling one is a cache hit (no new
    # compile record)
    before = len([1 for k in telemetry._PROGRAMS])
    obs.telemetry.call("bounded", f, jnp.zeros((5,)))
    assert len(telemetry._PROGRAMS) == before


def test_aot_compile_probe_api(tmp_path):
    """aot_compile always measures (the probe scripts' contract) but only
    records when the knob is on."""
    C.initialize()                          # knob off
    f = jax.jit(lambda x: x @ x)
    spec = jax.ShapeDtypeStruct((16, 16), np.float64)
    prog = obs.telemetry.aot_compile("probe", f, spec)
    assert math.isfinite(prog.compile_s) and math.isfinite(prog.trace_s)
    assert prog.memory is not None and "peak" in prog.memory
    assert prog.memory["peak"] >= 0
    # executing the compiled program works (concrete args)
    out = prog.compiled(jnp.eye(16, dtype=np.float64))
    np.testing.assert_array_equal(np.asarray(out), np.eye(16))

    path = _telemetry_on(tmp_path)
    obs.telemetry.aot_compile("probe", f, spec)
    obs.flush()
    recs = obs.read_records(path)
    assert any(r.get("type") == "program" and r.get("event") == "compile"
               and r.get("site") == "probe" for r in recs)


def test_cholesky_local_bitwise_noop_and_telemetry(tmp_path):
    """The acceptance pin: knob off == knob on, bitwise, on the local
    cholesky path — and with the knob on the artifact carries the
    cholesky.local program telemetry."""
    n, nb = 64, 16
    a = _hpd(n)
    C.initialize()
    ref = cholesky_bytes(a, nb)

    path = _telemetry_on(tmp_path)
    assert obs.telemetry.active()
    got = cholesky_bytes(a, nb)
    np.testing.assert_array_equal(ref, got)   # exact — same program
    obs.flush()
    recs = obs.read_records(path)
    sites = {r.get("site") for r in recs if r.get("type") == "program"}
    assert "cholesky.local" in sites
    assert obs.validate_file(path, require_telemetry=True) == []


def cholesky_bytes(a, nb):
    from dlaf_tpu.algorithms.cholesky import cholesky

    mat = Matrix.from_global(a, TileElementSize(nb, nb))
    out = cholesky("L", mat)
    return np.asarray(out.to_numpy()).tobytes()


def test_cholesky_distributed_bitwise_noop(devices8):
    """Same pin on the distributed builder (2x2 grid): telemetry reroutes
    dispatch through the AOT executable; the numbers must not move."""
    from dlaf_tpu.comm.grid import Grid

    n, nb = 64, 16
    a = _hpd(n)

    def run():
        from dlaf_tpu.algorithms.cholesky import cholesky

        mat = Matrix.from_global(a, TileElementSize(nb, nb),
                                 grid=Grid(2, 2))
        return np.asarray(cholesky("L", mat).to_numpy()).tobytes()

    C.initialize()
    ref = run()
    C.initialize(C.Configuration(program_telemetry=True))
    assert obs.telemetry.active()
    got = run()
    assert ref == got
    # the registry carries the dist site's trace count even without a sink
    snap = obs.registry().snapshot()
    retr = [m for m in snap if m["name"] == "dlaf_retrace_total"
            and m["labels"].get("site") == "cholesky.dist"]
    assert retr and retr[0]["value"] >= 1


def test_triangular_solve_dist_telemetry_bitwise(tmp_path, devices8):
    """telemetry.call on the distributed triangular solve: bitwise, and
    the site lands in the artifact."""
    from dlaf_tpu.algorithms.triangular import triangular_solve
    from dlaf_tpu.comm.grid import Grid

    n, nb = 32, 8
    rng = np.random.default_rng(1)
    a = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    b = rng.standard_normal((n, n))

    def run():
        am = Matrix.from_global(a, TileElementSize(nb, nb), grid=Grid(2, 2))
        bm = Matrix.from_global(b, TileElementSize(nb, nb), grid=Grid(2, 2))
        return np.asarray(
            triangular_solve("L", "L", "N", "N", 1.0, am, bm)
            .to_numpy()).tobytes()

    C.initialize()
    ref = run()
    path = _telemetry_on(tmp_path)
    got = run()
    assert ref == got
    obs.flush()
    sites = {r.get("site") for r in obs.read_records(path)
             if r.get("type") == "program"}
    assert "triangular_solve.dist" in sites


# ---------------------------------------------------------------------------
# rank-aware artifacts (%r template, rank stamping)
# ---------------------------------------------------------------------------

def test_rank_template_and_stamping(tmp_path):
    """%r in DLAF_METRICS_PATH resolves to the process rank and every
    record carries the rank field."""
    jax.process_index()     # ensure a live backend: rank resolution is
    tpl = str(tmp_path / "art.r%r.jsonl")   # deliberately non-forcing
    C.initialize(C.Configuration(metrics_path=tpl))
    with obs.span("x"):
        pass
    obs.flush()
    rank = jax.process_index()
    path = tpl.replace("%r", str(rank))
    assert os.path.exists(path)
    recs = obs.read_records(path)
    assert recs and all(r.get("rank") == rank for r in recs)
    assert obs.validate_file(path) == []


def test_set_rank_overrides_stamp(tmp_path):
    path = str(tmp_path / "ranked.jsonl")
    C.initialize(C.Configuration(metrics_path=path))
    obs.set_rank(7)
    with obs.span("x"):
        pass
    assert all(r["rank"] == 7 for r in obs.read_records(path))


def test_rank_template_defers_without_backend(tmp_path, monkeypatch):
    """Before any backend exists the %r template must NOT force
    jax.process_index() (it would initialize the local backend — fatal
    on a multi-host worker that has yet to run jax.distributed
    .initialize); expansion defers to the sink's first write."""
    from dlaf_tpu.obs import _state, sinks

    monkeypatch.setattr(_state, "current_rank", lambda: None)
    tpl = str(tmp_path / "d.r%r.jsonl")
    assert sinks.expand_rank_template(tpl) == tpl       # deferred
    sink = sinks.JsonlSink(tpl)
    # the backend comes up (multihost init pinned rank 2) before the
    # first write: the deferred template resolves there
    monkeypatch.setattr(_state, "current_rank", lambda: 2)
    sink.write({"type": "log", "level": "info", "logger": "t", "msg": "m",
                "fields": {}})
    sink.close()
    assert sink.path.endswith("d.r2.jsonl") and os.path.exists(sink.path)
    assert obs.read_records(sink.path)[0]["rank"] == 2


# ---------------------------------------------------------------------------
# aggregation + Chrome export
# ---------------------------------------------------------------------------

def _write_rank_artifact(path, rank, t0, extra_metrics=()):
    sink = obs.JsonlSink(str(path))
    # two nested spans; ts is the EXIT time by schema
    sink.write({"type": "span", "name": "cholesky", "dur_s": 0.4,
                "depth": 1, "parent": "run", "attrs": {"lookahead": 1},
                "ts": t0 + 0.45, "rank": rank})
    sink.write({"type": "span", "name": "run", "dur_s": 0.5, "depth": 0,
                "parent": None, "attrs": {}, "ts": t0 + 0.5, "rank": rank})
    sink.write({"type": "program", "site": "cholesky.dist",
                "event": "compile", "compile_s": 0.1, "trace_s": 0.02,
                "hbm": {"peak": 1024.0}, "attrs": {}, "ts": t0 + 0.2,
                "rank": rank})
    sink.write({"type": "metrics", "ts": t0 + 0.6, "rank": rank,
                "metrics": [
                    {"name": "dlaf_comm_collective_bytes_total",
                     "kind": "counter",
                     "labels": {"kind": "bcast", "axis": "row"},
                     "value": 1000.0 * (1 + rank)},
                    *extra_metrics]})
    sink.close()


def test_aggregate_merges_and_reports(tmp_path, capsys):
    from dlaf_tpu.obs import aggregate as agg

    t0 = 1000.0
    p0, p1 = tmp_path / "a.r0.jsonl", tmp_path / "a.r1.jsonl"
    _write_rank_artifact(p0, 0, t0)
    _write_rank_artifact(p1, 1, t0 + 0.1)
    records = agg.merge_artifacts([str(p0), str(p1)])
    assert sorted({r["rank"] for r in records}) == [0, 1]
    # ts-ordered merge
    assert [r.get("ts") for r in records] == \
        sorted(r.get("ts") for r in records)

    rows = agg.rank_skew_rows(records)
    by_name = {row["name"]: row for row in rows}
    assert by_name["run"]["per_rank"][0]["count"] == 1
    assert by_name["run"]["skew_s"] == pytest.approx(0.0)

    imb = agg.collective_imbalance(records)
    assert imb and imb[0]["ratio"] == pytest.approx(2.0)

    ov = agg.overlap_report(records)
    assert set(ov["rank_wall_s"]) == {0, 1}
    # rank 1 starts 0.1 s late over a 0.4 s span -> 75% aligned
    assert ov["aligned"]["cholesky"] == pytest.approx(0.75, abs=1e-6)
    assert ov["knobs"] == {"lookahead": [1]}


def test_rebase_per_rank_removes_clock_offset(tmp_path):
    """--align: a constant inter-host clock offset must drop out of the
    cross-rank aligned fraction (simultaneous work on offset clocks
    reads ~0% aligned without it)."""
    from dlaf_tpu.obs import aggregate as agg

    t0 = 7000.0
    p0, p1 = tmp_path / "c.r0.jsonl", tmp_path / "c.r1.jsonl"
    _write_rank_artifact(p0, 0, t0)
    _write_rank_artifact(p1, 1, t0 + 50.0)   # 50 s clock offset: disjoint
    records = agg.merge_artifacts([str(p0), str(p1)])
    assert agg.overlap_report(records)["aligned"]["cholesky"] == 0.0
    aligned = agg.overlap_report(agg.rebase_per_rank(records))
    assert aligned["aligned"]["cholesky"] == pytest.approx(1.0)
    # walls are offset-invariant either way
    assert aligned["rank_wall_s"] == \
        agg.overlap_report(records)["rank_wall_s"]


def test_overlap_wall_spans_latest_end(tmp_path):
    """The per-rank wall runs to the LATEST span end, not the end of the
    latest-starting span: a short step span nested inside a long entry
    span must not understate the wall (and inflate every share)."""
    from dlaf_tpu.obs import aggregate as agg

    t0 = 5000.0
    p = tmp_path / "w.r0.jsonl"
    sink = obs.JsonlSink(str(p))
    sink.write({"type": "span", "name": "entry", "dur_s": 10.0, "depth": 0,
                "parent": None, "attrs": {}, "ts": t0 + 10.0, "rank": 0})
    sink.write({"type": "span", "name": "step", "dur_s": 1.0, "depth": 1,
                "parent": "entry", "attrs": {}, "ts": t0 + 2.0, "rank": 0})
    sink.close()
    ov = agg.overlap_report(agg.merge_artifacts([str(p)]))
    assert ov["rank_wall_s"][0] == pytest.approx(10.0)
    assert ov["share"]["entry"][0] == pytest.approx(1.0)
    assert ov["share"]["step"][0] == pytest.approx(0.1)


def test_aggregate_cli_chrome_and_merged(tmp_path, capsys):
    from dlaf_tpu.obs.aggregate import main

    t0 = 2000.0
    p0, p1 = tmp_path / "b.r0.jsonl", tmp_path / "b.r1.jsonl"
    _write_rank_artifact(p0, 0, t0)
    _write_rank_artifact(p1, 1, t0)
    merged = str(tmp_path / "merged.jsonl")
    chrome = str(tmp_path / "trace.json")
    assert main([str(p0), str(p1), "-o", merged, "--chrome", chrome]) == 0
    capsys.readouterr()
    # merged artifact is schema-valid and rank-complete
    assert obs.validate_file(merged) == []
    ranks = {r.get("rank") for r in obs.read_records(merged)}
    assert ranks == {0, 1}
    # chrome export: valid trace-event JSON, spans from EVERY rank,
    # process metadata naming each rank
    doc = json.load(open(chrome))
    evs = doc["traceEvents"]
    span_pids = {e["pid"] for e in evs
                 if e.get("ph") == "X" and e.get("tid") == 0}
    assert span_pids == {0, 1}
    names = {(e["pid"], e["args"]["name"]) for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert names == {(0, "rank 0"), (1, "rank 1")}
    # program compiles ride their own track
    assert any(e.get("tid") == 1 and e.get("ph") == "X" for e in evs)
    # durations are microseconds: the 0.5 s span
    run_ev = [e for e in evs if e.get("ph") == "X" and e["name"] == "run"]
    assert run_ev and run_ev[0]["dur"] == pytest.approx(0.5e6)


def test_aggregate_cli_exit_codes(tmp_path, capsys):
    from dlaf_tpu.obs.aggregate import main

    assert main([]) == 2
    assert main(["--bogus", "x.jsonl"]) == 2
    missing = str(tmp_path / "missing.jsonl")
    assert main([missing]) == 1
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert main([empty]) == 1
    capsys.readouterr()


def test_aggregate_infers_rank_from_filename(tmp_path):
    from dlaf_tpu.obs.aggregate import (UNRESOLVED_RANK_BASE, infer_rank,
                                        merge_artifacts)

    assert infer_rank("metrics.r3.jsonl", 9) == 3
    assert infer_rank("mc_r12.jsonl", 9) == 12
    assert infer_rank("metrics.jsonl", 9) == 9
    # an unresolved-rank placeholder file (pre-backend-init writes) must
    # NOT absorb into a positional rank that may collide with a real one
    # — with or without the conventional 'r' template prefix
    assert infer_rank("metrics.ru4242.jsonl", 3) == \
        UNRESOLVED_RANK_BASE + 4242
    assert infer_rank("metrics.u4242.jsonl", 3) == \
        UNRESOLVED_RANK_BASE + 4242
    p = tmp_path / "c.r5.jsonl"
    sink = obs.JsonlSink(str(p))
    sink.write({"type": "log", "level": "info", "logger": "t", "msg": "m",
                "fields": {}})
    sink.close()
    recs = merge_artifacts([str(p)])
    # records that already carry a stamped rank keep it; only unstamped
    # ones inherit the filename rank — here the sink stamped the live
    # process rank, so strip it to exercise the fallback
    raw = [json.loads(line) for line in open(p)]
    for r in raw:
        r.pop("rank", None)
    with open(p, "w") as f:
        for r in raw:
            f.write(json.dumps(r) + "\n")
    recs = merge_artifacts([str(p)])
    assert all(r["rank"] == 5 for r in recs)


def test_profile_summary_shares_skew_table(tmp_path, capsys):
    """scripts/profile_summary.py JSONL mode prints the per-rank skew
    table through obs.aggregate (shared code, not a fork)."""
    import profile_summary

    t0 = 3000.0
    p = tmp_path / "ps.r0.jsonl"
    _write_rank_artifact(p, 0, t0)
    profile_summary.summarize_jsonl(str(p), 10)
    out = capsys.readouterr().out
    assert "per-rank span skew" in out
    assert "program telemetry" in out


# ---------------------------------------------------------------------------
# schema-validated bench history
# ---------------------------------------------------------------------------

def _history_line(**over):
    line = {"variant": "ozaki", "platform": "tpu", "dtype": "float64",
            "n": 4096, "nb": 256, "gflops": 100.0, "t": 0.229,
            "ts": "2026-08-03T00:00:00", "source": "test"}
    line.update(over)
    return line


def test_append_history_line_rejects_non_finite(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    obs.append_history_line(path, _history_line())
    with pytest.raises(ValueError, match="gflops"):
        obs.append_history_line(path, _history_line(gflops=float("nan")))
    with pytest.raises(ValueError, match="variant"):
        obs.append_history_line(path, _history_line(variant=""))
    # the bad lines never landed
    assert len(obs.read_history_records(path)) == 1


def test_measure_common_append_validates(tmp_path, monkeypatch):
    import measure_common

    monkeypatch.setattr(measure_common, "repo_root", lambda: str(tmp_path))
    line = measure_common.append_history("cpu", 64, 16, 1.5, 0.01,
                                         source="test", variant="loop")
    assert line["gflops"] == 1.5
    with pytest.raises(ValueError):
        measure_common.append_history("cpu", 64, 16, float("inf"), 0.01,
                                      source="test", variant="loop")
    hist = obs.read_history_records(str(tmp_path / ".bench_history.jsonl"))
    assert len(hist) == 1


def test_history_reader_fails_loudly_on_malformed_history(tmp_path):
    """The one validating reader of .bench_history.jsonl (bench_gate's
    and mfu_table's source) raises on a malformed or non-finite line
    instead of skipping it."""
    path = str(tmp_path / "hist.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(_history_line()) + "\n")
        f.write('{"variant": "ozaki", "gflops": NaN}\n')
    with pytest.raises(ValueError):
        obs.read_history_records(path)
    # a clean file still resolves
    with open(path, "w") as f:
        f.write(json.dumps(_history_line()) + "\n")
    assert obs.read_history_records(path)[0]["gflops"] == 100.0


def test_validate_cli_history_mode(tmp_path, capsys):
    from dlaf_tpu.obs.validate import main

    good = str(tmp_path / "good.jsonl")
    with open(good, "w") as f:
        f.write(json.dumps(_history_line()) + "\n")
    assert main([good, "--history"]) == 0
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write(json.dumps(_history_line(t=float("nan"))) + "\n")
    assert main([bad, "--history"]) == 1
    capsys.readouterr()


# ---------------------------------------------------------------------------
# bench-regression gate
# ---------------------------------------------------------------------------

def _gate_history(tmp_path, gflops_by_key):
    path = str(tmp_path / "gate_hist.jsonl")
    with open(path, "w") as f:
        for (variant, platform), values in gflops_by_key.items():
            for g in values:
                f.write(json.dumps(_history_line(
                    variant=variant, platform=platform, gflops=g,
                    t=1.0 / max(g, 1e-9))) + "\n")
    return path


def test_bench_gate_clean_replay_and_injection(tmp_path, capsys):
    import bench_gate

    hist = _gate_history(tmp_path, {
        ("ozaki", "tpu"): [100.0, 104.0, 102.0, 98.0, 103.0],
        ("xla", "tpu"): [40.0, 41.0, 39.5],
    })
    assert bench_gate.main(["--history", hist, "--replay"]) == 0
    out = capsys.readouterr().out
    assert "no regressions" in out
    # the acceptance drill: 20% injected slowdown must exit nonzero
    assert bench_gate.main(["--history", hist, "--replay",
                            "--inject-slowdown", "0.2"]) == 1
    out = capsys.readouterr()
    assert "REGRESSION" in out.out


def test_bench_gate_fresh_artifacts(tmp_path, capsys):
    """Fresh measurements from an obs artifact's bench_result records:
    at baseline passes, 20% under baseline fails."""
    import bench_gate

    hist = _gate_history(tmp_path, {
        ("ozaki", "tpu"): [100.0, 104.0, 102.0, 98.0, 103.0]})

    def artifact(gflops):
        path = str(tmp_path / f"fresh_{gflops}.jsonl")
        sink = obs.JsonlSink(path)
        sink.write({"type": "bench_result",
                    "payload": _history_line(gflops=gflops)})
        sink.close()
        return path

    ok = artifact(101.0)
    assert bench_gate.main(["--history", hist, "--fresh", ok]) == 0
    slow = artifact(80.0)   # baseline median-of-best-3 = 103 -> floor 92.7
    assert bench_gate.main(["--history", hist, "--fresh", slow]) == 1
    capsys.readouterr()


def test_bench_gate_thin_history_is_report_only(tmp_path, capsys):
    import bench_gate

    hist = _gate_history(tmp_path, {("ozaki", "tpu"): [100.0, 101.0]})
    # 2 entries < --min-history 3: even a huge slowdown only reports
    assert bench_gate.main(["--history", hist, "--replay",
                            "--inject-slowdown", "0.5"]) == 0
    out = capsys.readouterr().out
    assert "THIN" in out and "report-only" in out


def test_bench_gate_new_key_is_report_only(tmp_path, capsys):
    import bench_gate

    hist = _gate_history(tmp_path, {
        ("ozaki", "tpu"): [100.0, 104.0, 102.0]})
    path = str(tmp_path / "new_key.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(_history_line(variant="brand_new",
                                         gflops=1.0)) + "\n")
    assert bench_gate.main(["--history", hist, "--fresh", path]) == 0
    out = capsys.readouterr().out
    assert "NEW" in out


def test_bench_gate_invalid_history_fails(tmp_path, capsys):
    import bench_gate

    bad = str(tmp_path / "bad_hist.jsonl")
    with open(bad, "w") as f:
        f.write(json.dumps(_history_line(gflops=float("nan"))) + "\n")
    assert bench_gate.main(["--history", bad, "--replay"]) == 1
    assert bench_gate.main(["--history", bad]) == 2   # no fresh, no replay
    capsys.readouterr()


def test_bench_gate_committed_history_replays_clean(capsys):
    """The real .bench_history.jsonl must pass its own gate (the CI
    smoke contract) and must flag the injected 20% drill."""
    import bench_gate

    assert bench_gate.main(["--replay"]) == 0
    assert bench_gate.main(["--replay", "--inject-slowdown", "0.2"]) == 1
    capsys.readouterr()
