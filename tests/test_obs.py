"""Tests for the observability subsystem (dlaf_tpu.obs — ISSUE 1).

Covers: span nesting/reentrancy, counter/gauge/histogram semantics, the
JSONL schema round-trip (including NaN rejection — the CI gate's reason
to exist), the Prometheus exposition, DLAF_LOG level handling, the
zero-allocation no-op fast path when observability is off (acceptance
criterion), and the miniapp_cholesky integration: metrics enabled must
emit per-step records whose derived GFlop/s is finite, locally and —
with collective byte counters — on a 2x2 grid.
"""

import contextlib
import math
import os

import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu import obs


@pytest.fixture(autouse=True)
def obs_reset():
    """Leave every test with the suite's default unobserved config."""
    yield
    os.environ.pop("DLAF_METRICS_PATH", None)
    os.environ.pop("DLAF_TRACE_DIR", None)
    os.environ.pop("DLAF_LOG", None)
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


def _configure_metrics(tmp_path, name="obs.jsonl"):
    path = str(tmp_path / name)
    C.initialize(C.Configuration(metrics_path=path))
    return path


# ---------------------------------------------------------------------------
# no-op fast path (acceptance criterion)
# ---------------------------------------------------------------------------

def test_noop_fast_path_when_disabled():
    """With observability unset every instrumented call site resolves to
    the same module-level no-op singleton — no per-call allocation."""
    C.initialize()   # defaults: no metrics path, no trace dir
    assert not obs.enabled()
    assert obs.span("a") is obs.NOOP_SPAN
    assert obs.span("b", flops=1.0, n=5) is obs.NOOP_SPAN
    assert obs.named_span("c") is obs.NOOP_CTX
    assert obs.counter("x", k="v") is obs.NOOP_COUNTER
    assert obs.gauge("y") is obs.NOOP_GAUGE
    assert obs.histogram("z") is obs.NOOP_HISTOGRAM
    # the singletons accept their whole API silently
    with obs.span("a") as sp:
        sp.set_attr("k", 1)
    obs.counter("x").inc(3)
    obs.gauge("y").set(2.0)
    obs.histogram("z").observe(0.1)
    # the comm instrumentation's gate
    assert not obs.metrics_active()
    # program telemetry off (the default): instrumented sites pass the
    # call straight to the SAME jitted callable — bitwise no-op (ISSUE 7
    # acceptance, pinned alongside the span/counter no-ops above)
    assert not obs.telemetry.active()
    sentinel = object()
    assert obs.telemetry.call("site", lambda x: x, sentinel) is sentinel
    obs.telemetry.count_retrace("site")       # silent no-op
    assert obs.telemetry._PROGRAMS == {}


def test_collectives_record_is_noop_when_disabled(devices8):
    """comm.collectives._record with metrics off touches no registry."""
    from dlaf_tpu.comm import collectives as cc

    C.initialize()
    cc._record("bcast", "row", np.zeros((4, 4)))
    assert not obs.metrics_active()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_reentrancy(tmp_path):
    path = _configure_metrics(tmp_path)
    with obs.span("outer", n=1):
        with obs.span("inner"):
            with obs.span("inner"):     # same name re-entered
                pass
    with obs.span("outer"):             # same name reused sequentially
        pass
    obs.flush()
    recs = [r for r in obs.read_records(path) if r["type"] == "span"]
    # spans emit on exit: innermost first
    names = [(r["name"], r["depth"], r["parent"]) for r in recs]
    assert names == [("inner", 2, "inner"), ("inner", 1, "outer"),
                     ("outer", 0, None), ("outer", 0, None)]
    for r in recs:
        assert r["dur_s"] >= 0 and math.isfinite(r["dur_s"])
    assert recs[2]["attrs"] == {"n": 1}


def test_span_gflops_derivation(tmp_path):
    path = _configure_metrics(tmp_path)
    with obs.span("work", flops=3e9):
        pass
    recs = [r for r in obs.read_records(path) if r["type"] == "span"]
    assert recs[0]["flops"] == 3e9
    assert math.isfinite(recs[0]["gflops"]) and recs[0]["gflops"] > 0
    # derived value consistent with the record's own duration
    assert recs[0]["gflops"] == pytest.approx(
        3e9 / recs[0]["dur_s"] / 1e9)


def test_entry_span_lazy_and_unfenced(tmp_path):
    """entry_span: attrs thunk never runs when off; when on, the record
    is marked unfenced and carries the flop model but no derived gflops
    (dispatch wall must not masquerade as throughput)."""
    C.initialize()
    calls = []
    assert obs.entry_span("algo", lambda: calls.append(1)) is obs.NOOP_SPAN
    assert calls == []

    path = _configure_metrics(tmp_path)
    with obs.entry_span("algo", lambda: dict(flops=1e9, n=64)):
        pass
    recs = [r for r in obs.read_records(path) if r["type"] == "span"]
    assert recs[0]["fenced"] is False
    assert recs[0]["flops"] == 1e9
    assert "gflops" not in recs[0]
    assert recs[0]["attrs"] == {"n": 64}
    # schema-valid, but does not satisfy the gflops requirement
    assert obs.validate_file(path, require_spans=True) == []
    assert obs.validate_file(path, require_gflops=True) != []


def test_bad_dlaf_log_env_is_lenient_on_lazy_path(monkeypatch, capsys):
    """A misspelled DLAF_LOG env must not crash informational log calls
    reached without config.initialize() (library use); it falls back to
    'info' with a note. The explicit initialize() path still raises."""
    obs._reset_for_tests()
    monkeypatch.setenv("DLAF_LOG", "warn")
    obs.get_logger("lenient").info("still works")
    err = capsys.readouterr().err
    assert "DLAF_LOG='warn'" in err and "using 'info'" in err
    assert "still works" in err
    with pytest.raises(ValueError):
        C.initialize()


def test_current_span_attrs(tmp_path):
    path = _configure_metrics(tmp_path)
    with obs.span("outer"):
        obs.current_span().set_attr("route", "mxu")
    recs = [r for r in obs.read_records(path) if r["type"] == "span"]
    assert recs[0]["attrs"] == {"route": "mxu"}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_semantics():
    reg = obs.Registry()
    c = reg.counter("hits", kind="bcast", axis="row")
    c.inc()
    c.inc(41)
    # same (name, labels) -> same accumulator; different labels -> distinct
    assert reg.counter("hits", kind="bcast", axis="row") is c
    other = reg.counter("hits", kind="bcast", axis="col")
    assert other is not c and other.value == 0.0
    snap = {(m["name"], tuple(sorted(m["labels"].items()))): m["value"]
            for m in reg.snapshot()}
    assert snap[("hits", (("axis", "row"), ("kind", "bcast")))] == 42.0


def test_gauge_and_histogram_semantics():
    reg = obs.Registry()
    g = reg.gauge("depth")
    g.set(3)
    g.set(7.5)
    assert reg.gauge("depth").value == 7.5

    h = reg.histogram("lat", bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(56.05)
    assert s["min"] == 0.05 and s["max"] == 50.0
    # cumulative Prometheus-style buckets, +Inf last
    assert s["buckets"] == [[0.1, 1], [1.0, 3], [10.0, 4], ["+Inf", 5]]


def test_prometheus_exposition():
    reg = obs.Registry()
    reg.counter("dlaf_comm_collective_bytes_total",
                kind="bcast", axis="row").inc(4096)
    reg.histogram("dlaf_span_seconds", bounds=(1.0,), span="x").observe(0.5)
    text = obs.prometheus_text(reg.snapshot())
    assert "# TYPE dlaf_comm_collective_bytes_total counter" in text
    assert ('dlaf_comm_collective_bytes_total{axis="row",kind="bcast"} '
            "4096.0") in text
    assert 'dlaf_span_seconds_bucket{le="1.0",span="x"} 1' in text
    assert 'dlaf_span_seconds_bucket{le="+Inf",span="x"} 1' in text
    assert 'dlaf_span_seconds_count{span="x"} 1' in text


def test_prometheus_histogram_inf_bucket_roundtrip():
    """The +Inf bucket renders as the literal ``le="+Inf"`` with the
    cumulative TOTAL count — including out-of-range observations that
    land in no finite bucket (the Prometheus invariant
    bucket{le="+Inf"} == count)."""
    reg = obs.Registry()
    h = reg.histogram("lat", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 100.0, 1e9):       # two past the last bound
        h.observe(v)
    text = obs.prometheus_text(reg.snapshot())
    assert 'lat_bucket{le="0.1"} 1' in text
    assert 'lat_bucket{le="1.0"} 2' in text
    assert 'lat_bucket{le="+Inf"} 4' in text
    assert "lat_count 4" in text
    # min/max survive the JSONL snapshot too
    s = h.snapshot()
    assert s["min"] == 0.05 and s["max"] == 1e9


def test_prometheus_label_escaping():
    """Backslash, double-quote, and newline in label values must escape
    per text exposition 0.0.4 — an unescaped newline would split the
    sample line and corrupt the whole scrape."""
    reg = obs.Registry()
    reg.counter("c", path='a\\b"c', msg="two\nlines").inc()
    text = obs.prometheus_text(reg.snapshot())
    assert '\\\\b' in text and '\\"c' in text
    assert "two\\nlines" in text
    assert "\ntwo" not in text            # no raw newline inside a value
    # exactly the TYPE line + one sample line
    assert len(text.strip().splitlines()) == 2


def test_prometheus_deterministic_ordering():
    """Exposition order is deterministic regardless of registration
    order: families sorted by (name, kind), series by sorted labels."""
    reg1, reg2 = obs.Registry(), obs.Registry()
    for reg, order in ((reg1, ("b", "a")), (reg2, ("a", "b"))):
        for axis in order:
            reg.counter("zz_total", axis=axis).inc()
        reg.gauge("aa_gauge").set(1)
    t1, t2 = obs.prometheus_text(reg1.snapshot()), \
        obs.prometheus_text(reg2.snapshot())
    assert t1 == t2
    assert t1.index("aa_gauge") < t1.index("zz_total")
    assert t1.index('axis="a"') < t1.index('axis="b"')


# ---------------------------------------------------------------------------
# JSONL schema round-trip + validation
# ---------------------------------------------------------------------------

def test_jsonl_schema_roundtrip(tmp_path):
    path = _configure_metrics(tmp_path)
    with obs.span("region", flops=1e9, n=64):
        obs.counter("dlaf_comm_collective_bytes_total",
                    kind="bcast", axis="row").inc(1 << 20)
    obs.get_logger("test").warning("note", key="val")
    obs.emit_event("bench_result", payload={"gflops": 1.5})
    obs.flush()
    errs = obs.validate_file(path, require_spans=True, require_gflops=True,
                             require_collectives=True)
    assert errs == []
    by_type = {}
    for r in obs.read_records(path):
        by_type.setdefault(r["type"], []).append(r)
        assert r["v"] == obs.SCHEMA_VERSION
        assert math.isfinite(r["ts"])
    assert set(by_type) == {"span", "log", "bench_result", "metrics"}
    assert by_type["bench_result"][0]["payload"] == {"gflops": 1.5}
    assert by_type["log"][0]["msg"] == "note"
    assert by_type["log"][0]["fields"] == {"key": "val"}


def test_validator_rejects_nan_and_missing_fields(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    sink = obs.JsonlSink(path)
    sink.write({"type": "span", "name": "x", "dur_s": float("nan"),
                "depth": 0, "parent": None, "attrs": {}})
    sink.write({"type": "span", "dur_s": 0.5, "depth": 0, "parent": None,
                "attrs": {}})                     # missing name
    sink.write({"type": "span", "name": "ok", "dur_s": 0.1, "depth": 0,
                "parent": None, "attrs": {}, "gflops": float("inf")})
    sink.write({"type": "mystery"})               # unknown type
    sink.write({"type": "span", "name": "d", "dur_s": 0.1, "depth": 0,
                "parent": None, "attrs": {}, "fenced": False,
                "gflops": 99999.0})   # dispatch wall masquerading as rate
    sink.close()
    errs = obs.validate_file(path)
    assert len(errs) == 5
    assert any("dur_s" in e for e in errs)
    assert any("without a name" in e for e in errs)
    assert any("gflops non-finite" in e for e in errs)
    assert any("unknown type" in e for e in errs)
    assert any("unfenced span must not carry gflops" in e for e in errs)


def test_validator_requires_content(tmp_path):
    path = str(tmp_path / "empty.jsonl")
    open(path, "w").close()
    errs = obs.validate_file(path, require_spans=True, require_gflops=True,
                             require_collectives=True)
    assert len(errs) == 3


def test_validator_requires_comm_overlap(tmp_path):
    """--require-comm-overlap: positive finite overlap counters AND finite
    per-axis byte counters for BOTH mesh axes (docs/comm_overlap.md);
    non-finite or single-axis artifacts fail."""
    def write(path, metrics):
        sink = obs.JsonlSink(str(path))
        sink.write({"type": "metrics", "metrics": metrics})
        sink.close()
        return str(path)

    def counter(name, value, **labels):
        return {"name": name, "kind": "counter", "value": value,
                "labels": labels}

    good = write(tmp_path / "good.jsonl", [
        counter("dlaf_comm_overlapped_total", 4, algo="cholesky_dist",
                axis="row"),
        counter("dlaf_comm_overlapped_total", 4, algo="cholesky_dist",
                axis="col"),
        counter("dlaf_comm_collective_bytes_total", 128, kind="bcast2d",
                axis="row"),
        counter("dlaf_comm_collective_bytes_total", 128, kind="bcast2d",
                axis="col"),
    ])
    assert obs.validate_file(good, require_comm_overlap=True) == []
    # one axis missing -> both obligations can fail independently
    partial = write(tmp_path / "partial.jsonl", [
        counter("dlaf_comm_overlapped_total", 4, algo="cholesky_dist",
                axis="row"),
        counter("dlaf_comm_collective_bytes_total", 128, kind="bcast",
                axis="row"),
    ])
    errs = obs.validate_file(partial, require_comm_overlap=True)
    assert any("dlaf_comm_overlapped_total" in e for e in errs)
    assert any("dlaf_comm_collective_bytes_total" in e for e in errs)
    # non-finite counter values (NaN AND +inf) must not satisfy the
    # requirement — the shared _finite gate filters both before the
    # axis sets are populated
    for bad in (float("nan"), float("inf")):
        art = write(tmp_path / f"bad_{bad}.jsonl", [
            counter("dlaf_comm_overlapped_total", bad,
                    algo="cholesky_dist", axis="row"),
            counter("dlaf_comm_overlapped_total", 4, algo="cholesky_dist",
                    axis="col"),
        ])
        errs = obs.validate_file(art, require_comm_overlap=True)
        assert any("dlaf_comm_overlapped_total" in e for e in errs), bad


def test_validate_cli(tmp_path, capsys):
    from dlaf_tpu.obs.validate import main

    path = _configure_metrics(tmp_path)
    with obs.span("r", flops=1e6):
        pass
    obs.flush()
    assert main([path, "--require-spans", "--require-gflops"]) == 0
    assert main([path, "--require-collectives"]) == 1
    assert main(["--nonsense", path]) == 2
    capsys.readouterr()


def test_validate_cli_exit_codes(tmp_path, capsys):
    """The pinned CLI contract (ISSUE 7 satellite): 2 on unknown flag or
    no/multiple paths; 1 on an empty artifact under ANY --require-*."""
    from dlaf_tpu.obs.validate import main

    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert main([]) == 2                               # no path
    assert main([empty, empty]) == 2                   # two paths
    assert main([empty, "--require-thing"]) == 2       # unknown flag
    assert main([empty, "--history", "--require-spans"]) == 2  # exclusive
    assert main([empty]) == 0                          # empty, no require
    for flag in ("--require-spans", "--require-gflops",
                 "--require-collectives", "--require-retries",
                 "--require-fallbacks", "--require-comm-overlap",
                 "--require-dc-batch", "--require-bt-overlap",
                 "--require-telemetry"):
        assert main([empty, flag]) == 1, flag
    capsys.readouterr()


def test_validator_rank_field(tmp_path):
    """Optional ``rank`` must be a non-negative int when present."""
    path = str(tmp_path / "rank.jsonl")
    sink = obs.JsonlSink(path)
    sink.write({"type": "span", "name": "x", "dur_s": 0.1, "depth": 0,
                "parent": None, "attrs": {}, "rank": 3})
    sink.write({"type": "span", "name": "y", "dur_s": 0.1, "depth": 0,
                "parent": None, "attrs": {}, "rank": -1})
    sink.write({"type": "span", "name": "z", "dur_s": 0.1, "depth": 0,
                "parent": None, "attrs": {}, "rank": "r0"})
    sink.close()
    errs = obs.validate_file(path)
    assert len(errs) == 2 and all("rank" in e for e in errs)


def test_validator_program_records(tmp_path):
    """The telemetry record type: compile events need a finite
    compile_s; hbm values must all be finite."""
    path = str(tmp_path / "prog.jsonl")
    sink = obs.JsonlSink(path)
    sink.write({"type": "program", "site": "cholesky.dist",
                "event": "compile", "compile_s": 0.5, "trace_s": 0.1,
                "hbm": {"args": 1.0, "peak": 2.0}, "attrs": {}})
    sink.write({"type": "program", "site": "cholesky.dist",
                "event": "retrace", "attrs": {}})
    sink.close()
    assert obs.validate_file(path) == []

    bad = str(tmp_path / "prog_bad.jsonl")
    sink = obs.JsonlSink(bad)
    sink.write({"type": "program", "event": "compile",
                "compile_s": 0.5, "attrs": {}})              # no site
    sink.write({"type": "program", "site": "s", "event": "compile",
                "compile_s": float("nan"), "attrs": {}})     # NaN wall
    sink.write({"type": "program", "site": "s", "event": "compile",
                "compile_s": 0.1, "hbm": {"peak": float("inf")},
                "attrs": {}})                                # inf HBM
    sink.write({"type": "program", "site": "s", "event": "link",
                "attrs": {}})                                # bad event
    sink.write({"type": "program", "site": "s", "event": "retrace",
                "compile_s": float("nan"), "attrs": {}})     # NaN anywhere
    sink.close()
    errs = obs.validate_file(bad)
    assert len(errs) == 5
    assert any("without a site" in e for e in errs)
    assert any("compile_s" in e for e in errs)
    assert any("hbm['peak']" in e for e in errs)
    assert any("compile|retrace" in e for e in errs)


def test_validator_require_telemetry(tmp_path):
    """--require-telemetry: compile observation + HBM accounting +
    retrace evidence must ALL be present; each missing leg fails
    independently, and each leg accepts either the metrics-snapshot or
    the program-record form."""
    path = str(tmp_path / "tele.jsonl")
    sink = obs.JsonlSink(path)
    sink.write({"type": "program", "site": "s", "event": "compile",
                "compile_s": 0.2, "attrs": {}})
    sink.write({"type": "metrics", "metrics": [
        {"name": "dlaf_hbm_bytes", "kind": "gauge",
         "labels": {"what": "peak", "site": "s"}, "value": 1024.0},
        {"name": "dlaf_retrace_total", "kind": "counter",
         "labels": {"site": "s"}, "value": 1.0}]})
    sink.close()
    assert obs.validate_file(path, require_telemetry=True) == []

    # program records ALONE satisfy all three legs: a run killed before
    # the final metrics snapshot still validates on its record trail
    recs_only = str(tmp_path / "tele_recs.jsonl")
    sink = obs.JsonlSink(recs_only)
    sink.write({"type": "program", "site": "s", "event": "retrace",
                "attrs": {}})
    sink.write({"type": "program", "site": "s", "event": "compile",
                "compile_s": 0.2, "hbm": {"peak": 1024.0}, "attrs": {}})
    sink.close()
    assert obs.validate_file(recs_only, require_telemetry=True) == []

    partial = str(tmp_path / "tele_partial.jsonl")
    sink = obs.JsonlSink(partial)
    sink.write({"type": "log", "level": "info", "logger": "t", "msg": "m",
                "fields": {}})
    sink.close()
    errs = obs.validate_file(partial, require_telemetry=True)
    assert len(errs) == 3
    assert any("compile-seconds" in e for e in errs)
    assert any("HBM accounting" in e for e in errs)
    assert any("retrace evidence" in e for e in errs)
    # one leg present, two missing: fails on exactly the missing two
    compile_only = str(tmp_path / "tele_compile_only.jsonl")
    sink = obs.JsonlSink(compile_only)
    sink.write({"type": "program", "site": "s", "event": "compile",
                "compile_s": 0.2, "attrs": {}})
    sink.close()
    errs = obs.validate_file(compile_only, require_telemetry=True)
    assert len(errs) == 2
    assert any("HBM accounting" in e for e in errs)
    assert any("retrace evidence" in e for e in errs)


# ---------------------------------------------------------------------------
# logging / DLAF_LOG
# ---------------------------------------------------------------------------

def test_log_levels(capsys):
    C.initialize(C.Configuration(log="warning"))
    lg = obs.get_logger("lvl")
    lg.info("hidden")
    lg.warning("shown", a=1)
    err = capsys.readouterr().err
    assert "hidden" not in err
    assert "dlaf_tpu[warning] lvl: shown [a=1]" in err

    C.initialize(C.Configuration(log="off"))
    lg.error("silent")
    assert capsys.readouterr().err == ""


def test_log_env_layering(monkeypatch):
    monkeypatch.setenv("DLAF_LOG", "error")
    cfg = C.update_configuration(C.Configuration(log="debug"))
    assert cfg.log == "error"            # env over user struct
    cfg = C.update_configuration(argv=["--dlaf:log=off"])
    assert cfg.log == "off"              # CLI over env
    monkeypatch.delenv("DLAF_LOG")
    with pytest.raises(ValueError):
        C.initialize(C.Configuration(log="loud"))


def test_warning_once(capsys):
    C.initialize()
    lg = obs.get_logger("once")
    lg.warning_once("k1", "first")
    lg.warning_once("k1", "first")
    lg.warning_once("k2", "second")
    err = capsys.readouterr().err
    assert err.count("first") == 1 and err.count("second") == 1


def test_warning_once_not_consumed_while_suppressed(capsys):
    """A suppressed one-shot key stays unconsumed: raising the log level
    later must still produce the single announcement (a process that
    starts with DLAF_LOG=error would otherwise permanently lose the
    auto-knob resolution notices)."""
    C.initialize(C.Configuration(log="error"))
    lg = obs.get_logger("once_lvl")
    lg.warning_once("k", "notice")
    assert capsys.readouterr().err == ""
    C.initialize(C.Configuration(log="info"))
    lg.warning_once("k", "notice")
    lg.warning_once("k", "notice")
    assert capsys.readouterr().err.count("notice") == 1


def test_resolution_notices_respect_dlaf_log(capsys):
    """The auto-knob notices (satellite: config.py print -> logger) are
    silenceable — DLAF_LOG=off in CI/pytest output."""
    C.initialize(C.Configuration(log="off"))
    key = ("t_obs_knob", "cpu", "native")
    from dlaf_tpu.obs.logging import forget_once

    forget_once("config", key)
    try:
        out = C.resolve_platform_auto("auto", knob="t_obs_knob",
                                      tpu_choice="mxu",
                                      other_choice="native", detail="d")
        assert out == "native"
        assert capsys.readouterr().err == ""
    finally:
        forget_once("config", key)


# ---------------------------------------------------------------------------
# PhaseTimer migration
# ---------------------------------------------------------------------------

def test_phase_timer_emits_spans(tmp_path):
    from dlaf_tpu.common.timer import PhaseTimer

    path = _configure_metrics(tmp_path)
    pt = PhaseTimer()
    with pt.phase("stage_a"):
        pass
    with pt.phase("stage_a"):
        pass
    assert set(pt.report()) == {"stage_a"}
    names = [r["name"] for r in obs.read_records(path)
             if r["type"] == "span"]
    assert names == ["stage_a", "stage_a"]


def test_phase_timer_profiler_single_owner(tmp_path, monkeypatch):
    """A timer-owned jax.profiler trace claims the obs layer's
    profiler_started flag, so a configure(trace_dir=...) landing mid-phase
    (lazy config init inside an algorithm call) cannot start_trace a
    second time over the live trace."""
    import jax

    from dlaf_tpu.common.timer import PhaseTimer
    from dlaf_tpu.obs._state import STATE

    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.__setitem__(
                            "start", calls["start"] + 1))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.__setitem__("stop", calls["stop"] + 1))

    pt = PhaseTimer(profile_dir=str(tmp_path / "timer_trace"))
    with pt.phase("stage_a"):
        # mid-phase: the obs layer comes up with its own trace dir and a
        # span triggers its lazy profiler start — must see the claim
        C.initialize(C.Configuration(trace_dir=str(tmp_path / "obs_trace")))
        with obs.span("inner"):
            pass
    assert calls["start"] == 1 and STATE.profiler_started
    pt.stop()
    assert calls["stop"] == 1 and not STATE.profiler_started


def test_stopped_profiler_does_not_restart(tmp_path, monkeypatch):
    """Once the process trace is stopped, later spans must not silently
    start a new one into the stale directory — in a long-lived process
    (pytest was the victim) that trace would record everything until
    interpreter exit."""
    import jax

    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.__setitem__(
                            "start", calls["start"] + 1))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.__setitem__("stop", calls["stop"] + 1))

    C.initialize(C.Configuration(trace_dir=str(tmp_path / "t")))
    with obs.span("a"):
        pass
    assert calls["start"] == 1
    obs.stop_profiler()
    assert calls["stop"] == 1
    with obs.span("b"):
        pass
    assert calls["start"] == 1, "span restarted a stopped process trace"


def test_pipeline_phase_names_avoid_entry_span_collision():
    """Pipeline stage spans must not reuse algorithm entry-span names: a
    fenced stage wall-time span sharing a name with an unfenced
    dispatch-time entry span would merge two different populations into
    one dlaf_span_seconds histogram."""
    import importlib
    import inspect
    import re

    # importlib: the packages re-export same-named functions that shadow
    # the submodule attribute on plain ``import a.b.c as c``
    es = importlib.import_module("dlaf_tpu.eigensolver.eigensolver")
    mods = [es] + [importlib.import_module(m) for m in (
        "dlaf_tpu.algorithms.cholesky",
        "dlaf_tpu.algorithms.gen_to_std",
        "dlaf_tpu.algorithms.triangular",
        "dlaf_tpu.eigensolver.reduction_to_band",
    )]
    phases = set(re.findall(r'\.phase\(\s*"([^"]+)"',
                            inspect.getsource(es)))
    entries = set()
    for mod in mods:
        entries |= set(re.findall(r'entry_span\(\s*"([^"]+)"',
                                  inspect.getsource(mod)))
    assert phases and entries
    assert phases.isdisjoint(entries), phases & entries


# ---------------------------------------------------------------------------
# miniapp integration (acceptance criterion)
# ---------------------------------------------------------------------------

def _run_miniapp_with_metrics(tmp_path, monkeypatch, extra_args=()):
    from dlaf_tpu.miniapp.miniapp_cholesky import run as crun

    path = str(tmp_path / "mc.jsonl")
    monkeypatch.setenv("DLAF_METRICS_PATH", path)
    out = crun(["-m", "128", "-b", "32", "--nruns", "2", *extra_args])
    assert len(out) == 2
    return path


def test_miniapp_cholesky_metrics_integration(tmp_path, monkeypatch):
    """miniapp_cholesky with metrics enabled emits per-step records whose
    derived GFlop/s is finite, and the artifact is schema-valid."""
    path = _run_miniapp_with_metrics(tmp_path, monkeypatch)
    assert obs.validate_file(path, require_spans=True,
                             require_gflops=True) == []
    runs = [r for r in obs.read_records(path)
            if r["type"] == "span" and r["name"] == "miniapp_cholesky.run"]
    timed = [r for r in runs if not r["attrs"]["warmup"]]
    assert len(timed) == 2                      # one record per timed step
    for r in runs:
        assert math.isfinite(r["gflops"]) and r["gflops"] > 0
        assert r["attrs"]["n"] == 128 and r["attrs"]["nb"] == 32


def test_miniapp_cholesky_metrics_distributed(tmp_path, monkeypatch,
                                              devices8):
    """The 2x2-grid artifact additionally carries positive per-axis
    collective byte counters (the CI smoke gate's contract)."""
    path = _run_miniapp_with_metrics(
        tmp_path, monkeypatch, ("--grid-rows", "2", "--grid-cols", "2"))
    assert obs.validate_file(path, require_spans=True, require_gflops=True,
                             require_collectives=True) == []
    snaps = [r for r in obs.read_records(path) if r["type"] == "metrics"]
    bytes_by_axis = {}
    for m in snaps[-1]["metrics"]:
        if m["name"] == "dlaf_comm_collective_bytes_total":
            bytes_by_axis[m["labels"]["axis"]] = \
                bytes_by_axis.get(m["labels"]["axis"], 0) + m["value"]
    assert bytes_by_axis.get("row", 0) > 0
    assert bytes_by_axis.get("col", 0) > 0


# ---------------------------------------------------------------------------
# spans on the profiler's clock (ISSUE 25): a live span annotates whatever
# jax.profiler session is running, whoever started it
# ---------------------------------------------------------------------------

#: the local Cholesky dispatches ONE program (ISSUE 30): one host phase
CHOLESKY_SPANS = ("stage.cholesky.factor",)
NATIVE_SPANS = ("stage.native.band_chase", "stage.native.secular",
                "stage.native.deflate")
#: host phases of ``triangular_solve`` (ISSUE 27), by branch
TRSM_SPANS = {"local": ("stage.triangular_solve.to_global",
                        "stage.triangular_solve.solve",
                        "stage.triangular_solve.to_tiles"),
              "2x2": ("stage.triangular_solve.dispatch",)}


@contextlib.contextmanager
def _test_owned_trace(trace_dir):
    """A jax.profiler session started by the test, as an operator (or
    benchmark/run.py) would: obs owns nothing of it."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _host_events(trace_dir, prefixes):
    """``[(start_ns, end_ns, name)]`` of the host planes' events whose name
    starts with one of ``prefixes``, from the newest xplane."""
    import glob

    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    assert files, f"no xplane under {trace_dir}"
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in ProfileData.from_file(files[-1]).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefixes)]


def _tiny_spd(n=64, nb=16):
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix

    g = np.random.default_rng(25).standard_normal((n, n))
    return Matrix.from_global(g @ g.T + n * np.eye(n),
                              TileElementSize(nb, nb))


def _call_cholesky():
    from dlaf_tpu.algorithms.cholesky import cholesky

    return cholesky("L", _tiny_spd())


def _call_trsm(grid_shape=None):
    from dlaf_tpu.algorithms.triangular import triangular_solve
    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix

    n, nb = 64, 16
    rng = np.random.default_rng(27)
    t = np.tril(rng.standard_normal((n, n)), -1) + 2.0 * n * np.eye(n)
    grid = Grid(*grid_shape) if grid_shape else None
    size = TileElementSize(nb, nb)
    return triangular_solve(
        "L", "L", "N", "N", 1.0, Matrix.from_global(t, size, grid=grid),
        Matrix.from_global(rng.standard_normal((n, n)), size, grid=grid))


def _call_trsm_2x2():
    return _call_trsm((2, 2))


def _call_fence():
    import jax.numpy as jnp

    from dlaf_tpu.common.sync import hard_fence

    hard_fence(jnp.ones(4), None, jnp.ones((2, 2)), np.ones(3))


def _native_inputs():
    rng = np.random.default_rng(7)
    ds = np.sort(rng.standard_normal(12))
    zs = rng.standard_normal(12)
    return ds, zs / np.linalg.norm(zs)


def _call_band_chase():
    from dlaf_tpu.native import bindings

    n, b = 24, 4
    band = np.random.default_rng(3).standard_normal((b + 1, n))
    bindings.band_to_tridiag(band, b, nthreads=1)


def _call_secular():
    from dlaf_tpu.native import bindings

    bindings.secular_roots(*_native_inputs(), 0.5)


def _call_deflate():
    from dlaf_tpu.native import bindings

    ds, zs = _native_inputs()
    bindings.deflate_scan(ds, zs.copy(), np.ones(12, dtype=np.uint8), 1e-3)


#: each new span name with a call that passes its call site
NEW_SPAN_SITES = {
    **{name: _call_cholesky for name in CHOLESKY_SPANS},
    **{name: _call_trsm for name in TRSM_SPANS["local"]},
    **{name: _call_trsm_2x2 for name in TRSM_SPANS["2x2"]},
    "stage.fence": _call_fence,
    "stage.native.band_chase": _call_band_chase,
    "stage.native.secular": _call_secular,
    "stage.native.deflate": _call_deflate,
}


def test_cholesky_host_phases_reach_a_foreign_profiler(tmp_path):
    """Only a metrics path configured, the session started by the test: a
    local cholesky call leaves its entry span and its one host phase in
    the xplane, the phase inside the entry, and no phase of the layout
    programs it no longer dispatches."""
    _configure_metrics(tmp_path)
    _call_cholesky()                       # compile outside the session
    with _test_owned_trace(tmp_path / "trace"):
        _call_cholesky()
    from dlaf_tpu.obs._state import STATE

    assert not STATE.profiler_started      # obs started nothing
    ev = _host_events(tmp_path / "trace", ("cholesky", "stage."))
    by_name = {}
    for s, e, name in ev:
        by_name.setdefault(name, []).append((s, e))
    assert len(by_name["cholesky"]) == 1
    entry = by_name["cholesky"][0]
    starts = []
    for name in CHOLESKY_SPANS:
        assert len(by_name[name]) == 1, (name, by_name.get(name))
        s, e = by_name[name][0]
        assert entry[0] <= s <= e <= entry[1], name
        starts.append(s)
    assert starts == sorted(starts)
    assert sorted(n for n in by_name if n.startswith("stage.cholesky.")) \
        == sorted(CHOLESKY_SPANS)


@pytest.mark.parametrize("branch", sorted(TRSM_SPANS))
def test_triangular_solve_host_phases_once_per_call(tmp_path, branch,
                                                    devices8):
    """The local solve leaves its three host phases, the distributed one
    its dispatch phase: once per call, inside the entry span, unfenced, in
    the JSONL and in a profiler session the test owns."""
    call = _call_trsm if branch == "local" else _call_trsm_2x2
    path = _configure_metrics(tmp_path)
    call()                                 # compile outside the session
    with _test_owned_trace(tmp_path / "trace"):
        call()
        call()
    ev = _host_events(tmp_path / "trace", ("triangular_solve",
                                           "stage.triangular_solve."))
    entries = sorted((s, e) for s, e, n in ev if n == "triangular_solve")
    assert len(entries) == 2
    phases = sorted((s, e, n) for s, e, n in ev if n.startswith("stage."))
    assert [n for _s, _e, n in phases] == list(TRSM_SPANS[branch]) * 2
    per_call = len(TRSM_SPANS[branch])
    for k, (s, e, name) in enumerate(phases):
        entry = entries[k // per_call]
        assert entry[0] <= s <= e <= entry[1], name
    recs = [r for r in obs.read_records(path) if r["type"] == "span"
            and r["name"].startswith("stage.triangular_solve.")]
    assert len(recs) == 3 * per_call
    assert all(r["fenced"] is False and r["parent"] == "triangular_solve"
               for r in recs)


def test_hard_fence_is_one_span_per_call(tmp_path):
    path = _configure_metrics(tmp_path)
    _call_fence()                          # compile the readbacks
    with _test_owned_trace(tmp_path / "trace"):
        _call_fence()                      # two jax arrays, one span
        _call_fence()
    ev = _host_events(tmp_path / "trace", ("stage.fence",))
    assert [name for _s, _e, name in ev] == ["stage.fence"] * 2
    recs = [r for r in obs.read_records(path)
            if r["type"] == "span" and r["name"] == "stage.fence"]
    assert len(recs) == 3 and all(r["fenced"] is False for r in recs)


@pytest.mark.parametrize("name", NATIVE_SPANS)
def test_native_binding_leaves_its_span(tmp_path, name):
    path = _configure_metrics(tmp_path)
    with _test_owned_trace(tmp_path / "trace"):
        NEW_SPAN_SITES[name]()
    ev = _host_events(tmp_path / "trace", ("stage.native.",))
    assert [n for _s, _e, n in ev] == [name]
    rec, = [r for r in obs.read_records(path)
            if r["type"] == "span" and r["name"].startswith("stage.native")]
    assert rec["name"] == name and rec["fenced"] is False
    want = {"n", "b", "threads"} if name.endswith("band_chase") else {"k"}
    assert set(rec["attrs"]) == want


def test_numpy_fallback_leaves_no_native_span(tmp_path):
    """The spans sit in bindings.py around the C++ calls, so a degraded run
    is never timed under a native name."""
    from dlaf_tpu.eigensolver.band_to_tridiag import band_to_tridiag
    from dlaf_tpu.eigensolver.tridiag_solver import (_deflation_scan,
                                                     _secular_roots_host)
    from dlaf_tpu.health import inject

    path = _configure_metrics(tmp_path)
    ds, zs = _native_inputs()
    band = np.random.default_rng(3).standard_normal((5, 24))
    with inject.force_native_failure():
        band_to_tridiag(band, 4, impl="native")
        _secular_roots_host(ds, zs, 0.5)
        _deflation_scan(ds, zs.copy(), np.ones(12, dtype=bool), 1e-3)
    obs.flush()
    recs = list(obs.read_records(path))
    assert not [r for r in recs if r["type"] == "span"
                and r["name"].startswith("stage.native")]
    sites = {m["labels"].get("site") for r in recs if r["type"] == "metrics"
             for m in r["metrics"] if m["name"] == "dlaf_fallback_total"}
    assert {"band_to_tridiag", "secular", "deflate"} <= sites


@pytest.mark.parametrize("name", sorted(NEW_SPAN_SITES))
def test_new_span_sites_are_noops_when_off(monkeypatch, name):
    """No sink, no trace dir: the call site resolves to the no-op
    singleton and no Span is ever constructed."""
    from dlaf_tpu.obs import trace as obs_trace

    C.initialize()
    assert not obs.enabled()
    made = []
    real_init = obs_trace.Span.__init__

    def spy(self, span_name, *a, **kw):
        made.append(span_name)
        real_init(self, span_name, *a, **kw)

    monkeypatch.setattr(obs_trace.Span, "__init__", spy)
    NEW_SPAN_SITES[name]()
    assert made == []
    assert obs.span(name, fenced=False) is obs.NOOP_SPAN


@pytest.mark.parametrize("sink", [False, True], ids=["obs_off", "sink_on"])
def test_phase_timer_profile_dir_labels_each_phase_once(tmp_path, sink):
    """PhaseTimer(profile_dir=...) owns the session; the phase's span is
    the one annotation (the timer adds none of its own), also when the
    metrics sink makes the span live a second way."""
    from dlaf_tpu.common.timer import PhaseTimer

    if sink:
        _configure_metrics(tmp_path)
    else:
        C.initialize()
    pt = PhaseTimer(profile_dir=str(tmp_path / "timer_trace"))
    with pt.phase("stage.a"):
        pass
    with pt.phase("stage.b"):
        pass
    pt.stop()
    ev = _host_events(tmp_path / "timer_trace", ("stage.",))
    assert sorted(n for _s, _e, n in ev) == ["stage.a", "stage.b"]
    assert set(pt.report()) == {"stage.a", "stage.b"}
