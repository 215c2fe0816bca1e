"""The span readers (``span_reduce.py``, ``launch_gap_share``, ``dispatch_s``,
``native_s.*``) on hand-made tuples, and the traced command on the CPU with
the rehearsal file's cells and the span metrics beside them."""

import json
import os

import pytest

import run as bench_run
import span_reduce as sr
import trace_reduce as tr
from test_command import last_line, on_cpu, tiny_root      # noqa: F401

ROOT = bench_run.ROOT
MS = 1_000_000      # ns
WINDOW = (0, 40 * MS)
DEVICE = "/device:TPU:0"


def synthetic():
    """Two calls of 20 ms on one device. Each: the fresh copy's program
    (1-2), the factorization's program (6-16) whose operations leave 9-11
    idle *inside* it, the fence's readback (17-18). Host: fresh-copy fence
    0.5-3, entry span 3-6 with two phases, the call's fence 6-19, one native
    span in call 1 and two in call 2."""
    modules, ops, host = [], [], [(0, 40 * MS, "bench_window")]
    for k, t in enumerate((0, 20 * MS)):
        modules += [(t + 1 * MS, t + 2 * MS, "jit_add"),
                    (t + 6 * MS, t + 16 * MS, "jit__cholesky_local"),
                    (t + 17 * MS, t + 18 * MS, "jit_dynamic_slice")]
        ops += [(t + 1 * MS, t + 2 * MS, "%add.1 = f64[8]{0} add(%a, %b)"),
                (t + 6 * MS, t + 9 * MS, "%fusion.1 = f32[8]{0} fusion(%a), "
                 "kind=kLoop, calls=%f"),
                (t + 11 * MS, t + 16 * MS, "%copy.2 = f64[8]{0} copy(%a)"),
                (t + 17 * MS, t + 18 * MS, "%slice.3 = f64[1]{0} slice(%a)")]
        host += [(t, t + 20 * MS - 1, "bench_call"),
                 (t + MS // 2, t + 3 * MS, "stage.fence"),
                 (t + 3 * MS, t + 6 * MS, "cholesky"),
                 (t + 3 * MS, t + 4 * MS, "stage.cholesky.to_global"),
                 (t + 4 * MS, t + 6 * MS, "stage.cholesky.factor"),
                 (t + 6 * MS, t + 19 * MS, "stage.fence")]
        host += [(t + 7 * MS + j * MS, t + 7 * MS + j * MS + (k + 1) * MS // 4,
                  "stage.native.secular") for j in range(k + 1)]
    return modules, ops, host


def test_launch_gap_share_is_the_part_of_idle_between_programs():
    modules, ops, host = synthetic()
    red = tr.reduce_trace({DEVICE: ops}, host, WINDOW)
    idle = bench_run.load_module("layer_metrics", "device_idle_share")
    idle_share = idle.read({"trace": red}, "device_idle_share")
    gap_share = sr.gap_share(modules, WINDOW)
    # per call: programs cover 12 of 20 ms, operations 10 of 20 ms
    assert gap_share == pytest.approx(40.0)
    assert idle_share == pytest.approx(50.0)
    assert gap_share <= idle_share
    # the 9-11 ms hole lies inside a program: idle for the operations only
    inside = (9 * MS, 11 * MS)
    assert inside in tr.idle_gaps(tr.busy_union(ops), WINDOW)
    assert inside not in sr.launch_gaps(modules, WINDOW)


def test_spans_belong_to_the_call_that_contains_them():
    _, _, host = synthetic()
    host.append((19 * MS, 21 * MS, "stage.native.secular"))   # straddles
    host.append((50 * MS, 51 * MS, "stage.native.secular"))   # outside
    per_call = sr.spans_by_call(host, "stage.native.secular")
    assert [len(spans) for spans in per_call] == [1, 2]
    assert per_call[1][0] == (27 * MS, 27 * MS + MS // 2)
    assert sr.calls_of(host) == [(0, 20 * MS - 1), (20 * MS, 40 * MS - 1)]


def test_native_s_sums_within_a_call_then_takes_the_median():
    _, _, host = synthetic()
    # call 1: one span of 0.25 ms; call 2: two of 0.5 ms -> median of
    # (0.25, 1.0) ms, not of the three spans
    assert sr.median_wall_per_call(host, "stage.native.secular") \
        == pytest.approx(0.625e-3)
    assert sr.median_wall_per_call(host, "cholesky") == pytest.approx(3e-3)
    assert sr.median_wall_per_call(host, "stage.native.deflate") is None


def test_gap_table_keeps_the_fresh_copys_fence_apart():
    modules, _, host = synthetic()
    table = {row[0]: row[1:] for row in sr.gap_table(modules, host, WINDOW)}
    # gaps (ms): 0-1 (midpoint in the fresh copy's fence), 2-6 and 22-26
    # (midpoint in the factor phase), 16-17 and 36-37 (the call's own
    # fence), 18-21 and 38-40 (after it: in_call; the second call's fresh
    # copy starts at once, so its fence labels no gap). Per call = / 2.
    assert table[sr.FRESH_FENCE] == [pytest.approx(0.5e-3), 0.5,
                                     pytest.approx(1e-3)]
    assert table["stage.cholesky.factor"][0] == pytest.approx(4e-3)
    assert table["stage.fence"][:2] == [pytest.approx(1e-3), 1.0]
    assert table["in_call"][0] == pytest.approx(2.5e-3)
    assert sum(row[0] for row in table.values()) == pytest.approx(8e-3)
    marked = sr.mark_fresh_fences(host)
    assert [n for _s, _e, n in marked].count(sr.FRESH_FENCE) == 2
    assert [n for _s, _e, n in marked].count(sr.FENCE) == 2


@pytest.mark.parametrize("name", ["launch_gap_share", "dispatch_s",
                                  "native_s.band_chase", "native_s.secular",
                                  "native_s.deflate"])
def test_readers_return_nothing_without_a_trace(name, tmp_path, monkeypatch):
    """No metrics path, then no trace beside it: None, never an exception."""
    mod = bench_run.load_module("layer_metrics", name)
    run = {"trace": None, "config": {"op": "cholesky"}}
    monkeypatch.delenv("DLAF_METRICS_PATH", raising=False)
    assert mod.read(run, name) is None
    monkeypatch.setenv("DLAF_METRICS_PATH", str(tmp_path / "metrics.jsonl"))
    assert mod.read(run, name) is None


def test_a_trace_without_a_modules_line_gives_no_launch_gap_share(
        tmp_path, monkeypatch):
    """What the CPU writes: host spans, no ``XLA Modules`` line. The span
    walls are still read; the share of the device's time is not."""
    _, _, host = synthetic()
    monkeypatch.setenv("DLAF_METRICS_PATH", str(tmp_path / "metrics.jsonl"))
    monkeypatch.setattr(sr, "trace_path", lambda: "some.xplane.pb")
    monkeypatch.setattr(sr, "load", lambda path: ({}, host))
    run = {"trace": None, "config": {"op": "cholesky"}}
    gap = bench_run.load_module("layer_metrics", "launch_gap_share")
    assert gap.read(run, "launch_gap_share") is None
    assert not (tmp_path / "launch_gaps.json").exists()
    disp = bench_run.load_module("layer_metrics", "dispatch_s")
    assert disp.read(run, "dispatch_s") == pytest.approx(3e-3)


def test_readers_on_a_loaded_trace(tmp_path, monkeypatch, capsys):
    modules, ops, host = synthetic()
    monkeypatch.setenv("DLAF_METRICS_PATH", str(tmp_path / "metrics.jsonl"))
    monkeypatch.setattr(sr, "trace_path", lambda: "some.xplane.pb")
    monkeypatch.setattr(sr, "load", lambda path: ({DEVICE: modules}, host))
    run = {"trace": tr.reduce_trace({DEVICE: ops}, host, WINDOW),
           "config": {"op": "cholesky"}}

    def read(name):
        return bench_run.load_module("layer_metrics", name).read(run, name)

    assert read("launch_gap_share") == pytest.approx(40.0)
    assert read("dispatch_s") == pytest.approx(3e-3)
    assert read("native_s.secular") == pytest.approx(0.625e-3)
    assert read("native_s.band_chase") is None
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[launch_gaps]")]
    assert len(lines) == 4 and "'stage.cholesky.factor'" in lines[0]
    saved = json.loads((tmp_path / "launch_gaps.json").read_text())
    assert [row[0] for row in saved["rows"]][0] == "stage.cholesky.factor"


#: ``eig_d_n2048_1x1`` is in the rehearsal file only (its ``call_s`` follows
#: the seed too far for the bound, PERF.md section 7), so the entries that
#: read its native spans stand here until a ``benchmark`` issue admits it.
NATIVE_S = [{"name": f"native_s.{part}", "unit": "s", "better": "lower",
             "source": "program_span", "layer": "host stages",
             "moves": "call_s", "workloads": ["eig_d_n2048_1x1"]}
            for part in ("band_chase", "secular", "deflate")]


@pytest.mark.parametrize("cell,want", [
    ("chol_d_n4096_1x1", {"dispatch_s"}),
    ("eig_d_n2048_1x1", {"native_s.band_chase", "native_s.secular",
                         "native_s.deflate", "stage_s.band_to_tridiag"}),
])
def test_traced_command_reports_the_span_metrics(cell, want, tiny_root,
                                                 on_cpu, capsys):
    """The rehearsal cells with the committed BENCHMARK.json's new metrics
    and ``native_s.*`` through the command at tiny N: the program's spans
    reach the harness's profiler session; the CPU trace has no ``XLA
    Modules`` line, so ``launch_gap_share`` is left out."""
    path = os.path.join(tiny_root, "BENCHMARK.json")
    bench = bench_run.load_json(path)
    known = {m["name"] for m in bench["per_layer"]}
    committed = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["per_layer"] += [m for m in committed["per_layer"] + NATIVE_S
                           if m["name"] not in known]
    with open(path, "w") as f:
        json.dump(bench, f)
    rc = bench_run.main(["--workload", cell, "--seed", "11",
                         "--seconds", "0.5", "--trace", "1"], root=tiny_root)
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True
    assert want <= set(line["metrics"])
    assert "launch_gap_share" not in line["metrics"]
    if cell.startswith("eig"):
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert 0 < m["native_s.band_chase"] <= m["stage_s.band_to_tridiag"]
        assert 0 < m["native_s.secular"] + m["native_s.deflate"] \
            <= m["stage_s.tridiag_solver"]
    else:
        assert 0 < line["metrics"]["dispatch_s"]["value"]
