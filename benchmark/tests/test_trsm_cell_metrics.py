"""The per-layer readers that came with the cell ``trsm_d_n8192_2x2``
(``phase_s.*``, ``collective_mib_per_call``) on hand-made span lists and
counter snapshots, and the cell's traced command on four virtual CPU devices
with the committed BENCHMARK.json's metrics."""

import json
import os

import pytest

import run as bench_run
import span_reduce as sr
from test_command import last_line, on_cpu, tiny_root      # noqa: F401

ROOT = bench_run.ROOT
MS = 1_000_000      # ns
MIB = 2 ** 20
CELL = "trsm_d_n8192_2x2"
PHASE = "phase_s.triangular_solve.dispatch"


def read(name, run):
    return bench_run.load_module("layer_metrics", name).read(run, name)


def host_spans():
    """Three calls of 20 ms: the entry span 3-6 ms holds the dispatch phase
    (3-4, 3-5 and 3-5.5 ms) and, in call 2, a second dispatch of 0.5 ms."""
    host = [(0, 60 * MS, "bench_window")]
    for k, width in enumerate((1.0, 2.0, 2.5)):
        t = k * 20 * MS
        host += [(t, t + 20 * MS - 1, "bench_call"),
                 (t + 3 * MS, t + 6 * MS, "triangular_solve"),
                 (t + 3 * MS, t + int((3 + width) * MS),
                  "stage.triangular_solve.dispatch"),
                 (t + 6 * MS, t + 19 * MS, "stage.fence")]
    host.append((27 * MS, 27 * MS + MS // 2,
                 "stage.triangular_solve.dispatch"))
    return host


def test_phase_s_sums_within_a_call_then_takes_the_median(tmp_path,
                                                          monkeypatch):
    monkeypatch.setenv("DLAF_METRICS_PATH", str(tmp_path / "metrics.jsonl"))
    monkeypatch.setattr(sr, "trace_path", lambda: "some.xplane.pb")
    monkeypatch.setattr(sr, "load", lambda path: ({}, host_spans()))
    run = {"trace": None}
    # per call 1.0, 2.0 + 0.5, 2.5 ms: the median call, not the median span
    assert read(PHASE, run) == pytest.approx(2.5e-3)
    assert read("phase_s.triangular_solve.solve", run) is None
    assert read("phase_s.cholesky.factor", run) is None


def test_phase_s_returns_nothing_without_a_trace(tmp_path, monkeypatch):
    run = {"trace": None}
    monkeypatch.delenv("DLAF_METRICS_PATH", raising=False)
    assert read(PHASE, run) is None
    monkeypatch.setenv("DLAF_METRICS_PATH", str(tmp_path / "metrics.jsonl"))
    assert read(PHASE, run) is None


def counter(name, value, **labels):
    return {"name": name, "kind": "counter", "labels": labels,
            "value": float(value)}


def test_collective_mib_sums_the_byte_counters_over_kind_and_axis():
    snap = [counter("dlaf_comm_collective_bytes_total", 3 * MIB,
                    kind="bcast", axis="row"),
            counter("dlaf_comm_collective_bytes_total", MIB // 2,
                    kind="bcast", axis="col"),
            counter("dlaf_comm_collective_bytes_total", MIB,
                    kind="bcast2d", axis="row"),
            counter("dlaf_comm_collective_count_total", 96,
                    kind="bcast", axis="row"),
            counter("dlaf_comm_overlapped_total", 64,
                    algo="triangular_solve_scan", axis="row"),
            {"name": "dlaf_span_seconds", "kind": "histogram",
             "labels": {"span": "stage.fence"}, "count": 3, "sum": 0.01}]
    assert read("collective_mib_per_call", {"counters": snap}) \
        == pytest.approx(4.5)


@pytest.mark.parametrize("run", [
    {}, {"counters": None}, {"counters": []},
    {"counters": [counter("dlaf_fallback_total", 0, site="x")]}])
def test_collective_mib_returns_nothing_without_the_counter(run):
    assert read("collective_mib_per_call", run) is None


def scan_solve_bytes(n, nb, p=2, q=2):
    """Payload bytes of one LLNN scan-form solve of an n x n float64 block
    on p x q devices, serial body, whole solve one telescoped segment
    (n / nb <= 8 steps): a step moves the diagonal tile once per axis, the
    pivot block row of B (n / nb / q tiles) and A's column panel (n / nb / p
    tiles)."""
    nt = n // nb
    assert nt <= 8
    return nt * nb * nb * 8 * (2 + nt // q + nt // p)


def test_traced_command_reports_the_cells_own_metrics(tiny_root, on_cpu,
                                                      capsys, monkeypatch):
    """The cell through the command at tiny N, scan form as on the chip,
    with the committed per-layer entries: the dispatch phase reaches the
    harness's profiler session, and the counted payload is that of ONE
    trace of the program (three calls and more run), by hand arithmetic."""
    from dlaf_tpu import obs

    obs._reset_for_tests()      # a run is a process: an empty registry
    monkeypatch.setenv("DLAF_DIST_STEP_MODE", "scan")
    monkeypatch.setenv("DLAF_CHOLESKY_LOOKAHEAD", "0")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    bench = bench_run.load_json(path)
    known = {m["name"] for m in bench["per_layer"]}
    committed = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [m for m in committed["per_layer"]
            if CELL in m.get("workloads", ())]
    assert {m["name"] for m in mine} == {
        "collective_time_share", PHASE, "collective_mib_per_call"}
    bench["per_layer"] += [m for m in mine if m["name"] not in known]
    with open(path, "w") as f:
        json.dump(bench, f)
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483777",
                         "--seconds", "0.5", "--trace", "1"], root=tiny_root)
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True and line["attempted"] >= 3
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m[PHASE] < 1.0
    assert line["metrics"][PHASE]["unit"] == "s"
    assert m["collective_mib_per_call"] == scan_solve_bytes(128, 32) / MIB
    assert line["metrics"]["collective_mib_per_call"]["unit"] == "MiB"
