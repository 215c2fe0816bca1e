"""The benchmark's own arithmetic: trace reduction on a hand-made event
list, percentiles, residual digits, and that every name in BENCHMARK.json
resolves to a file."""

import json
import math
import os
import re

import pytest

import arith
import run as bench_run
import trace_reduce as tr

ROOT = bench_run.ROOT
MS = 1_000_000      # ns


# names as the v5e prints them: the instruction's whole HLO text
WHILE = ("%while.5264 = (u32[]{:T(128)}, f32[3584,3584]{0,1:T(8,128)}) "
         "while((u32[]{:T(128)}, f32[3584,3584]{0,1:T(8,128)}) %tuple.1), "
         "condition=%cond, body=%body")
PRODUCT = ("%subtract_select_fusion.1509 = (f32[3584,3584]{0,1:T(8,128)}, "
           "f32[3584,3584]{0,1:T(8,128)}) fusion(f32[3584,3584]{0,1:T(8,128)} "
           "%get-tuple-element.117786, s8[7,3584,256]{2,1,0:T(8,128)(4,1)} "
           "%get-tuple-element.117802), kind=kOutput, "
           "calls=%fused_computation.4073.clone.clone")


def synthetic():
    """Two calls on one device. Call 1 (0-10 ms): a while of 2-8 ms holding
    a matrix product 2-5 and an all-reduce 5-7, so 1 ms of the while's own.
    Gap 8-12 straddles the end of call 1 (midpoint 10: between calls). Call 2
    (11-20 ms): a fusion 12-15, then a gap 15-19 inside stage.chase, then a
    copy 19-20."""
    dev = [(2 * MS, 8 * MS, WHILE),
           (2 * MS, 5 * MS, PRODUCT),
           (5 * MS, 7 * MS, "%all-reduce.3 = f32[256,256]{1,0} all-reduce("
            "f32[256,256]{1,0} %x), replica_groups={{0,1}}"),
           (12 * MS, 15 * MS, "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %a), "
            "kind=kLoop, calls=%fused_computation.9"),
           (19 * MS, 20 * MS, "%copy.2 = f64[4096,4096]{1,0:T(8,128)} copy("
            "f64[4096,4096]{0,1:T(8,128)} %custom-call.6)")]
    host = [(0, 10 * MS, "bench_call"), (11 * MS, 20 * MS, "bench_call"),
            (14 * MS, 19 * MS + 1, "stage.chase")]
    return {"/device:TPU:0": dev}, host, (0, 20 * MS)


def test_busy_union_merges_nested_and_touching_intervals():
    dev, _, _ = synthetic()
    assert tr.busy_union(dev["/device:TPU:0"]) == [
        (2 * MS, 8 * MS), (12 * MS, 15 * MS), (19 * MS, 20 * MS)]


def test_reduce_trace_idle_share_classes_and_gap_labels():
    red = tr.reduce_trace(*synthetic())
    d = red["devices"]["/device:TPU:0"]
    assert d["busy_ns"] == 10 * MS and red["window_s"] == pytest.approx(0.02)
    run = {"trace": red, "traced_calls": 2}
    idle = bench_run.load_module("layer_metrics", "device_idle_share")
    busy = bench_run.load_module("layer_metrics", "device_busy_s")
    mm = bench_run.load_module("layer_metrics", "matmul_time_share")
    coll = bench_run.load_module("layer_metrics", "collective_time_share")
    assert idle.read(run, "device_idle_share") == pytest.approx(50.0)
    assert busy.read(run, "device_busy_s") == pytest.approx(0.005)
    # own time: product 3, all-reduce 2, loop fusion 3, copy 1 (the while's
    # own 1 ms is control and counts in no share)
    assert d["classes"] == {"matmul": 3 * MS, "collective": 2 * MS,
                            "control": 1 * MS, "other": 4 * MS}
    assert mm.read(run, "x") == pytest.approx(100 * 3 / 9)
    assert coll.read(run, "x") == pytest.approx(100 * 2 / 9)
    gaps = dict(g for g in red["idle_gaps"] if not g[0].startswith("longest"))
    assert gaps == {"in_call": pytest.approx(0.002),          # 0-2 ms
                    "between_calls": pytest.approx(0.004),    # 8-12 ms
                    "stage.chase": pytest.approx(0.004)}      # 15-19 ms
    assert red["device_ops"][0] == ["subtract_select_fusion kOutput",
                                    pytest.approx(0.003)]
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_events_outside_the_window_are_cut():
    dev, host, _ = synthetic()
    red = tr.reduce_trace(dev, host, (4 * MS, 13 * MS))
    assert red["devices"]["/device:TPU:0"]["busy_ns"] == 5 * MS


def test_readers_return_nothing_without_a_device_trace():
    for name in ("device_idle_share", "device_busy_s", "matmul_time_share",
                 "collective_time_share", "stage_s.band_to_tridiag",
                 "first_call_s", "cache_misses"):
        mod = bench_run.load_module("layer_metrics", name)
        assert mod.read({"trace": None}, name) is None


@pytest.mark.parametrize("text,want,label", [
    (PRODUCT, "matmul", "subtract_select_fusion kOutput"),
    ("%convolution.3 = f32[8,8]{1,0} convolution(f32[8,8]{1,0} %a, "
     "f32[8,8]{1,0} %b), dim_labels=bf_io->bf", "matmul", "convolution"),
    ("%convert.5 = f32[8]{0} convert(f64[8]{0} %a)", "other", "convert"),
    ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f",
     "other", "fusion kLoop"),
    ("%all-gather-start.1 = (f32[8]{0}, f32[32]{0}) all-gather-start("
     "f32[8]{0} %a), dimensions={0}", "collective", "all-gather-start"),
    ("%collective-permute-done.2.clone = f32[8]{0} collective-permute-done("
     "(f32[8]{0}, f32[8]{0}) %s)", "collective", "collective-permute-done"),
    (WHILE, "control", "while"),
    ("%custom-call.2 = f64[4096,4096]{1,0:T(8,128)} custom-call(f32[4096,4096]"
     "{1,0:T(8,128)} %bitcast.68), custom_call_target=\"X64Combine\"",
     "other", "custom-call X64Combine"),
    ("fusion.12", "other", "fusion"),           # a bare name, as on the CPU
    ("all-reduce.7", "collective", "all-reduce"),
])
def test_classify_and_label(text, want, label):
    assert tr.classify(text) == want
    assert tr.parse_op(text)[0] == label


def test_percentile_matches_numpy():
    import numpy as np

    xs = [0.3, 0.1, 0.9, 0.5, 0.7, 0.2]
    for q in (0, 50, 90, 100):
        assert arith.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert arith.percentile([3.0], 90) == 3.0
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_residual_digits_and_tolerance():
    assert arith.residual_digits(2.8e-15) == pytest.approx(14.5528, abs=1e-3)
    assert arith.residual_digits(0.0) == arith.MAX_DIGITS
    assert arith.residual_digits(float("nan")) == 0.0
    g = {"c": 60.0, "eps_tpu": 2.0 ** -47, "eps_native": 2.0 ** -52}
    assert arith.tolerance(g, 4096, "tpu") == 60 * 4096 * 2.0 ** -47
    assert arith.tolerance(g, 4096, "cpu") == 60 * 4096 * 2.0 ** -52
    assert math.isclose(arith.tolerance(g, 4096, "tpu"), 1.746e-9, rel_tol=1e-3)


@pytest.mark.parametrize("path", [
    "BENCHMARK.json", "benchmark/tests/rehearsal_benchmark.json"])
def test_every_name_in_benchmark_json_resolves_to_a_file(path, monkeypatch):
    bench = bench_run.load_json(os.path.join(ROOT, path))
    monkeypatch.setattr(bench_run, "load_json", lambda p, real=bench_run.
                        load_json: bench if p.endswith("BENCHMARK.json")
                        else real(p))
    name_ok = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        cell = bench_run.load_cell(ROOT, w["name"])      # config + traffic
        assert name_ok.match(w["name"]) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "ops", cell["config"]["op"] + ".py"))
        assert cell["config"]["grid"][0] * cell["config"]["grid"][1] \
            == w["chips"]
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    assert {c["name"] for c in bench["configs"]} \
        == {w["config"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        mod = bench_run.load_module("layer_metrics", m["name"])
        assert callable(mod.read) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    assert len(json.dumps(bench)) < 64 * 1024
