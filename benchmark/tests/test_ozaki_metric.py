"""``ozaki_zero_mac_share`` (PR 28) on hand-made counter snapshots, its
``BENCHMARK.json`` entry, and the traced command on the CPU at tiny N with
the committed entry: the library's ``dlaf_ozaki_macs_total`` reaches the
line; the distributed solve's bulk products multiply no padding, the local
Cholesky's padded scans (syrk and panel products) still do."""

import json
import os

import pytest

import run as bench_run
from test_command import last_line, on_cpu, tiny_root      # noqa: F401

ROOT = bench_run.ROOT
NAME = "ozaki_zero_mac_share"


def read(run):
    return bench_run.load_module("layer_metrics", NAME).read(run, NAME)


def macs(route, kind, value):
    return {"name": "dlaf_ozaki_macs_total", "kind": "counter",
            "labels": {"route": route, "kind": kind}, "value": float(value)}


def test_share_is_zero_over_real_plus_zero_summed_over_routes():
    # the padded scan at s = 7: every group 7 k deep, 28 of 49 slots real
    padded = [macs("scan", "real", 28 * 256), macs("scan", "zero", 21 * 256)]
    assert read({"counters": padded}) == pytest.approx(100 * 21 / 49)
    assert read({"counters": padded}) == pytest.approx(42.857, abs=1e-3)
    ragged = [macs("scan", "real", 28 * 256), macs("scan", "zero", 0),
              macs("dots", "real", 5), macs("dots", "zero", 0),
              {"name": "dlaf_ozaki_mirror_total", "kind": "counter",
               "labels": {"route": "scan"}, "value": 14.0}]
    assert read({"counters": ragged}) == 0.0
    mixed = ragged + [macs("concat", "real", 1000), macs("concat", "zero", 827)]
    assert read({"counters": mixed}) == pytest.approx(
        100 * 827 / (28 * 256 + 5 + 1000 + 827))


@pytest.mark.parametrize("run", [
    {}, {"counters": None}, {"counters": []},
    {"counters": [{"name": "dlaf_ozaki_mirror_total", "kind": "counter",
                   "labels": {"route": "scan"}, "value": 14.0}]},
    {"counters": [macs("scan", "real", 0), macs("scan", "zero", 0)]}])
def test_share_is_nothing_without_the_counter(run):
    """As on the parent commit, whose program has no such counter."""
    assert read(run) is None


def test_entry_is_appended_and_reported_in_every_cell():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert bench["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter",
        "layer": "step builders and precision routes", "moves": "call_s"}
    # the entries that were there, in the order they had
    assert [m["name"] for m in bench["per_layer"][:-1]] == [
        "device_idle_share", "device_busy_s", "matmul_time_share",
        "first_call_s", "cache_misses", "launch_gap_share", "dispatch_s",
        "collective_time_share", "phase_s.triangular_solve.dispatch",
        "collective_mib_per_call"]
    for w in bench["workloads"]:
        cell = bench_run.load_cell(ROOT, w["name"])
        assert NAME in [m["name"] for m in cell["per_layer"]]


@pytest.mark.parametrize("cell,lo,hi", [
    # tiny N: local 64 x 64 blocks, so only the two-slot updates are bulk
    ("trsm_d_n8192_2x2", 0.0, 40.0),
    # off the TPU s = 8: syrks at 20 zero slots of 40, panel products at
    # 28 of 64 (on the chip, s = 7: 12 of 28 and 21 of 49, 3/7 both)
    ("chol_d_n4096_1x1", 100 * 28 / 64, 100 * 20 / 40)])
def test_traced_command_reads_the_padding_that_is_left(cell, lo, hi,
                                                       tiny_root, on_cpu,
                                                       capsys, monkeypatch):
    """Both cells through the command with the product routes the chip
    resolves (Ozaki gemms from 32 up, concat groups, the sequenced
    schedule): the counter is in the registry's snapshot; the solve's
    bulk products are ragged, so its share falls below the padded scans'
    3/7, which is what the Cholesky reads."""
    from dlaf_tpu import obs

    obs._reset_for_tests()      # a run is a process: an empty registry
    for knob, value in (("F64_GEMM", "mxu"), ("F64_GEMM_MIN_DIM", "32"),
                        ("OZAKI_GROUP", "concat"), ("OZAKI_ACCUM", "scan"),
                        ("CHOLESKY_TRAILING", "ozaki")):
        monkeypatch.setenv("DLAF_" + knob, value)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    bench = bench_run.load_json(path)
    committed = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["per_layer"].append(committed["per_layer"][-1])
    with open(path, "w") as f:
        json.dump(bench, f)
    rc = bench_run.main(["--workload", cell, "--seed", "2147483801",
                         "--seconds", "0.5", "--trace", "1"], root=tiny_root)
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"][NAME]["unit"] == "%"
    assert lo <= line["metrics"][NAME]["value"] <= hi
    assert any(m["name"] == "dlaf_ozaki_macs_total" and m["value"] > 0
               for m in obs.registry().snapshot())
