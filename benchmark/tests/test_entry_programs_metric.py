"""``entry_programs_per_call`` (PR 30) on hand-made counter snapshots, its
``BENCHMARK.json`` entry, and the traced command on the CPU at tiny N with
the committed entry: the library's ``dlaf_entry_programs_total`` and
``dlaf_entry_calls_total`` reach the line and read one program a call of
the local Cholesky; the distributed solve counts no program and reports
nothing."""

import json
import os

import pytest

import run as bench_run
from test_command import last_line, on_cpu, tiny_root      # noqa: F401

ROOT = bench_run.ROOT
NAME = "entry_programs_per_call"
ENTRY = {"name": NAME, "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "entry points",
         "moves": "call_s", "workloads": ["chol_d_n4096_1x1"]}


def read(run):
    return bench_run.load_module("layer_metrics", NAME).read(run, NAME)


def count(name, entry, value):
    return {"name": f"dlaf_entry_{name}_total", "kind": "counter",
            "labels": {"entry": entry}, "value": float(value)}


def test_ratio_is_programs_over_calls_of_the_entries_that_count_both():
    one = [count("programs", "cholesky", 412), count("calls", "cholesky", 412)]
    assert read({"counters": one}) == 1.0
    # the three-program form, had the parent counted it
    assert read({"counters": [count("programs", "cholesky", 36),
                              count("calls", "cholesky", 12)]}) == 3.0
    # an entry that counts its calls only (every entry does, through
    # obs.entry_span) stays out of both sums; other counters are not read
    mixed = one + [count("calls", "triangular_solve", 100),
                   count("programs", "hegst", 5),
                   {"name": "dlaf_ozaki_macs_total", "kind": "counter",
                    "labels": {"route": "scan", "kind": "real"},
                    "value": 7.0}]
    assert read({"counters": mixed}) == 1.0
    two = one + [count("programs", "triangular_solve", 30),
                 count("calls", "triangular_solve", 10)]
    assert read({"counters": two}) == pytest.approx(442 / 422)


@pytest.mark.parametrize("run", [
    {}, {"counters": None}, {"counters": []},
    {"counters": [count("calls", "cholesky", 9)]},
    {"counters": [count("programs", "cholesky", 9)]},
    {"counters": [count("programs", "cholesky", 9),
                  count("calls", "triangular_solve", 9)]},
    {"counters": [count("programs", "cholesky", 0),
                  count("calls", "cholesky", 0)]}])
def test_ratio_is_nothing_without_both_counters(run):
    """As on the parent commit, whose program has neither."""
    assert read(run) is None


def test_entry_is_appended_and_reported_in_the_cholesky_cell_only():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    # the entries that were there, in the order they had, then this one
    # (by position in the list, so that a later PR's append does not
    # break this test as this PR's broke test_ozaki_metric.py's pin)
    before = ["device_idle_share", "device_busy_s", "matmul_time_share",
              "first_call_s", "cache_misses", "launch_gap_share",
              "dispatch_s", "collective_time_share",
              "phase_s.triangular_solve.dispatch", "collective_mib_per_call",
              "ozaki_zero_mac_share"]
    assert names[:len(before) + 1] == before + [NAME]
    assert bench["per_layer"][len(before)] == ENTRY
    for w in bench["workloads"]:
        cell = bench_run.load_cell(ROOT, w["name"])
        assert (NAME in [m["name"] for m in cell["per_layer"]]) == (
            w["name"] in ENTRY["workloads"])


def test_traced_command_reads_one_program_a_call(tiny_root, on_cpu, capsys):
    from dlaf_tpu import obs

    obs._reset_for_tests()      # a run is a process: an empty registry
    path = os.path.join(tiny_root, "BENCHMARK.json")
    bench = bench_run.load_json(path)
    bench["per_layer"].append(ENTRY)
    with open(path, "w") as f:
        json.dump(bench, f)
    rc = bench_run.main(["--workload", "chol_d_n4096_1x1", "--seed",
                         "2147483830", "--seconds", "0.5", "--trace", "1"],
                        root=tiny_root)
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"][NAME] == {"value": 1.0, "unit": "count"}
    snap = {(m["name"], m["labels"].get("entry")): m["value"]
            for m in obs.registry().snapshot() if m["kind"] == "counter"}
    calls = snap["dlaf_entry_calls_total", "cholesky"]
    assert calls >= 3 and snap["dlaf_entry_programs_total", "cholesky"] == calls
