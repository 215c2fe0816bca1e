"""The phase readers (``phase_table.py``, ``phase_ms.*``, ``program_temp_gib``,
``program_code_mib``; PR 35): the placement arithmetic on a hand-made event
list and table, the entries of BENCHMARK.json found by name, and the traced
command on the CPU (where a trace has no device plane, so the program
counters read and the phase split does not). Nothing here is a device
measurement."""

import os

import pytest

import phase_table as pt
import run as bench_run
from test_command import last_line, on_cpu, tiny_root      # noqa: F401

ROOT = bench_run.ROOT
MS = 1_000_000      # ns
CELLS = ("chol_d_n4096_1x1", "trsm_d_n8192_2x2", "chol_d_n16384_1x1",
         "red2band_d_n8192_1x1")
ENTRIES = {
    "phase_ms.panel": CELLS,
    "phase_ms.bulk": ("chol_d_n4096_1x1", "chol_d_n16384_1x1",
                      "trsm_d_n8192_2x2"),
    "phase_ms.strip": ("chol_d_n4096_1x1", "chol_d_n16384_1x1"),
    "phase_ms.larft": ("red2band_d_n8192_1x1",),
    "phase_ms.w": ("red2band_d_n8192_1x1",),
    "phase_ms.update": ("red2band_d_n8192_1x1",),
    "phase_ms.unattributed": CELLS,
    "program_temp_gib": CELLS,
    "program_code_mib": CELLS,
}


def table():
    """What ``telemetry.phase_table`` hands out, by hand: two fusions and a
    loop with phases, a shared kernel emitted under the panel's name, a
    ``call`` with a phase, and instructions without one."""
    return {
        "site": "toy.local", "module": "jit_toy", "stale": False,
        "phases": {"fusion.1": "panel", "while.2": "panel",
                   "fusion.3": "bulk", "while.4": "bulk",
                   "fusion.5": "bulk", "kernel.6": "panel",
                   "call.7": "strip", "fusion.8": "w"},
        "operands": {"copy.10": ["fusion.3"],
                     "bitcast.11": ["get-tuple-element.12", "fusion.1"],
                     "get-tuple-element.12": ["while.2"],
                     "copy.13": ["parameter.14"], "parameter.14": [],
                     "deep.15": ["deep.16"], "deep.16": ["deep.17"],
                     "deep.17": ["deep.18"], "deep.18": ["fusion.1"]},
        "counts": {"panel": 3, "bulk": 3, "strip": 1, "w": 1},
    }


def ev(start, end, inst, opcode="fusion"):
    return (start * MS, end * MS,
            f"%{inst} = f32[8]{{0}} {opcode}(%a), kind=kLoop")


def one_run(t=0):
    """One run of ``jit_toy`` from ``t`` ms on, 100 ms long:

    * 0-10 ``fusion.1`` (panel, direct);
    * 10-40 ``while.2`` (panel) around ``kernel.6`` 12-20 and ``fusion.8``
      30-38: the loop's own 14 ms are the panel's;
    * 40-44 ``copy.10`` (no metadata; its operand ``fusion.3`` is bulk);
    * 44-46 ``bitcast.11`` (its first operand resolves two levels up: panel);
    * 46-76 ``while.4`` (bulk) around ``fusion.5`` 48-58 (bulk) and
      ``kernel.6`` 60-70: the same kernel under a second loop, so it is
      shared: there it is the bulk's (the nearest earlier direct event);
      under ``while.2`` the nearest earlier one is ``fusion.1``, the panel;
    * 76-90 ``call.7`` (strip) around ``fusion.8`` 78-88, whose own phase
      (w) gives way to the call's;
    * 90-95 ``copy.13`` (operand without a phase) and 95-100 ``deep.15``
      (a phase four levels up): unattributed.
    """
    rows = [(0, 10, "fusion.1"), (10, 40, "while.2", "while"),
            (12, 20, "kernel.6"), (30, 38, "fusion.8"),
            (40, 44, "copy.10", "copy"), (44, 46, "bitcast.11", "bitcast"),
            (46, 76, "while.4", "while"), (48, 58, "fusion.5"),
            (60, 70, "kernel.6"), (76, 90, "call.7", "call"),
            (78, 88, "fusion.8"), (90, 95, "copy.13", "copy"),
            (95, 100, "deep.15")]
    return [ev(t + r[0], t + r[1], *r[2:]) for r in rows]


WANT = {("panel", "direct"): 10 + 14, ("panel", "neighbour"): 8,
        ("w", "direct"): 8, ("bulk", "operand"): 4, ("panel", "operand"): 2,
        ("bulk", "direct"): 10 + 10, ("bulk", "neighbour"): 10,
        ("strip", "direct"): 4, ("strip", "call"): 10,
        ("unattributed", "unattributed"): 10}


def test_resolve_direct_operand_and_nothing():
    tab = table()
    assert pt.resolve(tab, "fusion.1") == ("panel", "direct")
    assert pt.resolve(tab, "copy.10") == ("bulk", "operand")
    assert pt.resolve(tab, "bitcast.11") == ("panel", "operand")
    assert pt.resolve(tab, "copy.13") == (None, None)
    assert pt.resolve(tab, "deep.15") == (None, None)      # four levels up
    assert pt.resolve(tab, "deep.16") == ("panel", "operand")
    assert pt.resolve(tab, "not_in_the_text") == (None, None)


def test_own_times_nest_and_sum_to_the_busy_time():
    timed = pt.own_times(one_run())
    assert sum(t[3] for t in timed) == 100 * MS
    by_inst = {}
    for _s, _e, name, own, parent in timed:
        by_inst.setdefault(pt.instruction(name), []).append(
            (own // MS, None if parent is None
             else pt.instruction(timed[parent][2])))
    assert by_inst["while.2"] == [(14, None)]
    assert by_inst["kernel.6"] == [(8, "while.2"), (10, "while.4")]
    assert by_inst["fusion.8"] == [(8, "while.2"), (10, "call.7")]


def test_every_placement_on_a_hand_made_run():
    placed = pt.place(one_run(), table())
    assert {k: v // MS for k, v in placed.items()} == WANT
    # phases and unattributed sum to the program's own time
    assert sum(placed.values()) == 100 * MS


def test_a_kernel_under_one_loop_is_not_shared():
    timed = pt.own_times(one_run())
    assert pt.shared_instructions(timed) == {"kernel.6"}
    alone = [e for e in one_run() if not (46 * MS <= e[0] < 76 * MS)]
    assert "kernel.6" not in pt.shared_instructions(pt.own_times(alone))


def test_split_reads_complete_calls_only_and_names_other_programs():
    """Three calls; the trace ends inside the third (its program's last
    events are missing), so two are read. A call holds the harness's
    ``jit_add`` beside the entry's program."""
    modules, events, host = [], [], [(0, 400 * MS, "bench_window")]
    for k in range(3):
        t = 120 * k
        host.append((t * MS, (t + 119) * MS, "bench_call"))
        modules += [(t * MS, (t + 5) * MS, "jit_add(1)"),
                    ((t + 10) * MS, (t + 110) * MS, "jit_toy(2)")]
        events.append(ev(t, t + 5, "add.1", "add"))
        events += one_run(t + 10)
    events = [e for e in events if e[0] < 330 * MS]     # the cut
    whole, calls = pt.complete_calls(host, (0, 400 * MS), events)
    assert len(calls) == 3 and len(whole) == 2
    found = pt.split_calls(modules, whole, table())
    assert found["calls"] == 2
    assert found["others"] == {"jit_add": pytest.approx(5.0)}
    assert found["program_ms"] == pytest.approx(100.0)
    assert found["phases"] == pytest.approx(
        {"panel": 34.0, "bulk": 34.0, "strip": 14.0, "w": 8.0,
         "unattributed": 10.0})
    assert found["placed"]["panel"] == pytest.approx(
        {"direct": 24.0, "neighbour": 8.0, "operand": 2.0})
    assert sum(found["phases"].values()) + sum(found["others"].values()) \
        == pytest.approx(105.0)


def test_readers_return_none_without_a_split():
    phase_ms = bench_run.load_module("layer_metrics", "phase_ms.panel")
    assert phase_ms.read({pt.KEY: None}, "phase_ms.panel") is None
    run = {pt.KEY: {"phases": {"panel": 3.5}}}
    assert phase_ms.read(run, "phase_ms.panel") == 3.5
    assert phase_ms.read(run, "phase_ms.larft") is None
    for name in ("program_temp_gib", "program_code_mib"):
        reader = bench_run.load_module("layer_metrics", name)
        assert reader.read({"phase_site": (None, None)}, name) is None


def test_a_stale_table_prints_stale_and_gives_no_split(capsys):
    class Telemetry:
        @staticmethod
        def phase_table(site):
            return dict(table(), phases={}, counts={}, stale=True)

    run = {"phase_site": ("toy.local", Telemetry),
           "trace": {"devices": {"d": {}}, "worst_device": "d"}}
    assert pt.split(run) is None
    assert "stale" in capsys.readouterr().out
    phase_ms = bench_run.load_module("layer_metrics", "phase_ms.panel")
    assert phase_ms.read(run, "phase_ms.unattributed") is None


# ---------------------------------------------------------------------------
# the entries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_entry_is_committed_with_a_file_that_loads(name):
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert tuple(entry["workloads"]) == ENTRIES[name]
    assert entry["better"] == "lower"
    if name.startswith("phase_ms."):
        assert (entry["unit"], entry["source"], entry["moves"],
                entry["layer"]) == ("ms", "device_trace", "call_s",
                                    "step builders and precision routes")
    else:
        assert (entry["source"], entry["moves"], entry["layer"]) == (
            "program_counter", "peak_hbm_gib", "compile cache and set-up")
        assert entry["unit"] == {"program_temp_gib": "GiB",
                                 "program_code_mib": "MiB"}[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert callable(bench_run.load_module("layer_metrics", name).read)
    # every listed cell reports the end-to-end metric the entry moves
    for cell in entry["workloads"]:
        moved = [m for m in bench["end_to_end"]
                 if m["name"] == entry["moves"]]
        assert moved and cell in moved[0].get("workloads", [cell])


def test_new_entries_are_appended_after_the_accepted_ones():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    first_new = min(names.index(n) for n in ENTRIES)
    assert set(names[first_new:]) == set(ENTRIES)
    assert "panel_hbm_share" in names[:first_new]


# ---------------------------------------------------------------------------
# the command, on the CPU
# ---------------------------------------------------------------------------

def test_traced_command_reads_the_program_counters(tmp_path, on_cpu, capsys):
    """The committed BENCHMARK.json's first cell at a tiny N: the entry
    remembers its program, so ``program_temp_gib`` / ``program_code_mib``
    read the executable's ``memory_analysis()``; a CPU trace has no device
    plane, so no ``phase_ms.*`` is reported, and nothing raises."""
    import json
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"),
                tmp_path / "BENCHMARK.json")
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "chol-d-n4096-nb256")
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    config.update(n=128, nb=32)
    dst = tmp_path / entry["file"]
    dst.parent.mkdir(parents=True)
    dst.write_text(json.dumps(config))
    tdir = tmp_path / "benchmark" / "traffic"
    tdir.mkdir(parents=True)
    traffic = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "traffic", "scf_closed_loop.json"))
    traffic["traced_window"].update(min_seconds=0.2)
    (tdir / "scf_closed_loop.json").write_text(json.dumps(traffic))
    rc = bench_run.main(["--workload", "chol_d_n4096_1x1", "--seed",
                         "2147483900", "--seconds", "0.3", "--trace", "1"],
                        root=str(tmp_path))
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert not [k for k in m if k.startswith("phase_ms.")]
    assert m["program_temp_gib"] > 0 and m["program_code_mib"] >= 0
    from dlaf_tpu import obs
    from dlaf_tpu.obs import telemetry

    assert telemetry.programs() == ["cholesky.local"]
    hbm = {x["labels"]["what"]: x["value"]
           for x in obs.registry().snapshot()
           if x["name"] == "dlaf_hbm_bytes"}
    assert m["program_temp_gib"] == hbm["temp"] / 2 ** 30
