"""The cell ``red2band_d_n8192_1x1`` (PR 33): its entries and files, its
three readers on a hand-made trace and counter snapshot, the hand counts of
the chip's shape, and its untraced and traced command on the CPU at a small
size (33 panels of 16 columns under a TPU's knob resolution, so the scan
builder is what runs). Entries of BENCHMARK.json are found by name, never
by position. Nothing here is a device measurement."""

import json
import os
import shutil

import numpy as np
import pytest

import run as bench_run
from test_command import last_line, on_cpu      # noqa: F401

ROOT = bench_run.ROOT
CELL = "red2band_d_n8192_1x1"
CONFIG = "red2band-d-n8192-nb512-b128"
METRICS = ("panel_time_share", "panel_column_us", "panel_hbm_share")
#: 33 panels of 16 columns, band < nb
TINY = {"n": 544, "nb": 64, "args": {"band_size": 16}}


def reader(name):
    return bench_run.load_module("layer_metrics", name)


def committed():
    return bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


# ---------------------------------------------------------------------------
# the entries
# ---------------------------------------------------------------------------

def test_the_cell_and_its_configuration_are_committed():
    bench = committed()
    cell = by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "scf_closed_loop", 1)
    assert len(cell["why"]) <= 200
    entry = by_name(bench["configs"], CONFIG)
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert (config["op"], config["dtype"], config["n"], config["nb"],
            config["grid"], config["args"]) == (
        "reduction_to_band", "float64", 8192, 512, [1, 1],
        {"band_size": 128})
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == ["grid", "n"]
    assert config["published"]["n"] == 16384
    assert config["published"]["band"] == config["args"]["band_size"]
    assert config["published"]["nb"] == config["nb"]
    # 2.0x one chip's share of the published run's flops, as the file says
    assert (config["n"] / (16384 / 16 ** (1 / 3))) ** 3 \
        == pytest.approx(2.0, abs=0.01)
    guarantee = config["guarantee"]
    assert guarantee["c"] == 100.0
    assert guarantee["eps_tpu"] == 2.0 ** -47
    assert guarantee["eps_native"] == 2.0 ** -52
    loaded = bench_run.load_cell(ROOT, CELL)
    assert loaded["config"] == config and loaded["chips"] == 1
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "call_s", "residual_digits", "peak_hbm_gib", "setup_s"}
    assert bench_run.load_module("ops", config["op"]).flops(config) \
        == 4 * 8192 ** 3 / 3


@pytest.mark.parametrize("name, unit, better", [
    ("panel_time_share", "%", "lower"), ("panel_column_us", "us", "lower"),
    ("panel_hbm_share", "%", "higher")])
def test_the_metrics_are_listed_for_this_cell_only(name, unit, better):
    assert by_name(committed()["per_layer"], name) == {
        "name": name, "unit": unit, "better": better,
        "source": "device_trace",
        "layer": "step builders and precision routes", "moves": "call_s",
        "workloads": [CELL]}
    assert callable(reader(name).read)
    assert name in {m["name"] for m in
                    bench_run.load_cell(ROOT, CELL)["per_layer"]}
    for other in ("chol_d_n4096_1x1", "chol_d_n16384_1x1",
                  "trsm_d_n8192_2x2"):
        assert name not in {m["name"] for m in
                            bench_run.load_cell(ROOT, other)["per_layer"]}


# ---------------------------------------------------------------------------
# the hand counts of the chip's shape
# ---------------------------------------------------------------------------

def test_hand_counts_of_the_chips_shape():
    """63 steps in 8 bodies, 8064 columns, 528 MB of panels a call."""
    from dlaf_tpu.types import telescope_segments

    n, band = 8192, 128
    panels = n // band - 1
    assert panels == 63
    assert telescope_segments(panels) == (8, 8, 8, 8, 8, 8, 8, 7)
    assert panels * band == 8064
    rows = [n - band * (k + 1) for k in range(panels)]
    assert rows[0] == 8064 and rows[-1] == 128
    moved = reader("panel_hbm_share").panel_bytes(n, band)
    assert moved == 2 * 8 * band * sum(rows) == 528482304
    assert reader("panel_hbm_share").panel_bytes(16, 16) == 0


# ---------------------------------------------------------------------------
# the readers, on a hand-made trace
# ---------------------------------------------------------------------------

def hlo(name, opcode="fusion", shape="f32[8,128]{1,0}"):
    """An event name as the v5e writes it: the instruction's HLO text."""
    return f"%{name} = {shape} {opcode}(%p.1), kind=kLoop"


def loop(name, start, trips, step_ns, children=("mul", "add")):
    """A ``while`` event with ``trips`` iterations of ``children``, each
    child ``step_ns`` long and back to back: ``(events, end)``."""
    events, at = [], start + 10
    for _ in range(trips):
        for child in children:
            events.append((at, at + step_ns, hlo(f"{child}.{name}")))
            at += step_ns
    end = at + 10
    return [(start, end, hlo(name, "while", "(s32[], f32[64,4]{1,0})"))] \
        + events, end


def synthetic_events(band=4):
    """One program: a top-level fusion, then a segment ``while`` of two
    steps. Each step: a column sweep (``band`` trips, with a nested loop in
    every column step: the emulated dot), the T factor's inversion
    (``band`` trips), a slice product's shift-group scan (7 trips), a row
    chunk ``lax.map`` (2 trips, a 4-trip loop nested in it), plain
    fusions. And a second top-level ``while`` of ``band`` trips that no
    segment encloses (an unrolled builder's sweep: not this reader's)."""
    events = [(0, 100, hlo("transpose.1", "fusion"))]
    at = 200
    seg_start = at
    at += 10
    panel_ns = 0
    for step in range(2):
        # column sweep: each column step is a fusion and a nested dot loop
        sweep_start = at
        inner, t = [], at + 5
        for col in range(band):
            inner.append((t, t + 20, hlo("column.7")))
            t += 20
            nested, t = loop("dotpass.9", t, 3, 5, children=("dot",))
            inner += nested
        sweep_end = t + 5
        events.append((sweep_start, sweep_end,
                       hlo("while.11", "while", "(u32[], f32[64,4]{0,1})")))
        events += inner
        panel_ns += sweep_end - sweep_start
        at = sweep_end + 10
        inv, end = loop("while.12", at, band, 7)
        events += inv
        panel_ns += end - at
        at = end + 10
        scan, end = loop("while.13", at, 7, 30, children=("convolution",))
        events += scan
        at = end + 10
        # lax.map over two row chunks, a band-trip loop nested in it
        map_start = at
        body, t = [], at + 5
        for chunk in range(2):
            nested, t = loop("while.15", t, band, 11)
            body += nested
            body.append((t, t + 40, hlo("update.16")))
            t += 40
        events.append((map_start, t + 5, hlo("while.14", "while")))
        events += body
        at = t + 15
        events.append((at, at + 50, hlo("subtract.17")))
        at += 60
    seg_end = at
    events.append((seg_start, seg_end, hlo("while.10", "while")))
    top, end = loop("while.20", seg_end + 100, band, 9)
    events += top
    return events, panel_ns, end


class FakeProfile:
    """What ``jax.profiler.ProfileData.from_file`` gives, as far as the
    readers look: planes with lines with events."""

    class Item:
        def __init__(self, **kw):
            self.__dict__.update(kw)

    def __init__(self, device_events, host_events, plane="/device:TPU:0"):
        def line(name, events):
            return self.Item(name=name, events=[
                self.Item(start_ns=s, duration_ns=e - s, name=n)
                for s, e, n in events])

        self.planes = [
            self.Item(name=plane, lines=[line("XLA Ops", device_events),
                                         line("XLA Modules", [])]),
            self.Item(name="/host:CPU", lines=[line("main", host_events)])]


def shifted(events, by):
    return [(s + by, e + by, n) for s, e, n in events]


def synthetic_run(tmp_path, monkeypatch, band=4, with_counter=True,
                  events=None, calls=1, cut=None):
    """A run as ``run.py`` hands it to a reader, the xplane a hand-made
    one: ``calls`` executions of the synthetic program, one a
    ``bench_call`` span; device events that end after ``cut`` are lost
    (the profiler's event limit: an enclosing loop goes with them, the
    operations inside that ended in time stay)."""
    import jax.profiler

    import span_reduce
    import trace_reduce

    made, panel_ns, end = synthetic_events(band)
    events = made if events is None else events
    period = end + 100
    device, host = [], []
    for c in range(calls):
        device += shifted(events, c * period)
        host.append((c * period, (c + 1) * period - 1, span_reduce.CALL))
    window = (0, calls * period)
    host.append((*window, span_reduce.WINDOW))
    if cut is not None:
        device = [ev for ev in device if ev[1] <= cut]
    plane = "/device:TPU:0"
    xplane = tmp_path / "trace" / "t.xplane.pb"
    xplane.parent.mkdir(exist_ok=True)
    xplane.write_bytes(b"")
    monkeypatch.setenv("DLAF_METRICS_PATH", str(tmp_path / "metrics.jsonl"))
    monkeypatch.setattr(
        jax.profiler, "ProfileData", FakeProfile.Item(
            from_file=lambda path: FakeProfile(device, host, plane)))
    span_reduce.load.cache_clear()
    reduced = trace_reduce.reduce_trace({plane: list(device)}, [], window)
    counters = [{"name": "dlaf_red2band_panel_columns_total",
                 "kind": "counter", "labels": {"form": "scan"},
                 "value": 2.0 * band}] if with_counter else []
    run = {"trace": reduced, "traced_calls": calls, "counters": counters,
           "config": {"n": 8192, "args": {"band_size": band}},
           "device": {"kind": "TPU v5 lite"}}
    return run, panel_ns, reduced


def test_readers_on_a_synthetic_trace(tmp_path, monkeypatch):
    run, panel_ns, reduced = synthetic_run(tmp_path, monkeypatch)
    dev = reduced["devices"][reduced["worst_device"]]
    own_ns = sum(dev["classes"].values())
    # every nanosecond an operation covers is some operation's own
    assert own_ns == dev["busy_ns"]
    share = reader("panel_time_share").read(run, "panel_time_share")
    assert share == pytest.approx(100.0 * panel_ns / own_ns)
    assert 0 < share < 100
    found = run["panel_sweep"]
    # two steps: a sweep and an inversion each; not the shift-group scan,
    # not the row-chunk map, not the loop nested in it, not the column
    # steps' nested loops, not the top-level loop no segment encloses
    assert found == {"panel_ns": panel_ns, "own_ns": own_ns, "loops": 4,
                     "calls": 1}
    us = reader("panel_column_us").read(run, "panel_column_us")
    assert us == pytest.approx(panel_ns / 8 / 1e3)
    hbm = reader("panel_hbm_share").read(run, "panel_hbm_share")
    moved = reader("panel_hbm_share").panel_bytes(8192, 4)
    assert hbm == pytest.approx(100.0 * moved / (panel_ns / 1e9) / 819e9)


def test_readers_count_complete_calls_only(tmp_path, monkeypatch):
    """Three calls, the trace cut off in the second (the device's event
    limit): per call the numbers are the first call's, whatever the
    harness's window says; with all three whole, the same."""
    whole, panel_ns, _ = synthetic_run(tmp_path, monkeypatch, calls=3)
    share = reader("panel_time_share").read(whole, "panel_time_share")
    assert whole["panel_sweep"]["calls"] == 3
    assert whole["panel_sweep"]["panel_ns"] == 3 * panel_ns
    us = reader("panel_column_us").read(whole, "panel_column_us")
    _made, _panel, end = synthetic_events()
    cut, _, _ = synthetic_run(tmp_path, monkeypatch, calls=3,
                              cut=end + 100 + end // 2)
    assert reader("panel_time_share").read(cut, "panel_time_share") \
        == pytest.approx(share)
    assert cut["panel_sweep"]["calls"] == 1
    assert cut["panel_sweep"]["panel_ns"] == panel_ns
    assert reader("panel_column_us").read(cut, "panel_column_us") \
        == pytest.approx(us)
    assert us == pytest.approx(panel_ns / 8 / 1e3)


def test_a_loop_of_band_minus_one_trips_is_the_t_factors(tmp_path,
                                                          monkeypatch):
    """The triangular inverse loops over ``band - 1`` rows: the same trace
    read with a band one larger counts the inversions' loops alone... and
    none at a band no loop matches."""
    run, panel_ns, _ = synthetic_run(tmp_path, monkeypatch, band=4)
    import panel_sweep as pts
    events, _, _ = synthetic_events(4)
    assert len(pts.panel_loops(events, 4)) == 4
    assert len(pts.panel_loops(events, 5)) == 4      # 4 = 5 - 1 trips
    assert pts.panel_loops(events, 9) == []
    assert len(pts.panel_loops(events, 7)) == 2      # the shift-group scans


def test_readers_return_nothing_where_there_is_nothing_to_read(
        tmp_path, monkeypatch):
    # no trace at all (a CPU run), no device plane
    for run in ({}, {"trace": None}, {"trace": {"devices": {}}}):
        for name in METRICS:
            assert reader(name).read(dict(run), name) is None
    # the parent: the trace is there, the column counter is not
    run, _panel_ns, _reduced = synthetic_run(tmp_path, monkeypatch,
                                             with_counter=False)
    assert reader("panel_column_us").read(run, "panel_column_us") is None
    assert reader("panel_time_share").read(run, "panel_time_share") > 0
    # another program: no segment loop, so no panel loop
    flat = [(0, 100, hlo("fusion.1")), (100, 300, hlo("copy.2", "copy"))]
    run, _panel_ns, _reduced = synthetic_run(tmp_path, monkeypatch,
                                             events=flat)
    for name in METRICS:
        assert reader(name).read(run, name) is None
    # a device kind without peaks
    run, _panel_ns, _reduced = synthetic_run(tmp_path, monkeypatch)
    run["device"] = {"kind": "TPU v9"}
    assert reader("panel_hbm_share").read(run, "panel_hbm_share") is None


# ---------------------------------------------------------------------------
# the op file and the command
# ---------------------------------------------------------------------------

@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout-shaped directory: the committed BENCHMARK.json, this
    cell's configuration cut to 33 panels of 16, the traffic with a short
    traced window."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"),
                tmp_path / "BENCHMARK.json")
    entry = by_name(committed()["configs"], CONFIG)
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    config.update(TINY)
    dst = tmp_path / entry["file"]
    dst.parent.mkdir(parents=True)
    dst.write_text(json.dumps(config))
    tdir = tmp_path / "benchmark" / "traffic"
    tdir.mkdir(parents=True)
    traffic = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "traffic", "scf_closed_loop.json"))
    traffic["traced_window"].update(min_seconds=0.2)
    (tdir / "scf_closed_loop.json").write_text(json.dumps(traffic))
    return str(tmp_path)


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """A TPU's knob resolution on this CPU (tests/conftest.py:as_on_tpu):
    33 panels then take the scan builder, as the cell's 63 do on the
    chip."""
    import jax

    import dlaf_tpu.config as C
    from dlaf_tpu import obs

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    obs._reset_for_tests()      # a run is a process: an empty registry
    C._clear_program_caches()
    yield
    monkeypatch.undo()
    obs._reset_for_tests()
    C.finalize()
    C.initialize()
    C._clear_program_caches()


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_command_runs_the_scan_form(trace, tiny_root, on_cpu, as_on_tpu,
                                    capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483813",
                         "--seconds", "0.3", "--trace", str(trace)],
                        root=tiny_root)
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(m) == {"call_s", "residual_digits", "peak_hbm_gib",
                          "setup_s"}
        assert m["residual_digits"] > 11
        return
    assert line["attempted"] >= 3
    # a CPU trace has no device plane: the three panel readers find
    # nothing and are left out, the counter readers read
    assert not set(METRICS) & set(m)
    assert {"first_call_s", "cache_misses"} <= set(m)
    from dlaf_tpu import obs

    snap = {(x["name"], tuple(sorted(x["labels"].items()))): x["value"]
            for x in obs.registry().snapshot() if x.get("kind") == "counter"}
    assert snap["dlaf_red2band_steps_total", (("form", "scan"),)] == 33
    assert snap["dlaf_red2band_bodies_total", (("form", "scan"),)] == 5
    assert snap["dlaf_red2band_panel_columns_total",
                (("form", "scan"),)] == 33 * 16
    calls = snap["dlaf_entry_calls_total",
                 (("entry", "reduction_to_band"),)]
    assert snap["dlaf_entry_programs_total",
                (("entry", "reduction_to_band"),)] == 3 * calls


def test_a_float32_grade_reduction_is_not_correct(tiny_root, on_cpu,
                                                  as_on_tpu, capsys,
                                                  monkeypatch):
    """The result rounded to float32 fails the double-precision limit."""
    real = bench_run.load_module

    def load(kind, name):
        mod = real(kind, name)
        if kind == "ops":
            host = mod.host
            mod.host = lambda out: tuple(
                x.astype(np.float32).astype(np.float64) for x in host(out))
        return mod

    monkeypatch.setattr(bench_run, "load_module", load)
    bench_run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                    "--trace", "0"], root=tiny_root)
    line = last_line(capsys)
    assert line["correct"] is False
    assert line["metrics"]["residual_digits"]["value"] < 9
