"""The cell ``bt_b2t_d_n4096_1x1`` (PR 39): its entries and files, its two
counter readers against hand counts, the op's two checks fed the plain
reference in float64 and in float32, and its untraced and traced command on
the CPU at a small size (n = 256, band 16, tile 64, under a TPU's knob
resolution so the group is the band). Entries of BENCHMARK.json are found by
name, never by position. Nothing here is a device measurement."""

import json
import os
import shutil

import numpy as np
import pytest

import arith
import run as bench_run
from test_command import last_line, on_cpu      # noqa: F401

ROOT = bench_run.ROOT
CELL = "bt_b2t_d_n4096_1x1"
CONFIG = "bt-b2t-d-n4096-nb512-b128"
SB = "step builders and precision routes"
ENTRIES = [
    ("phase_ms.stair", "ms", "device_trace", SB),
    ("phase_ms.tfactor", "ms", "device_trace", SB),
    ("phase_ms.project", "ms", "device_trace", SB),
    ("phase_ms.apply", "ms", "device_trace", SB),
    ("phase_s.bt_band_to_tridiag.upload", "s", "program_span",
     "entry points"),
    ("bt_levels_per_call", "count", "program_counter", SB),
    ("bt_null_reflector_share", "%", "program_counter", SB),
]
TINY = {"n": 256, "nb": 64, "args": {"band_size": 16, "evec_cols": 256}}


def reader(name):
    return bench_run.load_module("layer_metrics", name)


def committed():
    return bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def hand_count(n, b, group):
    return reader("bt_null_reflector_share").hand_count(n, b, group)


# ---------------------------------------------------------------------------
# the entries
# ---------------------------------------------------------------------------

def test_the_cell_and_its_configuration_are_committed():
    bench = committed()
    cell = by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "scf_closed_loop", 1)
    assert len(cell["why"]) <= 200 and "null" in cell["why"]
    entry = by_name(bench["configs"], CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "miniapp_bt_band_to_tridiag.cpp" in entry["source"]
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert set(config) == {
        "op", "dtype", "n", "nb", "grid", "args", "source", "reduced",
        "published", "reduced_why", "deployment", "assumed",
        "configuration", "guarantee"}
    assert config["source"] == entry["source"]
    assert (config["op"], config["dtype"], config["n"], config["nb"],
            config["grid"], config["args"]) == (
        "bt_band_to_tridiag", "float64", 4096, 512, [1, 1],
        {"band_size": 128, "evec_cols": 4096})
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == sorted(config["reduced_why"]) == ["grid", "n"]
    # the published block and band, the order cut
    assert config["published"]["n"] == 16384
    assert config["published"]["band"] == config["args"]["band_size"]
    assert config["published"]["nb"] == config["nb"]
    assert set(config["assumed"]) == {"evec_cols", "input", "defaults"}
    guarantee = config["guarantee"]
    assert guarantee["c"] == 100.0
    assert guarantee["eps_tpu"] == 2.0 ** -47
    assert guarantee["eps_native"] == 2.0 ** -52
    assert "chase_reflectors.py" in guarantee["reference"]
    assert arith.tolerance(guarantee, 4096, "tpu") \
        == pytest.approx(2.9e-9, rel=0.01)
    # no other configuration claims this source or this file
    assert [c["name"] for c in bench["configs"]
            if c["source"] == entry["source"]
            or c["file"] == entry["file"]] == [CONFIG]
    loaded = bench_run.load_cell(ROOT, CELL)
    assert loaded["config"] == config and loaded["chips"] == 1
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "call_s", "residual_digits", "peak_hbm_gib", "setup_s"}
    assert bench_run.load_module("ops", config["op"]).flops(config) \
        == 2.0 * 4096 ** 3
    # half of the cells may take four chips: three of six
    assert len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2


@pytest.mark.parametrize("name, unit, source, layer", ENTRIES)
def test_the_metrics_are_listed_for_this_cell_only(name, unit, source,
                                                   layer):
    assert by_name(committed()["per_layer"], name) == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": "call_s", "workloads": [CELL]}
    assert callable(reader(name).read)
    assert name in {m["name"] for m in
                    bench_run.load_cell(ROOT, CELL)["per_layer"]}
    for other in ("chol_d_n4096_1x1", "chol_d_n16384_1x1",
                  "trsm_d_n8192_2x2", "red2band_d_n8192_1x1",
                  "chol_d_n4096_2x2"):
        assert name not in {m["name"] for m in
                            bench_run.load_cell(ROOT, other)["per_layer"]}


def test_new_entries_follow_the_accepted_ones_in_order():
    names = [m["name"] for m in committed()["per_layer"]]
    mine = [e[0] for e in ENTRIES]
    at = [names.index(n) for n in mine]
    assert at == sorted(at) and at[0] > names.index("comm_overlapped_share")
    assert [w["name"] for w in committed()["workloads"]][-1] == CELL
    assert [c["name"] for c in committed()["configs"]][-1] == CONFIG


def test_the_cell_reads_the_entries_that_list_no_cells():
    """``ozaki_zero_mac_share`` and the device's and set-up's entries have
    no ``workloads`` list: the cell reports them."""
    names = {m["name"] for m in bench_run.load_cell(ROOT, CELL)["per_layer"]}
    assert {"device_idle_share", "device_busy_s", "matmul_time_share",
            "first_call_s", "cache_misses", "launch_gap_share",
            "ozaki_zero_mac_share"} <= names
    # entries that list other cells stay as they are
    assert not {"phase_ms.unattributed", "program_temp_gib",
                "program_code_mib", "entry_programs_per_call"} & names


# ---------------------------------------------------------------------------
# the hand counts and the two counter readers
# ---------------------------------------------------------------------------

def test_hand_counts_of_the_chips_shape():
    """4094 sweeps in 32 groups of 128 at 32 steps: 1024 levels; sweep s
    has ceil((4095 - s) / 128) live reflectors."""
    levels, live, null = hand_count(4096, 128, 128)
    assert levels == 32 * 32 == 1024
    assert live == sum(-(-(4095 - s) // 128) for s in range(4094)) == 67551
    assert live + null == 1024 * 128 and null == 63521
    # the sweeps form multiplies the uniform layout as the chase left it
    assert hand_count(4096, 128, 0) == (4094, 67551, 4094 * 32 - 67551)
    # the reduction cell's order: four times the levels
    assert hand_count(8192, 128, 128)[0] == 4096
    assert hand_count(2, 128, 128) == (0, 0, 0)


def counters(levels, live, null, impl="blocked"):
    def c(name, value, **labels):
        return {"name": name, "kind": "counter", "labels": labels,
                "value": float(value)}
    return [c("dlaf_bt_b2t_levels_total", levels, impl=impl),
            c("dlaf_bt_b2t_reflectors_total", live, impl=impl, kind="live"),
            c("dlaf_bt_b2t_reflectors_total", null, impl=impl, kind="null"),
            c("dlaf_entry_calls_total", 5, entry="bt_band_to_tridiag")]


def test_readers_against_the_hand_counts():
    levels, live, null = hand_count(4096, 128, 128)
    run = {"counters": counters(levels, live, null)}
    assert reader("bt_levels_per_call").read(run, "bt_levels_per_call") \
        == 1024.0
    share = reader("bt_null_reflector_share").read(
        run, "bt_null_reflector_share")
    assert share == 100.0 * 63521 / 131072 == pytest.approx(48.4627, abs=1e-4)
    # the parent: no such counter, nothing to read, nothing raised
    for run in ({}, {"counters": None}, {"counters": []},
                {"counters": counters(1, 1, 1)[3:]}):
        for name in ("bt_levels_per_call", "bt_null_reflector_share"):
            assert reader(name).read(run, name) is None


def test_the_library_counts_what_the_hand_count_says():
    from dlaf_tpu.eigensolver.back_transform import chase_reflector_slots

    for n, b, g in ((4096, 128, 128), (8192, 128, 128), (257, 32, 32),
                    (130, 16, 7), (96, 8, 0), (3, 4, 4)):
        sweeps, steps = n - 2, -(-(n - 1) // b)
        assert chase_reflector_slots(n, b, sweeps, steps, g) \
            == hand_count(n, b, g), (n, b, g)


# ---------------------------------------------------------------------------
# the op file and the command
# ---------------------------------------------------------------------------

@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout-shaped directory: the committed BENCHMARK.json, this
    cell's configuration cut to n = 256, band 16, the traffic with a short
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
    """A TPU's knob resolution on this CPU (tests/conftest.py:as_on_tpu),
    and the device's own answer to ``bt_b2t_group`` auto: G = the band."""
    import jax

    import dlaf_tpu.config as C
    from dlaf_tpu import obs, tpu_info
    from dlaf_tpu.types import Device

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tpu_info, "default_device", lambda: Device.TPU)
    obs._reset_for_tests()      # a run is a process: an empty registry
    C._clear_program_caches()
    yield
    monkeypatch.undo()
    obs._reset_for_tests()
    C.finalize()
    C.initialize()
    C._clear_program_caches()


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_command_runs_the_blocked_form(trace, tiny_root, on_cpu, as_on_tpu,
                                       capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483913",
                         "--seconds", "0.3", "--trace", str(trace)],
                        root=tiny_root)
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(m) == {"call_s", "residual_digits", "peak_hbm_gib",
                          "setup_s"}
        assert m["residual_digits"] > 12
        return
    assert line["attempted"] >= 3
    # a CPU trace has no device plane: the phase readers find nothing and
    # are left out; the span and counter readers read
    assert not {e[0] for e in ENTRIES[:4]} & set(m)
    assert {"first_call_s", "cache_misses",
            "phase_s.bt_band_to_tridiag.upload"} <= set(m)
    levels, live, null = hand_count(256, 16, 16)
    assert m["bt_levels_per_call"] == levels == 16 * 16
    assert m["bt_null_reflector_share"] == 100.0 * null / (live + null)
    from dlaf_tpu import obs

    snap = {(x["name"], tuple(sorted(x["labels"].items()))): x["value"]
            for x in obs.registry().snapshot() if x.get("kind") == "counter"}
    calls = snap["dlaf_entry_calls_total",
                 (("entry", "bt_band_to_tridiag"),)]
    assert snap["dlaf_entry_programs_total",
                (("entry", "bt_band_to_tridiag"),)] == 3 * calls


def test_the_parent_reads_nothing_and_does_not_raise(tiny_root, on_cpu,
                                                     as_on_tpu, capsys,
                                                     monkeypatch):
    """A tree without this PR's counters, spans and telemetry site: the
    traced line leaves the seven metrics out."""
    import contextlib
    import importlib

    from dlaf_tpu import obs

    bt = importlib.import_module("dlaf_tpu.eigensolver.back_transform")
    monkeypatch.setattr(bt, "_count_slots", lambda *a, **kw: None)
    monkeypatch.setattr(bt, "_local_phase",
                        lambda *a, **kw: contextlib.nullcontext())
    monkeypatch.setattr(obs.telemetry, "call",
                        lambda site, fn, *a, **kw: fn(*a, **kw))
    rc = bench_run.main(["--workload", CELL, "--seed", "7", "--seconds",
                         "0.2", "--trace", "1"], root=tiny_root)
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True
    assert not {e[0] for e in ENTRIES} & set(line["metrics"])
    assert "first_call_s" in line["metrics"]


def test_the_checks_tell_float64_from_float32(tiny_root):
    """The plain reference in float64 passes both checks at a TPU's limit;
    computed in float32 it fails the sampled comparison (and the Gram
    check)."""
    op = bench_run.load_module("ops", "bt_band_to_tridiag")
    from reference import chase_reflectors as ref

    config = bench_run.load_cell(tiny_root, CELL)["config"]
    import jax

    state = op.build(config, 11, jax.devices()[:1])
    tri, band = state["tri"], state["band"]
    e = np.asarray(state["ref"].to_numpy())
    tol = arith.tolerance(config["guarantee"], config["n"], "tpu")
    assert len(state["cols"]) == op.SAMPLE == 64
    good = op.check(state, ref.apply_q(tri.v, tri.tau, e, band))
    assert len(good) == 2 and max(good.values()) <= tol / 100
    low = op.check(state, ref.apply_q(
        tri.v, tri.tau, e, band, dtype=np.float32).astype(np.float64))
    sample, gram = low.values()
    assert sample > tol and gram > tol
    # a wrong column outside the sample: the Gram check alone catches it
    out = ref.apply_q(tri.v, tri.tau, e, band)
    miss = next(j for j in range(config["n"]) if j not in state["cols"])
    out[:, miss] *= 1 + 1e-5
    sample, gram = op.check(state, out).values()
    assert sample <= tol / 100 and gram > tol
