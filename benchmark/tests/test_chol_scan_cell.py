"""The cell ``chol_d_n16384_1x1`` (PR 31): its reader on hand-made counters,
its op file's refusal, and its untraced and traced command on the CPU at a
small size, 32 block steps as on the chip, under a TPU's knob resolution
(so the scan builder and the slice products are what runs). Entries of
BENCHMARK.json are found by name, never by position."""

import json
import os
import shutil

import pytest

import run as bench_run
from test_command import last_line, on_cpu      # noqa: F401

ROOT = bench_run.ROOT
CELL = "chol_d_n16384_1x1"
CONFIG = "chol-d-n16384-nb512"
METRIC = "masked_mac_share"
#: 32 block steps of 16: the step count of the chip's shape (16384 / 512)
TINY = {"n": 512, "nb": 16}
SLICES = 7


def read(run):
    return bench_run.load_module("layer_metrics", METRIC).read(run, METRIC)


def counter(name, value, **labels):
    return {"name": name, "kind": "counter", "labels": labels,
            "value": float(value)}


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
    entry = by_name(bench["configs"], CONFIG)
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert (config["op"], config["dtype"], config["n"], config["nb"],
            config["grid"], config["args"]) == (
        "cholesky_scan", "float64", 16384, 512, [1, 1], {"uplo": "L"})
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == ["grid", "n"]
    # one chip's share of the north star's flops, as the file states
    assert 65536 ** 3 // 64 == config["n"] ** 3
    assert config["guarantee"]["c"] == 60.0
    small = bench_run.load_json(os.path.join(
        ROOT, by_name(bench["configs"], "chol-d-n4096-nb256")["file"]))
    assert config["guarantee"] == small["guarantee"]


def test_the_metric_is_listed_for_this_cell_only():
    metric = by_name(committed()["per_layer"], METRIC)
    assert metric == {
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "program_counter",
        "layer": "step builders and precision routes", "moves": "call_s",
        "workloads": [CELL]}
    cell = bench_run.load_cell(ROOT, CELL)
    assert METRIC in {m["name"] for m in cell["per_layer"]}
    other = bench_run.load_cell(ROOT, "chol_d_n4096_1x1")
    assert METRIC not in {m["name"] for m in other["per_layer"]}


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------

def test_reader_forms_masked_over_all_macs_summed_over_routes():
    snap = [counter("dlaf_ozaki_macs_total", 600, route="scan", kind="real"),
            counter("dlaf_ozaki_macs_total", 200, route="scan", kind="zero"),
            counter("dlaf_ozaki_macs_total", 200, route="dots", kind="real"),
            counter("dlaf_ozaki_masked_macs_total", 150, route="scan"),
            counter("dlaf_ozaki_masked_macs_total", 100, route="dots"),
            counter("dlaf_ozaki_mirror_total", 4, route="scan"),
            {"name": "dlaf_span_seconds", "kind": "histogram",
             "labels": {"span": "stage.fence"}, "count": 3, "sum": 0.01}]
    assert read({"counters": snap}) == pytest.approx(25.0)


@pytest.mark.parametrize("run", [
    {}, {"counters": None}, {"counters": []},
    # the parent: slice products counted, nothing masked
    {"counters": [counter("dlaf_ozaki_macs_total", 5, route="scan",
                          kind="real")]},
    {"counters": [counter("dlaf_ozaki_masked_macs_total", 0,
                          route="scan")]}])
def test_reader_returns_nothing_without_its_counters(run):
    assert read(run) is None


def hand_share(nt, nb, s, chunk, chunk_at):
    """Masked over all multiply-accumulates of the look-ahead scan form, in
    percent, by hand: per executed step a panel product and a strip product
    ((m, nb) outputs at the padded depth s^2 nb; live: the rows below the
    pivot, the stored trapezoid of the next block column) and the bulk of
    the step before (none in the first body): one (m, m) padded syrk at
    depth 4 s nb below ``chunk_at`` rows, else ragged trapezoids of
    ``chunk`` columns from their own diagonal down at depth s (s + 1) / 2
    nb; live: the stored triangle past the pivot. Segments of eight steps
    on the shrinking trailing block."""
    all_macs = masked = 0
    pad, syrk, ragged = s * s * nb, 4 * s * nb, s * (s + 1) // 2 * nb
    off = 0
    while off < nt:
        seg = min(8, nt - off)
        m = (nt - off) * nb
        for k in range(seg):
            lo = (k + 1) * nb
            first = off == 0 and k == 0
            all_macs += 2 * m * nb * pad
            masked += lo * nb * pad
            masked += (m * nb - sum(m - j for j in
                                    range(lo, min(lo + nb, m)))) * pad
            cols = ([(0, m)] if m < chunk_at else
                    [(c, min(c + chunk, m)) for c in range(0, m, chunk)])
            for c0, c1 in cols:
                out = m * m if m < chunk_at else (m - c0) * (c1 - c0)
                live = 0 if first else sum(
                    m - j for j in range(max(c0, lo), c1))
                depth = syrk if m < chunk_at else ragged
                all_macs += out * depth
                masked += (out - live) * depth
        off += seg
    return 100.0 * masked / all_macs


#: ``hand_share`` of the chip's shape, and of the same shape with the one
#: full square a step that PR 31's chunks replaced
CHIP_SHARE = 48.42241923014323
UNCHUNKED_SHARE = 63.42680636319247


def test_hand_count_of_the_chips_shape():
    """N=16384, nb=512, seven slices, chunks of 4096 from 8192 rows on:
    what the traced run on the chip has to read (PERF.md section 5)."""
    assert hand_share(32, 512, 7, 4096, 8192) == pytest.approx(
        CHIP_SHARE, abs=1e-9)
    # the full squares this PR's chunks replaced, for the record
    assert hand_share(32, 512, 7, 4096, 10 ** 9) == pytest.approx(
        UNCHUNKED_SHARE, abs=1e-9)


# ---------------------------------------------------------------------------
# the op file and the command
# ---------------------------------------------------------------------------

@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout-shaped directory: the committed BENCHMARK.json, this
    cell's configuration cut to 32 steps of 16, the traffic with a short
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
    the step count then picks the scan builder at 32 steps and the
    products are slice products, as in the cell's program on the chip."""
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


def test_op_refuses_a_tree_whose_local_route_is_not_the_scan_form(
        tiny_root, on_cpu, capsys, monkeypatch):
    """On the CPU's own resolution 32 steps stay unrolled (it switches at
    128): the op refuses before it makes an input, with a message and a
    non-zero exit; so it does where the library cannot be asked at all
    (the parent commit)."""
    import dlaf_tpu.config as C

    C.initialize()
    argv = ["--workload", CELL, "--seed", "2147483801", "--seconds", "0.2",
            "--trace", "0"]
    with pytest.raises(SystemExit) as exc:
        bench_run.main(argv, root=tiny_root)
    assert "unrolled builder at 32 block steps" in str(exc.value.code)
    assert "{" not in capsys.readouterr().out       # no result line
    op = bench_run.load_module("ops", "cholesky_scan")
    monkeypatch.setattr(op, "local_step_form", lambda steps: None)
    with pytest.raises(SystemExit):
        op.build(dict(TINY), 1, None)


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_command_runs_the_scan_form_and_reads_the_masked_share(
        trace, tiny_root, on_cpu, as_on_tpu, capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483803",
                         "--seconds", "0.3", "--trace", str(trace)],
                        root=tiny_root)
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    cell = bench_run.load_cell(tiny_root, CELL)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(m) >= {"call_s", "residual_digits", "peak_hbm_gib",
                          "setup_s"}
        assert "call_p90_s" not in m          # the cell does not list it
        return
    assert line["attempted"] >= 3
    listless = {x["name"] for x in cell["per_layer"]
                if "workloads" not in x}
    # every list-less reader has something to read in this cell (device
    # lines are the chip's; on the CPU the trace has none)
    assert listless - set(m) <= {"device_idle_share", "device_busy_s",
                                 "matmul_time_share", "launch_gap_share"}
    assert {"first_call_s", "cache_misses", "ozaki_zero_mac_share"} <= set(m)
    assert line["metrics"][METRIC]["unit"] == "%"
    assert m[METRIC] == pytest.approx(
        hand_share(32, TINY["nb"], SLICES, 4096, 8192), rel=1e-12)
    # which builder, how many bodies: the run says so
    from dlaf_tpu import obs

    snap = {(x["name"], tuple(sorted(x["labels"].items()))): x["value"]
            for x in obs.registry().snapshot() if x.get("kind") == "counter"}
    assert snap["dlaf_cholesky_bodies_total",
                (("algo", "cholesky_scan"),)] == 4
    assert snap["dlaf_cholesky_steps_total",
                (("algo", "cholesky_scan"), ("mode", "overlapped"))] == 32
    calls = snap["dlaf_entry_calls_total", (("entry", "cholesky"),)]
    assert snap["dlaf_entry_programs_total",
                (("entry", "cholesky"),)] == calls
