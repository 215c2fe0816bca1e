"""The cell ``red2band_d_n16384_2x2``: its entries and files, its op file's
refusal, its assembly of the global matrix from the shards and its checks
on hand-made results (the plain reference's reduction, the block-cyclic map
of it, and the same with a float32-grade result, two chips' shards swapped,
one chip missing or one chip's taus perturbed), and its untraced and
traced command on the CPU's four virtual devices at a small size under a
TPU's knob resolution (32 panels of 32 columns: the scan-form distributed
builder is what runs, as the cell's 127 panels take it on the chip).
Entries of BENCHMARK.json are found by name, never by position. Nothing
here is a device measurement."""

import json
import os
import shutil

import numpy as np
import pytest

import run as bench_run
from test_chol_scan_cell import as_on_tpu       # noqa: F401
from test_command import last_line, on_cpu      # noqa: F401

ROOT = bench_run.ROOT
CELL = "red2band_d_n16384_2x2"
CONFIG = "red2band-d-n16384-nb512-b128-2x2"
NEW_METRICS = {
    "phase_s.reduction_to_band.dispatch": ("s", "program_span",
                                           "entry points"),
}
#: the scopes are in the program, their entries held back: one call's device
#: events may not fit the profiler's trace (PERF.md section 7)
HELD = ("phase_ms.gather", "phase_ms.exchange")
#: 32 panels of 32 columns on 9 tiles of 128 (the last one 32 wide): the
#: step count takes the scan form unasked (from 32 on a TPU), band < nb
TINY = {"n": 1056, "nb": 128, "args": {"band_size": 32}}
TAUS = "max|taus(chip) - taus(0,0)|/max|taus|"


def committed():
    return bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def op():
    return bench_run.load_module("ops", "reduction_to_band_dist")


# ---------------------------------------------------------------------------
# the entries
# ---------------------------------------------------------------------------

def test_the_cell_resolves_to_its_configuration_op_and_readers():
    cell = bench_run.load_cell(ROOT, CELL)
    assert cell["chips"] == 4
    config = cell["config"]
    assert (config["op"], config["dtype"], config["n"], config["nb"],
            config["grid"], config["args"]) == (
        "reduction_to_band_dist", "float64", 16384, 512, [2, 2],
        {"band_size": 128})
    mod = op()
    assert all(hasattr(mod, f) for f in ("build", "fresh", "call", "host",
                                         "check", "flops"))
    assert mod.flops(config) == 4.0 * 16384 ** 3 / 3.0
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= names and not set(HELD) & names
    for m in cell["per_layer"]:
        assert callable(bench_run.load_module("layer_metrics",
                                              m["name"]).read)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "call_s", "residual_digits", "peak_hbm_gib", "setup_s"}
    assert cell["traffic"] == bench_run.load_cell(
        ROOT, "red2band_d_n8192_1x1")["traffic"]


def test_the_configuration_file_and_its_entry_agree():
    bench = committed()
    cell = by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "scf_closed_loop", 4)
    assert len(cell["why"]) <= 200
    entry = by_name(bench["configs"], CONFIG)
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] and len(entry["source"]) < 200
    assert entry["reduced"] == config["reduced"] == ["grid"]
    # the published row verbatim but for the grid
    published = config["published"]
    assert (published["n"], published["nb"], published["band"]) == (
        config["n"], config["nb"], config["args"]["band_size"])
    assert published["dtype"] == config["dtype"]
    one_chip = bench_run.load_json(os.path.join(
        ROOT, by_name(bench["configs"], "red2band-d-n8192-nb512-b128")[
            "file"]))
    assert config["assumed"]["input"].startswith(
        one_chip["assumed"]["input"].split(":")[0])
    for key in ("c", "eps_tpu", "eps_native"):
        assert config["guarantee"][key] == one_chip["guarantee"][key]
    assert "local_slot" in config["guarantee"]["what"]
    # at most half the cells, rounded down, take four chips (three of
    # seven when this cell came)
    fours = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert CELL in fours
    assert len(fours) <= len(bench["workloads"]) // 2


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_metrics_are_listed_for_this_cell_only(name):
    unit, source, layer = NEW_METRICS[name]
    assert by_name(committed()["per_layer"], name) == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": "call_s", "workloads": [CELL]}
    for other in ("red2band_d_n8192_1x1", "chol_d_n4096_2x2"):
        assert name not in {m["name"] for m in
                            bench_run.load_cell(ROOT, other)["per_layer"]}


def test_the_span_and_phase_readers_are_data_only():
    """The entry and the two held back are read by readers that were there:
    the phase reader takes any phase of the split, the span reader any
    ``stage.<x>`` the host-span filter keeps."""
    read = bench_run.load_module("layer_metrics", "phase_ms").read
    split = {"phase_split": {"phases": {"gather": 1.5, "exchange": 2.5}}}
    assert read(split, "phase_ms.gather") == 1.5
    assert read(split, "phase_ms.exchange") == 2.5
    assert read({"phase_split": None}, "phase_ms.exchange") is None
    import span_reduce

    span = "stage.reduction_to_band.dispatch"
    assert span.startswith(span_reduce.HOST_PREFIXES)
    spans = [(0, 100, "bench_call"), (10, 30, span),
             (200, 300, "bench_call"), (210, 250, span)]
    assert span_reduce.median_wall_per_call(spans, span) == \
        pytest.approx(30e-9)


# ---------------------------------------------------------------------------
# the op file on hand-made results
# ---------------------------------------------------------------------------

SMALL = {"n": 96, "nb": 16, "band": 8}


@pytest.fixture(scope="module")
def made():
    """The plain reference's reduction of a small seeded input, its taus,
    and the state the op's check reads (what ``build`` would hold)."""
    ref = bench_run.load_module("reference", "band_reduction")
    n, nb, band = SMALL["n"], SMALL["nb"], SMALL["band"]
    seed = 2147483777
    g = np.random.default_rng(seed).standard_normal((n, n))
    a = (g + g.T) / 2
    red, taus = ref.reduce_to_band(a, band)
    state = {"a": a, "lam": np.linalg.eigvalsh(a), "band": band,
             "seed": seed, "nb": nb, "grid": (2, 2), "last": None}
    return {"state": state, "red": red, "taus": taus, "ref": ref}


def _shards(red):
    """What every rank holds of ``red`` by the block-cyclic map."""
    cyclic = bench_run.load_module("reference", "cholesky_block_cyclic")
    return {r: cyclic.local_tiles(red, SMALL["nb"], (2, 2), r)
            for r in np.ndindex(2, 2)}


def _got(made, red=None, taus=None, shards=None):
    """``host``'s dict for a result: the global matrix assembled from the
    shards (by default the block-cyclic map of ``red``), every rank the
    same taus."""
    red = made["red"] if red is None else red
    taus = made["taus"] if taus is None else taus
    shards = _shards(red) if shards is None else shards
    return {"red": op().assemble(shards, SMALL["nb"], (2, 2), red.shape),
            "taus": taus,
            "taus_by_rank": {r: taus.copy() for r in np.ndindex(2, 2)}}


def _fresh_state(made):
    return dict(made["state"], last=None)


def test_check_passes_the_reference_reduction(made):
    tol = 100 * SMALL["n"] * 2.0 ** -52
    found = op().check(_fresh_state(made), _got(made))
    assert len(found) == 4 and found[TAUS] == 0.0
    assert all(v <= tol for v in found.values()), found


@pytest.mark.parametrize("shape", [(96, 96), (88, 88), (96, 40)],
                         ids=["whole-tiles", "edge-tiles", "wide"])
def test_assemble_inverts_the_block_cyclic_map(shape):
    """Bit for bit, edge tiles and a grid with more tile columns than rows
    included; a rank no device answered for leaves NaN."""
    cyclic = bench_run.load_module("reference", "cholesky_block_cyclic")
    a = np.random.default_rng(3).standard_normal(shape)
    shards = {r: cyclic.local_tiles(a, 16, (2, 2), r, (1, 0))
              for r in np.ndindex(2, 2)}
    assert np.array_equal(op().assemble(shards, 16, (2, 2), shape, (1, 0)),
                          a)
    del shards[0, 1]
    got = op().assemble(shards, 16, (2, 2), shape, (1, 0))
    assert np.isnan(got).any() and np.array_equal(got[~np.isnan(got)],
                                                  a[~np.isnan(got)])


def test_check_fails_a_float32_grade_reduction(made):
    """The plain reference computed in float32 (its shards and taus placed
    right): the numpy checks fail at the double-precision limit, the taus
    read 0."""
    ref = made["ref"]
    low, low_taus = ref.reduce_to_band(made["state"]["a"], SMALL["band"],
                                       dtype=np.float32)
    got = _got(made, low.astype(np.float64), low_taus.astype(np.float64))
    found = op().check(_fresh_state(made), got)
    tol = 100 * SMALL["n"] * 2.0 ** -47
    plain = {k: v for k, v in found.items() if k != TAUS}
    assert len(plain) == 3 and all(v > tol for v in plain.values()), found
    assert found[TAUS] == 0.0


def test_swapped_shards_fail_the_plain_checks(made):
    """Two chips hold each other's tiles: the matrix assembled by the
    reference's map is not the reduction, and the plain checks read
    order 1; a chip that did not answer reads NaN, which fails."""
    shards = _shards(made["red"])
    shards[0, 1], shards[1, 0] = shards[1, 0], shards[0, 1]
    found = op().check(_fresh_state(made), _got(made, shards=shards))
    plain = [v for k, v in found.items() if k != TAUS]
    assert min(plain) > 1e-3 and found[TAUS] == 0.0, found
    del shards[1, 1]
    found = op().check(_fresh_state(made), _got(made, shards=shards))
    plain = {k: v for k, v in found.items() if k != TAUS}
    assert len(plain) == 3 and all(v != v for v in plain.values()), found
    # the names stand for the plain check's own
    assert set(plain) == set(op().check(_fresh_state(made), _got(made))) - {
        TAUS}


def test_a_perturbed_taus_copy_fails_the_taus_check(made):
    got = _got(made)
    got["taus_by_rank"][1, 0][3, 2] += 1e-6
    found = op().check(_fresh_state(made), got)
    assert found[TAUS] == pytest.approx(1e-6 / np.abs(made["taus"]).max())
    assert found[TAUS] > 100 * SMALL["n"] * 2.0 ** -47


def test_an_equal_result_is_checked_once(made, monkeypatch):
    """The warm-up's and the last call's results are the same bits: the
    plain checks (two eigenvalue problems at the published N) run once,
    and ``A``'s eigenvalues are taken by the first check, not before."""
    mod = op()
    calls = []
    real = mod._plain.check
    monkeypatch.setattr(mod._plain, "check",
                        lambda state, out: calls.append(1) or real(state,
                                                                   out))
    state = _fresh_state(made)
    del state["lam"]
    first = mod.check(state, _got(made))
    assert np.array_equal(state["lam"], made["state"]["lam"])
    again = mod.check(state, _got(made, made["red"].copy()))
    assert first == again and len(calls) == 1
    other = made["red"].copy()
    other[5, 0] += 1.0
    mod.check(state, _got(made, other))
    assert len(calls) == 2


def test_build_refuses_a_run_that_is_not_four_devices(on_cpu):
    import jax

    config = bench_run.load_cell(ROOT, CELL)["config"]
    with pytest.raises(SystemExit) as exc:
        op().build(dict(config, **TINY), 1, jax.devices()[:1])
    assert "four" in str(exc.value.code)
    with pytest.raises(SystemExit):
        op().build(dict(config, grid=[1, 1], **TINY), 1, jax.devices()[:4])


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------

@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout-shaped directory: the committed BENCHMARK.json, this
    cell's configuration cut to 32 panels of 32, the traffic with a short
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


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_command_runs_the_distributed_scan_form(trace, tiny_root, on_cpu,
                                                as_on_tpu, capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483837",
                         "--seconds", "0.3", "--trace", str(trace)],
                        root=tiny_root)
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert out.count("[check]") == 9       # four checks a call, the tally
    assert TAUS in out
    m = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(m) == {"call_s", "residual_digits", "peak_hbm_gib",
                          "setup_s"}
        assert m["residual_digits"] > 11
        return
    assert line["attempted"] >= 3
    assert m["phase_s.reduction_to_band.dispatch"] > 0
    from dlaf_tpu import obs

    snap = {(x["name"], tuple(sorted(x["labels"].items()))): x["value"]
            for x in obs.registry().snapshot() if x.get("kind") == "counter"}
    assert snap["dlaf_red2band_steps_total", (("form", "dist_scan"),)] == 32
    assert snap["dlaf_red2band_panel_columns_total",
                (("form", "dist_scan"),)] == 32 * 32
    calls = snap["dlaf_entry_calls_total", (("entry", "reduction_to_band"),)]
    assert snap["dlaf_entry_programs_total",
                (("entry", "reduction_to_band"),)] == calls


def test_a_float32_grade_result_is_not_correct(tiny_root, on_cpu, as_on_tpu,
                                               capsys, monkeypatch):
    """The command's result rounded to float32, the matrix and every taus
    copy alike: the numpy checks fail the double-precision limit; the taus
    agree."""
    real = bench_run.load_module

    def load(kind, name):
        mod = real(kind, name)
        if kind == "ops":
            host = mod.host

            def rounded(out):
                got = host(out)

                def low(x):
                    return x.astype(np.float32).astype(np.float64)

                return {"red": low(got["red"]), "taus": low(got["taus"]),
                        "taus_by_rank": {r: low(t) for r, t in
                                         got["taus_by_rank"].items()}}

            mod.host = rounded
        return mod

    monkeypatch.setattr(bench_run, "load_module", load)
    bench_run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                    "--trace", "0"], root=tiny_root)
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["metrics"]["residual_digits"]["value"] < 9
    for row in out.splitlines():
        if row.startswith("[check]") and TAUS in row:
            assert "value=0 " in row, row
