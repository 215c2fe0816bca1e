"""The cell ``chol_d_n4096_2x2`` (PR 37): its entries, its two readers on
hand-made counters, the plain reference on its own and against the
library's distribution, its op file's refusal and check, and its untraced
and traced command on the CPU's four virtual devices at a small size, 16
block steps as on the chip, under a TPU's knob resolution (so the unrolled
distributed builder with the hoisted chains and the slice products is what
runs). Entries of BENCHMARK.json are found by name, never by position."""

import json
import os
import shutil

import numpy as np
import pytest

import run as bench_run
from test_command import on_cpu                 # noqa: F401
from test_chol_scan_cell import as_on_tpu       # noqa: F401

ROOT = bench_run.ROOT
CELL = "chol_d_n4096_2x2"
CONFIG = "chol-d-n4096-nb256-2x2"
NEW_METRICS = {
    "phase_s.cholesky.dispatch": ("s", "lower", "program_span",
                                  "entry points"),
    "phase_ms.comm": ("ms", "lower", "device_trace", "collectives"),
    "collectives_per_call": ("count", "lower", "program_counter",
                             "collectives"),
    "comm_overlapped_share": ("%", "higher", "program_counter",
                              "collectives"),
}
#: 16 block steps of 128 (= f64_gemm_min_dim: the smallest block at which
#: the distributed route traces slice products): the chip's step count
TINY = {"n": 2048, "nb": 128}
#: per-axis records a call at 16 steps on 2x2, and the hoisted ones
#: (tests/test_chol_dist_route.py:hand_comm)
RECORDS, HOISTED = 62, 58


def reader(name):
    return bench_run.load_module("layer_metrics", name).read


def counter(name, value, **labels):
    return {"name": name, "kind": "counter", "labels": labels,
            "value": float(value)}


def committed():
    return bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def reference():
    return bench_run.load_module("reference", "cholesky_block_cyclic")


# ---------------------------------------------------------------------------
# the entries
# ---------------------------------------------------------------------------

def test_the_cell_resolves_to_its_configuration_op_and_readers():
    cell = bench_run.load_cell(ROOT, CELL)
    assert cell["chips"] == 4
    config = cell["config"]
    assert (config["op"], config["dtype"], config["n"], config["nb"],
            config["grid"], config["args"]) == (
        "cholesky_dist", "float64", 4096, 256, [2, 2], {"uplo": "L"})
    op = bench_run.load_module("ops", config["op"])
    assert all(hasattr(op, f) for f in ("build", "fresh", "call", "host",
                                        "check", "flops"))
    assert op.flops(config) == 4096 ** 3 / 3.0
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= names
    for m in cell["per_layer"]:
        assert callable(bench_run.load_module("layer_metrics",
                                              m["name"]).read)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "call_s", "residual_digits", "peak_hbm_gib", "setup_s"}
    assert cell["traffic"] == bench_run.load_cell(
        ROOT, "chol_d_n4096_1x1")["traffic"]


def test_the_configuration_file_and_its_entry_agree():
    bench = committed()
    cell = by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "scf_closed_loop", 4)
    entry = by_name(bench["configs"], CONFIG)
    config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] and len(entry["source"]) < 200
    assert entry["reduced"] == config["reduced"] == []
    assert set(config["assumed"]) == {"pairing", "input"}
    # configs[0]'s matrix on configs[1]'s grid, the one-chip file's guarantee
    # plus the placement
    small = bench_run.load_json(os.path.join(
        ROOT, by_name(bench["configs"], "chol-d-n4096-nb256")["file"]))
    solve = bench_run.load_json(os.path.join(
        ROOT, by_name(bench["configs"], "trsm-d-n8192-nb256-2x2")["file"]))
    assert (config["n"], config["nb"], config["dtype"], config["args"]) == (
        small["n"], small["nb"], small["dtype"], small["args"])
    assert config["grid"] == solve["grid"]
    assert config["assumed"]["input"] == small["assumed"]["input"]
    for key in ("c", "eps_tpu", "eps_native"):
        assert config["guarantee"][key] == small["guarantee"][key]
    assert "local_tiles" in config["guarantee"]["what"]
    # of five cells two take four chips: the cap
    fours = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert fours == ["trsm_d_n8192_2x2", CELL]
    assert len(fours) <= len(bench["workloads"]) // 2


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_metrics_are_listed_for_this_cell_only(name):
    unit, better, source, layer = NEW_METRICS[name]
    assert by_name(committed()["per_layer"], name) == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "call_s", "workloads": [CELL]}
    for other in ("chol_d_n4096_1x1", "trsm_d_n8192_2x2"):
        assert name not in {m["name"] for m in
                            bench_run.load_cell(ROOT, other)["per_layer"]}


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def chip_counters():
    """The registry of one process that traced the cell's program once: 16
    diagonal broadcasts on each axis, 15 panel broadcasts along ``col``, 15
    all-gathers along ``row``; the chains of steps 1..15 hoisted."""
    count, over = "dlaf_comm_collective_count_total", \
        "dlaf_comm_overlapped_total"
    return [counter(count, 16, kind="bcast2d", axis="row"),
            counter(count, 16, kind="bcast2d", axis="col"),
            counter(count, 15, kind="bcast", axis="col"),
            counter(count, 15, kind="all_gather", axis="row"),
            counter("dlaf_comm_collective_bytes_total", 12345,
                    kind="bcast", axis="col"),
            counter(over, 29, algo="cholesky_dist", axis="row"),
            counter(over, 29, algo="cholesky_dist", axis="col"),
            {"name": "dlaf_span_seconds", "kind": "histogram",
             "labels": {"span": "stage.cholesky.dispatch"}, "count": 3,
             "sum": 0.01}]


def test_readers_on_the_chips_counters():
    run = {"counters": chip_counters()}
    assert reader("collectives_per_call")(run, "collectives_per_call") \
        == RECORDS
    assert reader("comm_overlapped_share")(run, "comm_overlapped_share") \
        == pytest.approx(100.0 * HOISTED / RECORDS)


@pytest.mark.parametrize("run", [
    {}, {"counters": None}, {"counters": []},
    # a one-device run traces no collective
    {"counters": [counter("dlaf_entry_calls_total", 3, entry="cholesky")]}])
@pytest.mark.parametrize("name", ["collectives_per_call",
                                  "comm_overlapped_share"])
def test_readers_return_nothing_on_a_one_device_run(name, run):
    assert reader(name)(run, name) is None


def test_overlapped_share_needs_both_counters():
    """Collectives and no hoist counter (the parent of a builder that
    hoists nothing): nothing, not 0."""
    run = {"counters": [c for c in chip_counters()
                        if c["name"] != "dlaf_comm_overlapped_total"]}
    assert reader("comm_overlapped_share")(run, "comm_overlapped_share") \
        is None
    assert reader("collectives_per_call")(run, "collectives_per_call") \
        == RECORDS


def test_the_span_and_phase_readers_are_data_only():
    """``phase_s.cholesky.dispatch`` and ``phase_ms.comm`` are entries of
    readers that were there: the span reader takes any ``stage.<x>``, the
    phase reader any phase of the split."""
    assert reader("phase_ms.comm")(
        {"phase_split": {"phases": {"comm": 1.25, "panel": 3.0}}},
        "phase_ms.comm") == 1.25
    assert reader("phase_ms.comm")({"phase_split": None},
                                   "phase_ms.comm") is None
    import span_reduce

    spans = [(0, 100, "bench_call"), (10, 30, "stage.cholesky.dispatch"),
             (200, 300, "bench_call"), (210, 250, "stage.cholesky.dispatch")]
    assert span_reduce.median_wall_per_call(
        spans, "stage.cholesky.dispatch") == pytest.approx(30e-9)
    assert "stage.cholesky.dispatch".startswith(span_reduce.HOST_PREFIXES)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _hpd(n, seed):
    g = np.random.default_rng(seed).standard_normal((n, n))
    return (g + g.T) / 2 + n * np.eye(n)


def test_the_reference_holds_no_import_of_jax_or_the_library():
    import ast

    with open(os.path.join(ROOT, "benchmark", "reference",
                           "cholesky_block_cyclic.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"__future__", "numpy"}


@pytest.mark.parametrize("n", [1, 7, 96, 257])
def test_the_reference_factorization_against_lapack(n):
    a = _hpd(n, seed=n)
    low = reference().cholesky_unblocked(a)
    want = np.linalg.cholesky(a)
    assert np.array_equal(np.triu(low, 1), np.zeros_like(low))
    assert np.linalg.norm(low - want) <= 8 * n * 2.0 ** -52 \
        * np.linalg.norm(want)
    assert np.linalg.norm(low @ low.T - a) <= 8 * n * 2.0 ** -52 \
        * np.linalg.norm(a)
    with pytest.raises(np.linalg.LinAlgError):
        reference().cholesky_unblocked(a - 2 * n * np.eye(n))


@pytest.mark.parametrize("grid, source, n, nb", [
    ((2, 2), (0, 0), 64, 8), ((2, 2), (1, 1), 60, 8), ((2, 2), (1, 0), 64, 8),
    ((1, 4), (0, 3), 50, 8), ((4, 1), (2, 0), 33, 4), ((2, 1), (0, 0), 24, 8),
    ((1, 1), (0, 0), 20, 8), ((2, 2), (0, 1), 8, 8)])
def test_the_block_cyclic_map_against_the_librarys_distribution(grid, source,
                                                                n, nb):
    """``local_tiles`` of every rank is the rank's block of the library's
    tile storage (``matrix/tiling.py:global_to_tiles``: storage rows ``pr
    ltr .. (pr + 1) ltr`` are rank row ``pr``'s slots), and ``owner`` /
    ``local_slot`` are ``util_distribution``'s rank and local index."""
    from dlaf_tpu.common.index2d import (GlobalElementSize, GridSize2D,
                                         RankIndex2D, TileElementSize)
    from dlaf_tpu.matrix import tiling
    from dlaf_tpu.matrix import util_distribution as ud
    from dlaf_tpu.matrix.distribution import Distribution

    import dlaf_tpu

    dlaf_tpu.initialize()       # float64 on (a run does so before it builds)
    ref = reference()
    a = np.random.default_rng(n).standard_normal((n, n + 3))
    dist = Distribution(size=GlobalElementSize(*a.shape),
                        block_size=TileElementSize(nb, nb),
                        grid_size=GridSize2D(*grid), rank=RankIndex2D(0, 0),
                        source_rank=RankIndex2D(*source))
    storage = np.asarray(tiling.global_to_tiles(a, dist))
    _, _, ltr, ltc = tiling.storage_tile_grid(dist)
    for pr, pc in np.ndindex(*grid):
        mine = ref.local_tiles(a, nb, grid, (pr, pc), source)
        np.testing.assert_array_equal(
            mine, storage[pr * ltr:(pr + 1) * ltr, pc * ltc:(pc + 1) * ltc])
    for i in range(dist.nr_tiles.row):
        for j in range(dist.nr_tiles.col):
            assert ref.owner(i, j, grid, source) == (
                ud.rank_global_tile(i, grid[0], source[0]),
                ud.rank_global_tile(j, grid[1], source[1]))
            assert ref.local_slot(i, j, grid) == (
                ud.local_tile_from_global_tile(i, grid[0]),
                ud.local_tile_from_global_tile(j, grid[1]))


# ---------------------------------------------------------------------------
# the op file
# ---------------------------------------------------------------------------

def _state(n=64, nb=8, seed=5):
    a = _hpd(n, seed)
    return {"a": a, "low": np.linalg.cholesky(a), "nb": nb, "grid": (2, 2),
            "seed": seed}


def _shards_of(state, full):
    return {rank: reference().local_tiles(full, state["nb"], state["grid"],
                                          rank)
            for rank in np.ndindex(*state["grid"])}


def test_check_passes_the_true_factor_and_fails_swapped_shards():
    op = bench_run.load_module("ops", "cholesky_dist")
    state = _state()
    tol = 60 * 64 * 2.0 ** -47
    full = state["low"] + np.triu(state["a"], 1)
    good = {"low": state["low"], "shards": _shards_of(state, full)}
    found = op.check(state, good)
    assert len(found) == 2 and all(v <= tol for v in found.values())
    # two chips hold each other's tiles: the gathered triangle is the true
    # one, so the residual passes and only the placement says so
    swapped = dict(good["shards"])
    swapped[(0, 1)], swapped[(1, 0)] = swapped[(1, 0)], swapped[(0, 1)]
    found = op.check(state, {"low": state["low"], "shards": swapped})
    residual, placement = found.values()
    assert residual <= tol and placement > 0.1
    # a chip that did not answer, a NaN
    missing = {k: v for k, v in good["shards"].items() if k != (1, 1)}
    assert max(op.check(state, {"low": state["low"],
                                "shards": missing}).values()) == np.inf
    poisoned = dict(good["shards"])
    poisoned[(0, 0)] = poisoned[(0, 0)] * np.nan
    worst = list(op.check(state, {"low": state["low"],
                                  "shards": poisoned}).values())[1]
    assert worst != worst


def test_check_fails_a_float32_factor_at_the_published_size():
    """The comparison is tight enough to catch a lower precision, at n =
    4096 and the chip's tolerance ``60 n 2^-47`` (numpy on the host: no
    device number): LAPACK's float32 factor of the cell's input fails the
    residual and the worst chip's placement check, each by over ten times;
    the float64 factor passes both."""
    op = bench_run.load_module("ops", "cholesky_dist")
    n, nb = 4096, 256
    a = _hpd(n, seed=2147483647)
    state = {"a": a, "low": np.linalg.cholesky(a), "nb": nb, "grid": (2, 2),
             "seed": 2147483647}
    tol = 60 * n * 2.0 ** -47
    low32 = np.linalg.cholesky(a.astype(np.float32)).astype(np.float64)
    got32 = {"low": low32,
             "shards": _shards_of(state, low32 + np.triu(a, 1))}
    found = op.check(state, got32)
    assert all(v > 10 * tol for v in found.values()), found
    # the chips that hold the diagonal tiles (entries of size sqrt(n)) read
    # 2.8e-8; the other two hold factor entries of size 1/sqrt(n) beside a
    # pass-through triangle of size 1 and read 5e-10: the worst chip decides
    diffs = op.shard_differences(state, got32["shards"])
    assert min(diffs[0, 0], diffs[1, 1]) > 10 * tol > tol \
        > max(diffs[0, 1], diffs[1, 0]) > 0
    got64 = {"low": state["low"],
             "shards": _shards_of(state, state["low"] + np.triu(a, 1))}
    assert all(v <= tol / 1e3 for v in op.check(state, got64).values())


def test_build_refuses_a_run_that_is_not_four_devices(on_cpu):
    import jax

    op = bench_run.load_module("ops", "cholesky_dist")
    config = bench_run.load_cell(ROOT, CELL)["config"]
    with pytest.raises(SystemExit) as exc:
        op.build(dict(config, **TINY), 1, jax.devices()[:1])
    assert "four" in str(exc.value.code)
    with pytest.raises(SystemExit):
        op.build(dict(config, grid=[1, 1], **TINY), 1, jax.devices()[:4])


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------

@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout-shaped directory: the committed BENCHMARK.json, this
    cell's configuration cut to 16 steps of 128, the traffic with a short
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
def test_command_runs_the_unrolled_distributed_builder(
        trace, tiny_root, on_cpu, as_on_tpu, capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483807",
                         "--seconds", "0.3", "--trace", str(trace)],
                        root=tiny_root)
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert out.count("[check]") >= 5       # two checks a call, and the tally
    assert "local_tiles" in out
    cell = bench_run.load_cell(tiny_root, CELL)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(m) == {"call_s", "residual_digits", "peak_hbm_gib",
                          "setup_s"}
        return
    assert line["attempted"] >= 3
    listless = {x["name"] for x in cell["per_layer"]
                if "workloads" not in x}
    # every list-less reader has something to read in this cell (device
    # lines are the chip's; on the CPU the trace has none)
    assert listless - set(m) <= {"device_idle_share", "device_busy_s",
                                 "matmul_time_share", "launch_gap_share"}
    assert {"first_call_s", "cache_misses", "ozaki_zero_mac_share"} <= set(m)
    assert m["collectives_per_call"] == RECORDS
    assert m["comm_overlapped_share"] == pytest.approx(
        100.0 * HOISTED / RECORDS)
    assert m["phase_s.cholesky.dispatch"] > 0
    assert line["metrics"]["phase_s.cholesky.dispatch"]["unit"] == "s"
    from dlaf_tpu import obs

    snap = {(x["name"], tuple(sorted(x["labels"].items()))): x["value"]
            for x in obs.registry().snapshot() if x.get("kind") == "counter"}
    assert snap["dlaf_cholesky_steps_total",
                (("algo", "cholesky_dist"), ("mode", "overlapped"))] == 15
    calls = snap["dlaf_entry_calls_total", (("entry", "cholesky"),)]
    assert snap["dlaf_entry_programs_total",
                (("entry", "cholesky"),)] == calls
