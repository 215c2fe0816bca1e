"""The command end to end at tiny N for each op, on the CPU. The platform
check is patched here and nowhere else: the command has no option that lets
a CPU run through."""

import json
import os
import shutil

import pytest

import run as bench_run

ROOT = bench_run.ROOT
#: BENCHMARK.json with all three cells this PR wrote files for; the
#: committed BENCHMARK.json lists only those proven on the chip.
REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "rehearsal_benchmark.json")
TINY = {"cholesky": {"n": 128, "nb": 32},
        "eigensolver": {"n": 96, "nb": 32},
        "triangular_solve": {"n": 128, "nb": 32}}


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout-shaped directory with the rehearsal BENCHMARK.json and
    every configuration cut to a tiny N (the harness unchanged)."""
    bench = bench_run.load_json(REHEARSAL)
    shutil.copy(REHEARSAL, tmp_path / "BENCHMARK.json")
    for entry in bench["configs"]:
        config = bench_run.load_json(os.path.join(ROOT, entry["file"]))
        config.update(TINY[config["op"]])
        if "nrhs" in config["args"]:
            config["args"]["nrhs"] = config["n"]
        dst = tmp_path / entry["file"]
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text(json.dumps(config))
    tdir = tmp_path / "benchmark" / "traffic"
    tdir.mkdir(parents=True)
    for f in os.listdir(os.path.join(ROOT, "benchmark", "traffic")):
        traffic = bench_run.load_json(
            os.path.join(ROOT, "benchmark", "traffic", f))
        traffic["traced_window"].update(min_seconds=0.2)
        (tdir / f).write_text(json.dumps(traffic))
    return str(tmp_path)


@pytest.fixture()
def on_cpu(monkeypatch):
    import jax

    monkeypatch.setattr(bench_run, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(bench_run, "P90_MIN_CALLS", 1)


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["chol_d_n4096_1x1", "eig_d_n2048_1x1",
                                  "trsm_d_n8192_2x2"])
def test_untraced_run_prints_the_end_to_end_line(cell, tiny_root, on_cpu,
                                                 capsys):
    rc = bench_run.main(["--workload", cell, "--seed", "2147483659",
                         "--seconds", "0.5", "--trace", "0"], root=tiny_root)
    line = last_line(capsys)
    assert rc == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    bench = bench_run.load_json(REHEARSAL)
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    for m in bench["end_to_end"]:
        if m["name"] in want:
            assert line["metrics"][m["name"]]["unit"] == m["unit"]
    assert line["metrics"]["residual_digits"]["value"] > 10
    assert line["device"]["count"] == (4 if cell.endswith("2x2") else 1)
    assert line["device"]["platform"] == "cpu"      # named for what it is


def test_traced_run_prints_the_per_layer_line(tiny_root, on_cpu, capsys):
    rc = bench_run.main(["--workload", "eig_d_n2048_1x1", "--seed", "7",
                         "--seconds", "0.5", "--trace", "1"], root=tiny_root)
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True
    # a CPU trace has no device plane: the device readers return nothing and
    # are left out; stage walls and set-up metrics are there
    names = set(line["metrics"])
    assert {"stage_s.band_to_tridiag", "stage_s.tridiag_solver",
            "first_call_s", "cache_misses"} <= names
    assert "call_s" not in names
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["attempted"] >= 3


def test_no_tpu_means_no_number(tiny_root, capsys):
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--workload", "chol_d_n4096_1x1", "--seed", "1",
                        "--seconds", "0.5", "--trace", "0"], root=tiny_root)
    assert exc.value.code == 2
    assert capsys.readouterr().out.strip() == ""


def test_a_wrong_answer_is_not_correct(tiny_root, on_cpu, capsys,
                                       monkeypatch):
    """An f32-grade answer fails the double-precision tolerance."""
    import numpy as np

    real = bench_run.load_module

    def load(kind, name):
        mod = real(kind, name)
        if kind == "ops":
            host = mod.host
            mod.host = lambda out: host(out).astype(np.float32).astype(
                np.float64)
        return mod

    monkeypatch.setattr(bench_run, "load_module", load)
    bench_run.main(["--workload", "chol_d_n4096_1x1", "--seed", "3",
                    "--seconds", "0.2", "--trace", "0"], root=tiny_root)
    line = last_line(capsys)
    assert line["correct"] is False
    assert line["metrics"]["residual_digits"]["value"] < 9
