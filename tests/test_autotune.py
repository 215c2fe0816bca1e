"""Tests for ISSUE 15: the accuracy-steered precision autotuner
(dlaf_tpu.autotune, docs/autotune.md).

Covers: the pure decision core (escalate-on-breach, relax-after-K,
hysteresis determinism under injected probe sequences), table
persistence round-trip + loud refusal of malformed/stale/version-
mismatched tables (naming the field), the DLAF_AUTOTUNE=0 bitwise
passthrough (factor bytes identical knob on/off, local + distributed),
the closed loop end-to-end (nan_tile breach -> escalate record + gauge,
exhaustion -> flight dump + DLAF_STRICT raise), the ``autotune`` record
schema + ``--require-autotune`` validator legs, per-bucket serve routing
with the zero-steady-state-retrace pin (a route change is a NEW program,
never a retrace), the probe-cadence knob, the ozaki_impl=pallas ladder
rung (selectable by route, drill-able via inject.disable_ozaki), and the
bench-gate autotune speedup leg.
"""

import json
import math
import multiprocessing
import os
import subprocess
import sys

import numpy as np
import pytest

import dlaf_tpu.config as C
import dlaf_tpu.autotune as at
from dlaf_tpu import obs
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.common.index2d import GlobalElementSize, TileElementSize
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.miniapp.generators import hpd_element_fn
from dlaf_tpu.obs.sinks import validate_records

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")
if SCRIPTS not in sys.path:
    sys.path.insert(0, SCRIPTS)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_AT_ENV = ("DLAF_AUTOTUNE", "DLAF_AUTOTUNE_TABLE", "DLAF_AUTOTUNE_MARGIN",
           "DLAF_AUTOTUNE_RELAX_AFTER", "DLAF_AUTOTUNE_BUDGET",
           "DLAF_AUTOTUNE_PROBE_EVERY", "DLAF_METRICS_PATH", "DLAF_LOG",
           "DLAF_STRICT", "DLAF_ACCURACY", "DLAF_PROGRAM_TELEMETRY",
           "DLAF_FLIGHT_RECORDER", "DLAF_F64_GEMM",
           "DLAF_F64_GEMM_MIN_DIM", "DLAF_OZAKI_IMPL")


@pytest.fixture(autouse=True)
def autotune_reset():
    """Every test leaves the suite with the default (steering-off)
    config, an empty process table, and the obs layer unconfigured."""
    yield
    for key in _AT_ENV:
        os.environ.pop(key, None)
    at._reset_for_tests()
    obs._reset_for_tests()
    C.finalize()
    C.initialize()


def _arm(tmp_path=None, **env):
    for k, v in env.items():
        os.environ[k] = str(v)
    if tmp_path is not None:
        os.environ["DLAF_METRICS_PATH"] = str(tmp_path / "art.jsonl")
    os.environ.setdefault("DLAF_LOG", "off")
    C.initialize()
    at._reset_for_tests()


def _records(tmp_path, rtype=None):
    obs.flush()
    path = tmp_path / "art.jsonl"
    recs = [json.loads(line) for line in open(path)]
    return [r for r in recs if rtype is None or r.get("type") == rtype]


def _hpd_matrix(n=48, nb=16, dtype=np.float64, grid=None):
    return Matrix.from_element_fn(
        hpd_element_fn(n, dtype), GlobalElementSize(n, n),
        TileElementSize(nb, nb), dtype=dtype, grid=grid)


F64 = at.LADDER_F64
KEY = at.site_key("cholesky", n=48, nb=16, dtype=np.float64,
                  platform="cpu")


def _decide_seq(ratios, *, margin=0.25, relax_after=3, budget=0,
                ladder=F64, start=None):
    """Replay a probe sequence through the PURE decision core from a
    fresh state; returns the (reason, rung) trail."""
    rung = ladder.start if start is None else start
    holds = changes = 0
    trail = []
    for ratio in ratios:
        reason, rung, holds, changes = at.decide(
            rung, holds, changes, ratio, ladder_len=len(ladder.rungs),
            margin=margin, relax_after=relax_after, budget=budget)
        trail.append((reason, rung))
    return trail


# ---------------------------------------------------------------------------
# Decision core (pure function: escalate / relax / hysteresis)
# ---------------------------------------------------------------------------

class TestDecisionCore:
    def test_escalate_on_breach_is_immediate(self):
        assert _decide_seq([3.0]) == [("escalate", F64.start + 1)]

    def test_nonfinite_probe_is_a_breach(self):
        for bad in (float("nan"), float("inf")):
            assert _decide_seq([bad]) == [("escalate", F64.start + 1)]

    def test_relax_needs_exactly_k_consecutive_comfortable(self):
        trail = _decide_seq([0.01] * 3, relax_after=3)
        assert trail == [("hold", 3), ("hold", 3), ("relax", 2)]
        # one probe short of K holds forever
        trail = _decide_seq([0.01] * 2, relax_after=3)
        assert all(reason == "hold" for reason, _ in trail)

    def test_hysteresis_band_resets_the_streak(self):
        # two comfortable, one in (margin, 1], two more comfortable:
        # the mid-band probe must restart the relax clock
        trail = _decide_seq([0.01, 0.01, 0.5, 0.01, 0.01], relax_after=3)
        assert [r for r, _ in trail] == ["hold"] * 5
        # ...and a third consecutive comfortable probe then relaxes
        trail = _decide_seq([0.01, 0.01, 0.5, 0.01, 0.01, 0.01],
                            relax_after=3)
        assert trail[-1] == ("relax", F64.start - 1)

    def test_relax_stops_at_the_floor(self):
        trail = _decide_seq([0.01] * 40, relax_after=3)
        rungs = [rung for _, rung in trail]
        assert min(rungs) == 0 and rungs[-1] == 0
        assert trail[-1][0] == "hold"

    def test_budget_limits_relaxes_not_escalations(self):
        # budget 1: one relax allowed, later comfortable streaks hold
        trail = _decide_seq([0.01] * 12, relax_after=3, budget=1)
        assert sum(r == "relax" for r, _ in trail) == 1
        # an escalation still runs with the budget exhausted
        trail = _decide_seq([0.01, 0.01, 0.01, 3.0], relax_after=3,
                            budget=1)
        assert trail[-1][0] == "escalate"

    def test_exhausted_at_the_top_rung(self):
        top = len(F64.rungs) - 1
        assert _decide_seq([5.0], start=top) == [("exhausted", top)]

    def test_breach_resets_the_comfortable_streak(self):
        trail = _decide_seq([0.01, 0.01, 3.0, 0.01, 0.01], relax_after=3)
        assert trail[2][0] == "escalate"
        assert all(r == "hold" for r, _ in trail[3:])

    def test_decision_trail_is_deterministic(self):
        seq = [0.01, 0.6, float("nan"), 0.01, 0.01, 0.01, 2.0, 0.1]
        assert _decide_seq(seq) == _decide_seq(seq)
        # and through the stateful table too: two fresh tables fed the
        # same probes produce the same entries (the drill replay pin)
        t1, t2 = at.RouteTable(), at.RouteTable()
        for table in (t1, t2):
            for ratio in seq:
                table.observe(KEY, F64, ratio, margin=0.25,
                              relax_after=3, budget=0)
        assert t1.to_json() == t2.to_json()

    def test_ladder_start_rungs_are_the_platform_defaults(self):
        # f64: the start rung pins s=7 (the TPU auto default) and
        # nothing else; f32: the start rung is the EMPTY route — the
        # knob-on/off bitwise passthrough rests on this
        assert F64.rungs[F64.start].as_dict() == {"f64_gemm_slices": 7}
        assert at.LADDER_F32.rungs[at.LADDER_F32.start].as_dict() == {}


# ---------------------------------------------------------------------------
# Table persistence: round-trip + loud refusal
# ---------------------------------------------------------------------------

def _hammer_table(path, n_saves):
    """Fork-child body of the write-rename race drill: repeatedly
    replace the table at ``path`` through the atomic save discipline.
    Touches only pure-python table code (fork-safe under a jax-hosting
    parent)."""
    table = at.RouteTable()
    for ratio in (3.0, 0.01, 0.01, 0.01):
        table.observe(KEY, F64, ratio, margin=0.25, relax_after=3,
                      budget=0)
    for _ in range(n_saves):
        table.save(path)


class TestTablePersistence:
    def _learned(self):
        table = at.RouteTable()
        for ratio in (3.0, float("nan"), 0.01, 0.01, 0.01):
            table.observe(KEY, F64, ratio, margin=0.25, relax_after=3,
                          budget=0)
        return table

    def test_roundtrip_preserves_entries(self, tmp_path):
        table = self._learned()
        path = str(tmp_path / "table.json")
        table.save(path)
        loaded = at.RouteTable()
        loaded.load(path)
        assert loaded.to_json() == table.to_json()
        # nonfinite history entries survive as nulls, not JSON NaN
        raw = open(path).read()
        assert "NaN" not in raw and "null" in raw

    def test_save_is_atomic(self, tmp_path):
        table = self._learned()
        path = str(tmp_path / "table.json")
        table.save(path)
        leftovers = [p for p in os.listdir(tmp_path) if ".tmp." in p]
        assert leftovers == []

    @pytest.mark.parametrize("mutate,field", [
        (lambda d: d.pop("version"), "version"),
        (lambda d: d.update(version=99), "version"),
        (lambda d: d.update(entries={}), "entries"),
        (lambda d: d["entries"][0].pop("rung"), "rung"),
        (lambda d: d["entries"][0].update(rung=-1), "rung"),
        (lambda d: d["entries"][0].update(rung=999), "rung"),
        (lambda d: d["entries"][0].pop("op"), "op"),
        (lambda d: d["entries"][0].update(ladder="f64:2:bogus"), "ladder"),
        (lambda d: d["entries"][0].update(dtype="int16"), "dtype"),
        (lambda d: d["entries"][0].update(history="x"), "history"),
    ])
    def test_malformed_or_stale_refuses_naming_the_field(self, mutate,
                                                         field):
        doc = self._learned().to_json()
        mutate(doc)
        with pytest.raises(ValueError, match=field):
            at.RouteTable().load_dict(doc)

    def test_unparsable_file_refuses_loudly(self, tmp_path):
        path = tmp_path / "table.json"
        path.write_text("{not json")
        with pytest.raises(ValueError, match="unparsable"):
            at.RouteTable().load(str(path))

    def test_observe_persists_when_armed(self, tmp_path):
        path = str(tmp_path / "table.json")
        table = at.RouteTable(path)
        table.observe(KEY, F64, 3.0, margin=0.25, relax_after=3, budget=0)
        on_disk = at.RouteTable()
        on_disk.load(path)
        assert on_disk.rung_of(KEY) == F64.start + 1

    def test_get_table_warm_starts_from_the_knob(self, tmp_path):
        path = str(tmp_path / "table.json")
        self._learned().save(path)
        _arm(tmp_path, DLAF_AUTOTUNE="1", DLAF_AUTOTUNE_TABLE=path)
        assert at.get_table().rung_of(KEY) is not None

    def test_get_table_refuses_a_malformed_committed_table(self, tmp_path):
        path = tmp_path / "table.json"
        path.write_text(json.dumps({"version": 42, "entries": []}))
        _arm(tmp_path, DLAF_AUTOTUNE="1", DLAF_AUTOTUNE_TABLE=str(path))
        with pytest.raises(ValueError, match="version"):
            at.get_table()

    def test_committed_repo_table_loads_clean(self):
        """The repo's warm-start table (.autotune_table.json) must stay
        loadable by this build — a ladder edit without a table refresh
        fails HERE, not in CI."""
        path = os.path.join(REPO, ".autotune_table.json")
        assert os.path.exists(path), "committed .autotune_table.json missing"
        table = at.RouteTable()
        table.load(path)
        assert table.snapshot(), "committed table has no entries"
        # the committed steady state: every entry fully relaxed (rung 0)
        # so the CI warm-start leg holds with ZERO route changes
        assert all(e["rung"] == 0 for e in table.snapshot().values())

    def test_load_retries_once_on_a_mid_replace_read(self, tmp_path,
                                                     monkeypatch):
        """A reader whose first open lands mid-replace (transient short
        read on the dying inode) must retry once and succeed — fleet
        workers warm-start from one shared committed table while the
        autotune loop may still be persisting to it."""
        from dlaf_tpu.autotune import table as table_mod
        path = str(tmp_path / "table.json")
        self._learned().save(path)
        calls = {"n": 0}
        real = table_mod.json.load

        def flaky(f, *args, **kwargs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ValueError("Expecting value: line 1 column 1")
            return real(f, *args, **kwargs)

        monkeypatch.setattr(table_mod.json, "load", flaky)
        loaded = at.RouteTable()
        loaded.load(path)
        assert calls["n"] == 2
        assert loaded.rung_of(KEY) is not None

    def test_load_still_refuses_a_genuinely_corrupt_table(self, tmp_path):
        path = tmp_path / "table.json"
        path.write_text('{"version": 3, "entr')    # truncated for real
        with pytest.raises(ValueError, match="unparsable autotune table"):
            at.RouteTable().load(str(path))

    @pytest.mark.skipif(
        "fork" not in multiprocessing.get_all_start_methods(),
        reason="needs the fork start method")
    def test_concurrent_writers_never_corrupt_a_reader(self, tmp_path):
        """N processes hammering one table path through the atomic
        write-rename (tmp + fsync + os.replace) while a reader loads in
        a loop: every load sees a COMPLETE table (old or new, never a
        torn one), and no .tmp litter survives."""
        path = str(tmp_path / "table.json")
        self._learned().save(path)      # the reader always has a table
        ctx = multiprocessing.get_context("fork")
        procs = [ctx.Process(target=_hammer_table, args=(path, 30))
                 for _ in range(3)]
        for p in procs:
            p.start()
        reads = 0
        try:
            while any(p.is_alive() for p in procs) or reads < 20:
                loaded = at.RouteTable()
                loaded.load(path)
                assert loaded.snapshot(), "reader saw an empty table"
                reads += 1
        finally:
            for p in procs:
                p.join(timeout=60)
        assert all(p.exitcode == 0 for p in procs), \
            [p.exitcode for p in procs]
        assert reads >= 20
        leftovers = [p for p in os.listdir(tmp_path) if ".tmp." in p]
        assert leftovers == []


# ---------------------------------------------------------------------------
# Bitwise passthrough + steering integration on the entries
# ---------------------------------------------------------------------------

class TestEntrySteering:
    @pytest.mark.parametrize("grid", [None, (2, 4)])
    def test_factor_bitwise_identical_knob_on_off(self, grid):
        """DLAF_AUTOTUNE=0 vs =1 at the start rung: factor bytes
        identical (the ladders' start routes ARE the platform
        defaults)."""
        g = Grid(*grid) if grid else None
        mat = _hpd_matrix(48, 16, grid=g)
        os.environ["DLAF_AUTOTUNE"] = "0"
        os.environ["DLAF_LOG"] = "off"
        C.initialize()
        at._reset_for_tests()
        ref = cholesky("L", mat).to_numpy()
        os.environ["DLAF_AUTOTUNE"] = "1"
        C.initialize()
        at._reset_for_tests()
        got = cholesky("L", mat).to_numpy()
        assert np.array_equal(np.tril(ref), np.tril(got))

    def test_knob_off_emits_no_records_and_no_table(self, tmp_path):
        _arm(tmp_path, DLAF_AUTOTUNE="0")
        cholesky("L", _hpd_matrix())
        assert _records(tmp_path, "autotune") == []

    def test_probes_feed_the_table_per_op(self, tmp_path):
        _arm(tmp_path, DLAF_AUTOTUNE="1")
        mat = _hpd_matrix()
        fac = cholesky("L", mat)
        from dlaf_tpu.algorithms.gen_to_std import gen_to_std

        gen_to_std("L", mat, fac)
        rungs = {k: e["rung"] for k, e in at.get_table().snapshot().items()}
        assert "cholesky.n64.nb16.float64.cpu" in rungs
        assert "hegst.n64.nb16.float64.cpu" in rungs
        recs = _records(tmp_path, "autotune")
        assert {r["op"] for r in recs} >= {"cholesky", "hegst"}
        assert all(r["reason"] == "hold" for r in recs)
        assert validate_records(_records(tmp_path)) == []

    def test_donated_input_skips_the_probe(self, tmp_path):
        _arm(tmp_path, DLAF_AUTOTUNE="1")
        mat = _hpd_matrix()
        cholesky("L", mat, donate=True)
        assert _records(tmp_path, "autotune") == []

    def test_probe_cadence_knob(self, tmp_path):
        _arm(tmp_path, DLAF_AUTOTUNE="1", DLAF_AUTOTUNE_PROBE_EVERY="3")
        mat = _hpd_matrix()
        for _ in range(6):
            cholesky("L", mat)
        recs = _records(tmp_path, "autotune")
        assert len(recs) == 2        # calls 1 and 4 probe; the rest skip

    def test_breach_escalates_and_next_call_uses_the_new_route(
            self, tmp_path):
        """The closed loop end-to-end: a nan_tile-grade breach escalates
        the site (decision record + gauge transition), and the NEXT call
        dispatches under the escalated route."""
        from dlaf_tpu.health import inject

        _arm(tmp_path, DLAF_AUTOTUNE="1")
        mat = _hpd_matrix()
        poisoned = inject.nan_tile(mat, tile=(1, 0), element=(2, 3))
        cholesky("L", poisoned)                 # NaN factor -> breach
        recs = _records(tmp_path, "autotune")
        assert recs[-1]["reason"] == "escalate"
        assert recs[-1]["nonfinite"] is True and recs[-1]["probe"] is None
        assert recs[-1]["rung_new"] == F64.start + 1
        key = at.site_key("cholesky", n=48, nb=16, dtype=np.float64,
                          platform="cpu")
        assert at.get_table().rung_of(key) == F64.start + 1
        assert at.get_table().route_for(key, F64).as_dict() == \
            F64.rungs[F64.start + 1].as_dict()
        gauge = obs.registry().gauge("dlaf_autotune_route", op="cholesky",
                                     knob="rung").snapshot()
        assert gauge["value"] == F64.start + 1
        esc = obs.registry().counter("dlaf_autotune_escalations_total",
                                     op="cholesky").snapshot()
        assert esc["value"] == 1
        # clean calls afterwards hold, then relax after K comfortable
        for _ in range(int(C.get_configuration().autotune_relax_after)):
            cholesky("L", mat)
        recs = _records(tmp_path, "autotune")
        assert recs[-1]["reason"] == "relax"
        assert at.get_table().rung_of(key) == F64.start

    def test_exhaustion_strict_raise_and_flight_dump(self, tmp_path):
        from dlaf_tpu.health.errors import AutotuneExhaustedError

        _arm(tmp_path, DLAF_AUTOTUNE="1", DLAF_STRICT="1",
             DLAF_FLIGHT_RECORDER="32")
        key = at.site_key("cholesky", n=48, nb=16, dtype=np.float64,
                          platform="cpu")
        top = len(F64.rungs) - 1
        for _ in range(top - F64.start):
            at.observe_ratio(key, F64, 5.0)
        with pytest.raises(AutotuneExhaustedError) as err:
            at.observe_ratio(key, F64, 5.0)
        assert err.value.site == key.label and err.value.rung == top
        flight = str(tmp_path / "art.jsonl") + ".flight.jsonl"
        assert os.path.exists(flight)
        header = json.loads(open(flight).readline())
        assert header["reason"] == "autotune_exhausted"
        # the exhausted decision record itself rode the ring
        ring = [json.loads(line) for line in open(flight)][1:]
        assert any(r.get("type") == "autotune"
                   and r.get("reason") == "exhausted" for r in ring)

    def test_eigensolver_pipeline_steers_and_probes(self, tmp_path):
        _arm(tmp_path, DLAF_AUTOTUNE="1")
        from dlaf_tpu.eigensolver import eigensolver

        mat = _hpd_matrix(32, 8)
        res = eigensolver("L", mat)
        assert np.isfinite(res.eigenvalues).all()
        recs = _records(tmp_path, "autotune")
        ops = {r["op"] for r in recs}
        assert "eigensolver" in ops
        assert validate_records(_records(tmp_path)) == []


# ---------------------------------------------------------------------------
# Record schema + --require-autotune
# ---------------------------------------------------------------------------

def _decision_record(**over):
    rec = {"v": 1, "type": "autotune", "ts": 1.0,
           "site": "cholesky.n64.nb16.float64.cpu", "op": "cholesky",
           "n_bucket": 64, "nb": 16, "dtype": "float64", "platform": "cpu",
           "reason": "escalate", "rung_old": 3, "rung_new": 4,
           "route_old": {"f64_gemm_slices": 7},
           "route_new": {"f64_gemm_slices": 8},
           "probe": 2.0, "attrs": {}}
    rec.update(over)
    return rec


class TestSchemaAndValidator:
    def test_valid_record_passes(self):
        assert validate_records([_decision_record()]) == []

    @pytest.mark.parametrize("over,msg", [
        ({"reason": "panic"}, "reason"),
        ({"rung_new": 3}, "escalate must raise"),
        ({"reason": "relax", "rung_new": 5}, "relax must lower"),
        ({"reason": "hold", "rung_new": 9}, "hold must keep"),
        ({"probe": float("nan")}, "probe"),
        ({"probe": None}, "probe"),
        ({"probe": None, "nonfinite": True, "rung_new": 3}, "escalate"),
        ({"site": ""}, "site"),
        ({"route_new": "s8"}, "route_new"),
        ({"n_bucket": -1}, "n_bucket"),
    ])
    def test_schema_rejections(self, over, msg):
        errs = validate_records([_decision_record(**over)])
        assert errs and any(msg in e for e in errs), errs

    def test_require_autotune_needs_a_route_move(self):
        hold = _decision_record(reason="hold", rung_new=3,
                                route_new={"f64_gemm_slices": 7})
        errs = validate_records([hold], require_autotune=True)
        assert any("never moved a route" in e for e in errs)
        assert validate_records([_decision_record()],
                                require_autotune=True) == []

    def test_require_autotune_rejects_an_exhausted_end_state(self):
        moved = _decision_record()
        exhausted = _decision_record(reason="exhausted", rung_old=5,
                                     rung_new=5, probe=None,
                                     nonfinite=True,
                                     route_old={"f64_gemm_slices": 8,
                                                "f64_trsm": "native"},
                                     route_new={"f64_gemm_slices": 8,
                                                "f64_trsm": "native"})
        errs = validate_records([moved, exhausted], require_autotune=True)
        assert any("exhausted" in e for e in errs)
        # ...but an exhaustion RECOVERED by a later relax is no longer
        # an open state
        relaxed = _decision_record(reason="relax", rung_old=5, rung_new=4,
                                   probe=0.01,
                                   route_new={"f64_gemm_slices": 8})
        assert validate_records([moved, exhausted, relaxed],
                                require_autotune=True) == []

    def test_validate_cli_flag(self, tmp_path):
        path = tmp_path / "a.jsonl"
        path.write_text(json.dumps(_decision_record()) + "\n")
        proc = subprocess.run(
            [sys.executable, "-m", "dlaf_tpu.obs.validate", str(path),
             "--require-autotune"], capture_output=True, text=True,
            cwd=REPO)
        assert proc.returncode == 0, proc.stderr
        assert "1 autotune decisions" in proc.stdout
        hold = _decision_record(reason="hold", rung_new=3)
        path.write_text(json.dumps(hold) + "\n")
        proc = subprocess.run(
            [sys.executable, "-m", "dlaf_tpu.obs.validate", str(path),
             "--require-autotune"], capture_output=True, text=True,
            cwd=REPO)
        assert proc.returncode == 1

    def test_aggregate_decision_trail_section(self, tmp_path):
        from dlaf_tpu.obs.aggregate import (autotune_rows,
                                            format_autotune_trail)

        rows = autotune_rows([
            _decision_record(),
            _decision_record(reason="hold", rung_old=4, rung_new=4,
                             probe=0.5),
        ])
        assert rows[0]["count"] == 2 and rows[0]["escalations"] == 1
        lines = format_autotune_trail(rows)
        assert any("escalate" in line for line in lines)
        assert any("rung 3 -> 4" in line for line in lines)


# ---------------------------------------------------------------------------
# Serve: per-bucket routing + zero-steady-state retrace
# ---------------------------------------------------------------------------

class TestServeRouting:
    def _queue(self, bn=32, batch=4):
        from dlaf_tpu.serve import Queue

        return Queue(buckets=(bn,), batch=batch, deadline_s=1e9)

    def _problems(self, k, n=20, seed=0):
        rng = np.random.default_rng(seed)
        out = []
        for _ in range(k):
            x = rng.standard_normal((n, n))
            out.append(x @ x.T + n * np.eye(n))
        return out

    def test_bucket_spec_carries_the_table_route(self, tmp_path):
        _arm(tmp_path, DLAF_AUTOTUNE="1")
        q = self._queue()
        from dlaf_tpu.serve import Request

        spec = q._spec(q._key(Request(op="cholesky",
                                      a=self._problems(1)[0])))
        assert dict(spec.route) == F64.rungs[F64.start].as_dict()
        assert "rt_s7" in spec.site
        # knob off: the spec keeps its route-free identity
        os.environ["DLAF_AUTOTUNE"] = "0"
        C.initialize()
        spec0 = q._spec(q._key(Request(op="cholesky",
                                       a=self._problems(1)[0])))
        assert spec0.route == () and "rt_" not in spec0.site

    def test_steady_state_zero_retrace_and_route_change_is_new_program(
            self, tmp_path):
        """The tentpole zero-retrace pin (docs/autotune.md): a warmed
        bucket stream under a HELD route shows dlaf_retrace_total == 1
        per serve site; an escalation dispatches a NEW program (visible
        miss, its own site) and the old program still never retraces."""
        from dlaf_tpu.serve import Request
        from dlaf_tpu.serve.programs import _reset_for_tests

        _arm(tmp_path, DLAF_AUTOTUNE="1", DLAF_PROGRAM_TELEMETRY="1")
        _reset_for_tests()
        q = self._queue()
        probs = self._problems(8)
        reqs = [Request(op="cholesky", a=a) for a in probs]
        q.warmup(reqs)
        for a in probs:
            q.submit(Request(op="cholesky", a=a))
        q.flush()
        st = q.service.stats()
        assert st["misses"] == 0 and st["hit_rate"] == 1.0
        key = q._key(Request(op="cholesky", a=probs[0]))
        site_held = q._spec(key).site
        snap = obs.registry().counter("dlaf_retrace_total",
                                      site=site_held).snapshot()
        assert snap["value"] == 1, snap
        # force an escalation of the bucket's table entry
        tkey = at.site_key("cholesky", n=32, nb=32, dtype="float64",
                           platform="cpu")
        at.observe_ratio(tkey, F64, 5.0)
        site_esc = q._spec(key).site
        assert site_esc != site_held
        q.submit(Request(op="cholesky", a=probs[0]))
        q.flush()
        assert q.service.stats()["misses"] == 1    # the new route compiles
        for site in (site_held, site_esc):
            snap = obs.registry().counter("dlaf_retrace_total",
                                          site=site).snapshot()
            assert snap["value"] == 1, (site, snap)

    def test_strict_exhaustion_is_not_a_dispatch_failure(self, tmp_path):
        """A strict AutotuneExhaustedError out of a serve dispatch's
        probe surfaces to the caller but the dispatch itself SUCCEEDED:
        tickets fulfilled, counted as a dispatch (never a failure), so
        stats()['dispatches'] stays in agreement with the dispatch
        records (the /healthz agreement leg)."""
        from dlaf_tpu.health.errors import AutotuneExhaustedError
        from dlaf_tpu.serve import Request
        from dlaf_tpu.serve.programs import _reset_for_tests

        _arm(tmp_path, DLAF_AUTOTUNE="1", DLAF_ACCURACY="1",
             DLAF_STRICT="1")
        _reset_for_tests()
        q = self._queue()
        tkey = at.site_key("cholesky", n=32, nb=32, dtype="float64",
                           platform="cpu")
        top = len(F64.rungs) - 1
        for _ in range(top - F64.start):     # walk the entry to the top
            at.observe_ratio(tkey, F64, 5.0)
        bad = np.full((20, 20), np.nan)      # NaN residual at the top
        ticket = q.submit(Request(op="cholesky", a=bad))
        with pytest.raises(AutotuneExhaustedError):
            q.flush()
        assert ticket.done and ticket.error is None
        st = q.stats()
        assert st["dispatches"] == 1
        bucket = next(iter(st["buckets"].values()))
        assert bucket["dispatches"] == 1 and bucket["failures"] == 0
        disp = [r for r in _records(tmp_path, "serve")
                if r["event"] == "dispatch"]
        assert len(disp) == st["dispatches"]

    def test_serve_residuals_feed_the_bucket_entry(self, tmp_path):
        from dlaf_tpu.serve import Request
        from dlaf_tpu.serve.programs import _reset_for_tests

        _arm(tmp_path, DLAF_AUTOTUNE="1", DLAF_ACCURACY="1")
        _reset_for_tests()
        q = self._queue()
        probs = self._problems(4)
        q.warmup([Request(op="cholesky", a=probs[0])])
        for a in probs:
            q.submit(Request(op="cholesky", a=a))
        q.flush()
        recs = _records(tmp_path, "autotune")
        assert recs and all(r["attrs"].get("source") == "serve"
                            for r in recs)
        assert at.get_table().rung_of(
            at.site_key("cholesky", n=32, nb=32, dtype="float64",
                        platform="cpu")) == F64.start


# ---------------------------------------------------------------------------
# Satellite 1: the ozaki_impl=pallas ladder rung
# ---------------------------------------------------------------------------

class TestOzakiPallasRung:
    def _force_rung0(self, tmp_path):
        _arm(tmp_path, DLAF_AUTOTUNE="1", DLAF_F64_GEMM="mxu",
             DLAF_F64_GEMM_MIN_DIM="8")
        key = at.site_key("cholesky", n=64, nb=8, dtype=np.float64,
                          platform="cpu")
        # walk the table to the fastest rung deterministically
        table = at.get_table()
        for _ in range(F64.start * int(
                C.get_configuration().autotune_relax_after)):
            table.observe(key, F64, 0.0, margin=0.25, relax_after=int(
                C.get_configuration().autotune_relax_after), budget=0)
        assert table.rung_of(key) == 0
        assert table.route_for(key, F64).ozaki_impl == "pallas"
        return key

    def test_rung0_selects_the_fused_pallas_reduction(self, tmp_path,
                                                      devices8):
        """The revived fused Ozaki slice kernels are selectable by the
        route ladder: at rung 0 the distributed cholesky runs the
        predicated masked kernel (interpret mode here) and matches the
        jnp route."""
        self._force_rung0(tmp_path)
        mat = _hpd_matrix(64, 8, grid=Grid(2, 4))
        a = mat.to_numpy()
        got = cholesky("L", mat).to_numpy()
        f = np.tril(got)
        resid = np.linalg.norm(f @ f.T - a) / np.linalg.norm(a)
        assert resid < 60 * 64 * np.finfo(np.float64).eps
        # the jnp-route reference under the SAME slice count (rung 0 is
        # s=5 + pallas; pin s=5 + jnp explicitly)
        os.environ["DLAF_AUTOTUNE"] = "0"
        os.environ["DLAF_F64_GEMM_SLICES"] = "5"
        C.initialize()
        try:
            ref = cholesky("L", mat).to_numpy()
        finally:
            os.environ.pop("DLAF_F64_GEMM_SLICES", None)
        assert np.abs(np.tril(got) - np.tril(ref)).max() < 1e-10

    def test_rung0_drillable_via_disable_ozaki(self, tmp_path, devices8):
        """inject.disable_ozaki degrades the whole mxu route under the
        fastest rung — counted at ozaki_gemm, correct result, and
        DLAF_STRICT raises (the route must be drill-able without a
        chip)."""
        from dlaf_tpu.health import inject
        from dlaf_tpu.health.errors import DegradationError

        self._force_rung0(tmp_path)
        mat = _hpd_matrix(64, 8, grid=Grid(2, 4))
        a = mat.to_numpy()
        with inject.disable_ozaki():
            got = cholesky("L", mat).to_numpy()
            snap = obs.registry().counter(
                "dlaf_fallback_total", site="ozaki_gemm",
                reason="injected_off").snapshot()
            assert snap["value"] >= 1
        f = np.tril(got)
        assert np.linalg.norm(f @ f.T - a) / np.linalg.norm(a) \
            < 60 * 64 * np.finfo(np.float64).eps
        os.environ["DLAF_STRICT"] = "1"
        C.initialize()
        with inject.disable_ozaki():
            with pytest.raises(DegradationError):
                cholesky("L", mat)


# ---------------------------------------------------------------------------
# Bench-gate autotune leg
# ---------------------------------------------------------------------------

class TestBenchGateLeg:
    def _line(self, speedup, n=192):
        return {"variant": "autotune", "platform": "cpu", "dtype":
                "float64", "n": n, "nb": 64, "gflops": 1.0, "t": 0.1,
                "ts": "2026-08-04T00:00:00", "source": "bench.py",
                "workload": "autotune", "speedup": speedup}

    def test_speedup_floor_trips_and_passes(self):
        from bench_gate import run_gate

        logs = []
        bad = run_gate([], [self._line(0.2)], tolerance=0.1,
                       min_history=3, best_k=3, log=logs.append,
                       min_autotune_speedup=0.5)
        assert bad == 1 and any("ISSUE-15" in line for line in logs)
        ok = run_gate([], [self._line(0.9)], tolerance=0.1,
                      min_history=3, best_k=3, log=lambda *a: None,
                      min_autotune_speedup=0.5)
        assert ok == 0

    def test_committed_history_line_gates_on_replay(self):
        """A committed autotune history line keeps the floor enforced in
        every --replay (the serve-line convention)."""
        from dlaf_tpu.obs.sinks import read_history_records

        history = read_history_records(
            os.path.join(REPO, ".bench_history.jsonl"))
        lines = [line for line in history
                 if line.get("workload") == "autotune"]
        assert lines, "no committed autotune history line"
        assert all(isinstance(line.get("speedup"), float)
                   for line in lines)
