"""Static-analysis layer (dlaf_tpu/analysis/, docs/static_analysis.md).

Every graphcheck invariant and lint rule gets three cases here: a
PASSING case (clean input produces no finding), a MUST-TRIP case (the
seeded-bad drill produces exactly the expected rule), and a SUPPRESSED
case (in-code ``dlaf: disable=RULE(reason)`` for lint, the committed-
baseline workflow for graph findings). Plus the depgraph traversal
vocabulary itself, pinned on toy programs with known structure.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from dlaf_tpu.analysis import (Finding, depgraph, diff_baseline, drills,
                               graphcheck, lint, load_baseline,
                               write_baseline)
from dlaf_tpu.analysis.__main__ import main as analysis_main


# ---------------------------------------------------------------------------
# depgraph: the traversal vocabulary on toy programs of known structure
# ---------------------------------------------------------------------------

def _toy_jaxpr():
    def fn(x):
        a = x * 2.0            # eqn 0 (mul)
        b = a + 1.0            # eqn 1 (add)    depends on mul
        c = x - 3.0            # eqn 2 (sub)    independent of mul
        return b @ c           # eqn 3 (dot_general)

    return depgraph.trace(fn, jax.ShapeDtypeStruct((4, 4), jnp.float64))


def test_depgraph_positions_and_closure():
    eqns = _toy_jaxpr().jaxpr.eqns
    [dot] = depgraph.positions(eqns, "dot_general")
    assert depgraph.depends_on(eqns, dot, "mul")
    [sub] = depgraph.positions(eqns, "sub")
    assert not depgraph.depends_on(eqns, sub, "mul")
    # closure of the dot's inputs contains all three producer eqns
    names = {e.primitive.name
             for e in depgraph.closure(eqns, eqns[dot].invars)}
    assert names == {"mul", "add", "sub"}


def test_depgraph_predicate_shorthand_and_is_bulk_dot():
    eqns = _toy_jaxpr().jaxpr.eqns
    by_name = depgraph.positions(eqns, "dot_general")
    by_pred = depgraph.positions(
        eqns, lambda e: e.primitive.name == "dot_general")
    assert by_name == by_pred and len(by_name) == 1
    assert depgraph.is_bulk_dot(eqns[by_name[0]], rank=2)
    assert not depgraph.is_bulk_dot(eqns[by_name[0]])   # default rank=4


def test_depgraph_shard_map_body_and_collectives(devices8):
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("row", "col"))

    def body(x):
        y = lax.psum(x, "row")
        return lax.all_gather(y, "col")

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("row", "col"),
                           out_specs=P(None, None), check_vma=False)
    sds = jax.ShapeDtypeStruct((4, 4), jnp.float64)
    eqns = depgraph.shard_map_body(fn, sds)
    colls = depgraph.collectives(eqns)
    assert [c.kind for c in colls] == ["psum", "all_gather"]
    assert colls[0].axes == ("row",) and colls[1].axes == ("col",)
    assert colls[0].shape == (2, 2)       # per-shard operand on the 2x2 mesh
    assert colls[0].dtype == "float64" and colls[0].nbytes == 4 * 8
    assert not colls[0].conditional
    # a non-shard_map program must refuse, not guess
    with pytest.raises(ValueError, match="shard_map"):
        depgraph.shard_map_body(lambda x: x + 1.0, sds)


def test_depgraph_scan_body_and_carry_slots():
    def fn(x):
        def body(carry, _):
            live, dead = carry
            live = live * 2.0
            return (live, dead), live.sum()

        # lax.scan itself forwards the passthrough carry out of the eqn
        # on the installed JAX; bind beneath it to keep the dead slot
        (live, _dead), ys = drills.scan_keeping_passthrough(
            body, (x, x + 1.0), 3)
        return live, ys

    jaxpr = depgraph.trace(fn, jax.ShapeDtypeStruct((4,), jnp.float64))
    [scan] = depgraph.scan_eqns(jaxpr.jaxpr.eqns)
    body = depgraph.scan_body(jaxpr.jaxpr.eqns)
    assert any(e.primitive.name == "mul" for e in body)
    slots = depgraph.scan_carry_slots(scan)
    assert [s.dead for s in slots] == [False, True]
    assert depgraph.dropped_outputs(scan) == []   # ys is returned


def test_depgraph_carry_feeding_a_later_slot_is_read():
    """A carry var that is passthrough at its own slot AND returned at a
    later slot flows somewhere every iteration — it must NOT be dead
    (every occurrence counts, not just the first)."""
    def fn(x):
        def body(carry, _):
            a, _b = carry
            return (a, a), None

        (a, b), _ = lax.scan(body, (x, x + 1.0), None, length=3)
        return a + b

    jaxpr = depgraph.trace(fn, jax.ShapeDtypeStruct((4,), jnp.float64))
    [scan] = depgraph.scan_eqns(jaxpr.jaxpr.eqns)
    slots = depgraph.scan_carry_slots(scan)
    assert not slots[0].dead, slots
    with pytest.raises(ValueError, match="no scan"):
        depgraph.scan_body(_toy_jaxpr().jaxpr.eqns)


def test_depgraph_iter_eqns_paths():
    def fn(x):
        def body(c, _):
            return c * 2.0, None

        c, _ = lax.scan(body, x, None, length=2)
        return c

    jaxpr = depgraph.trace(fn, jax.ShapeDtypeStruct((4,), jnp.float64))
    paths = {e.primitive.name: path
             for path, e in depgraph.iter_eqns(jaxpr.jaxpr)}
    assert paths["scan"] == ()
    assert paths["mul"] == (("scan", "jaxpr"),)
    assert not depgraph.path_has_conditional(paths["mul"])


# ---------------------------------------------------------------------------
# graphcheck invariants: passing / must-trip / baseline-suppressed
# ---------------------------------------------------------------------------

def test_graphcheck_clean_program_has_no_findings():
    """PASSING case for every graph rule at once: an unconditional-
    collective, callback-free, f64-preserving, lean toy program."""
    fs = graphcheck.audit_jaxpr("toy", _toy_jaxpr())
    assert fs == []


@pytest.mark.parametrize("drill", sorted(drills.DRILLS))
def test_drills_trip_their_rules(drill, devices8):
    """MUST-TRIP case for every rule: each seeded-bad drill reports
    exactly the rules it was built to violate."""
    findings, expected = drills.run(drill)
    rules = {f.rule for f in findings}
    assert set(expected) <= rules, (drill, rules)


def test_graphcheck_repo_builders_audit_clean(devices8):
    """The acceptance pin: the full builder matrix audits clean (any
    future violation lands in CI with the rule named)."""
    findings = graphcheck.run()
    assert findings == [], [str(f) for f in findings]


def test_graphcheck_specs_are_not_vacuous(devices8):
    """Stale-audit guard: the audited programs must actually contain
    collectives and scans, or the invariants pin nothing."""
    with graphcheck.pinned_native_config():
        specs = graphcheck.program_specs()
        assert len(specs) >= 30
        dist = [s for s in specs if ".dist" in s.name]
        scans = [s for s in specs if "scan" in s.name]
        assert len(dist) >= 15 and scans
        ncoll = 0
        for spec in dist[:4] + scans[:2]:
            fn, args = spec.build()
            jaxpr = depgraph.trace(fn, *args)
            ncoll += len(depgraph.collectives(jaxpr.jaxpr))
        assert ncoll > 10


def test_graphcheck_hbm_denominator_is_per_shard(devices8):
    """Inside a shard_map body the blow-up budget denominator is the
    body's own (per-shard) input bytes — a 16x-per-shard broadcast
    temporary on a 2x2 mesh is only 4x the GLOBAL inputs and would
    otherwise slip under the 8x budget by exactly the mesh size."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("row", "col"))

    def body(x):
        big = jnp.broadcast_to(x, (16,) + x.shape) * 2.0
        return big.sum(axis=0)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("row", "col"),
                           out_specs=P("row", "col"), check_vma=False)
    jaxpr = depgraph.trace(fn, jax.ShapeDtypeStruct((16, 16), jnp.float64))
    fs = graphcheck.audit_jaxpr("shardtoy", jaxpr)
    assert any(f.rule == "graph-hbm-blowup" for f in fs), \
        [str(f) for f in fs]


def test_graphcheck_hbm_factor_is_configurable():
    """The blow-up budget is a knob: the clean toy program trips once
    the budget drops below its honest ~1x intermediates."""
    fs = graphcheck.audit_jaxpr("toy", _toy_jaxpr(), hbm_factor=0.5)
    assert any(f.rule == "graph-hbm-blowup" for f in fs)


def test_baseline_workflow_suppresses_graph_findings(tmp_path, devices8):
    """SUPPRESSED case for graph rules: a finding whose key is in the
    committed baseline no longer fails the gate; fixing it reports the
    key as stale."""
    findings, _ = drills.run("hbm_blowup")
    assert findings
    base = tmp_path / "baseline.json"
    write_baseline(str(base), findings)
    new, stale = diff_baseline(findings, load_baseline(str(base)))
    assert new == [] and stale == []
    # fixed code -> no findings -> every baselined key reported stale
    new, stale = diff_baseline([], load_baseline(str(base)))
    assert new == [] and stale == sorted({f.key for f in findings})


def test_baseline_rejects_malformed(tmp_path):
    bad = tmp_path / "b.json"
    bad.write_text(json.dumps({"findings": "nope"}))
    with pytest.raises(ValueError, match="baseline"):
        load_baseline(str(bad))
    assert load_baseline(str(tmp_path / "missing.json")) == []


# ---------------------------------------------------------------------------
# lint rules: passing / must-trip / suppressed for each
# ---------------------------------------------------------------------------

ALGO_PATH = "dlaf_tpu/algorithms/fake.py"


def _rules(src, path=ALGO_PATH):
    return {f.rule for f in lint.lint_source(src, path)}


def test_lint_unregistered_knob_cases():
    trip = 'import os\nV = os.environ.get("DLAF_NOT_A_KNOB")\n'
    ok = 'import os\nV = os.environ.get("DLAF_LOG")\n'   # registered field
    sup = ('import os\nV = os.environ.get("DLAF_NOT_A_KNOB")'
           '  # dlaf: disable=lint-unregistered-knob(test hook)\n')
    assert "lint-unregistered-knob" in _rules(trip)
    assert "lint-unregistered-knob" not in _rules(ok)
    assert "lint-unregistered-knob" not in _rules(sup)
    # multi-line statements are suppressible from any of their lines
    multi = ('import os\nV = os.environ.get(\n'
             '    "DLAF_NOT_A_KNOB"'
             '  # dlaf: disable=lint-unregistered-knob(test hook)\n)\n')
    assert "lint-unregistered-knob" not in _rules(multi)
    # non-DLAF env reads are out of scope
    other = 'import os\nV = os.environ.get("JAX_PLATFORMS")\n'
    assert "lint-unregistered-knob" not in _rules(other)


def test_lint_traced_metric_cases():
    trip = ('from dlaf_tpu import obs\n'
            'def _build_x(dist, mesh):\n'
            '    def fn(s):\n'
            '        obs.counter("dlaf_x_total", mode="a").inc()\n'
            '        return s\n'
            '    return fn\n')
    guarded = trip.replace(
        '        obs.counter("dlaf_x_total", mode="a").inc()\n',
        '        if obs.metrics_active():\n'
        '            obs.counter("dlaf_x_total", mode="a").inc()\n')
    sup = trip.replace(
        '.inc()\n',
        '.inc()  # dlaf: disable=lint-unguarded-traced-metric(host-side '
        'builder accounting, runs once per build)\n')
    assert "lint-unguarded-traced-metric" in _rules(trip)
    assert "lint-unguarded-traced-metric" not in _rules(guarded)
    assert "lint-unguarded-traced-metric" not in _rules(sup)
    # outside the traced layers the rule does not apply
    assert "lint-unguarded-traced-metric" not in _rules(
        trip, "dlaf_tpu/health/fake.py")


def test_lint_np_in_traced_cases():
    trip = ('import jax\nimport numpy as np\n'
            '@jax.jit\n'
            'def f(a):\n'
            '    return np.abs(a)\n')
    # np on static index math at builder level (not in a nested def) is
    # the documented-legal pattern
    ok = ('import numpy as np\n'
          'def _build_x(dist, mesh, nb):\n'
          '    idx = np.arange(nb)\n'
          '    def fn(s):\n'
          '        return s[idx[0]]\n'
          '    return fn\n')
    sup = trip.replace(
        'return np.abs(a)\n',
        'return np.abs(a)  # dlaf: disable=lint-np-in-traced(constant-'
        'folded at trace time on purpose)\n')
    assert "lint-np-in-traced" in _rules(trip)
    assert "lint-np-in-traced" not in _rules(ok)
    assert "lint-np-in-traced" not in _rules(sup)
    # nested def inside a _build_* builder is a traced body
    nested = ('import numpy as np\n'
              'def _build_x(dist, mesh):\n'
              '    def fn(s):\n'
              '        return np.abs(s)\n'
              '    return fn\n')
    assert "lint-np-in-traced" in _rules(nested)
    # outside algorithms/eigensolver the rule does not apply
    assert "lint-np-in-traced" not in _rules(trip, "dlaf_tpu/comm/fake.py")


def test_lint_host_sync_cases():
    trip = ('import jax\n'
            'def f(a):\n'
            '    return jax.device_get(a)\n')
    printer = 'def f(x):\n    print(x)\n'
    sup = trip.replace(
        'return jax.device_get(a)\n',
        'return jax.device_get(a)  # dlaf: disable=lint-host-sync(debug '
        'helper, never on the hot path)\n')
    assert "lint-host-sync" in _rules(trip)
    assert "lint-host-sync" in _rules(printer)
    assert "lint-host-sync" not in _rules(sup)
    # allow-listed host boundaries: miniapps and the tridiag host stage
    assert "lint-host-sync" not in _rules(
        printer, "dlaf_tpu/miniapp/fake.py")
    assert "lint-host-sync" not in _rules(
        trip, "dlaf_tpu/eigensolver/tridiag_solver.py")
    # outside dlaf_tpu/ (tests, scripts) the rule does not apply
    assert "lint-host-sync" not in _rules(printer, "scripts/fake.py")


def test_lint_suppression_reason_cases():
    bare = ('import os\nV = os.environ.get("DLAF_NOT_A_KNOB")'
            '  # dlaf: disable=lint-unregistered-knob\n')
    rules = _rules(bare)
    # a reason-less suppression is itself a finding AND does not suppress
    assert "lint-suppression-reason" in rules
    assert "lint-unregistered-knob" in rules
    good = bare.replace("disable=lint-unregistered-knob",
                        "disable=lint-unregistered-knob(justified)")
    rules = _rules(good)
    assert "lint-suppression-reason" not in rules
    assert "lint-unregistered-knob" not in rules


def test_lint_env_write_is_not_a_read():
    """Setting an env var (propagating a knob to a child process) is a
    write — only Load-context subscripts count as unregistered reads."""
    write = 'import os\nos.environ["DLAF_NOT_A_KNOB"] = "1"\n'
    read = 'import os\nV = os.environ["DLAF_NOT_A_KNOB"]\n'
    assert "lint-unregistered-knob" not in _rules(write)
    assert "lint-unregistered-knob" in _rules(read)


def test_lint_empty_walk_refuses_to_pass(tmp_path):
    """Zero files scanned must raise, not report a vacuously clean
    gate (a wrong --root would otherwise disable the linter)."""
    with pytest.raises(FileNotFoundError, match="vacuously"):
        lint.run(str(tmp_path))
    with pytest.raises(SystemExit) as e:
        analysis_main(["--lint-only", "--root", str(tmp_path)])
    assert e.value.code == 2


def test_pinned_native_config_restores_caller_struct_config():
    """A programmatically-installed Configuration survives a graphcheck
    audit: the exit path re-installs the caller's active config, not
    the env-derived defaults."""
    import dlaf_tpu.config as config

    config.initialize(config.Configuration(dc_level_batch="1"))
    try:
        with graphcheck.pinned_native_config():
            assert config.get_configuration().dc_level_batch == "0"
        assert config.get_configuration().dc_level_batch == "1"
    finally:
        config.initialize(config.Configuration())


def test_lint_suppression_in_string_is_inert():
    """Only real COMMENT tokens suppress (or trip the bare-suppression
    rule): a docstring quoting the syntax is neither a phantom finding
    nor a silent suppressor."""
    doc = ('"""Usage: append # dlaf: disable=lint-host-sync to a '
           'line."""\n')
    assert _rules(doc) == set()
    # a string-literal marker on an offending line must NOT suppress
    quoted = ('import os\n'
              'V = os.environ.get("DLAF_NOT_A_KNOB"), '
              '"# dlaf: disable=lint-unregistered-knob(quoted)"\n')
    assert "lint-unregistered-knob" in _rules(quoted)


def test_lint_syntax_error_is_a_finding():
    assert "lint-syntax-error" in _rules("def f(:\n")


import os as _os

#: Repo root derived from this file, so the acceptance pins hold from
#: any pytest invocation directory.
REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))


def test_lint_repo_is_clean():
    """The acceptance pin: the tree lints clean against the committed
    (empty) baseline."""
    assert lint.run(REPO) == []


def test_lint_key_is_line_number_free():
    """Baseline keys must survive unrelated edits: the same violation
    at a different line keeps its key."""
    a = lint.lint_source('import os\nV = os.environ.get("DLAF_NOPE")\n',
                         ALGO_PATH)
    b = lint.lint_source('import os\n\n\nV = os.environ.get("DLAF_NOPE")\n',
                         ALGO_PATH)
    assert [f.key for f in a] == [f.key for f in b]
    assert a[0].site != b[0].site   # the human report still moves


# ---------------------------------------------------------------------------
# CLI: exit codes + baseline diff + drill semantics
# ---------------------------------------------------------------------------

def test_cli_lint_only_clean_and_failing(tmp_path, capsys):
    # clean tree, empty baseline -> 0
    assert analysis_main(["--lint-only", "--root", REPO]) == 0
    assert "PASSED" in capsys.readouterr().out
    # a seeded-bad file under a fake root -> 1 with the rule named
    root = tmp_path / "repo"
    (root / "dlaf_tpu" / "algorithms").mkdir(parents=True)
    (root / "dlaf_tpu" / "algorithms" / "bad.py").write_text(
        'import os\nV = os.environ.get("DLAF_NOT_A_KNOB")\n')
    assert analysis_main(["--lint-only", "--root", str(root)]) == 1
    out = capsys.readouterr().out
    assert "lint-unregistered-knob" in out and "NEW" in out


def test_cli_write_baseline_then_clean(tmp_path, capsys, devices8):
    root = tmp_path / "repo"
    (root / "dlaf_tpu" / "algorithms").mkdir(parents=True)
    bad = root / "dlaf_tpu" / "algorithms" / "bad.py"
    bad.write_text('import os\nV = os.environ.get("DLAF_NOT_A_KNOB")\n')
    base = root / ".analysis_baseline.json"
    # --write-baseline demands a FULL run: a partial one would overwrite
    # the shared baseline with only the selected checker's findings,
    # silently erasing the other checker's grandfathered keys
    with pytest.raises(SystemExit) as e:
        analysis_main(["--lint-only", "--root", str(root),
                       "--write-baseline"])
    assert e.value.code == 2
    assert analysis_main(["--root", str(root), "--write-baseline"]) == 0
    assert load_baseline(str(base))
    # grandfathered -> gate passes; fixing the file -> stale key report
    assert analysis_main(["--lint-only", "--root", str(root)]) == 0
    bad.write_text("\n")
    assert analysis_main(["--lint-only", "--root", str(root)]) == 0
    assert "stale" in capsys.readouterr().out


def test_cli_drill_exit_codes(capsys, devices8):
    """A drill must exit 1 (proof the gate can fail) and name its rule;
    a drill that stops tripping must exit 3, not 1."""
    assert analysis_main(["--drill", "lint_violation"]) == 1
    assert "lint-unregistered-knob" in capsys.readouterr().out
    # sabotage: a drill that produces no findings is a broken checker
    import dlaf_tpu.analysis.drills as drills_mod

    orig = drills_mod.DRILLS["lint_violation"]
    drills_mod.DRILLS["lint_violation"] = (lambda: [], orig[1])
    try:
        assert analysis_main(["--drill", "lint_violation"]) == 3
    finally:
        drills_mod.DRILLS["lint_violation"] = orig
    with pytest.raises(KeyError, match="unknown drill"):
        drills.run("nonexistent")
    # a typo'd drill name via the CLI is a usage error (2), NEVER the
    # rc=1 "drill tripped" success contract CI greps for
    with pytest.raises(SystemExit) as e:
        analysis_main(["--drill", "nonexistent"])
    assert e.value.code == 2


def test_committed_baseline_is_valid():
    """The committed baseline EXISTS (load_baseline maps a missing file
    to empty for the gate, so existence must be pinned separately),
    parses, and carries only known-rule keys (currently empty: the tree
    is clean end to end)."""
    path = _os.path.join(REPO, ".analysis_baseline.json")
    assert _os.path.exists(path), "committed baseline file is missing"
    keys = load_baseline(path)
    assert isinstance(keys, list)
    for k in keys:
        assert k.split("|", 1)[0].startswith(("graph-", "lint-")), k


def test_finding_str_and_key():
    f = Finding("lint-host-sync", "a.py:3", "msg", key_detail="a.py|x")
    assert f.key == "lint-host-sync|a.py|x"
    assert str(f) == "a.py:3: [lint-host-sync] msg"
    assert Finding("r", "s", "m").key == "r|s"
