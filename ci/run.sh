#!/usr/bin/env bash
# One-command reproducible CI gate (reference analog: `ci/mpi-ctest` +
# the RANK_N-labeled ctest tiers of `cmake/DLAF_AddTest.cmake:60-193`).
#
#   ci/run.sh smoke   — the `quick` marker tier (< ~2 min; per-push gate)
#   ci/run.sh main    — full suite minus the slow tier + both driver
#                       entry checks (the default; what a PR must pass)
#   ci/run.sh full    — everything: main + the slow deep-distributed tier
#
# Every tier finishes with the multi-chip sharding dry run: an 8-virtual-
# device CPU mesh jit of the full distributed training-step analog
# (`__graft_entry__.dryrun_multichip`), which is the in-repo stand-in for
# the reference's RANK_6 MPI jobs. All tiers are hermetic: CPU platform,
# no chip, no network.
set -euo pipefail
cd "$(dirname "$0")/.."

TIER=${1:-main}

# CI never takes a chip: the platform is held to the CPU in JAX's own
# spelling (chip_smoke.py is the on-chip check, run through the chip tool)
export JAX_PLATFORMS=cpu

case "$TIER" in
  smoke)
    # post-mortem evidence (ISSUE 14 satellite): every leg registers its
    # scratch dirs here; on ANY smoke failure the trap copies them into
    # one repo-local smoke_artifacts/ dir (gitignored) instead of
    # leaving the devtrace/flight/merged-JSONL evidence scattered in
    # per-leg mktemp dirs under /tmp
    SMOKE_KEEP=()
    archive_smoke_artifacts() {
      rc=$?
      if [ "$rc" -ne 0 ] && [ "${#SMOKE_KEEP[@]}" -gt 0 ]; then
        dest="smoke_artifacts"
        rm -rf "$dest"; mkdir -p "$dest"
        for p in "${SMOKE_KEEP[@]}"; do
          if [ -e "$p" ]; then cp -r "$p" "$dest/" || true; fi
        done
        echo "smoke FAILED (rc=$rc): evidence archived in $dest/" >&2
        ls "$dest" >&2
      fi
      exit "$rc"
    }
    trap archive_smoke_artifacts EXIT
    python -m pytest tests/ -q -m quick
    echo "== smoke: miniapp_cholesky observability artifact =="
    # distributed run on a 2x2 virtual-CPU grid so the artifact carries
    # real collective byte counters; the validator fails the tier on any
    # missing or non-finite field (NaN GFlop/s must not scrape as data)
    # comm look-ahead pinned ON (the CPU auto would resolve it off): the
    # artifact must additionally carry the dlaf_comm_overlapped_total
    # trace-time counters and finite per-axis collective byte counts —
    # the audit trail that the hoisted-collective programs were built
    # (docs/comm_overlap.md)
    # per-rank artifact convention (%r -> jax.process_index()) + program
    # telemetry (ISSUE 7): compile walls, retrace counters, and HBM
    # gauges must land in the artifact; obs.aggregate merges the
    # per-rank files into one timeline and exports a Chrome trace
    # accuracy telemetry rides the same run (DLAF_ACCURACY=1,
    # docs/accuracy.md): every timed run probes its factor in-graph and
    # the merged artifact must carry the accuracy records
    # (--require-accuracy) that scripts/accuracy_gate.py gates below
    # device-timeline attribution rides the same run (ISSUE 14): the
    # trace dir arms the jax.profiler Chrome trace that obs.devtrace
    # attributes below — per-phase device walls, measured overlap,
    # coverage — gated by --require-devtrace
    OBS_DIR=$(mktemp -d)
    SMOKE_KEEP+=("$OBS_DIR")
    OBS_ART="$OBS_DIR/miniapp_cholesky.r%r.jsonl"
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
      DLAF_METRICS_PATH="$OBS_ART" DLAF_PROGRAM_TELEMETRY=1 \
      DLAF_ACCURACY=1 DLAF_TRACE_DIR="$OBS_DIR/trace" \
      DLAF_CHOLESKY_LOOKAHEAD=1 DLAF_COMM_LOOKAHEAD=1 \
      python -m dlaf_tpu.miniapp.miniapp_cholesky -m 256 -b 64 \
        --grid-rows 2 --grid-cols 2 --nruns 2
    python -m dlaf_tpu.obs.aggregate "$OBS_DIR"/miniapp_cholesky.r*.jsonl \
      -o "$OBS_DIR/merged.jsonl" --chrome "$OBS_DIR/trace.json"
    python -m dlaf_tpu.obs.validate "$OBS_DIR/merged.jsonl" \
      --require-spans --require-gflops --require-collectives \
      --require-comm-overlap --require-telemetry --require-accuracy
    # the Chrome export must be valid trace-event JSON with spans from
    # EVERY rank that produced an artifact
    python - "$OBS_DIR" <<'EOF'
import glob, json, sys
d = sys.argv[1]
doc = json.load(open(f"{d}/trace.json"))
evs = doc["traceEvents"]
span_pids = {e["pid"] for e in evs if e.get("ph") == "X" and e.get("tid") == 0}
# the rank-from-filename convention has ONE owner (obs.aggregate);
# unresolved-rank placeholder files map >= UNRESOLVED_RANK_BASE
from dlaf_tpu.obs.aggregate import UNRESOLVED_RANK_BASE, infer_rank
ranks = set()
for i, p in enumerate(sorted(glob.glob(f"{d}/miniapp_cholesky.r*.jsonl"))):
    rk = infer_rank(p, i)
    if rk < UNRESOLVED_RANK_BASE:
        ranks.add(rk)
assert ranks and span_pids >= ranks, (ranks, span_pids)
print(f"chrome trace ok: {len(evs)} events, span ranks {sorted(span_pids)}")
EOF
    echo "== smoke: device-timeline attribution (obs.devtrace, ISSUE 14) =="
    # the traced 2x2 run's profiler artifact, attributed end-to-end: the
    # enriched artifact must carry >= 1 finite measured_overlap record
    # with positive collective time AND coverage >= the documented floor
    # (sinks.DEVTRACE_COVERAGE_FLOOR) — --require-devtrace gates both
    python -m dlaf_tpu.obs.devtrace "$OBS_DIR/trace" \
      "$OBS_DIR/merged.jsonl" -o "$OBS_DIR/devtrace.jsonl" \
      | tee "$OBS_DIR/devtrace_report.txt"
    grep -q "MXU-overlapped" "$OBS_DIR/devtrace_report.txt"
    python -m dlaf_tpu.obs.validate "$OBS_DIR/devtrace.jsonl" \
      --require-devtrace
    # profile_summary's trace mode shares the parser (single owner) and
    # must print the per-phase attribution section for the same join
    python scripts/profile_summary.py "$OBS_DIR/trace" 10 \
      --jsonl "$OBS_DIR/merged.jsonl" > "$OBS_DIR/profile_summary.txt"
    grep -q "device-time attribution" "$OBS_DIR/profile_summary.txt"
    grep -q "coverage" "$OBS_DIR/profile_summary.txt"
    echo "== smoke: perf_diff must-trip drill (regression explainer) =="
    # identity diff must pass; an injected slowdown on the cholesky
    # phase must exit SPECIFICALLY 1 with the phase NAMED in a
    # REGRESSION line — the gate-to-diagnosis contract bench_gate's
    # verdict points at
    python scripts/perf_diff.py "$OBS_DIR/devtrace.jsonl" \
      "$OBS_DIR/devtrace.jsonl"
    drill_rc=0
    python scripts/perf_diff.py "$OBS_DIR/devtrace.jsonl" \
      "$OBS_DIR/devtrace.jsonl" --inject-slowdown cholesky=0.5 \
      > "$OBS_DIR/perf_diff_drill.log" 2>&1 || drill_rc=$?
    if [ "$drill_rc" -ne 1 ] \
        || ! grep -q "REGRESSION.*cholesky" "$OBS_DIR/perf_diff_drill.log"; then
      echo "perf_diff drill did not name the injected phase" \
           "(rc=$drill_rc, wanted rc=1 + REGRESSION naming cholesky)" >&2
      cat "$OBS_DIR/perf_diff_drill.log" >&2; exit 1
    fi
    echo "perf_diff correctly named the injected regressing phase"
    # zero-attribution rejection drill: a trace stripped of its
    # collectives attributes NO collective time — the devtrace artifact
    # it produces must be REJECTED by --require-devtrace
    python - "$OBS_DIR" <<'EOF'
import json, sys
from dlaf_tpu.obs import devtrace
from dlaf_tpu.obs.aggregate import merge_artifacts
d = sys.argv[1]
events = [e for e in devtrace.load_trace(f"{d}/trace")
          if devtrace.classify_op(e.get("name", ""))[0] != "collective"]
records = merge_artifacts([f"{d}/merged.jsonl"])
report = devtrace.attribute(events, records)
assert not report["overlap"], "stripped trace still attributed collectives"
with open(f"{d}/devtrace_nocoll.jsonl", "w") as f:
    for r in devtrace.records_from_report(report, "stripped.json.gz"):
        f.write(json.dumps(r) + "\n")
print("zero-collective artifact written")
EOF
    if python -m dlaf_tpu.obs.validate "$OBS_DIR/devtrace_nocoll.jsonl" \
        --require-devtrace > /dev/null 2>&1; then
      echo "--require-devtrace FAILED to reject the zero-attribution" \
           "artifact" >&2; exit 1
    fi
    echo "--require-devtrace correctly rejected the zero-attribution artifact"
    echo "== smoke: measured-MFU replay (mfu_table --measured fixture) =="
    # the committed devtrace fixture must replay hermetically into the
    # measured(dev) column (CPU-labeled)
    python scripts/mfu_table.py --no-ici --measured \
      > "$OBS_DIR/mfu_measured.txt"
    grep -q "measured(dev) GF/s" "$OBS_DIR/mfu_measured.txt"
    grep -Eq "cpu [0-9]+/[0-9]+" "$OBS_DIR/mfu_measured.txt"
    # the measured bound column must also fill from the critpath fixture
    grep -q "measured bound" "$OBS_DIR/mfu_measured.txt"
    echo "== smoke: critical-path attribution (obs.critpath, ISSUE 16) =="
    # the telemetry-armed traced run above carries schedule records:
    # reconstruct the live per-step timeline and gate the artifact with
    # --require-critpath (>= 1 multi-step critpath record at or above
    # the coverage floor + >= 1 whatif projection)
    python -m dlaf_tpu.obs.critpath "$OBS_DIR/trace" \
      "$OBS_DIR/merged.jsonl" -o "$OBS_DIR/critpath.jsonl" \
      | tee "$OBS_DIR/critpath_report.txt"
    grep -q "critical path" "$OBS_DIR/critpath_report.txt"
    grep -q "what-if" "$OBS_DIR/critpath_report.txt"
    python -m dlaf_tpu.obs.validate "$OBS_DIR/critpath.jsonl" \
      --require-critpath
    # hermetic fixture replay: the committed tests/fixtures/critpath/
    # fixture must reproduce per-step bound classification AND a NONZERO
    # measured step-boundary gap (the fixture's documented 2 ms
    # synthetic injection — scripts/refresh_devtrace_fixture.py)
    python -m dlaf_tpu.obs.critpath tests/fixtures/critpath/trace.json.gz \
      tests/fixtures/critpath/merged.jsonl \
      -o "$OBS_DIR/critpath_fixture.jsonl" > /dev/null
    python -m dlaf_tpu.obs.validate "$OBS_DIR/critpath_fixture.jsonl" \
      --require-critpath
    python - "$OBS_DIR" <<'EOF'
import json, sys
recs = [json.loads(l) for l in open(f"{sys.argv[1]}/critpath_fixture.jsonl")]
cps = [r for r in recs if r["type"] == "critpath" and r["algo"] == "cholesky"]
assert cps, "fixture replay produced no cholesky critpath record"
steps = [s for r in cps for s in r["steps"] if not s.get("empty")]
bounds = {s["bound"] for s in steps}
gaps = [s.get("gap_after_s", 0.0) for s in steps]
assert bounds, "no per-step bound classification"
assert max(gaps) > 0.0, f"fixture carries no step-boundary gap: {gaps}"
print(f"fixture replay ok: bounds {sorted(bounds)}, "
      f"max step-boundary gap {max(gaps) * 1e3:.3f} ms")
EOF
    echo "== smoke: gap-injection must-trip drill (critpath explainer) =="
    # inject a 5 ms stall before cholesky.step003 at the TRACE level and
    # diff against the clean fixture replay: perf_diff must exit
    # SPECIFICALLY 1 with a REGRESSION line naming the injected step's
    # gap — the step-level gate-to-diagnosis contract
    python -m dlaf_tpu.obs.critpath tests/fixtures/critpath/trace.json.gz \
      tests/fixtures/critpath/merged.jsonl \
      --inject-gap cholesky.step003=5.0 \
      -o "$OBS_DIR/critpath_injected.jsonl" > /dev/null
    drill_rc=0
    python scripts/perf_diff.py "$OBS_DIR/critpath_fixture.jsonl" \
      "$OBS_DIR/critpath_injected.jsonl" \
      > "$OBS_DIR/critpath_drill.log" 2>&1 || drill_rc=$?
    if [ "$drill_rc" -ne 1 ] \
        || ! grep -q "REGRESSION.*cholesky\.step003 gap" \
             "$OBS_DIR/critpath_drill.log"; then
      echo "gap-injection drill did not name the injected step" \
           "(rc=$drill_rc, wanted rc=1 + REGRESSION naming" \
           "cholesky.step003 gap)" >&2
      cat "$OBS_DIR/critpath_drill.log" >&2; exit 1
    fi
    echo "perf_diff correctly named the injected step-boundary gap"
    echo "== smoke: bench-regression gate (replay + injection drill) =="
    # clean replay of the committed history must pass; a 20% synthetic
    # slowdown must trip the gate (exit nonzero) — proving the gate
    # would catch a real regression of that size
    python scripts/bench_gate.py --replay
    if python scripts/bench_gate.py --replay --inject-slowdown 0.2 \
        > /dev/null 2>&1; then
      echo "bench_gate FAILED to flag a 20% injected slowdown" >&2; exit 1
    fi
    echo "bench_gate correctly flagged the injected slowdown"
    echo "== smoke: accuracy gate (fresh artifact + corruption drill) =="
    # the fresh accuracy records of the run above must pass BOTH gate
    # legs (analytic c*n*eps budget + drift vs the committed
    # .accuracy_history.jsonl), the history must validate standalone,
    # and the corrupt-collective drill — a REAL injected fault through
    # health.inject, not a synthetic number — must trip the gate
    python -m dlaf_tpu.obs.validate --accuracy-history .accuracy_history.jsonl
    python scripts/accuracy_gate.py --replay
    python scripts/accuracy_gate.py --fresh "$OBS_DIR/merged.jsonl"
    # require SPECIFICALLY exit 1 + a REGRESSION verdict: a crash in the
    # inject path (any other nonzero exit) must not masquerade as the
    # corruption-detection proof
    drill_rc=0
    python scripts/accuracy_gate.py --inject corrupt_collective \
      > "$OBS_DIR/accuracy_drill.log" 2>&1 || drill_rc=$?
    if [ "$drill_rc" -ne 1 ] \
        || ! grep -q "regressed key(s)" "$OBS_DIR/accuracy_drill.log"; then
      echo "accuracy_gate injection drill did not trip cleanly" \
           "(rc=$drill_rc)" >&2
      cat "$OBS_DIR/accuracy_drill.log" >&2; exit 1
    fi
    echo "accuracy_gate correctly flagged the injected corruption"
    echo "== smoke: fault-injection / graceful-degradation artifact =="
    # drive the robustness layer end-to-end (docs/robustness.md): a tiny
    # non-SPD robust_cholesky must recover through shift-retry (leaving
    # robust_cholesky.attempt spans), and an injected native-load failure
    # must degrade to numpy (leaving a dlaf_fallback_total counter); the
    # validator fails the tier unless the artifact records BOTH
    HEALTH_DIR=$(mktemp -d)
    SMOKE_KEEP+=("$HEALTH_DIR")
    HEALTH_ART="$HEALTH_DIR/health_metrics.jsonl"
    DLAF_METRICS_PATH="$HEALTH_ART" python - <<'EOF'
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import health, obs
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.eigensolver.band_to_tridiag import band_to_tridiag
from dlaf_tpu.health import inject
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(0)
x = rng.standard_normal((64, 64))
indef = x @ x.T + 64 * np.eye(64) - 100 * np.eye(64)   # non-SPD
mat = Matrix.from_global(indef, TileElementSize(16, 16))
res = health.robust_cholesky("L", mat)
assert res.attempts > 1 and res.infos[-1] == 0, res
print(f"robust_cholesky recovered: attempts={res.attempts} "
      f"shifts={list(res.shifts)}")
band = np.zeros((3, 16))
band[0] = np.arange(1, 17); band[1, :-1] = 0.5; band[2, :-2] = 0.1
with inject.force_native_failure():
    band_to_tridiag(band, 2)
c = obs.registry().counter("dlaf_fallback_total", site="band_to_tridiag",
                           reason="native_unavailable").snapshot()
assert c["value"] >= 1, c
print("native-load injection degraded to numpy:", c)
obs.flush()
EOF
    python -m dlaf_tpu.obs.validate "$HEALTH_ART" \
      --require-spans --require-retries --require-fallbacks
    echo "== smoke: fused Pallas panel route (panel_impl=fused) =="
    # tiny local + 2x2-distributed f32 cholesky on the FUSED panel route
    # (off-TPU the kernels run in interpret mode, docs/pallas_panel.md);
    # the artifact must carry the trace-time
    # dlaf_panel_kernel_total{impl="fused"} counters AND a finite
    # accuracy record next to them
    PANEL_DIR=$(mktemp -d)
    SMOKE_KEEP+=("$PANEL_DIR")
    PANEL_ART="$PANEL_DIR/panel_metrics.jsonl"
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
      DLAF_METRICS_PATH="$PANEL_ART" DLAF_PANEL_IMPL=fused DLAF_ACCURACY=1 \
      python - <<'EOF'
import numpy as np
import scipy.linalg as sla
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.obs import accuracy

C.initialize()
rng = np.random.default_rng(0)
x = rng.standard_normal((64, 64)).astype(np.float32)
a = x @ x.T + 64 * np.eye(64, dtype=np.float32)
ref = sla.cholesky(a, lower=True)
for grid_shape in (None, (2, 2)):
    grid = Grid(*grid_shape) if grid_shape else None
    mat = Matrix.from_global(a, TileElementSize(16, 16), grid=grid)
    fac = cholesky("L", mat)
    rel = abs(np.tril(fac.to_numpy()) - ref).max() / abs(ref).max()
    assert rel < 1e-5, rel
    accuracy.emit("ci_panel", "cholesky_residual",
                  accuracy.cholesky_residual(
                      "L", Matrix.from_global(a, TileElementSize(16, 16),
                                              grid=grid), fac),
                  n=64, nb=16, c=60.0, dtype=np.float32, of=fac.storage)
fused = obs.registry().counter("dlaf_panel_kernel_total", impl="fused",
                               op="potrf").snapshot()
assert fused["value"] >= 8, fused   # 4 steps x (local + dist)
print("fused panel smoke ok:", fused)
obs.flush()
EOF
    python -m dlaf_tpu.obs.validate "$PANEL_ART" --require-accuracy
    python - "$PANEL_ART" <<'EOF'
import json, sys
recs = [json.loads(line) for line in open(sys.argv[1])]
mets = [m for r in recs if r.get("type") == "metrics"
        for m in r["metrics"]]
fused = [m for m in mets if m["name"] == "dlaf_panel_kernel_total"
         and m["labels"].get("impl") == "fused"]
assert fused and all(m["value"] > 0 for m in fused), fused
print(f"panel artifact ok: {len(fused)} fused kernel counter series")
EOF
    echo "== smoke: disable_pallas must-trip drill (panel route) =="
    # non-strict leg: the injected pallas-off must COUNT the degradation
    # at site=panel and once-announce it; strict leg: the same injection
    # must exit SPECIFICALLY 1 with DegradationError named (any other
    # exit = a crash masquerading as detection — PR 8/9 drill contract)
    PANEL_DRILL_LOG=$(mktemp)
    drill0_rc=0
    # metrics must be armed or the fallback counter is a no-op singleton
    DLAF_PANEL_IMPL=fused DLAF_METRICS_PATH=$(mktemp -d)/panel_drill.jsonl \
      python - > "$PANEL_DRILL_LOG" 2>&1 <<'EOF' || drill0_rc=$?
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.health import inject
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(0)
x = rng.standard_normal((32, 32)).astype(np.float32)
a = x @ x.T + 32 * np.eye(32, dtype=np.float32)
with inject.disable_pallas():
    cholesky("L", Matrix.from_global(a, TileElementSize(8, 8)))
c = obs.registry().counter("dlaf_fallback_total", site="panel",
                           reason="injected_off").snapshot()
assert c["value"] >= 1, c
print("panel fallback counted:", c)
EOF
    if [ "$drill0_rc" -ne 0 ] \
        || ! grep -q "panel fallback counted" "$PANEL_DRILL_LOG"; then
      echo "panel fallback counter leg failed (rc=$drill0_rc)" >&2
      cat "$PANEL_DRILL_LOG" >&2; exit 1
    fi
    grep -q "degraded path at 'panel'" "$PANEL_DRILL_LOG" || {
      echo "panel degradation was not once-announced" >&2
      cat "$PANEL_DRILL_LOG" >&2; exit 1; }
    drill_rc=0
    DLAF_PANEL_IMPL=fused DLAF_STRICT=1 python - > "$PANEL_DRILL_LOG" 2>&1 \
      <<'EOF' || drill_rc=$?
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.health import inject
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(0)
x = rng.standard_normal((32, 32)).astype(np.float32)
a = x @ x.T + 32 * np.eye(32, dtype=np.float32)
with inject.disable_pallas():
    cholesky("L", Matrix.from_global(a, TileElementSize(8, 8)))
raise SystemExit(3)   # reaching here = the strict raise never fired
EOF
    if [ "$drill_rc" -ne 1 ] \
        || ! grep -q "DegradationError" "$PANEL_DRILL_LOG"; then
      echo "disable_pallas panel drill did not trip cleanly" \
           "(rc=$drill_rc, wanted rc=1 + DegradationError)" >&2
      cat "$PANEL_DRILL_LOG" >&2; exit 1
    fi
    echo "disable_pallas panel drill tripped as required (DegradationError)"
    echo "== smoke: fused step kernel route (step_impl=fused, ISSUE 19) =="
    # tiny local + 2x2-distributed f32 cholesky on the FUSED STEP route
    # (one pallas_call per strip-bearing blocked step: panel potrf +
    # strip solve + adjacent trailing slab, docs/pallas_panel.md "Fused
    # step kernel"; off-TPU the kernel runs in interpret mode); the
    # artifact must carry the trace-time
    # dlaf_step_kernel_total{impl="fused"} counters AND a finite
    # accuracy record next to them
    STEP_DIR=$(mktemp -d)
    SMOKE_KEEP+=("$STEP_DIR")
    STEP_ART="$STEP_DIR/step_metrics.jsonl"
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
      DLAF_METRICS_PATH="$STEP_ART" DLAF_STEP_IMPL=fused DLAF_ACCURACY=1 \
      python - <<'EOF'
import numpy as np
import scipy.linalg as sla
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.matrix.matrix import Matrix
from dlaf_tpu.obs import accuracy

C.initialize()
rng = np.random.default_rng(0)
x = rng.standard_normal((64, 64)).astype(np.float32)
a = x @ x.T + 64 * np.eye(64, dtype=np.float32)
ref = sla.cholesky(a, lower=True)
for grid_shape in (None, (2, 2)):
    grid = Grid(*grid_shape) if grid_shape else None
    mat = Matrix.from_global(a, TileElementSize(16, 16), grid=grid)
    fac = cholesky("L", mat)
    rel = abs(np.tril(fac.to_numpy()) - ref).max() / abs(ref).max()
    assert rel < 1e-5, rel
    accuracy.emit("ci_step", "cholesky_residual",
                  accuracy.cholesky_residual(
                      "L", Matrix.from_global(a, TileElementSize(16, 16),
                                              grid=grid), fac),
                  n=64, nb=16, c=60.0, dtype=np.float32, of=fac.storage)
fused = obs.registry().counter("dlaf_step_kernel_total",
                               impl="fused").snapshot()
assert fused["value"] >= 6, fused   # 3 strip-bearing steps x (local + dist)
print("fused step smoke ok:", fused)
obs.flush()
EOF
    python -m dlaf_tpu.obs.validate "$STEP_ART" --require-accuracy
    python - "$STEP_ART" <<'EOF'
import json, sys
recs = [json.loads(line) for line in open(sys.argv[1])]
mets = [m for r in recs if r.get("type") == "metrics"
        for m in r["metrics"]]
fused = [m for m in mets if m["name"] == "dlaf_step_kernel_total"
         and m["labels"].get("impl") == "fused"]
assert fused and all(m["value"] > 0 for m in fused), fused
print(f"step artifact ok: {len(fused)} fused step counter series")
EOF
    echo "== smoke: fused step degrade must-trip drill (VMEM budget) =="
    # the ladder's automatic-degrade contract, drilled end to end: a
    # starved DLAF_STEP_VMEM_LIMIT must land the explicitly-requested
    # fused step route on the composed per-op chain, COUNTING the
    # fallback at site=step reason=vmem_budget and once-announcing it;
    # the injected route-off must count reason=injected_off the same
    # way; and the same starvation under DLAF_STRICT=1 must exit
    # SPECIFICALLY 1 naming DegradationError (any other exit = a crash
    # masquerading as detection — PR 8/9 drill contract)
    STEP_DRILL_LOG=$(mktemp)
    sdrill0_rc=0
    DLAF_STEP_IMPL=fused DLAF_STEP_VMEM_LIMIT=1024 \
      DLAF_METRICS_PATH=$(mktemp -d)/step_drill.jsonl \
      python - > "$STEP_DRILL_LOG" 2>&1 <<'EOF' || sdrill0_rc=$?
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.health import inject
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(0)
x = rng.standard_normal((32, 32)).astype(np.float32)
a = x @ x.T + 32 * np.eye(32, dtype=np.float32)
cholesky("L", Matrix.from_global(a, TileElementSize(8, 8)))
c = obs.registry().counter("dlaf_fallback_total", site="step",
                           reason="vmem_budget").snapshot()
assert c["value"] >= 1, c
print("step vmem fallback counted:", c)
EOF
    if [ "$sdrill0_rc" -ne 0 ] \
        || ! grep -q "step vmem fallback counted" "$STEP_DRILL_LOG"; then
      echo "step vmem fallback counter leg failed (rc=$sdrill0_rc)" >&2
      cat "$STEP_DRILL_LOG" >&2; exit 1
    fi
    grep -q "degraded path at 'step'" "$STEP_DRILL_LOG" || {
      echo "step degradation was not once-announced" >&2
      cat "$STEP_DRILL_LOG" >&2; exit 1; }
    sdrill1_rc=0
    DLAF_STEP_IMPL=fused DLAF_METRICS_PATH=$(mktemp -d)/step_drill2.jsonl \
      python - > "$STEP_DRILL_LOG" 2>&1 <<'EOF' || sdrill1_rc=$?
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.health import inject
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(0)
x = rng.standard_normal((32, 32)).astype(np.float32)
a = x @ x.T + 32 * np.eye(32, dtype=np.float32)
with inject.disable_route("pallas"):
    cholesky("L", Matrix.from_global(a, TileElementSize(8, 8)))
c = obs.registry().counter("dlaf_fallback_total", site="step",
                           reason="injected_off").snapshot()
assert c["value"] >= 1, c
print("step injected_off fallback counted:", c)
EOF
    if [ "$sdrill1_rc" -ne 0 ] \
        || ! grep -q "step injected_off fallback counted" "$STEP_DRILL_LOG"
    then
      echo "step disable_route counter leg failed (rc=$sdrill1_rc)" >&2
      cat "$STEP_DRILL_LOG" >&2; exit 1
    fi
    sdrill_rc=0
    DLAF_STEP_IMPL=fused DLAF_STEP_VMEM_LIMIT=1024 DLAF_STRICT=1 \
      python - > "$STEP_DRILL_LOG" 2>&1 <<'EOF' || sdrill_rc=$?
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(0)
x = rng.standard_normal((32, 32)).astype(np.float32)
a = x @ x.T + 32 * np.eye(32, dtype=np.float32)
cholesky("L", Matrix.from_global(a, TileElementSize(8, 8)))
raise SystemExit(3)   # reaching here = the strict raise never fired
EOF
    if [ "$sdrill_rc" -ne 1 ] \
        || ! grep -q "DegradationError" "$STEP_DRILL_LOG"; then
      echo "step vmem-budget drill did not trip cleanly" \
           "(rc=$sdrill_rc, wanted rc=1 + DegradationError)" >&2
      cat "$STEP_DRILL_LOG" >&2; exit 1
    fi
    echo "fused step degrade drill tripped as required (DegradationError)"
    echo "== smoke: fstep bench A/B pair + completeness gate (ISSUE 19) =="
    # the fused-step A/B bench arms (plain fstep pins step_impl=xla,
    # fstep+fs1 pins fused) must land paired records in one artifact
    # that clears bench_gate --fresh; a HALF-pair artifact must trip
    # the gate's history-free completeness leg — the pair IS the claim
    FSTEP_BENCH_ART="$STEP_DIR/fstep_bench.jsonl"
    for v in fstep fstep+fs1; do
      DLAF_BENCH_VARIANT="$v" DLAF_METRICS_PATH="$FSTEP_BENCH_ART" \
        DLAF_BENCH_HISTORY_PATH="$STEP_DIR/bench_history.jsonl" \
        DLAF_BENCH_FSTEP_N=64 DLAF_ACCURACY=1 python bench.py > /dev/null
    done
    python scripts/bench_gate.py --fresh "$FSTEP_BENCH_ART"
    FSTEP_HALF_ART="$STEP_DIR/fstep_half.jsonl"
    DLAF_BENCH_VARIANT=fstep+fs1 DLAF_METRICS_PATH="$FSTEP_HALF_ART" \
      DLAF_BENCH_HISTORY_PATH="$STEP_DIR/bench_history.jsonl" \
      DLAF_BENCH_FSTEP_N=64 python bench.py > /dev/null
    if python scripts/bench_gate.py --fresh "$FSTEP_HALF_ART" \
        > /dev/null 2>&1; then
      echo "bench_gate FAILED to flag a half fstep A/B pair" >&2
      exit 1
    fi
    echo "bench_gate fstep completeness leg trips as required"
    echo "== smoke: batched serving layer (warm queue stream, ISSUE 11) =="
    # drive serve.Queue end-to-end (docs/serving.md): warmup a bucket
    # set, then a seeded mixed-shape cholesky/solve/eigh request stream
    # — the artifact must carry >= 1 batched dispatch, all-hit cache
    # (post-warmup contract), finite per-request latency, per-request
    # accuracy records, and zero post-warmup retraces (--require-serve)
    # ISSUE 13 additions to the same run: the live exporter is scraped
    # MID-STREAM (/metrics parses, counters monotone across two scrapes,
    # exemplar trace IDs live; /healthz parses and must agree with the
    # artifact's dispatch records), the flight recorder is ARMED and the
    # clean stream must produce NO flight artifact, and one request's
    # trace ID is saved for the aggregate --trace waterfall check below
    SERVE_DIR=$(mktemp -d)
    SMOKE_KEEP+=("$SERVE_DIR")
    SERVE_ART="$SERVE_DIR/serve_metrics.jsonl"
    SERVE_PORT=${DLAF_CI_METRICS_PORT:-$((18000 + RANDOM % 2000))}
    DLAF_METRICS_PATH="$SERVE_ART" DLAF_PROGRAM_TELEMETRY=1 \
      DLAF_ACCURACY=1 DLAF_SERVE_BUCKETS=32,64 DLAF_SERVE_BATCH=4 \
      DLAF_METRICS_PORT="$SERVE_PORT" DLAF_FLIGHT_RECORDER=64 \
      SERVE_TRACE_OUT="$SERVE_DIR/trace_id.txt" \
      SERVE_HEALTHZ_OUT="$SERVE_DIR/healthz.json" \
      python - <<'EOF'
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.serve import Queue, Request, get_service

C.initialize()
rng = np.random.default_rng(0)


def hpd(n):
    x = rng.standard_normal((n, n))
    return x @ x.T + n * np.eye(n)


reqs = [Request(op="cholesky", a=hpd(int(rng.integers(17, 33))))
        for _ in range(8)]
for _ in range(4):
    n = int(rng.integers(17, 33))
    reqs.append(Request(op="solve",
                        a=np.tril(rng.standard_normal((n, n)))
                        + 3 * np.eye(n),
                        b=rng.standard_normal((n, 5))))
for _ in range(4):
    x = rng.standard_normal((int(rng.integers(17, 33)),) * 2)
    reqs.append(Request(op="eigh", a=(x + x.T) / 2))
q = Queue()
q.warmup(reqs)
import json as _json
import os
import urllib.request

port = int(os.environ["DLAF_METRICS_PORT"])


def scrape(route, accept=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{route}")
    if accept:
        req.add_header("Accept", accept)
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.read().decode()


def counters(text):
    out = {}
    for ln in text.splitlines():
        name, _, val = ln.rpartition(" ")
        if name and ("_total" in name or "_count" in name) \
                and not name.startswith("#"):
            out[name] = float(val)
    return out


tickets = [q.submit(r) for r in reqs[:8]]
m1 = scrape("/metrics")            # MID-stream scrape (live process)
tickets += [q.submit(r) for r in reqs[8:]]
q.flush()
assert all(t.done for t in tickets)
for t in tickets:
    a = np.asarray(t.request.a)
    assert t.info == 0, (t.request.op, t.info)
    if t.request.op == "cholesky":
        fac = np.tril(t.result())
        ref = np.tril(a) + np.tril(a, -1).T
        assert np.allclose(fac @ fac.T, ref, atol=1e-8)
    elif t.request.op == "solve":
        x = t.result()
        assert np.allclose(np.tril(a) @ x, np.asarray(t.request.b),
                           atol=1e-8)
    else:
        w, v = t.result()
        assert np.allclose(a @ v, v * w[None, :], atol=1e-8)
st = get_service().stats()
assert st["misses"] == 0 and st["hit_rate"] == 1.0, st
print(f"serve smoke ok: {q.requests} requests over {q.dispatches} "
      f"dispatches, {st['warmups']} warmed programs, hit rate "
      f"{st['hit_rate']:.2f}")
# live scrape checks (ISSUE 13): both scrapes parse, counters monotone,
# the classic rendering stays exemplar-free (the 0.0.4 grammar has no
# exemplar clause), the OpenMetrics rendering carries exemplar trace
# IDs + the # EOF terminator, healthz saved for the artifact-agreement
# check in the driver
m2 = scrape("/metrics")
c1, c2 = counters(m1), counters(m2)
assert c1 and set(c1) <= set(c2), "second scrape lost counter series"
assert all(c2[k] >= v for k, v in c1.items()), \
    "counters not monotone across scrapes"
assert " # {" not in m2, "classic /metrics leaked an exemplar clause"
om = scrape("/metrics", accept="application/openmetrics-text;"
            "version=1.0.0,text/plain;version=0.0.4")
assert " # {trace_id=" in om, "no exemplar trace IDs on OpenMetrics scrape"
assert om.endswith("# EOF\n"), "OpenMetrics scrape lacks the terminator"
hz = _json.loads(scrape("/healthz"))
assert hz["status"] == "ok" and hz["queues"], hz
with open(os.environ["SERVE_HEALTHZ_OUT"], "w") as f:
    f.write(_json.dumps(hz))
obs.flush()
# end-to-end trace join (ISSUE 13 acceptance): ONE trace_id on the
# request's serve record, its dispatch (membership), its span records,
# and its accuracy record
from dlaf_tpu.obs.context import trace_matches

recs = obs.read_records(os.environ["DLAF_METRICS_PATH"])
tid = tickets[0].trace_id
mine = [r for r in recs if trace_matches(r, tid)]
types = {r["type"] for r in mine}
assert {"serve", "span", "accuracy"} <= types, types
events = {r.get("event") for r in mine if r["type"] == "serve"}
assert events == {"request", "dispatch"}, events
with open(os.environ["SERVE_TRACE_OUT"], "w") as f:
    f.write(tid)
print("live scrape ok: counters monotone, exemplars live, trace "
      f"{tid} joins {len(mine)} records")
EOF
    python -m dlaf_tpu.obs.validate "$SERVE_ART" --require-serve
    # must-NOT-trip leg: a clean stream with the recorder armed writes
    # no incident artifact — its existence IS the incident signal
    if [ -e "$SERVE_ART.flight.jsonl" ]; then
      echo "clean serve run produced a flight artifact" >&2; exit 1
    fi
    echo "clean serve run produced no flight artifact (must-not-trip ok)"
    echo "== smoke: trace waterfall (obs.aggregate --trace) =="
    SERVE_TRACE_ID=$(cat "$SERVE_DIR/trace_id.txt")
    python -m dlaf_tpu.obs.aggregate "$SERVE_ART" \
        --trace "$SERVE_TRACE_ID" > "$SERVE_DIR/trace_report.txt"
    for stage in "queue wait" compose program fetch unpad; do
      if ! grep -q "$stage" "$SERVE_DIR/trace_report.txt"; then
        echo "aggregate --trace waterfall missing stage '$stage'" >&2
        cat "$SERVE_DIR/trace_report.txt" >&2; exit 1
      fi
    done
    python -m dlaf_tpu.obs.aggregate "$SERVE_ART" --top-slow 3 \
        > "$SERVE_DIR/top_slow.txt"
    grep -q "slowest requests" "$SERVE_DIR/top_slow.txt"
    echo "aggregate --trace waterfall + --top-slow ok"
    # the mid-stream /healthz must agree with the artifact: queue
    # drained, dispatch count == the artifact's dispatch records,
    # breaker states are the documented names
    python - "$SERVE_ART" "$SERVE_DIR/healthz.json" <<'EOF'
import json
import sys

art, hz_path = sys.argv[1], sys.argv[2]
hz = json.load(open(hz_path))
recs = [json.loads(ln) for ln in open(art)]
disp = [r for r in recs if r.get("type") == "serve"
        and r.get("event") == "dispatch"]
q = hz["queues"][0]
assert q["pending"] == 0, q
assert q["dispatches"] == len(disp), (q["dispatches"], len(disp))
assert q["buckets"], "healthz carries no per-bucket table"
for site, b in q["buckets"].items():
    assert b["breaker"] in (None, "closed", "half_open", "open"), (site, b)
print(f"healthz/artifact agreement ok: {q['dispatches']} dispatches == "
      f"{len(disp)} artifact dispatch records, depth 0")
EOF
    echo "== smoke: flight-recorder must-trip drill (ISSUE 13) =="
    # leg A: a TRANSIENT fault retries and recovers — the retry record
    # must carry the members' trace IDs (the resilience leg of the
    # trace-join acceptance) and must NOT trip the recorder. leg B:
    # SUSTAINED fail_dispatch opens the bucket breaker — the flight
    # artifact must exist, hold the pre-trigger dispatch records, and
    # pass --require-flight
    FLIGHT_ART="$SERVE_DIR/flight_drill.jsonl"
    DLAF_METRICS_PATH="$FLIGHT_ART" DLAF_FLIGHT_RECORDER=64 \
      DLAF_CIRCUIT_THRESHOLD=2 DLAF_SERVE_RETRY_ATTEMPTS=2 \
      DLAF_SERVE_RETRY_BACKOFF_MS=0 python - <<'EOF'
import os

import numpy as np

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.health import inject
from dlaf_tpu.obs.context import trace_matches
from dlaf_tpu.serve import Queue, Request

C.initialize()
rng = np.random.default_rng(3)


def hpd(n):
    x = rng.standard_normal((n, n))
    return x @ x.T + n * np.eye(n)


q = Queue(buckets=(32,), batch=2, deadline_s=1e9)
q.warmup([Request(op="cholesky", a=hpd(24))])
with inject.fail_dispatch(count=1):
    tickets = [q.submit(Request(op="cholesky", a=hpd(24)))
               for _ in range(2)]
for t in tickets:
    t.result()                     # the retry recovered the batch
obs.flush()
recs = obs.read_records(os.environ["DLAF_METRICS_PATH"])
tid = tickets[0].trace_id
mine = [r for r in recs if trace_matches(r, tid)]
assert any(r.get("type") == "resilience" and r.get("event") == "retry"
           for r in mine), "retry record missing the batch trace stamp"
flight_path = os.environ["DLAF_METRICS_PATH"] + ".flight.jsonl"
assert not os.path.exists(flight_path), \
    "a recovered transient fault must not trip the flight recorder"
with inject.fail_dispatch(count=100):
    for i in range(3):
        try:
            q.submit(Request(op="cholesky", a=hpd(24)))
        except Exception:
            pass
assert os.path.exists(flight_path), \
    "breaker open did not trip the flight recorder"
print("flight drill ok: retry carries the trace, breaker-open dump "
      "landed")
obs.flush()
EOF
    if ! grep -q '"reason": "breaker_open"' "$FLIGHT_ART.flight.jsonl"; then
      echo "flight dump header does not name breaker_open" >&2; exit 1
    fi
    if ! grep -q '"type": "serve"' "$FLIGHT_ART.flight.jsonl"; then
      echo "flight dump holds no pre-trigger dispatch records" >&2; exit 1
    fi
    python -m dlaf_tpu.obs.validate "$FLIGHT_ART.flight.jsonl" \
        --require-flight
    echo "flight must-trip drill passed (--require-flight)"
    echo "== smoke: serve evict/miss must-trip drill =="
    # an evicted bucket hit by the next in-bucket request, and an
    # out-of-bucket shape, must BOTH recompile and bump the miss
    # counter (rc 0 + marker = the metrics recorded it); then the
    # drill's own artifact must FAIL --require-serve (miss dispatches +
    # a retraced serve site) — proving the validator leg has teeth
    SERVE_DRILL_ART="$SERVE_DIR/serve_drill.jsonl"
    SERVE_DRILL_LOG=$(mktemp)
    drill_rc=0
    DLAF_METRICS_PATH="$SERVE_DRILL_ART" DLAF_PROGRAM_TELEMETRY=1 \
      DLAF_SERVE_BUCKETS=32 DLAF_SERVE_BATCH=2 \
      python - > "$SERVE_DRILL_LOG" 2>&1 <<'EOF' || drill_rc=$?
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.serve import Queue, Request, get_service

C.initialize()
rng = np.random.default_rng(1)


def hpd(n):
    x = rng.standard_normal((n, n))
    return x @ x.T + n * np.eye(n)


q = Queue()
sample = [Request(op="cholesky", a=hpd(24))]
q.warmup(sample)
(spec,) = q.warmup_specs(sample)
svc = get_service()
assert svc.evict(spec), "warm bucket was not resident"
base = svc.stats()
# leg 1: the evicted bucket's next in-bucket request must recompile
q.submit(Request(op="cholesky", a=hpd(24)))
q.submit(Request(op="cholesky", a=hpd(20)))
st = svc.stats()
assert st["misses"] == base["misses"] + 1, (base, st)
assert st["compiles"] == base["compiles"] + 1, (base, st)
retrace = obs.registry().counter("dlaf_retrace_total",
                                 site=spec.site).snapshot()
assert retrace["value"] >= 2, retrace
# leg 2: an out-of-bucket shape (above every configured ceiling) lands
# in a cold power-of-two bucket — another miss + compile
q.submit(Request(op="cholesky", a=hpd(40)))
q.submit(Request(op="cholesky", a=hpd(40)))
st2 = svc.stats()
assert st2["misses"] == st["misses"] + 1, (st, st2)
assert st2["compiles"] == st["compiles"] + 1, (st, st2)
print(f"serve evict drill ok: misses {base['misses']}->{st2['misses']}, "
      f"recompiles {base['compiles']}->{st2['compiles']}, "
      f"retrace[{spec.site}]={retrace['value']}")
obs.flush()
EOF
    if [ "$drill_rc" -ne 0 ] \
        || ! grep -q "serve evict drill ok" "$SERVE_DRILL_LOG"; then
      echo "serve evict/miss drill failed (rc=$drill_rc)" >&2
      cat "$SERVE_DRILL_LOG" >&2; exit 1
    fi
    grep "serve evict drill ok" "$SERVE_DRILL_LOG"
    if python -m dlaf_tpu.obs.validate "$SERVE_DRILL_ART" --require-serve \
        > /dev/null 2>&1; then
      echo "--require-serve FAILED to flag the evict-drill artifact" \
           "(miss dispatches + retraced serve site)" >&2; exit 1
    fi
    echo "--require-serve correctly rejected the evict-drill artifact"
    echo "== smoke: serve bench arm + speedup gate =="
    # the serving workload arm (bench.py, workload=serve) must clear the
    # ISSUE-11 floor — batched entry >= 3x a loop of singleton cholesky
    # calls — enforced by bench_gate's history-free speedup leg; an
    # absurd floor must trip it (the leg's own must-trip)
    SERVE_BENCH_ART="$SERVE_DIR/serve_bench.jsonl"
    # history redirected: a CI container's numbers must never enter the
    # git-tracked drift baselines (the gate reads the obs artifact)
    DLAF_BENCH_VARIANT=serve DLAF_METRICS_PATH="$SERVE_BENCH_ART" \
      DLAF_BENCH_HISTORY_PATH="$SERVE_DIR/bench_history.jsonl" \
      DLAF_ACCURACY=1 python bench.py > /dev/null
    python scripts/bench_gate.py --fresh "$SERVE_BENCH_ART"
    if python scripts/bench_gate.py --fresh "$SERVE_BENCH_ART" \
        --min-serve-speedup 1000 > /dev/null 2>&1; then
      echo "bench_gate FAILED to flag a sub-floor serve speedup" >&2
      exit 1
    fi
    echo "bench_gate serve-speedup leg trips as required"
    echo "== smoke: chaos drill 1 — preempt at b2t -> resume -> identical =="
    # the kill-and-resume proof (docs/robustness.md §5), CROSS-PROCESS:
    # (a) an uninterrupted reference run records its eigenpairs; (b) a
    # checkpointing run is killed by inject.preempt at the b2t stage
    # boundary (must die with PreemptionError, nonzero exit); (c) a fresh
    # process resumes from the on-disk checkpoints and must reproduce the
    # reference BITWISE; the shared artifact must then validate under
    # --require-resilience (resume records present, no breaker open)
    RESUME_TMP=$(mktemp -d)
    SMOKE_KEEP+=("$RESUME_TMP")
    RESIL_ART="$RESUME_TMP/resilience.jsonl"
    python - "$RESUME_TMP" <<'EOF'
import sys
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.eigensolver.eigensolver import eigensolver
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(12)
n, nb = 48, 8
x = rng.standard_normal((n, n))
a = (x + x.T) / 2
res = eigensolver("L", Matrix.from_global(a, TileElementSize(nb, nb)))
np.savez(f"{sys.argv[1]}/ref.npz", w=np.asarray(res.eigenvalues),
         v=res.eigenvectors.to_numpy())
print("reference eigenpairs recorded")
EOF
    preempt_rc=0
    DLAF_RESUME_DIR="$RESUME_TMP/ck" DLAF_METRICS_PATH="$RESIL_ART" \
      python - > "$RESUME_TMP/preempt.log" 2>&1 <<'EOF' || preempt_rc=$?
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.eigensolver.eigensolver import eigensolver
from dlaf_tpu.health import inject
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(12)
n, nb = 48, 8
x = rng.standard_normal((n, n))
a = (x + x.T) / 2
with inject.preempt("b2t"):
    eigensolver("L", Matrix.from_global(a, TileElementSize(nb, nb)))
raise SystemExit(3)   # reaching here = the preemption never fired
EOF
    if [ "$preempt_rc" -eq 0 ] || [ "$preempt_rc" -eq 3 ] \
        || ! grep -q "PreemptionError" "$RESUME_TMP/preempt.log"; then
      echo "preemption drill did not kill the pipeline (rc=$preempt_rc)" >&2
      cat "$RESUME_TMP/preempt.log" >&2; exit 1
    fi
    DLAF_RESUME_DIR="$RESUME_TMP/ck" DLAF_METRICS_PATH="$RESIL_ART" \
      python - "$RESUME_TMP" <<'EOF'
import sys
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.eigensolver.eigensolver import eigensolver
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(12)
n, nb = 48, 8
x = rng.standard_normal((n, n))
a = (x + x.T) / 2
res = eigensolver("L", Matrix.from_global(a, TileElementSize(nb, nb)),
                  resume=True)
ref = np.load(f"{sys.argv[1]}/ref.npz")
np.testing.assert_array_equal(np.asarray(res.eigenvalues), ref["w"])
np.testing.assert_array_equal(res.eigenvectors.to_numpy(), ref["v"])
print("kill -> resume -> eigenpairs BITWISE identical to the "
      "uninterrupted run")
obs.flush()
EOF
    python -m dlaf_tpu.obs.validate "$RESIL_ART" --require-resilience
    echo "== smoke: chaos drill 2 — dispatch retry + breaker teeth =="
    # leg A: fail_dispatch twice -> the policy engine retries and the
    # stream succeeds; the artifact's retry records satisfy
    # --require-resilience. leg B (separate process/artifact): a
    # sustained fault exhausts the retries, the bucket breaker OPENS, and
    # the process dies mid-storm (os._exit models the real crash) — that
    # artifact must be REJECTED by --require-resilience (breaker left
    # open), proving the gate has teeth
    RETRY_DIR=$(mktemp -d)
    SMOKE_KEEP+=("$RETRY_DIR")
    DLAF_METRICS_PATH="$RETRY_DIR/retry.jsonl" python - <<'EOF'
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.health import inject
from dlaf_tpu.serve import ProgramService, Queue, Request

C.initialize()
rng = np.random.default_rng(3)
x = rng.standard_normal((24, 24))
a = x @ x.T + 24 * np.eye(24)
q = Queue(ProgramService(), batch=2, deadline_s=1e9, buckets=(32,),
          retry_attempts=3)
with inject.fail_dispatch(nth=0, count=2):
    t1 = q.submit(Request(op="cholesky", a=a))
    t2 = q.submit(Request(op="cholesky", a=a + np.eye(24)))
assert t1.done and t2.done, "retry did not recover the dispatch"
retries = [m for m in obs.registry().snapshot()
           if m["name"] == "dlaf_retry_total"
           and m["labels"].get("site", "").startswith("serve.")]
assert retries and sum(m["value"] for m in retries) >= 2, retries
print(f"fail_dispatch x2 recovered by retry "
      f"({int(sum(m['value'] for m in retries))} retries counted)")
obs.flush()
EOF
    python -m dlaf_tpu.obs.validate "$RETRY_DIR/retry.jsonl" \
      --require-resilience
    DLAF_METRICS_PATH="$RETRY_DIR/breaker.jsonl" python - <<'EOF'
import os
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.health import circuit, inject
from dlaf_tpu.health.errors import CircuitOpenError
from dlaf_tpu.serve import ProgramService, Queue, Request

C.initialize()
rng = np.random.default_rng(4)
x = rng.standard_normal((24, 24))
a = x @ x.T + 24 * np.eye(24)
q = Queue(ProgramService(), batch=1, deadline_s=1e9, buckets=(32,),
          retry_attempts=3)
with inject.fail_dispatch(nth=0, count=100):
    try:
        q.submit(Request(op="cholesky", a=a))
        raise SystemExit(3)   # the sustained fault must fail the dispatch
    except RuntimeError:
        pass
    (bucket,) = q.stats()["buckets"].values()
    assert bucket["breaker"] == "open", bucket
    try:
        q.submit(Request(op="cholesky", a=a))
        raise SystemExit(3)   # the open breaker must fail fast
    except CircuitOpenError:
        pass
    print("thrice-consecutive failure opened the breaker; fails fast")
    obs.flush()
    # model the real incident: the process dies while the breaker is
    # open (skip atexit/injection cleanup — the artifact must end in
    # the tripped state the validator exists to reject)
    os._exit(0)
EOF
    if python -m dlaf_tpu.obs.validate "$RETRY_DIR/breaker.jsonl" \
        --require-resilience > /dev/null 2>&1; then
      echo "--require-resilience FAILED to reject the open-breaker" \
           "artifact" >&2; exit 1
    fi
    echo "--require-resilience correctly rejected the open-breaker artifact"
    echo "== smoke: chaos drill 3 — overload shed, bounded depth =="
    # a burst at 2x DLAF_SERVE_MAX_DEPTH: the overflow must shed fast
    # with OverloadError (counted per bucket), pending depth must NEVER
    # exceed the bound, and every accepted ticket must complete — zero
    # stranded (docs/serving.md overload protection)
    DLAF_SERVE_MAX_DEPTH=8 DLAF_METRICS_PATH="$RETRY_DIR/overload.jsonl" \
      python - <<'EOF'
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.health.errors import OverloadError
from dlaf_tpu.serve import ProgramService, Queue, Request

C.initialize()
rng = np.random.default_rng(5)
q = Queue(ProgramService(), batch=16, deadline_s=1e9, buckets=(16,))
assert q.max_depth == 8, q.max_depth     # the env knob reached the queue
tickets, shed, max_seen = [], 0, 0
for i in range(16):                      # 2x the admission bound
    x = rng.standard_normal((12, 12))
    try:
        tickets.append(q.submit(Request(op="cholesky",
                                        a=x @ x.T + 12 * np.eye(12))))
    except OverloadError:
        shed += 1
    max_seen = max(max_seen, q.pending())
assert shed == 8 and len(tickets) == 8, (shed, len(tickets))
assert max_seen <= 8, f"depth {max_seen} exceeded the bound"
q.flush()
stranded = [t for t in tickets if not t.done and t.error is None]
assert not stranded, f"{len(stranded)} stranded tickets"
assert q.stats()["shed"] == 8, q.stats()
snap = [m for m in obs.registry().snapshot()
        if m["name"] == "dlaf_serve_shed_total"]
assert snap and sum(m["value"] for m in snap) == 8, snap
print(f"overload drill ok: shed={shed}, max depth {max_seen}/8, "
      f"0 stranded of {len(tickets)} accepted")
obs.flush()
EOF
    python -m dlaf_tpu.obs.validate "$RETRY_DIR/overload.jsonl"
    echo "== smoke: chaos drill 4 — fleet replica kill, zero loss =="
    # 3 REAL subprocess workers behind one fleet Router (docs/fleet.md):
    # a mixed cholesky/solve stream is mid-flight when the replica
    # holding unacked tickets dies by SIGKILL — every ticket must still
    # resolve with a CORRECT answer, zero tickets lost, >= 1 observed
    # redispatch, and the merged per-process artifact must PASS
    # --require-fleet (trace-stamped route records, zero-loss contract).
    # One driver script, three modes (FLEET_MODE): the kill drill, its
    # graceful SIGTERM twin, and the failover-off must-trip leg
    FLEET_DIR=$(mktemp -d)
    SMOKE_KEEP+=("$FLEET_DIR")
    cat > "$FLEET_DIR/drill.py" <<'EOF'
"""Fleet chaos-drill driver (ci/run.sh smoke; mode from FLEET_MODE)."""
import os
import signal
import subprocess
import sys
import time

import numpy as np

import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.fleet import Router
from dlaf_tpu.serve import Request, cholesky_spec

mode = os.environ["FLEET_MODE"]
C.initialize()
router = Router(port=0)
env = dict(os.environ, DLAF_METRICS_PATH=os.environ["FLEET_WORKER_ART"])
procs = [subprocess.Popen(
    [sys.executable, "-m", "dlaf_tpu.fleet.worker",
     "--connect", f"127.0.0.1:{router.port}", "--worker", str(k)],
    env=env) for k in range(3)]
deadline = time.monotonic() + 120
while True:
    states = router.stats()["workers"]
    if sum(1 for m in states.values() if m["state"] == "up") == 3:
        break
    assert time.monotonic() < deadline, f"workers never joined: {states}"
    router.poll()
    time.sleep(0.05)
router.warmup([cholesky_spec(batch=4, n=16, nb=16, dtype="float64")])

rng = np.random.default_rng(0)


def hpd(n):
    x = rng.standard_normal((n, n))
    return x @ x.T + n * np.eye(n)


reqs = [Request(op="cholesky", a=hpd(int(rng.integers(10, 17))))
        for _ in range(8)]
for _ in range(4):
    reqs.append(Request(op="solve",
                        a=np.tril(rng.standard_normal((12, 12)))
                        + 3 * np.eye(12),
                        b=rng.standard_normal((12, 3))))
tickets = [router.submit(r) for r in reqs[:6]]

# the victim: whichever replica holds an unresolved ticket's unacked
# dispatch — batch=4/huge-deadline guarantees a partial batch is still
# queued there, so the kill strands real work, not an idle socket
router.poll()
pending = [t for t in tickets if not t.resolved()]
assert pending, "no unacked tickets to strand (batch/deadline config?)"
victim = pending[0].attempts[-1]
vpid = router.stats()["workers"][victim]["pid"]
os.kill(vpid, signal.SIGTERM if mode == "sigterm" else signal.SIGKILL)
procs[victim].wait(timeout=60)

tickets += [router.submit(r) for r in reqs[6:]]  # routed around the hole
router.flush()
ok = router.join(tickets, timeout_s=180.0)
st = router.stats()
if mode == "nofailover":
    assert st["lost"] >= 1, st
    lost = [t for t in tickets if t.error is not None]
    assert lost, "failover off but no ticket was poisoned"
    for t in lost:
        try:
            t.result()
            raise SystemExit(3)  # a lost ticket must NOT answer
        except RuntimeError:
            pass
    print(f"failover OFF: {st['lost']} ticket(s) stranded as designed")
else:
    assert ok, f"stream did not complete: {st}"
    for t in tickets:
        a = np.asarray(t.request.a)
        if t.request.op == "cholesky":
            fac = np.tril(t.result())
            ref = np.tril(a) + np.tril(a, -1).T
            assert np.allclose(fac @ fac.T, ref, atol=1e-8)
        else:
            x = t.result()
            assert np.allclose(np.tril(a) @ x, np.asarray(t.request.b),
                               atol=1e-8)
    assert st["lost"] == 0, st
    assert st["workers"][victim]["state"] == "dead", st
    if mode == "sigkill":
        assert st["redispatches"] >= 1, st
        assert procs[victim].returncode != 0, "SIGKILL exited cleanly?"
    else:                       # sigterm: drained handbacks, NO failover
        assert st["redispatches"] == 0, st
        assert st["handbacks"] >= 1, st
        assert procs[victim].returncode == 0, procs[victim].returncode
    print(f"fleet {mode} drill ok: {len(tickets)} tickets resolved, "
          f"lost={st['lost']}, redispatches={st['redispatches']}, "
          f"handbacks={st['handbacks']}")
router.drain_fleet()
obs.flush()
for p in procs:
    if p.poll() is None:
        p.terminate()
        p.wait(timeout=30)
EOF
    DLAF_METRICS_PATH="$FLEET_DIR/kill_router.jsonl" \
      FLEET_WORKER_ART="$FLEET_DIR/kill_worker.r%r.jsonl" \
      FLEET_MODE=sigkill DLAF_SERVE_BATCH=4 DLAF_SERVE_BUCKETS=16 \
      DLAF_SERVE_DEADLINE_MS=60000 PYTHONPATH="$PWD" \
      python "$FLEET_DIR/drill.py"
    python -m dlaf_tpu.obs.aggregate "$FLEET_DIR"/kill_*.jsonl \
      -o "$FLEET_DIR/kill_merged.jsonl"
    python -m dlaf_tpu.obs.validate "$FLEET_DIR/kill_merged.jsonl" \
      --require-fleet
    # graceful twin: SIGTERM the same victim profile — the worker drains
    # (absorbs + hands back its undispatched tickets, exit 0) and the
    # router re-routes the handbacks with ZERO failover redispatches;
    # the artifact still passes --require-fleet (worker_dead carries
    # reason=drained, so no redispatch obligation applies)
    DLAF_METRICS_PATH="$FLEET_DIR/drain_router.jsonl" \
      FLEET_WORKER_ART="$FLEET_DIR/drain_worker.r%r.jsonl" \
      FLEET_MODE=sigterm DLAF_SERVE_BATCH=4 DLAF_SERVE_BUCKETS=16 \
      DLAF_SERVE_DEADLINE_MS=60000 PYTHONPATH="$PWD" \
      python "$FLEET_DIR/drill.py"
    python -m dlaf_tpu.obs.aggregate "$FLEET_DIR"/drain_*.jsonl \
      -o "$FLEET_DIR/drain_merged.jsonl"
    python -m dlaf_tpu.obs.validate "$FLEET_DIR/drain_merged.jsonl" \
      --require-fleet
    # must-trip: with failover OFF the same kill strands tickets — the
    # artifact carries ticket_lost records and --require-fleet must
    # REJECT it, proving the zero-loss contract has teeth
    DLAF_METRICS_PATH="$FLEET_DIR/off_router.jsonl" \
      FLEET_WORKER_ART="$FLEET_DIR/off_worker.r%r.jsonl" \
      FLEET_MODE=nofailover DLAF_FLEET_FAILOVER=0 DLAF_SERVE_BATCH=4 \
      DLAF_SERVE_BUCKETS=16 DLAF_SERVE_DEADLINE_MS=60000 \
      PYTHONPATH="$PWD" python "$FLEET_DIR/drill.py"
    python -m dlaf_tpu.obs.aggregate "$FLEET_DIR"/off_*.jsonl \
      -o "$FLEET_DIR/off_merged.jsonl"
    off_out=$(python -m dlaf_tpu.obs.validate \
      "$FLEET_DIR/off_merged.jsonl" --require-fleet 2>&1) && {
      echo "--require-fleet FAILED to reject the lost-ticket artifact" >&2
      exit 1
    }
    echo "$off_out" | grep -q "ticket_lost" || {
      echo "lost-ticket rejection did not name ticket_lost:" >&2
      echo "$off_out" >&2; exit 1
    }
    echo "--require-fleet correctly rejected the failover-off artifact"
    echo "== smoke: fleet bench arm + scaling gate =="
    # the fleet workload arm (bench.py, workload=fleet): requests/s over
    # N real subprocess replicas vs one through the same router, plus
    # the mid-stream SIGKILL recovery_s leg — gated by bench_gate's
    # history-free --min-fleet-scaling floor, whose must-trip is an
    # absurd floor the measured ratio cannot clear
    FLEET_BENCH_ART="$FLEET_DIR/fleet_bench.jsonl"
    DLAF_BENCH_VARIANT=fleet DLAF_METRICS_PATH="$FLEET_BENCH_ART" \
      DLAF_BENCH_HISTORY_PATH="$FLEET_DIR/bench_history.jsonl" \
      python bench.py > /dev/null
    python scripts/bench_gate.py --fresh "$FLEET_BENCH_ART"
    if python scripts/bench_gate.py --fresh "$FLEET_BENCH_ART" \
        --min-fleet-scaling 1000 > /dev/null 2>&1; then
      echo "bench_gate FAILED to flag a sub-floor fleet scaling" >&2
      exit 1
    fi
    echo "bench_gate fleet-scaling leg trips as required"
    echo "== smoke: eigensolver pipeline (batched D&C + pipelined bt) =="
    # distributed eigensolver on a 2x2 virtual-CPU grid with the two
    # ISSUE-6 knobs pinned ON (the CPU auto would resolve both off): the
    # artifact must carry the level-batched merge counters
    # (dlaf_dc_merges_total{mode=batched}) AND the hoisted bt-collective
    # counters (dlaf_comm_overlapped_total{algo=bt_*}) — the audit trail
    # that the batched/pipelined programs were actually built
    # (docs/eigensolver_perf.md)
    EIG_DIR=$(mktemp -d)
    SMOKE_KEEP+=("$EIG_DIR")
    EIG_ART="$EIG_DIR/eigensolver_metrics.jsonl"
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
      DLAF_METRICS_PATH="$EIG_ART" \
      DLAF_DC_LEVEL_BATCH=1 DLAF_BT_LOOKAHEAD=1 DLAF_DIST_STEP_MODE=unrolled \
      python - <<'EOF'
import numpy as np
import dlaf_tpu.config as C
from dlaf_tpu import obs
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.eigensolver.eigensolver import eigensolver
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(0)
n, nb = 64, 8
x = rng.standard_normal((n, n))
a = (x + x.T) / 2
res = eigensolver("L", Matrix.from_global(a, TileElementSize(nb, nb),
                                          grid=Grid(2, 2)))
q = res.eigenvectors.to_numpy()
resid = np.linalg.norm(a @ q - q * res.eigenvalues[None, :])
assert resid < 1e-10 * n, resid
print(f"eigensolver smoke ok: n={n} residual={resid:.2e}")
obs.flush()
EOF
    python -m dlaf_tpu.obs.validate "$EIG_ART" \
      --require-spans --require-dc-batch --require-bt-overlap
    echo "== smoke: sanitizers (debug_nans + transfer guard happy path) =="
    # dynamic counterpart of the static no-host-callback audit below: a
    # tiny local AND 2x2-distributed cholesky must neither produce NaNs
    # on the happy path (jax_debug_nans re-executes op-by-op on any NaN)
    # nor fetch device values mid-factorization (device->host transfer
    # guard; result fetch happens AFTER the guard, the caller's explicit
    # decision — the same contract test_health pins for with_info)
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
      python - <<'EOF'
import numpy as np
import jax
import dlaf_tpu.config as C
from dlaf_tpu.algorithms.cholesky import cholesky
from dlaf_tpu.comm.grid import Grid
from dlaf_tpu.common.index2d import TileElementSize
from dlaf_tpu.matrix.matrix import Matrix

C.initialize()
rng = np.random.default_rng(0)
for grid_shape in (None, (2, 2)):
    x = rng.standard_normal((32, 32))
    a = x @ x.T + 32 * np.eye(32)
    grid = Grid(*grid_shape) if grid_shape else None
    label = "2x2" if grid_shape else "local"
    # phase 1: NaN sanitizer armed, full run + fetch
    jax.config.update("jax_debug_nans", True)
    try:
        fac = cholesky("L", Matrix.from_global(a, TileElementSize(8, 8),
                                               grid=grid))
        l = np.tril(fac.to_numpy())
    finally:
        jax.config.update("jax_debug_nans", False)
    assert np.isfinite(l).all()
    assert np.allclose(l @ l.T, a, atol=1e-8), abs(l @ l.T - a).max()
    # phase 2: transfer guard armed — the hot path must not host-sync
    mat = Matrix.from_global(a, TileElementSize(8, 8), grid=grid)
    with jax.transfer_guard_device_to_host("disallow"):
        fac = cholesky("L", mat)
        jax.block_until_ready(fac.storage)
    print(f"sanitizer smoke ok: {label} (debug_nans + transfer guard)")
EOF
    ;;
  main)
    python -m pytest tests/ -q -m "not slow" ;;
  full)
    python -m pytest tests/ -q ;;
  *)
    echo "usage: ci/run.sh [smoke|main|full]" >&2; exit 2 ;;
esac

echo "== static analysis gate (jaxpr auditor + convention linter) =="
# every tier: the graph auditor traces every builder on the 8-virtual-
# device CPU platform (no compile/exec) and the AST linter walks
# dlaf_tpu/; any finding not in the committed .analysis_baseline.json
# fails the tier (docs/static_analysis.md)
python -m dlaf_tpu.analysis

echo "== static analysis must-trip drills =="
# like the bench/accuracy gates, the analysis gate must PROVE it can
# fail: each seeded-bad program must exit SPECIFICALLY 1 with its rule
# named in the log (exit 3 = the checker lost its teeth; any other exit
# = a crash masquerading as detection). Deliberately per-drill fresh
# interpreters — the exit-code contract IS the thing under test; the
# six processes cost ~45 s total, within every tier's budget
ANALYSIS_DRILL_LOG=$(mktemp)
# the drill list comes from the registry itself (--list-drills), so a
# drill added to analysis/drills.py is automatically exercised here; the
# CLI prints "drill <name>: tripped [<rules>] as required" only when
# every expected rule was reported, and exits 3 when a checker lost its
# teeth — so rc=1 + that line IS the proof, with the rules named
ANALYSIS_DRILLS=$(python -m dlaf_tpu.analysis --list-drills)
[ -n "$ANALYSIS_DRILLS" ] || { echo "no analysis drills found" >&2; exit 1; }
for drill in $ANALYSIS_DRILLS; do
  drill_rc=0
  python -m dlaf_tpu.analysis --drill "$drill" \
    > "$ANALYSIS_DRILL_LOG" 2>&1 || drill_rc=$?
  if [ "$drill_rc" -ne 1 ] \
      || ! grep -q "as required" "$ANALYSIS_DRILL_LOG"; then
    echo "analysis drill $drill did not trip cleanly" \
         "(rc=$drill_rc, wanted rc=1 + 'tripped ... as required')" >&2
    cat "$ANALYSIS_DRILL_LOG" >&2; exit 1
  fi
  grep "as required" "$ANALYSIS_DRILL_LOG"
done

echo "== ruff check (style linter; config in pyproject.toml) =="
if command -v ruff >/dev/null 2>&1; then
  ruff check .
else
  # hermetic CI images may lack ruff; the repo-specific conventions are
  # still enforced by the dlaf_tpu.analysis gate above
  echo "ruff not installed in this environment; skipping"
fi

echo "== driver entry: single-device compile check =="
python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
import __graft_entry__ as g
fn, args = g.entry()
jax.jit(fn)(*args).block_until_ready()
print("entry() ok")
EOF

echo "== driver entry: 8-device sharding dry run =="
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "CI tier '$TIER': PASSED"
