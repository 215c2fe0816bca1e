#!/usr/bin/env python
"""CI bench-regression gate over the append-only measurement history.

    python scripts/bench_gate.py --replay                  # CI smoke mode
    python scripts/bench_gate.py --fresh obs_artifact.jsonl [...]

Compares fresh measurements against a noise-aware baseline derived from
the git-tracked ``.bench_history.jsonl`` (121+ entries; the trajectory
the bench artifacts cite). Per key ``(variant, platform, n, nb, workload,
dtype)``:

* **baseline** = median of the ``--best-k`` (default 3) best historical
  GFlop/s — median-of-best, so one lucky outlier cannot ratchet the bar
  and one slow wedge-window entry cannot lower it;
* **fresh**    = the best GFlop/s among the new measurements for that
  key (matching bench.py's own best-of-reps protocol);
* **regression** iff ``fresh < (1 - tolerance) * baseline`` (default
  tolerance 0.10 — an injected 20 % slowdown must trip the gate, run-
  to-run noise must not);
* keys with fewer than ``--min-history`` (default 3) historical entries
  are **report-only**: a new benchmark arm needs a few rounds of history
  before it can gate anyone.

Fresh measurements come from ``--fresh`` files — obs JSONL artifacts
whose ``bench_result`` records carry the measurement payload (bench.py's
per-variant artifacts), or bare history-style line files. ``--replay``
instead replays the history's own best entry per key as the fresh
measurement — the hermetic CI mode: clean history must exit 0, and
``--inject-slowdown 0.2`` (the synthetic-regression drill ci/run.sh
smoke runs) must exit 1, proving the gate would catch a real 20 % loss.

The history is schema-validated first (``dlaf_tpu.obs.sinks`` history
schema — the ``--history`` mode of the validator CLI): a malformed or
non-finite line fails the gate loudly instead of skewing a baseline.

``workload="serve"`` lines (bench.py's serving arm, docs/serving.md)
additionally face a HISTORY-FREE absolute leg: their batched-vs-
loop-of-singles ``speedup`` field must be >= ``--min-serve-speedup``
(default 3.0 — the ISSUE-11 acceptance floor). Like accuracy_gate's
analytic-budget leg, this gates a brand-new serve measurement before
any history accumulates, and a committed serve history line keeps the
floor enforced in every ``--replay``.

``workload="fstep"`` lines (bench.py's fused-step A/B arm, ISSUE 19,
docs/pallas_panel.md "Fused step kernel") face a history-free
COMPLETENESS leg: the pair is the claim — when any fstep line is fresh,
both the pinned composed-chain arm (``fstep``) and the fused-step arm
(``fstep+fs1``) must be present, so a half-pair cannot pass as an A/B.

``workload="fleet"`` lines (bench.py's multi-replica serve-tier arm,
ISSUE 18, docs/fleet.md) carry the third history-free leg: their
N-replica vs 1-replica requests/s ``speedup`` field must be >=
``--min-fleet-scaling`` (default 0.8 — the single-threaded router's
wire serialization bounds toy-size CPU scaling at parity-ish; the
floor trips routing collapse, not transport physics).

Exit status: 0 = no regression; 1 = regression (or invalid history /
no usable fresh measurements); 2 = usage error.
"""

from __future__ import annotations

import argparse
import math
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dlaf_tpu.obs.sinks import (read_history_records, read_records,
                                validate_history_line)


def worst_step_category(paths) -> str | None:
    """The largest per-step category wall (incl. step-boundary gaps)
    summed across the fresh artifacts' ``critpath`` records, as a human
    line, or None when no artifact carries them. Delegates the
    ``<algo>.stepNNN <category>`` vocabulary to ``perf_diff.extract`` —
    single owner — so the verdict and the explainer name steps
    identically."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from perf_diff import extract
    except ImportError:
        return None
    acc: dict = {}
    for p in paths:
        try:
            facts = extract(read_records(p))
        except (OSError, ValueError):
            continue
        for lbl, v in facts["step_cat"].items():
            acc[lbl] = acc.get(lbl, 0.0) + v
    if not acc:
        return None
    lbl, v = max(acc.items(), key=lambda kv: kv[1])
    return f"{lbl} ({v * 1e3:.2f} ms)"


def measurement_key(line: dict) -> tuple:
    """The baseline key: (variant, platform, n, nb, workload, dtype).
    The ISSUE-7 5-tuple plus dtype — a float32 arm must never gate a
    float64 baseline (different flop weights, same label otherwise)."""
    return (line.get("variant"), line.get("platform"), line.get("n"),
            line.get("nb"), line.get("workload") or "cholesky",
            line.get("dtype"))


def fmt_key(key: tuple) -> str:
    variant, platform, n, nb, workload, dtype = key
    wl = "" if workload == "cholesky" else f" workload={workload}"
    return f"{variant} [{platform}] n={n} nb={nb} {dtype}{wl}"


def load_fresh(paths) -> list:
    """Measurement lines from ``--fresh`` files: ``bench_result`` records
    of obs artifacts (payload = the measurement line), or bare
    history-style lines. Invalid lines are rejected loudly."""
    fresh = []
    for path in paths:
        for r in read_records(path):
            if not isinstance(r, dict):
                raise ValueError(f"{path}: non-object record")
            line = r.get("payload") if r.get("type") == "bench_result" else \
                (r if "gflops" in r and "type" not in r else None)
            if line is None:
                continue        # spans/metrics/logs ride along in artifacts
            errors = validate_history_line(line)
            if errors:
                raise ValueError(f"{path}: invalid fresh measurement: "
                                 + "; ".join(errors))
            fresh.append(line)
    return fresh


def baselines(history, best_k: int) -> dict:
    """{key: (baseline gflops, n_history)} — median of the best_k best."""
    per_key: dict = {}
    for line in history:
        per_key.setdefault(measurement_key(line), []).append(line["gflops"])
    return {key: (statistics.median(sorted(vals, reverse=True)[:best_k]),
                  len(vals))
            for key, vals in per_key.items()}


DEFAULT_MIN_SERVE_SPEEDUP = 3.0

#: History-free floor on the fleet arm's N-replica vs 1-replica
#: requests/s ratio (ISSUE 18, docs/fleet.md). The single-threaded
#: router serializes every request onto the wire, so at the arm's toy
#: CPU sizes the bound is protocol cost, not compute — the honest
#: expectation there is parity-ish (measured 1.03-1.09x at n=64-128,
#: 3 replicas). 0.8 trips the real failure modes — every bucket
#: hash-colliding onto one replica, failover thrash re-dispatching the
#: steady state — without demanding scaling the transport can't give;
#: on TPU-class program runtimes the replicas' parallel compute is the
#: point and the ratio sits well above 1.
DEFAULT_MIN_FLEET_SCALING = 0.8


def _best_speedup_per_key(fresh, workload: str) -> dict:
    """Best finite ``speedup`` field per key among ``workload`` lines —
    the bench protocol is best-of, so one slow pass must not trip a key
    whose best pass cleared the bar."""
    best: dict = {}
    for line in fresh:
        if line.get("workload") != workload:
            continue
        s = line.get("speedup")
        if not isinstance(s, (int, float)) or isinstance(s, bool) \
                or not math.isfinite(s):
            continue
        key = measurement_key(line)
        if key not in best or s > best[key]:
            best[key] = float(s)
    return best


def run_gate(history, fresh, *, tolerance: float, min_history: int,
             best_k: int, log=print,
             min_serve_speedup: float = DEFAULT_MIN_SERVE_SPEEDUP,
             min_fleet_scaling: float = DEFAULT_MIN_FLEET_SCALING) -> int:
    """Compare fresh bests against history baselines; returns the number
    of regressed keys. Keys without fresh measurements are skipped (the
    gate judges what this run measured, not what it skipped — bench.py's
    budget/wedge handling legitimately drops arms); keys with thin
    history are report-only.

    ``workload="serve"`` lines additionally carry the ISSUE-11 absolute
    floor: the batched-vs-loop-of-singles ``speedup`` field (bench.py's
    serve arm) must be >= ``min_serve_speedup`` — this leg is
    history-free (like accuracy_gate's analytic-budget leg), so a
    first-round serve measurement already gates."""
    base = baselines(history, best_k)
    fresh_best: dict = {}
    for line in fresh:
        key = measurement_key(line)
        if key not in fresh_best or line["gflops"] > fresh_best[key]:
            fresh_best[key] = line["gflops"]
    regressions = 0
    for key in sorted(fresh_best, key=fmt_key):
        new = fresh_best[key]
        if key not in base:
            log(f"NEW        {fmt_key(key)}: {new:.2f} GF/s "
                "(no history; report-only)")
            continue
        bl, n_hist = base[key]
        floor = (1.0 - tolerance) * bl
        if n_hist < min_history:
            log(f"THIN       {fmt_key(key)}: {new:.2f} vs baseline "
                f"{bl:.2f} GF/s ({n_hist} < {min_history} entries; "
                "report-only)")
            continue
        if new < floor:
            regressions += 1
            log(f"REGRESSION {fmt_key(key)}: {new:.2f} < {floor:.2f} GF/s "
                f"(baseline {bl:.2f} = median of best {best_k} over "
                f"{n_hist} entries, tolerance {tolerance:.0%})")
        else:
            log(f"OK         {fmt_key(key)}: {new:.2f} >= {floor:.2f} GF/s "
                f"(baseline {bl:.2f}, {n_hist} entries)")
    # serve-speedup floor: judge the BEST fresh speedup per key
    best_speedup = _best_speedup_per_key(fresh, "serve")
    for key in sorted(best_speedup, key=fmt_key):
        s = best_speedup[key]
        if s < min_serve_speedup:
            regressions += 1
            log(f"REGRESSION {fmt_key(key)}: batched-vs-singles speedup "
                f"{s:.2f}x < {min_serve_speedup:.1f}x (ISSUE-11 serving "
                "floor; history-free leg)")
        else:
            log(f"OK         {fmt_key(key)}: batched-vs-singles speedup "
                f"{s:.2f}x >= {min_serve_speedup:.1f}x")
    # fleet-scaling floor (ISSUE 18, docs/fleet.md): N replicas vs one
    # through the same router — history-free like the serve
    # leg, so a first-round fleet measurement already gates
    for key, s in sorted(_best_speedup_per_key(fresh, "fleet").items(),
                         key=lambda kv: fmt_key(kv[0])):
        if s < min_fleet_scaling:
            regressions += 1
            log(f"REGRESSION {fmt_key(key)}: fleet N-vs-1 scaling "
                f"{s:.2f}x < {min_fleet_scaling:.2f}x "
                "(ISSUE-18 fleet floor; history-free leg)")
        else:
            log(f"OK         {fmt_key(key)}: fleet N-vs-1 scaling "
                f"{s:.2f}x >= {min_fleet_scaling:.2f}x")
    # fused-step A/B completeness (ISSUE 19, docs/pallas_panel.md
    # "Fused step kernel"): the fstep workload is a PAIRED claim — a
    # fused-step measurement without its pinned composed-chain partner
    # (or vice versa) cannot support the step-gap story, so the gate
    # fails the half-pair loudly. History-free like the floors above.
    fstep_variants = {line.get("variant") for line in fresh
                      if line.get("workload") == "fstep"}
    if fstep_variants:
        missing = {"fstep", "fstep+fs1"} - fstep_variants
        if missing:
            regressions += 1
            log(f"REGRESSION fstep A/B pair incomplete: missing "
                f"{sorted(missing)} (ISSUE-19 fused-step leg; "
                "history-free)")
        else:
            log(f"OK         fstep A/B pair complete "
                f"({sorted(fstep_variants)})")
    return regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="bench-regression gate (see module docstring)")
    ap.add_argument("--history", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".bench_history.jsonl"))
    ap.add_argument("--fresh", nargs="*", default=[],
                    help="obs artifacts (bench_result records) or bare "
                         "measurement-line files with the fresh numbers")
    ap.add_argument("--replay", action="store_true",
                    help="replay the history's own best entry per key as "
                         "the fresh measurement (hermetic CI mode)")
    ap.add_argument("--tolerance", type=float, default=0.10)
    ap.add_argument("--min-history", type=int, default=3)
    ap.add_argument("--best-k", type=int, default=3)
    ap.add_argument("--inject-slowdown", type=float, default=0.0,
                    metavar="F",
                    help="scale every fresh measurement by (1 - F): the "
                         "synthetic-regression drill (CI runs F=0.2 and "
                         "requires a nonzero exit)")
    ap.add_argument("--min-serve-speedup", type=float,
                    default=DEFAULT_MIN_SERVE_SPEEDUP,
                    help="history-free floor on the serve arm's batched-"
                         "vs-singles speedup field (ISSUE 11: >= 3x)")
    ap.add_argument("--min-fleet-scaling", type=float,
                    default=DEFAULT_MIN_FLEET_SCALING,
                    help="history-free floor on the fleet arm's "
                         "N-replica vs 1-replica requests/s ratio "
                         "(ISSUE 18; docs/fleet.md)")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code not in (0, None) else 0
    if not args.replay and not args.fresh:
        print("bench_gate: need --fresh artifacts or --replay",
              file=sys.stderr)
        return 2
    if not 0.0 <= args.tolerance < 1.0 or not 0.0 <= args.inject_slowdown < 1.0:
        print("bench_gate: tolerance/inject-slowdown must be in [0, 1)",
              file=sys.stderr)
        return 2

    try:
        history = read_history_records(args.history)
    except (OSError, ValueError) as e:
        print(f"bench_gate: {e}", file=sys.stderr)
        return 1
    if args.replay:
        best_per_key: dict = {}
        for line in history:
            key = measurement_key(line)
            if key not in best_per_key \
                    or line["gflops"] > best_per_key[key]["gflops"]:
                best_per_key[key] = line
        fresh = list(best_per_key.values())
        mode = "replay"
    else:
        try:
            fresh = load_fresh(args.fresh)
        except (OSError, ValueError) as e:
            print(f"bench_gate: {e}", file=sys.stderr)
            return 1
        mode = f"fresh x{len(args.fresh)}"
    if not fresh:
        print("bench_gate: no fresh measurements found", file=sys.stderr)
        return 1
    if args.inject_slowdown:
        fresh = [dict(line, gflops=line["gflops"]
                      * (1.0 - args.inject_slowdown)) for line in fresh]
        mode += f" +{args.inject_slowdown:.0%} injected slowdown"

    print(f"bench_gate: {mode}, {len(history)} history entries, "
          f"{len(fresh)} fresh measurements "
          f"(tolerance {args.tolerance:.0%}, min-history "
          f"{args.min_history}, best-k {args.best_k})")
    regressions = run_gate(history, fresh, tolerance=args.tolerance,
                           min_history=args.min_history,
                           best_k=args.best_k,
                           min_serve_speedup=args.min_serve_speedup,
                           min_fleet_scaling=args.min_fleet_scaling)
    if regressions:
        print(f"bench_gate: {regressions} regressed key(s)",
              file=sys.stderr)
        # the per-step attribution is already in the fresh artifact
        # (ISSUE 16 critpath records): name the dominant step category
        # in the verdict itself, so the trip says WHERE before anyone
        # runs the explainer
        step = worst_step_category(args.fresh or [])
        if step is not None:
            print(f"bench_gate: dominant step category in fresh "
                  f"artifact: {step}", file=sys.stderr)
        # the explainer is one command away (ISSUE 14): diff the fresh
        # obs artifact against a known-good merged artifact — per-phase
        # device walls, compile seconds, retraces, comm bytes, overlap
        # fractions, accuracy — and the ranked report names the phase;
        # --json adds the per-step category deltas machine-readably
        fresh_art = args.fresh[0] if args.fresh else "<fresh.jsonl>"
        print("bench_gate: diagnose with: python scripts/perf_diff.py "
              f"<baseline_merged.jsonl> {fresh_art} [--json]",
              file=sys.stderr)
        return 1
    print("bench_gate: no regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
