"""CLI validator for DLAF_METRICS_PATH artifacts (the CI gate).

    python -m dlaf_tpu.obs.validate <artifact.jsonl> [flags]

Flags:
    --require-spans         fail unless >= 1 span record
    --require-gflops        fail unless >= 1 span has finite derived gflops
    --require-collectives   fail unless a metrics snapshot carries a
                            positive dlaf_comm_collective_bytes_total
    --require-retries       fail unless >= 1 robust_cholesky.attempt span
                            with attempt >= 1 (an actual shifted retry —
                            the fault-injection smoke's audit trail)
    --require-fallbacks     fail unless a metrics snapshot carries a
                            positive dlaf_fallback_total
    --require-comm-overlap  fail unless a metrics snapshot carries positive
                            finite dlaf_comm_overlapped_total{algo,axis}
                            counters AND finite per-axis
                            dlaf_comm_collective_bytes_total for BOTH mesh
                            axes (the comm look-ahead audit trail,
                            docs/comm_overlap.md)
    --require-dc-batch      fail unless a metrics snapshot carries a
                            positive dlaf_dc_merges_total{mode=batched}
                            counter (the level-batched D&C audit trail,
                            docs/eigensolver_perf.md)
    --require-bt-overlap    fail unless a metrics snapshot carries a
                            positive dlaf_comm_overlapped_total counter
                            with a bt_* algo label (the pipelined
                            back-transform's hoisted collectives)
    --require-telemetry     fail unless the artifact carries the program
                            telemetry audit trail (DLAF_PROGRAM_TELEMETRY,
                            docs/observability.md): >= 1 finite
                            compile-seconds observation, finite HBM
                            accounting, and retrace evidence — each leg
                            satisfiable by a metrics snapshot OR by the
                            per-event program records
    --require-accuracy      fail unless >= 1 accuracy record carries a
                            finite value AND a finite bound_ratio (the
                            DLAF_ACCURACY audit trail, docs/accuracy.md;
                            informational-only or all-nonfinite artifacts
                            do not satisfy it)
    --require-serve         fail unless the artifact carries a warmed
                            steady-state serving trail (docs/serving.md):
                            >= 1 batched serve dispatch (lanes >= 2,
                            cache hit), ZERO cache-miss dispatches, >= 1
                            request record with finite latency, >= 1
                            per-request accuracy record (site serve),
                            and no serve bucket program retraced twice
                            (dlaf_retrace_total{site=serve.*} < 2)
    --require-resilience    fail unless the artifact carries the
                            resilience audit trail (docs/robustness.md):
                            >= 1 ``resilience`` record with event retry
                            or resume (recovery actually exercised), and
                            NO dlaf_circuit_state gauge left at the open
                            value (2) in the last metrics snapshot — a
                            run that ended with a tripped breaker must
                            fail the gate, not scrape as healthy
    --require-flight        validate the file as a flight-recorder
                            incident dump (docs/observability.md live
                            operations): >= 1 flight_trigger record with
                            a known reason AND >= 1 ordinary pre-trigger
                            record captured by the ring
    --require-devtrace      fail unless the artifact carries the
                            device-timeline attribution trail (ISSUE 14,
                            docs/observability.md): >= 1 measured_overlap
                            record with finite overlap_frac and POSITIVE
                            attributed collective device time, and >= 1
                            devtrace record with attribution coverage >=
                            the documented floor
                            (sinks.DEVTRACE_COVERAGE_FLOOR); NaN phase
                            walls are schema errors regardless
    --require-critpath      fail unless the artifact carries the
                            per-step critical-path attribution trail
                            (ISSUE 16, docs/observability.md): >= 1
                            critpath record with >= 1 step and join
                            coverage >= the documented floor
                            (sinks.CRITPATH_COVERAGE_FLOOR), and >= 1
                            whatif projection record; NaN step walls
                            are schema errors regardless
    --require-fleet         fail unless the artifact carries the
                            multi-replica zero-loss trail (docs/
                            fleet.md): >= 1 fleet record with event
                            route (the router actually dispatched),
                            ZERO ticket_lost records (any lost ticket
                            REJECTS the artifact — the exact failure
                            the fleet tier exists to prevent), and
                            every ungraceful worker_dead (reason !=
                            drained) answered by >= 1 redispatch
                            record (failover actually ran)
    --history               validate the file as an append-only bench
                            history log (.bench_history.jsonl: bare
                            measurement lines — finite gflops/t/n/nb,
                            non-empty variant/platform/dtype/ts/source)
                            instead of an obs artifact; incompatible with
                            the --require-* flags
    --accuracy-history      validate the file as an append-only accuracy
                            history log (.accuracy_history.jsonl: finite
                            value/bound_ratio/n/nb, non-empty site/metric/
                            platform/dtype/ts/source); incompatible with
                            --history and the --require-* flags
    --prom                  print the last metrics snapshot as Prometheus
                            text exposition after validating

Exit status 0 = schema-valid (and all required content present); 1 =
errors (printed one per line); 2 = usage error (unknown flag, or not
exactly one path). ``ci/run.sh smoke`` runs this over the miniapp
artifacts — missing or NaN fields fail the tier.
"""

from __future__ import annotations

import sys

from .metrics import prometheus_text
from .sinks import read_records, validate_history_records, validate_records


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    flags = {a for a in argv if a.startswith("--")}
    paths = [a for a in argv if not a.startswith("--")]
    known = {"--require-spans", "--require-gflops", "--require-collectives",
             "--require-retries", "--require-fallbacks",
             "--require-comm-overlap", "--require-dc-batch",
             "--require-bt-overlap", "--require-telemetry",
             "--require-accuracy", "--require-serve",
             "--require-resilience", "--require-flight",
             "--require-devtrace", "--require-critpath",
             "--require-fleet", "--history", "--accuracy-history",
             "--prom"}
    requires = {f for f in flags if f.startswith("--require-")}
    history_modes = flags & {"--history", "--accuracy-history"}
    if len(paths) != 1 or flags - known \
            or (history_modes and requires) or len(history_modes) > 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = paths[0]
    try:
        records = read_records(path)
    except (OSError, ValueError) as e:
        print(f"INVALID {path}: {e}", file=sys.stderr)
        return 1
    if history_modes:
        kind = "accuracy" if "--accuracy-history" in flags else "bench"
        errors = validate_history_records(records, kind)
        if errors:
            for e in errors:
                print(f"INVALID {path}: {e}", file=sys.stderr)
            return 1
        print(f"VALID {path}: {len(records)} {kind} history entries")
        return 0
    errors = validate_records(
        records,
        require_spans="--require-spans" in flags,
        require_gflops="--require-gflops" in flags,
        require_collectives="--require-collectives" in flags,
        require_retries="--require-retries" in flags,
        require_fallbacks="--require-fallbacks" in flags,
        require_comm_overlap="--require-comm-overlap" in flags,
        require_dc_batch="--require-dc-batch" in flags,
        require_bt_overlap="--require-bt-overlap" in flags,
        require_telemetry="--require-telemetry" in flags,
        require_accuracy="--require-accuracy" in flags,
        require_serve="--require-serve" in flags,
        require_resilience="--require-resilience" in flags,
        require_flight="--require-flight" in flags,
        require_devtrace="--require-devtrace" in flags,
        require_critpath="--require-critpath" in flags,
        require_fleet="--require-fleet" in flags)
    if errors:
        for e in errors:
            print(f"INVALID {path}: {e}", file=sys.stderr)
        return 1
    n_spans = sum(r.get("type") == "span" for r in records)
    n_logs = sum(r.get("type") == "log" for r in records)
    n_progs = sum(r.get("type") == "program" for r in records)
    n_acc = sum(r.get("type") == "accuracy" for r in records)
    n_serve = sum(r.get("type") == "serve" for r in records)
    n_res = sum(r.get("type") == "resilience" for r in records)
    n_flight = sum(r.get("type") == "flight_trigger" for r in records)
    n_devtrace = sum(r.get("type") in ("devtrace", "measured_overlap")
                     for r in records)
    n_critpath = sum(r.get("type") in ("schedule", "critpath", "whatif")
                     for r in records)
    n_fleet = sum(r.get("type") == "fleet" for r in records)
    snaps = [r for r in records if r.get("type") == "metrics"]
    ranks = sorted({r["rank"] for r in records if "rank" in r})
    extra = f", {n_progs} program events" if n_progs else ""
    extra += f", {n_acc} accuracy records" if n_acc else ""
    extra += f", {n_serve} serve records" if n_serve else ""
    extra += f", {n_res} resilience records" if n_res else ""
    extra += f", {n_flight} flight triggers" if n_flight else ""
    extra += f", {n_devtrace} devtrace records" if n_devtrace else ""
    extra += f", {n_critpath} critpath records" if n_critpath else ""
    extra += f", {n_fleet} fleet records" if n_fleet else ""
    extra += f", ranks {ranks}" if ranks else ""
    print(f"VALID {path}: {len(records)} records ({n_spans} spans, "
          f"{len(snaps)} metrics snapshots, {n_logs} logs{extra})")
    if "--prom" in flags and snaps:
        sys.stdout.write(prometheus_text(snaps[-1]["metrics"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
