#!/usr/bin/env python
"""Offline summary of dlaf_tpu observability artifacts.

Two input shapes, auto-detected:

* a ``DLAF_METRICS_PATH`` JSON-lines artifact (``dlaf_tpu.obs`` schema) —
  prints per-span aggregates (count/total/mean, best derived GFlop/s from
  the structured records, no stdout scraping), the collective byte/count
  counters per (kind, axis) from the last metrics snapshot, and any
  captured log events;
* a ``--dlaf:profile-dir`` / ``DLAF_TRACE_DIR`` directory — reads the
  newest ``plugins/profile/<ts>/*.trace.json.gz`` (Chrome trace event
  format; written alongside the xplane since the span tracer enables
  ``create_perfetto_trace``) and prints, per process track (device vs
  host threads), the top-N ops by total duration. This is the instrument
  for deciding WHERE config #1's wall time actually goes.
  The trace parsing is :mod:`dlaf_tpu.obs.devtrace`'s (ISSUE 14) —
  single owner, not a fork — and ``--jsonl merged.jsonl`` additionally
  prints the per-phase device-time attribution section (op classes per
  algorithm phase, measured overlap, coverage) for the trace joined to
  that artifact.

Usage: python scripts/profile_summary.py <profile_dir | metrics.jsonl> \\
           [top_n] [--jsonl merged.jsonl ...]
"""
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def newest_trace(root: str) -> str:
    """Kept as the documented entry point; the implementation moved to
    :func:`dlaf_tpu.obs.devtrace.newest_trace` (single parser owner)."""
    from dlaf_tpu.obs.devtrace import newest_trace as _newest

    return _newest(root)


def summarize_jsonl(path: str, top_n: int) -> None:
    """Aggregate a dlaf_tpu.obs JSONL artifact (schema: obs.sinks)."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from dlaf_tpu.obs import read_records

    records = read_records(path)
    spans = [r for r in records if r.get("type") == "span"]
    snaps = [r for r in records if r.get("type") == "metrics"]
    logs = [r for r in records if r.get("type") == "log"]
    progs = [r for r in records if r.get("type") == "program"]

    agg = collections.defaultdict(lambda: {"count": 0, "total": 0.0,
                                           "best_gflops": None})
    for s in spans:
        a = agg[s.get("name", "?")]
        a["count"] += 1
        a["total"] += s.get("dur_s", 0.0)
        g = s.get("gflops")
        if isinstance(g, (int, float)) and \
                (a["best_gflops"] is None or g > a["best_gflops"]):
            a["best_gflops"] = g
    print(f"== spans ({len(spans)} records) ==")
    ranked = sorted(agg.items(), key=lambda kv: -kv[1]["total"])[:top_n]
    for name, a in ranked:
        gf = (f"  best {a['best_gflops']:8.1f} GFlop/s"
              if a["best_gflops"] is not None else "")
        print(f"  {a['total'] * 1e3:10.2f} ms  x{a['count']:<4d} "
              f"mean {a['total'] / a['count'] * 1e3:8.2f} ms  {name}{gf}")

    # per-rank view when the artifact carries rank-stamped records (the
    # %r per-rank convention, docs/observability.md): the table code is
    # obs.aggregate's — single owner, not a fork
    if any("rank" in r for r in spans):
        from dlaf_tpu.obs.aggregate import format_skew_table, rank_skew_rows

        print("\n== per-rank span skew ==")
        for line in format_skew_table(rank_skew_rows(records), top_n):
            print(f"  {line}")

    if progs:
        print(f"\n== program telemetry ({len(progs)} events) ==")
        # every site with ANY program event gets a row: the in-body
        # retrace counters (tridiag.secular_batched etc.) emit retrace
        # events with no compile record, and hiding them would hide the
        # very compile-cost tail they exist to surface
        by_site = collections.defaultdict(lambda: {"n": 0, "compile": 0.0,
                                                   "peak": None})
        retraces = collections.Counter(p.get("site", "?") for p in progs
                                       if p.get("event") == "retrace")
        for p in progs:
            a = by_site[p.get("site", "?")]
            if p.get("event") != "compile":
                continue
            a["n"] += 1
            a["compile"] += p.get("compile_s", 0.0) or 0.0
            peak = (p.get("hbm") or {}).get("peak")
            if peak is not None:
                a["peak"] = max(a["peak"] or 0.0, peak)
        for site, a in sorted(by_site.items(), key=lambda kv: -kv[1]["compile"]):
            peak = (f"  peak {a['peak'] / 1024**3:.2f}G"
                    if a["peak"] is not None else "")
            print(f"  {a['compile']:8.2f} s compile  x{a['n']:<3d} "
                  f"traces {retraces.get(site, a['n']):<3d} {site}{peak}")

    if any(r.get("type") == "accuracy" for r in records):
        # accuracy table code is obs.aggregate's — single owner, not a
        # fork (docs/accuracy.md)
        from dlaf_tpu.obs.aggregate import (accuracy_rows,
                                            format_accuracy_table)

        print("\n== accuracy (worst bound_ratio per rank) ==")
        for line in format_accuracy_table(accuracy_rows(records), top_n):
            print(f"  {line}")

    serve = [r for r in records if r.get("type") == "serve"]
    resil = [r for r in records if r.get("type") == "resilience"]
    if serve or resil:
        print("\n== serve / resilience ==")
        reqs = [r for r in serve if r.get("event") == "request"]
        disp = [r for r in serve if r.get("event") == "dispatch"]
        if disp:
            hits = sum(r.get("cache") == "hit" for r in disp)
            print(f"  {len(disp)} dispatches ({hits} cache hits), "
                  f"{len(reqs)} requests")
        if reqs:
            # shared quantile computation (obs.metrics.quantile — the
            # same numpy-linear estimator behind the SLO window gauges
            # and bench.py's arms), not another hand-rolled p99
            from dlaf_tpu.obs.metrics import quantile

            lat = [r.get("total_s", 0.0) for r in reqs]
            print(f"  request latency: mean {sum(lat) / len(lat) * 1e3:.2f}"
                  f" ms  p99 {quantile(lat, 0.99) * 1e3:.2f} ms")
        if resil:
            events = collections.Counter(r.get("event", "?") for r in resil)
            print("  resilience events: "
                  + ", ".join(f"{k}={v}" for k, v in sorted(events.items())))
        if reqs:
            # requests section (ISSUE 13): slowest trace IDs with their
            # stage breakdown + per-op percentiles — the join code is
            # obs.aggregate's (request_rows/format_request_table),
            # single owner, not a fork
            from dlaf_tpu.obs.aggregate import (format_request_table,
                                                request_rows)
            from dlaf_tpu.obs.metrics import quantile

            print("\n== requests (slowest first; obs.aggregate "
                  "--trace <id> for the waterfall) ==")
            for line in format_request_table(request_rows(records),
                                             top_n=5):
                print(f"  {line}")
            by_op = collections.defaultdict(list)
            for r in reqs:
                by_op[r.get("op", "?")].append(r.get("total_s", 0.0))
            for op in sorted(by_op):
                lat = by_op[op]
                qs = "  ".join(
                    f"p{int(q * 100)} {quantile(lat, q) * 1e3:.2f} ms"
                    for q in (0.5, 0.95, 0.99))
                print(f"  {op:<9s} ({len(lat)} reqs): {qs}")
        # queue depth / shed / expired / breaker state from the last
        # snapshot (the gauges Queue.stats() exports — single owner of
        # the semantics, this is just the offline view)
        if snaps:
            rows = [m for m in snaps[-1]["metrics"]
                    if m.get("name") in ("dlaf_serve_depth",
                                         "dlaf_serve_shed_total",
                                         "dlaf_deadline_exceeded_total",
                                         "dlaf_circuit_state")]
            for m in sorted(rows, key=lambda m: m["name"]):
                labels = ",".join(f"{k}={v}" for k, v in
                                  sorted(m.get("labels", {}).items()))
                val = m.get("value", 0)
                state = ""
                if m["name"] == "dlaf_circuit_state":
                    state = "  (" + {0: "closed", 1: "half_open",
                                     2: "open"}.get(int(val), "?") + ")"
                print(f"  {val:>10.0f}  {m['name']}{{{labels}}}{state}")

    if snaps:
        print("\n== counters (last snapshot) ==")
        for m in snaps[-1]["metrics"]:
            if m.get("kind") != "counter":
                continue
            labels = ",".join(f"{k}={v}" for k, v in
                              sorted(m.get("labels", {}).items()))
            print(f"  {m['value']:>16.0f}  {m['name']}{{{labels}}}")
    if logs:
        print(f"\n== logs ({len(logs)}) ==")
        for r in logs[:top_n]:
            print(f"  [{r.get('level')}] {r.get('logger')}: {r.get('msg')}")


def main():
    argv = sys.argv[1:]
    jsonls = []
    while "--jsonl" in argv:
        i = argv.index("--jsonl")
        if i + 1 >= len(argv):
            raise SystemExit(__doc__)
        jsonls.append(argv[i + 1])
        del argv[i:i + 2]
    if not argv:
        raise SystemExit(__doc__)
    root = argv[0]
    top_n = int(argv[1]) if len(argv) > 1 else 25
    if os.path.isfile(root) and not root.endswith((".json", ".json.gz")):
        summarize_jsonl(root, top_n)
        return
    # trace mode: the parsing/classification is obs.devtrace's (single
    # owner, not a fork); this CLI keeps the per-track output contract
    from dlaf_tpu.obs import devtrace

    path = root if os.path.isfile(root) else newest_trace(root)
    print(f"trace: {path}")
    events = devtrace.load_trace(path)

    for track, total, rows in devtrace.track_tables(events):
        print(f"\n== {track}: {total:.1f} ms total (sum of events) ==")
        for name, dur in rows[:top_n]:
            print(f"  {dur:10.2f} ms  {100 * dur / max(total, 1e-9):5.1f}%"
                  f"  {name[:100]}")

    if jsonls:
        # per-phase attribution (ISSUE 14): device op classes joined to
        # the artifact's span windows — report code is devtrace's
        from dlaf_tpu.obs.aggregate import merge_artifacts

        print("\n== device-time attribution (obs.devtrace) ==")
        try:
            report = devtrace.attribute(events, merge_artifacts(jsonls))
        except ValueError as e:
            print(f"  (unavailable: {e})")
            return
        for line in devtrace.format_report(report, top_n):
            print(f"  {line}")


if __name__ == "__main__":
    main()
