"""Critical-path and stall attribution from device traces.

Reconstructs the executed per-step timeline of the pipelined builders
(cholesky, trsm, trmm, hegst, red2band, bt_r2b) by joining device
intervals from a profiler trace to the per-step ``named_scope`` structure
recovered from compiled HLO (``schedule`` records emitted by
``obs.telemetry.aot_compile``).  Per step k it reports the measured
panel / strip / bulk / collective / copy walls, the idle *gap* between
step k's last op and step k+1's first dependent op, the critical path
through the step DAG, a bound classification, and Amdahl-style what-if
projections ("collectives free -> wall -X%", "gaps closed -> +Y GF/s").

Usage:
    python -m dlaf_tpu.obs.critpath TRACE MERGED.jsonl [options]

    TRACE           profiler trace file (*.trace.json[.gz]) or a
                    directory to search for the newest one
    MERGED.jsonl    merged observability artifact; must contain the
                    ``schedule`` records for the traced programs

Options:
    -o PATH             append critpath/whatif JSONL records to PATH
    --json PATH         write the full report as JSON to PATH
    --top N             show at most N steps per program (default 32)
    --steps N           scan-built programs: force the step count when it
                        cannot be inferred from the trace
    --inject-gap SPEC   testing: shift the device timeline to open an
                        artificial gap, SPEC = <algo>.step<k>=<ms>
                        (e.g. cholesky.step002=5 injects 5 ms of idle
                        immediately before step 2 in every run)
    --distill PATH      write a minimal replayable trace JSON to PATH

Exit codes: 0 ok, 1 no per-step attribution possible, 2 bad arguments.
"""

from __future__ import annotations

import gzip
import json
import os
import re
import sys
import time
from typing import Any

from .devtrace import (
    _fallback_windows,
    _intersect_len,
    _is_device_event,
    _meta_maps,
    _union,
    classify_op,
    distill as _devtrace_distill,
    host_span_events,
    load_trace,
)
from . import scopes
from .sinks import SCHEMA_VERSION

PHASES = ("panel", "comm", "strip", "bulk", "other")

# The phases on the panel-chain critical path, in chain order: the potrf
# and panel solve, the chain's collectives with the selects and gathers
# around them (``cholesky.comm``, the distributed Cholesky builders), the
# eager next-column strip.
PANEL_CHAIN = ("panel", "comm", "strip")

# Bound classes, in reporting order.  "panel" folds in the rest of the
# panel chain (all of it sits on the critical path), "comm"/"copy" are the
# collective/copy CATEGORIES of an operation regardless of its phase (the
# ``comm`` phase also holds the masks and gathers around a collective),
# "gap" is measured idle.
BOUNDS = ("panel", "bulk", "comm", "copy", "gap")

# op_name metadata scopes are parsed by :mod:`dlaf_tpu.obs.scopes`, the one
# parser the library has: innermost scope wins, so a comm-lookahead panel
# chain hoisted into step k's outer scope but tagged
# ``<algo>.step<k+1>.panel`` is attributed to step k+1.


# ---------------------------------------------------------------------------
# schedule extraction (compile time)


def schedule_from_hlo(hlo_text: str) -> dict[str, Any]:
    """Parse optimized HLO text into a schedule map.

    Returns ``{"module": name, "ops": {instr_name: [algo, step, phase]}}``
    where ``step`` is an int for unrolled builders and ``-1`` for scan
    bodies (a scan body is traced once for all iterations, so its ops
    carry no step index; the joiner reconstructs iterations from
    occurrence order).  ``phase`` is whatever token the builder wrote
    (``panel``, ``comm``, ``strip``, ``bulk``, the reduction's ``larft`` / ``w`` /
    ``update``...; ``other`` for a step marker alone): the per-step
    reports fold every token outside :data:`PHASES` into ``other``.
    Instructions without a step scope are omitted.
    """
    ops: dict[str, list[Any]] = {}
    for name, op_name, _rest in scopes.instructions(hlo_text):
        scope = scopes.parse(op_name) if op_name else None
        if scope is not None and scope.step is not None:
            ops[name] = [scope.algo, scope.step, scope.phase or "other"]
    return {"module": scopes.module_name(hlo_text), "ops": ops}


def schedule_record(site: str, hlo_text: str) -> dict[str, Any] | None:
    """Build a ``schedule`` JSONL record from compiled HLO, or ``None``
    when the program carries no per-step scopes (nothing to join)."""
    sched = schedule_from_hlo(hlo_text)
    if not sched["ops"]:
        return None
    algos: dict[str, dict[str, Any]] = {}
    for algo, step, _phase in sched["ops"].values():
        a = algos.setdefault(algo, {"steps": 0, "scan": False})
        if step < 0:
            a["scan"] = True
        else:
            a["steps"] = max(a["steps"], step + 1)
    return {
        "type": "schedule",
        "v": SCHEMA_VERSION,
        "ts": time.time(),
        "site": site,
        "module": sched["module"],
        "n_ops": len(sched["ops"]),
        "algos": algos,
        "ops": [[k, *v] for k, v in sched["ops"].items()],
    }


def _op_maps(records: list[dict]) -> tuple[dict, dict, dict]:
    """Collapse schedule records into lookup maps.

    Returns ``(by_module_op, by_op, algo_meta)`` where the first keys on
    ``(module, instr)``, the second on bare ``instr`` (fallback when a
    device event carries no hlo_module), and the third maps algo ->
    {"steps", "scan"} merged across programs.
    """
    by_mod: dict[tuple[str, str], list] = {}
    by_op: dict[str, list] = {}
    meta: dict[str, dict] = {}
    for rec in records:
        if rec.get("type") != "schedule":
            continue
        module = rec.get("module", "")
        for entry in rec.get("ops", ()):
            name, algo, step, phase = entry[0], entry[1], int(entry[2]), entry[3]
            by_mod[(module, name)] = [algo, step, phase]
            by_op[name] = [algo, step, phase]
        for algo, a in (rec.get("algos") or {}).items():
            cur = meta.setdefault(algo, {"steps": 0, "scan": False})
            cur["steps"] = max(cur["steps"], int(a.get("steps", 0)))
            cur["scan"] = cur["scan"] or bool(a.get("scan", False))
    return by_mod, by_op, meta


# ---------------------------------------------------------------------------
# device-event join


def _scheduled_events(events: list[dict], records: list[dict]):
    """Join raw trace events to the schedule.

    Returns ``(joined, busy_total_s, busy_sched_modules_s)`` where
    ``joined`` is a list of dicts with keys lo/hi (seconds), algo, step,
    phase, cat, name, domain.  ``busy_sched_modules_s`` counts device busy
    restricted to modules that have a schedule (the coverage denominator:
    unrelated programs in the trace must not dilute coverage).
    """
    by_mod, by_op, _meta = _op_maps(records)
    if not by_op:
        raise ValueError(
            "artifact contains no schedule records; run with "
            "DLAF_PROGRAM_TELEMETRY=1 so obs.telemetry.aot_compile can "
            "record the per-step HLO schedule"
        )
    modules = {m for (m, _n) in by_mod}
    procs, _threads = _meta_maps(events)
    joined: list[dict] = []
    busy_total = 0.0
    busy_sched = 0.0
    for e in events:
        if e.get("ph") != "X" or not _is_device_event(e, procs):
            continue
        dur = float(e.get("dur", 0.0))
        if dur <= 0.0:
            continue
        busy_total += dur
        args = e.get("args") or {}
        op = args.get("hlo_op") or e.get("name", "")
        module = args.get("hlo_module", "")
        if module in modules:
            busy_sched += dur
        entry = by_mod.get((module, op)) if module else None
        if entry is None:
            entry = by_op.get(op)
        if entry is None:
            continue
        cat, _kind = classify_op(e.get("name", ""))
        ts = float(e["ts"])
        pid = e.get("pid")
        proc = procs.get(pid, "")
        joined.append(
            {
                "lo": ts * 1e-6,
                "hi": (ts + dur) * 1e-6,
                "algo": entry[0],
                "step": int(entry[1]),
                "phase": entry[2] if entry[2] in PHASES else "other",
                "cat": cat or "compute",
                "name": e.get("name", ""),
                "domain": pid if "/device:" in proc.lower() else (pid, e.get("tid")),
            }
        )
    denom = busy_sched if busy_sched > 0.0 else busy_total
    return joined, busy_total * 1e-6, denom * 1e-6


def _run_windows(events: list[dict], records: list[dict]):
    """Per-run host windows, newest-devtrace style.

    Prefers in-trace host span events matching the span vocabulary in
    ``records`` (annotation join); falls back to rebasing per-rank span
    records onto the device-time origin (mirror-less traces).  Returns
    ``(windows, join)`` with windows sorted by start, each
    ``(lo_s, hi_s, name)``.
    """
    span_names = {r.get("name") for r in records if r.get("type") == "span"}
    span_names.discard(None)
    procs, _threads = _meta_maps(events)
    devs = []  # µs, as _fallback_windows expects
    for e in events:
        if e.get("ph") == "X" and float(e.get("dur", 0) or 0) > 0 and _is_device_event(e, procs):
            ts = float(e["ts"])
            devs.append((ts, ts + float(e["dur"])))
    hosts = host_span_events(events, span_names)
    join = "annotation"
    if not hosts:
        hosts = _fallback_windows(records, devs)
        join = "rebase"
    windows = sorted(
        ((lo * 1e-6, hi * 1e-6, name) for (lo, hi, name) in hosts),
        key=lambda w: (w[0], -(w[1])),
    )
    return windows, join


def _assign_runs(joined: list[dict], windows) -> None:
    """Tag every joined event with a run id (innermost containing host
    window, by window identity).  Without windows: a single run for scan
    programs, and step-index-drop segmentation for unrolled ones."""
    if windows:
        from bisect import bisect_right

        # nested/overlapping windows (miniapp.run > factor > entry span,
        # or one run's spans mirrored from several ranks) collapse into
        # one physical-run interval each
        merged = _union([(lo, hi) for (lo, hi, _name) in windows])
        starts = [lo for lo, _hi in merged]
        for ev in joined:
            mid = 0.5 * (ev["lo"] + ev["hi"])
            # containing interval, else the nearest preceding one (device
            # ops dispatched after the host span closed stay in their run)
            ev["run"] = max(0, bisect_right(starts, mid) - 1)
        return
    # no windows at all: synthetic traces / stripped fixtures
    by_algo: dict[str, list[dict]] = {}
    for ev in joined:
        by_algo.setdefault(ev["algo"], []).append(ev)
    for evs in by_algo.values():
        evs.sort(key=lambda e: e["lo"])
        run = 0
        prev_step = -1
        for ev in evs:
            if 0 <= ev["step"] < prev_step:
                run += 1
            if ev["step"] >= 0:
                prev_step = ev["step"]
            ev["run"] = run


def _scan_steps(evs: list[dict], steps_hint: int | None) -> None:
    """Assign step indices to one run of a scan-built program.

    A scan body is traced once, so every iteration executes the same
    instruction set once per device; the anchor — the (op, device) pair
    whose occurrence count matches the expected iteration total (or the
    modal count across pairs) — marks iteration boundaries and events
    bucket by start time.
    """
    from bisect import bisect_right
    from collections import Counter

    occ: dict[tuple, list[float]] = {}
    for ev in evs:
        occ.setdefault((ev["name"], ev["domain"]), []).append(ev["lo"])
    if not occ:
        return
    counts = Counter(len(v) for v in occ.values())
    if steps_hint and steps_hint in counts:
        target = steps_hint
    elif steps_hint and any(c <= steps_hint for c in counts):
        # inner device loops repeat per iteration; the closest count not
        # exceeding the expected iteration total is the body's own rank
        target = max(c for c in counts if c <= steps_hint)
    else:
        target = counts.most_common(1)[0][0]
    anchors = [key for key, v in occ.items() if len(v) == target]
    # earliest-starting anchor bounds each iteration
    anchor = min(anchors, key=lambda k: min(occ[k]))
    bounds = sorted(occ[anchor])
    for ev in evs:
        ev["step"] = max(0, bisect_right(bounds, ev["lo"]) - 1)


# ---------------------------------------------------------------------------
# per-step accounting


def _detangle_shared(revs: list[dict]) -> None:
    """Re-assign CSE-shared instructions within one unrolled run.

    XLA deduplicates identical subcomputations across steps; the shared
    instruction keeps the FIRST emitter's op_name metadata, so its every
    execution would land in that step and stretch its window across the
    run.  Ops executing once in the run are reliably tagged; ops
    executing more than once keep their tag only when they fall inside
    that step's unique-op window, otherwise they move to the step whose
    window contains them (innermost on overlap), or the nearest one.
    """
    from collections import Counter

    # one execution per device is the unrolled norm — shared/CSE'd ops
    # stand out by repeating within a single overlap domain
    counts = Counter((e["name"], e["domain"]) for e in revs)
    win: dict[int, list[float]] = {}
    for e in revs:
        if counts[(e["name"], e["domain"])] == 1 and e["step"] >= 0:
            w = win.setdefault(e["step"], [e["lo"], e["hi"]])
            w[0] = min(w[0], e["lo"])
            w[1] = max(w[1], e["hi"])
    if not win:
        return
    for e in revs:
        if counts[(e["name"], e["domain"])] == 1:
            continue
        mid = 0.5 * (e["lo"] + e["hi"])
        tagged = win.get(e["step"])
        if tagged and tagged[0] <= mid <= tagged[1]:
            continue
        inside = [(hi - lo, k) for k, (lo, hi) in win.items() if lo <= mid <= hi]
        if inside:
            e["step"] = min(inside)[1]
        else:
            e["step"] = min(win, key=lambda k: min(abs(mid - win[k][0]),
                                                   abs(mid - win[k][1])))


def _infer_steps(algo: str, records: list[dict]) -> int | None:
    """Step count from the entry span's (n, nb) attrs — the scan joiner's
    default iteration total when ``--steps`` is not given."""
    for r in records:
        if r.get("type") != "span":
            continue
        name = r.get("name", "")
        attrs = r.get("attrs") or r
        n, nb = attrs.get("n"), attrs.get("nb")
        if n and nb and (name == algo or algo in name):
            return -(-int(n) // int(nb))
    return None


def _flops_for(algo: str, records: list[dict]) -> float | None:
    """Per-run flop count from the entry span records, if recorded."""
    best = None
    for r in records:
        if r.get("type") != "span":
            continue
        name = r.get("name", "")
        fl = (r.get("attrs") or {}).get("flops") or r.get("flops")
        if fl and (name == algo or algo in name):
            best = float(fl)
    return best


def _trimmed_window(sevs: list[dict], tail: float = 0.005) -> tuple[float, float]:
    """Duration-weighted robust window of one step's events.

    Near-zero-duration stragglers (fusion metadata pollution: a fused
    final-layout copy can carry a step-0 op_name) must not stretch the
    step across the run, so the window keeps the span holding all but a
    ``tail`` fraction of the step's busy time at each end.  Steps whose
    events are all zero-length fall back to the plain min/max.
    """
    total = sum(e["hi"] - e["lo"] for e in sevs)
    if total <= 0.0:
        return (min(e["lo"] for e in sevs), max(e["hi"] for e in sevs))
    cut = tail * total
    acc = 0.0
    lo = sevs[0]["lo"]
    for e in sorted(sevs, key=lambda e: e["lo"]):
        lo = e["lo"]
        acc += e["hi"] - e["lo"]
        if acc > cut:
            break
    acc = 0.0
    hi = sevs[-1]["hi"]
    for e in sorted(sevs, key=lambda e: e["hi"], reverse=True):
        hi = e["hi"]
        acc += e["hi"] - e["lo"]
        if acc > cut:
            break
    return (lo, hi) if lo < hi else (min(e["lo"] for e in sevs),
                                     max(e["hi"] for e in sevs))


def _step_table(evs: list[dict], n_steps: int) -> list[dict]:
    """Per-step walls, category exposure and boundary gaps for one run."""
    steps: list[dict] = []
    by_step: dict[int, list[dict]] = {}
    for ev in evs:
        by_step.setdefault(ev["step"], []).append(ev)
    for k in range(n_steps):
        sevs = by_step.get(k, [])
        if not sevs:
            steps.append({"step": k, "empty": True})
            continue
        lo, hi = _trimmed_window(sevs)
        phase_w = {}
        for ph in PHASES:
            u = _union([(e["lo"], e["hi"]) for e in sevs if e["phase"] == ph])
            if u:
                phase_w[ph] = sum(b - a for a, b in u)
        comm_u = _union([(e["lo"], e["hi"]) for e in sevs if e["cat"] == "collective"])
        copy_u = _union([(e["lo"], e["hi"]) for e in sevs if e["cat"] == "copy"])
        comp_u = _union(
            [(e["lo"], e["hi"]) for e in sevs if e["cat"] not in ("collective", "copy")]
        )
        busy_u = _union([(e["lo"], e["hi"]) for e in sevs])
        busy = sum(b - a for a, b in busy_u)
        comm = sum(b - a for a, b in comm_u)
        copy = sum(b - a for a, b in copy_u)
        comm_exposed = comm - _intersect_len(comm_u, comp_u)
        steps.append(
            {
                "step": k,
                "start_s": lo,
                "wall_s": hi - lo,
                "busy_s": busy,
                "idle_s": max(0.0, (hi - lo) - busy),
                "phases": phase_w,
                "comm_s": comm,
                "comm_exposed_s": max(0.0, comm_exposed),
                "copy_s": copy,
                "end_s": hi,
            }
        )
    # boundary gaps: idle between step k's last op and step k+1's first op,
    # clamped at zero when steps overlap (lookahead pipelining)
    for k in range(len(steps) - 1):
        a, b = steps[k], steps[k + 1]
        if a.get("empty") or b.get("empty"):
            continue
        a["gap_after_s"] = max(0.0, b["start_s"] - a["end_s"])
    return steps


def _bound_of(step: dict) -> str:
    """Classify what bounds a step: argmax over exposure per category."""
    ph = step.get("phases", {})
    panel = sum(ph.get(p, 0.0) for p in PANEL_CHAIN)
    bulk = ph.get("bulk", 0.0) + ph.get("other", 0.0)
    comm = step.get("comm_exposed_s", 0.0)
    copy = step.get("copy_s", 0.0)
    gap = step.get("gap_after_s", 0.0) + step.get("idle_s", 0.0)
    scores = {"panel": panel - comm - copy, "bulk": bulk, "comm": comm, "copy": copy, "gap": gap}
    scores["panel"] = max(0.0, scores["panel"])
    return max(BOUNDS, key=lambda b: scores[b])


def _critical_path(steps: list[dict], lookahead: bool) -> dict:
    """Longest path through the step DAG.

    Nodes are (step, phase) with measured walls; edges are
    panel_k -> comm_k -> strip_k -> bulk_k within a step, bulk_k -> bulk_{k+1}
    (trailing updates serialize on the matrix), and the next panel hangs
    off strip_k when lookahead overlaps it with bulk_k, else off bulk_k.
    Boundary gaps ride the cross-step edges.
    """
    dist: dict[tuple[int, str], float] = {}
    prev: dict[tuple[int, str], tuple[int, str] | None] = {}

    def relax(node, base, src, w):
        if base + w > dist.get(node, -1.0):
            dist[node] = base + w
            prev[node] = src

    for st in steps:
        if st.get("empty"):
            continue
        k = st["step"]
        ph = st.get("phases", {})
        gap = steps[k - 1].get("gap_after_s", 0.0) if 0 < k <= len(steps) else 0.0
        chain = [p for p in PHASES if p in ph]
        for i, p in enumerate(chain):
            w = ph[p]
            node = (k, p)
            relax(node, gap, None, w)
            if i > 0:
                relax(node, dist[(k, chain[i - 1])], (k, chain[i - 1]), w)
            # cross-step dependencies from step k-1
            if i == 0:
                # the panel hangs off strip_{k-1} (lookahead overlap) or the
                # end of step k-1 entirely (serial)
                srcs = PANEL_CHAIN[::-1] if lookahead \
                    else ("bulk", "other") + PANEL_CHAIN[::-1]
            elif p in ("bulk", "other"):
                srcs = ("bulk", "other")  # trailing updates serialize
            else:
                srcs = ()
            for pp in srcs:
                src = (k - 1, pp)
                if src in dist:
                    relax(node, dist[src] + gap, src, w)
    if not dist:
        return {"length_s": 0.0, "nodes": []}
    last = max(dist, key=lambda n: dist[n])
    path = []
    node: tuple[int, str] | None = last
    while node is not None:
        path.append(f"step{node[0]:03d}.{node[1]}")
        node = prev.get(node)
    return {"length_s": dist[last], "nodes": list(reversed(path))}


def _mean_steps(per_run: list[list[dict]]) -> list[dict]:
    """Average per-step numbers across runs (element-wise over steps)."""
    if not per_run:
        return []
    n_steps = max(len(r) for r in per_run)
    out = []
    for k in range(n_steps):
        rows = [r[k] for r in per_run if k < len(r) and not r[k].get("empty")]
        if not rows:
            out.append({"step": k, "empty": True})
            continue
        agg: dict[str, Any] = {"step": k}
        for key in ("wall_s", "busy_s", "idle_s", "comm_s", "comm_exposed_s", "copy_s",
                    "gap_after_s"):
            vals = [r.get(key) for r in rows if r.get(key) is not None]
            if vals:
                agg[key] = sum(vals) / len(vals)
        phases: dict[str, float] = {}
        for ph in PHASES:
            vals = [r["phases"].get(ph) for r in rows if r["phases"].get(ph) is not None]
            if vals:
                phases[ph] = sum(vals) / len(vals)
        agg["phases"] = phases
        agg["bound"] = _bound_of(agg)
        out.append(agg)
    return out


def attribute(
    events: list[dict],
    records: list[dict],
    *,
    steps_hint: int | None = None,
) -> dict[str, Any]:
    """Join device events to schedule records and build the full report.

    Raises ``ValueError`` when the artifact has no schedule records or
    the trace has no device events to join.
    """
    joined, busy_total, busy_denom = _scheduled_events(events, records)
    if busy_total <= 0.0:
        raise ValueError("trace contains no device events (complete XSpace only?)")
    windows, join = _run_windows(events, records)
    _assign_runs(joined, windows)
    _by_mod, _by_op, meta = _op_maps(records)
    attributed = sum(e["hi"] - e["lo"] for e in joined)
    coverage = attributed / busy_denom if busy_denom > 0 else 0.0
    knobs = {}
    for rec in records:
        if rec.get("type") == "metrics" and rec.get("knobs"):
            knobs = rec["knobs"]
    lookahead = bool(knobs.get("cholesky_lookahead") or knobs.get("lookahead") or True)

    programs: dict[str, Any] = {}
    by_algo: dict[str, list[dict]] = {}
    for ev in joined:
        by_algo.setdefault(ev["algo"], []).append(ev)
    for algo, evs in sorted(by_algo.items()):
        am = meta.get(algo, {"steps": 0, "scan": False})
        scan = bool(am.get("scan")) and am.get("steps", 0) == 0
        runs: dict[int, list[dict]] = {}
        for ev in evs:
            runs.setdefault(ev.get("run", 0), []).append(ev)
        per_run_steps: list[list[dict]] = []
        run_walls: list[float] = []
        gaps_per_run: list[float] = []
        cp_lengths: list[float] = []
        comm_exposed_run: list[float] = []
        panel_exposed_run: list[float] = []
        copy_run: list[float] = []
        hint = steps_hint or (_infer_steps(algo, records) if scan else None)
        for _rid, revs in sorted(runs.items(), key=lambda kv: min(e["lo"] for e in kv[1])):
            if scan:
                _scan_steps(revs, hint)
            else:
                _detangle_shared(revs)
            n_steps = max((e["step"] for e in revs), default=-1) + 1
            if n_steps <= 0:
                continue
            table = _step_table(revs, n_steps)
            per_run_steps.append(table)
            lo = min(e["lo"] for e in revs)
            hi = max(e["hi"] for e in revs)
            run_walls.append(hi - lo)
            gaps_per_run.append(sum(s.get("gap_after_s", 0.0) for s in table))
            cp_lengths.append(_critical_path(table, lookahead)["length_s"])
            comm_u = _union([(e["lo"], e["hi"]) for e in revs if e["cat"] == "collective"])
            comp_u = _union(
                [(e["lo"], e["hi"]) for e in revs if e["cat"] not in ("collective", "copy")]
            )
            comm_exposed_run.append(
                max(0.0, sum(b - a for a, b in comm_u) - _intersect_len(comm_u, comp_u))
            )
            pan_u = _union(
                [(e["lo"], e["hi"]) for e in revs if e["phase"] in PANEL_CHAIN]
            )
            blk_u = _union([(e["lo"], e["hi"]) for e in revs if e["phase"] in ("bulk", "other")])
            panel_exposed_run.append(
                max(0.0, sum(b - a for a, b in pan_u) - _intersect_len(pan_u, blk_u))
            )
            copy_run.append(
                sum(b - a for a, b in _union(
                    [(e["lo"], e["hi"]) for e in revs if e["cat"] == "copy"]))
            )
        if not per_run_steps:
            continue
        mean = _mean_steps(per_run_steps)
        n_runs = len(per_run_steps)
        wall = sum(run_walls) / n_runs
        gaps = sum(gaps_per_run) / n_runs
        cp = _critical_path(mean, lookahead)
        flops = _flops_for(algo, records)

        def project(saved_s: float, label: str) -> dict:
            new_wall = max(1e-12, wall - min(saved_s, wall))
            w: dict[str, Any] = {
                "scenario": label,
                "saved_s": saved_s,
                "wall_s": wall,
                "projected_wall_s": new_wall,
                "wall_pct": 100.0 * (wall - new_wall) / wall if wall > 0 else 0.0,
            }
            if flops:
                w["gflops"] = flops / wall / 1e9
                w["projected_gflops"] = flops / new_wall / 1e9
            return w

        whatifs = [
            project(sum(comm_exposed_run) / n_runs, "collectives_free"),
            project(gaps, "gaps_closed"),
            project(sum(panel_exposed_run) / n_runs, "panel_free"),
            project(sum(copy_run) / n_runs, "copies_free"),
        ]
        whatifs.sort(key=lambda w: -w["saved_s"])
        bounds = [s.get("bound") for s in mean if not s.get("empty")]
        overall = max(BOUNDS, key=lambda b: bounds.count(b)) if bounds else "gap"
        programs[algo] = {
            "scan": scan,
            "n_runs": n_runs,
            "n_steps": len(mean),
            "wall_s": wall,
            "gap_total_s": gaps,
            "critical_path_s": cp["length_s"],
            "critical_path": cp["nodes"],
            "bound": overall,
            "steps": mean,
            "whatif": whatifs,
        }
        if flops:
            programs[algo]["gflops"] = flops / wall / 1e9

    return {
        "device_busy_s": busy_total,
        "attributed_s": attributed,
        "coverage": coverage,
        "join": join,
        "events": len(joined),
        "lookahead": lookahead,
        "programs": programs,
    }


# ---------------------------------------------------------------------------
# gap injection (testing / CI drill)


def parse_inject(spec: str) -> tuple[str, int, float]:
    """Parse ``<algo>.step<k>=<ms>`` into (algo, step, seconds)."""
    m = re.fullmatch(r"([A-Za-z0-9_]+)\.step(\d+)=([0-9.]+)", spec.strip())
    if not m:
        raise ValueError(f"bad --inject-gap spec {spec!r}; want <algo>.step<k>=<ms>")
    return m.group(1), int(m.group(2)), float(m.group(3)) * 1e-3


def inject_gap(events: list[dict], records: list[dict], algo: str, step: int,
               seconds: float, *, steps_hint: int | None = None) -> int:
    """Shift the timeline so an idle gap of ``seconds`` opens immediately
    before ``step`` of ``algo`` in every run.

    Scheduled device events with step >= ``step`` shift by the delta;
    host windows straddling the boundary stretch so run segmentation
    still contains the shifted ops.  On a serial (non-overlapping)
    timeline the measured boundary gap grows by *exactly* the delta; with
    lookahead overlap the earlier step's tail eats into it, so the
    recovered gap is ``delta - overlap`` (still >> 0 for drill-sized
    deltas).  Mutates ``events`` in place; returns the number of runs
    injected into.
    """
    by_mod, by_op, _meta = _op_maps(records)
    joined, _bt, _bd = _scheduled_events(events, records)
    windows, _join = _run_windows(events, records)
    _assign_runs(joined, windows)
    runs: dict[int, list[dict]] = {}
    for ev in joined:
        if ev["algo"] == algo:
            runs.setdefault(ev.get("run", 0), []).append(ev)
    starts = []
    for revs in runs.values():
        if all(e["step"] < 0 for e in revs):
            _scan_steps(revs, steps_hint)
        sevs = [e["lo"] for e in revs if e["step"] == step]
        if sevs:
            starts.append(min(sevs))
    if not starts:
        return 0
    starts.sort()
    delta_us = seconds * 1e6
    procs, _threads = _meta_maps(events)
    run_ivs = _union([(lo, hi) for (lo, hi, _name) in windows])

    def run_end_us(t0: float) -> float:
        for lo, hi in run_ivs:
            if lo <= t0 <= hi:
                return hi * 1e6
        return float("inf")

    def sched_step(e) -> int | None:
        args = e.get("args") or {}
        op = args.get("hlo_op") or e.get("name", "")
        entry = by_mod.get((args.get("hlo_module", ""), op)) or by_op.get(op)
        if entry is None or entry[0] != algo:
            return None
        return int(entry[1])

    # process runs back-to-front so earlier shifts don't move later anchors
    for t0 in reversed(starts):
        t0_us = t0 * 1e6 - 0.5  # nudge so the boundary op itself shifts
        end_us = run_end_us(t0)
        for e in events:
            if e.get("ph") != "X":
                continue
            ts = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0) or 0.0)
            if ts + dur <= t0_us:
                continue
            if _is_device_event(e, procs):
                if ts < t0_us:
                    continue
                st = sched_step(e)
                # within the injected run, target-algo ops of EARLIER
                # steps keep their place even past the boundary (their
                # lookahead tail overlaps it); everything else shifts
                if st is not None and 0 <= st < step and ts < end_us:
                    continue
                e["ts"] = ts + delta_us
            elif ts >= t0_us:
                e["ts"] = ts + delta_us  # host event entirely after the boundary
            else:
                e["dur"] = dur + delta_us  # straddling host window stretches
    return len(starts)


# ---------------------------------------------------------------------------
# records + rendering


def records_from_report(report: dict, trace: str) -> list[dict]:
    ts = time.time()
    base = os.path.basename(trace)
    out = []
    for algo, prog in report.get("programs", {}).items():
        steps = []
        for s in prog["steps"]:
            if s.get("empty"):
                steps.append({"step": s["step"], "empty": True})
                continue
            steps.append(
                {
                    "step": s["step"],
                    "wall_s": round(s.get("wall_s", 0.0), 9),
                    "panel_s": round(
                        sum(s["phases"].get(p, 0.0) for p in PANEL_CHAIN), 9),
                    "bulk_s": round(
                        s["phases"].get("bulk", 0.0) + s["phases"].get("other", 0.0), 9),
                    "comm_s": round(s.get("comm_s", 0.0), 9),
                    "comm_exposed_s": round(s.get("comm_exposed_s", 0.0), 9),
                    "copy_s": round(s.get("copy_s", 0.0), 9),
                    "idle_s": round(s.get("idle_s", 0.0), 9),
                    "gap_after_s": round(s.get("gap_after_s", 0.0), 9),
                    "bound": s.get("bound", "gap"),
                }
            )
        rec = {
            "type": "critpath",
            "v": SCHEMA_VERSION,
            "ts": ts,
            "trace": base,
            "algo": algo,
            "scan": prog["scan"],
            "join": report.get("join"),
            "coverage": round(report.get("coverage", 0.0), 6),
            "n_runs": prog["n_runs"],
            "n_steps": prog["n_steps"],
            "wall_s": round(prog["wall_s"], 9),
            "gap_total_s": round(prog["gap_total_s"], 9),
            "critical_path_s": round(prog["critical_path_s"], 9),
            "critical_path": prog["critical_path"],
            "bound": prog["bound"],
            "steps": steps,
        }
        if "gflops" in prog:
            rec["gflops"] = round(prog["gflops"], 3)
        out.append(rec)
        for w in prog["whatif"]:
            wrec = {
                "type": "whatif",
                "v": SCHEMA_VERSION,
                "ts": ts,
                "trace": base,
                "algo": algo,
                "scenario": w["scenario"],
                "saved_s": round(w["saved_s"], 9),
                "wall_s": round(w["wall_s"], 9),
                "projected_wall_s": round(w["projected_wall_s"], 9),
                "wall_pct": round(w["wall_pct"], 3),
            }
            if "projected_gflops" in w:
                wrec["gflops"] = round(w["gflops"], 3)
                wrec["projected_gflops"] = round(w["projected_gflops"], 3)
            out.append(wrec)
    return out


def _fmt_ms(s: float) -> str:
    return f"{s * 1e3:8.3f}"


def format_report(report: dict, top_n: int = 32) -> str:
    lines = []
    lines.append(
        f"critpath: {report['events']} scheduled device events, "
        f"coverage {report['coverage']:.1%} (join={report['join']}, "
        f"device busy {report['device_busy_s'] * 1e3:.3f} ms)"
    )
    for algo, prog in report.get("programs", {}).items():
        hdr = (
            f"\n{algo}: {prog['n_steps']} steps x {prog['n_runs']} runs"
            f"{' (scan)' if prog['scan'] else ''}, wall {_fmt_ms(prog['wall_s']).strip()} ms, "
            f"gaps {_fmt_ms(prog['gap_total_s']).strip()} ms, "
            f"critical path {_fmt_ms(prog['critical_path_s']).strip()} ms, "
            f"bound: {prog['bound']}"
        )
        if "gflops" in prog:
            hdr += f", {prog['gflops']:.1f} GF/s"
        lines.append(hdr)
        lines.append(
            "  step     wall ms  panel ms   bulk ms   comm ms  exp.comm   copy ms"
            "   idle ms    gap ms  bound"
        )
        for s in prog["steps"][:top_n]:
            if s.get("empty"):
                lines.append(f"  {s['step']:4d}  (no device events)")
                continue
            ph = s.get("phases", {})
            panel = sum(ph.get(p, 0.0) for p in PANEL_CHAIN)
            bulk = ph.get("bulk", 0.0) + ph.get("other", 0.0)
            lines.append(
                f"  {s['step']:4d}  {_fmt_ms(s.get('wall_s', 0.0))}  {_fmt_ms(panel)}"
                f"  {_fmt_ms(bulk)}  {_fmt_ms(s.get('comm_s', 0.0))}"
                f"  {_fmt_ms(s.get('comm_exposed_s', 0.0))}  {_fmt_ms(s.get('copy_s', 0.0))}"
                f"  {_fmt_ms(s.get('idle_s', 0.0))}  {_fmt_ms(s.get('gap_after_s', 0.0))}"
                f"  {s.get('bound', '')}"
            )
        if len(prog["steps"]) > top_n:
            lines.append(f"  ... {len(prog['steps']) - top_n} more steps")
        lines.append(f"  critical path: {' -> '.join(prog['critical_path'])}")
        lines.append("  what-if:")
        for w in prog["whatif"]:
            line = (
                f"    {w['scenario']:<17} saves {_fmt_ms(w['saved_s']).strip()} ms "
                f"-> wall -{w['wall_pct']:.1f}%"
            )
            if "projected_gflops" in w:
                line += f", {w['gflops']:.1f} -> {w['projected_gflops']:.1f} GF/s"
            lines.append(line)
    if not report.get("programs"):
        lines.append("(no per-step programs attributed)")
    return "\n".join(lines)


def load_records(path: str) -> list[dict]:
    records = []
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return records


# ---------------------------------------------------------------------------
# CLI


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    out_path = json_path = distill_path = inject = None
    top_n = 32
    steps_hint = None
    positional = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a in ("-h", "--help"):
            print(__doc__)
            return 0
        if a == "-o":
            i += 1
            out_path = argv[i]
        elif a == "--json":
            i += 1
            json_path = argv[i]
        elif a == "--distill":
            i += 1
            distill_path = argv[i]
        elif a == "--top":
            i += 1
            top_n = int(argv[i])
        elif a == "--steps":
            i += 1
            steps_hint = int(argv[i])
        elif a == "--inject-gap":
            i += 1
            inject = argv[i]
        elif a.startswith("-"):
            print(f"critpath: unknown option {a}", file=sys.stderr)
            return 2
        else:
            positional.append(a)
        i += 1
    if len(positional) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    trace_path, jsonl_path = positional
    try:
        events = load_trace(trace_path)
        records = load_records(jsonl_path)
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print(f"critpath: {exc}", file=sys.stderr)
        return 2
    try:
        if inject is not None:
            algo, step, seconds = parse_inject(inject)
            n = inject_gap(events, records, algo, step, seconds, steps_hint=steps_hint)
            print(
                f"critpath: injected {seconds * 1e3:.1f} ms before "
                f"{algo}.step{step:03d} in {n} runs",
                file=sys.stderr,
            )
        report = attribute(events, records, steps_hint=steps_hint)
    except ValueError as exc:
        print(f"critpath: {exc}", file=sys.stderr)
        return 1
    # artifacts before stdout: a SIGPIPE from a closed pager must not lose them
    if out_path:
        recs = records_from_report(report, trace_path)
        with open(out_path, "a", encoding="utf-8") as fh:
            for rec in recs:
                fh.write(json.dumps(rec, sort_keys=True) + "\n")
    if json_path:
        with open(json_path, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if distill_path:
        kept = _devtrace_distill(events, records)
        payload = json.dumps({"traceEvents": kept})
        if distill_path.endswith(".gz"):
            with gzip.open(distill_path, "wt", encoding="utf-8") as fh:
                fh.write(payload)
        else:
            with open(distill_path, "w", encoding="utf-8") as fh:
                fh.write(payload)
        print(f"critpath: distilled {len(kept)} events -> {distill_path}", file=sys.stderr)
    print(format_report(report, top_n))
    if not report.get("programs"):
        print("critpath: WARNING: no per-step programs attributed", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
