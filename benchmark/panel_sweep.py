"""The panel factorizations of a reduction to band in a traced run: the
Householder column sweep (``tile_ops/qr_panel.py:householder_qr``, one
``fori_loop`` a panel) and the T factor's triangular inverse
(``tile_ops/lapack.py:larft``). What the readers ``panel_time_share``,
``panel_column_us`` and ``panel_hbm_share`` share.

Read from the run's xplane, by structure alone (an event's name is its HLO
text and carries no source): the scan-form builder's program runs each
telescoped segment as a ``while`` at the top of the ``XLA Ops`` line, and
inside a segment's step the column sweep is the ``while`` that makes
``band`` trips (one a Householder column; every operation of one step of it
is its direct child once an iteration, so the trips are the occurrences of
the most frequent child). The inversion of the (band, band) T factor loops
over its ``band - 1`` off-diagonal rows and belongs to the same
factorization. The other loops nested in a step make a few trips (the slice
products' shift groups, the emulated-f64 dots' passes, the ``lax.map`` row
chunks) or lie deeper (the dots inside a column step) and are not counted;
the loops counted are counted whole, with everything nested in them.

Only COMPLETE calls are read. The device's trace holds a bounded number of
events (4.56 million on the v5e: PERF.md section 7) and a call of this
program is three million, so a traced window of three calls is cut off in
its second: a call (the harness's ``bench_call`` span) counts where it
holds as many events as the fullest call of the window (every call runs the
same programs), and time and loops are summed over those calls alone.

``sweep`` is kept on the ``run`` so that the xplane is read once for the
three readers. None where the trace has no device plane or no such loop (an
unrolled builder sweeps in a ``while`` at the top level: another program,
another reader).
"""

import sys

import span_reduce
import trace_reduce

KEY = "panel_sweep"


def nest(events):
    """``[(start, end, name, parent index or None)]`` of the events of one
    line in start order: an event's parent is the innermost earlier event
    that encloses its start (``trace_reduce.self_times``' nesting)."""
    out, stack = [], []
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        out.append((s, e, name, stack[-1] if stack else None))
        stack.append(len(out) - 1)
    return out


def is_while(name: str) -> bool:
    return trace_reduce.parse_op(name)[1] == "while"


def panel_loops(events, band: int):
    """``[(start, end)]`` of the ``while`` events that lie directly inside a
    top-level ``while`` and make ``band`` or ``band - 1`` iterations."""
    tree = nest(events)
    children = {}
    for _s, _e, name, parent in tree:
        if parent is not None:
            counts = children.setdefault(parent, {})
            counts[name] = counts.get(name, 0) + 1
    found = []
    for i, (s, e, name, parent) in enumerate(tree):
        if parent is None or tree[parent][3] is not None:
            continue                    # not at depth one
        if not (is_while(name) and is_while(tree[parent][2])):
            continue
        if band - 1 <= max(children.get(i, {"": 0}).values()) <= band:
            found.append((s, e))
    return found


def device_events(path: str, plane_name: str):
    """The ``XLA Ops`` events of one device plane as ``(start, end, name)``
    (``trace_reduce.read_xplane``'s tuples, for that line alone and with the
    names interned: millions of events carry a few thousand distinct HLO
    texts, and the harness's own pass has just held them all once)."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                return [(e.start_ns, e.start_ns + e.duration_ns,
                         sys.intern(e.name)) for e in line.events]
    return []


def sweep(run):
    """``{"panel_ns", "own_ns", "loops", "calls"}`` summed over the
    complete calls of the traced window on the least busy device, or
    None."""
    if KEY in run:
        return run[KEY]
    run[KEY] = None
    trace = run.get("trace")
    band = (run.get("config") or {}).get("args", {}).get("band_size")
    loaded = span_reduce.load_run(run) if band and trace_reduce.worst_device(
        trace) else None
    if loaded is None:
        return None
    _modules, host_spans, window = loaded
    events = device_events(span_reduce.trace_path(), trace["worst_device"])
    calls = []
    for s, e, name in host_spans:
        if name != span_reduce.CALL or s < window[0] or e > window[1]:
            continue
        inside = trace_reduce.clip(events, (s, e))
        loops = panel_loops(inside, int(band))
        calls.append({
            "events": len(inside), "loops": len(loops),
            "panel_ns": sum(b - a for a, b in loops),
            "own_ns": sum(trace_reduce.self_times(inside).values())})
    # every call runs the same programs, so a whole one has the most events
    most = max((c["events"] for c in calls), default=0)
    whole = [c for c in calls
             if c["loops"] and c["events"] >= 0.999 * most]
    if not whole:
        return None
    run[KEY] = {key: sum(c[key] for c in whole)
                for key in ("panel_ns", "own_ns", "loops")}
    run[KEY]["calls"] = len(whole)
    print(f"[panel_sweep] calls_in_window={len(calls)} complete={len(whole)} "
          f"loops={run[KEY]['loops']} events={len(events)} "
          f"events_by_call={[c['events'] for c in calls]}", flush=True)
    return run[KEY]
