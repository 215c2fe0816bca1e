"""The program's own spans and the device's programs, on the traced run's
clock: what the per-layer readers ``launch_gap_share``, ``dispatch_s`` and
``native_s.*`` share.

``trace_reduce`` reduces the device's *operations*; this module looks one
level up, at the *programs* (line ``XLA Modules`` of a device plane: one
event per program run) and at the host spans the library emits
(``dlaf_tpu.obs`` spans are ``jax.profiler.TraceAnnotation`` events of the
span's name on the profiler's clock). ``load`` turns the xplane into plain
``(start, end, name)`` tuples, once per file and without the operations;
everything else works on those tuples, so it is checked on hand-made lists
(tests/test_span_metrics.py). Times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import functools
import os
import statistics

import trace_reduce

#: Line of a device plane with one event per executed program.
MODULES_LINE = "XLA Modules"
CALL = "bench_call"
WINDOW = "bench_window"
FENCE = "stage.fence"
#: Inside one ``bench_call`` the first fence is the untimed fresh copy's.
FRESH_FENCE = "stage.fence (fresh copy)"
#: Host events kept: the harness's annotations, the library's host phases,
#: and the entry spans ``dispatch_s`` reads (matched by exact name there).
HOST_PREFIXES = ("bench_", "stage.", "cholesky")


def trace_path():
    """The traced run's xplane, or None: ``run.py`` puts the trace beside
    the metrics file it names in ``DLAF_METRICS_PATH``."""
    metrics = os.environ.get("DLAF_METRICS_PATH")
    if not metrics:
        return None
    return trace_reduce.newest_xplane(
        os.path.join(os.path.dirname(metrics), "trace"))


@functools.lru_cache(maxsize=2)
def load(path: str):
    """``(modules, host_spans)`` of one xplane file: ``modules`` maps a
    device plane's name to its program events, ``host_spans`` are the host
    events named with one of ``HOST_PREFIXES`` (the planes and the filter of
    ``trace_reduce.read_xplane``). A pass of its own that never touches the
    ``XLA Ops`` line: the eigensolver's traced window holds 6.3 million
    operation events, which ``read_xplane`` would turn into tuples again."""
    from jax.profiler import ProfileData

    modules, host_spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith("/device:") \
            and "CUSTOM" not in plane.name
        for line in plane.lines:
            if is_device and line.name == MODULES_LINE:
                modules.setdefault(plane.name, []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
            elif not is_device:
                host_spans.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                    if e.name.startswith(HOST_PREFIXES))
    return modules, host_spans


def load_run(run):
    """``(module events of the run's least busy device, host spans, window)``
    for the traced run ``run.py`` hands a reader; None without a trace or a
    window annotation. The module events are None where no device plane has
    an ``XLA Modules`` line (a CPU trace)."""
    path = trace_path()
    if path is None:
        return None
    modules, host_spans = load(path)
    window = next(((s, e) for s, e, n in host_spans if n == WINDOW), None)
    if window is None:
        return None
    if not modules:
        return None, host_spans, window
    worst = (run.get("trace") or {}).get("worst_device")
    if worst not in modules:
        worst = min(modules, key=lambda d: sum(
            e - s for s, e in trace_reduce.busy_union(
                trace_reduce.clip(modules[d], window))))
    return modules[worst], host_spans, window


def calls_of(host_spans):
    """The ``bench_call`` intervals ``[(start, end)]``, in order."""
    return sorted((s, e) for s, e, n in host_spans if n == CALL)


def spans_by_call(host_spans, name: str):
    """For each ``bench_call``, in order, the spans called ``name`` that lie
    inside it (a span belongs to the call that contains it)."""
    mine = [(s, e) for s, e, n in host_spans if n == name]
    return [[(s, e) for s, e in mine if cs <= s and e <= ce]
            for cs, ce in calls_of(host_spans)]


def median_wall_per_call(host_spans, name: str):
    """Median over the calls of the summed wall, in seconds, of the spans
    called ``name`` inside one call; None if no call holds such a span."""
    per_call = spans_by_call(host_spans, name)
    if not any(per_call):
        return None
    return statistics.median(sum(e - s for s, e in spans) / 1e9
                             for spans in per_call)


def span_wall(run, name: str):
    """``median_wall_per_call`` of the span ``name`` in the traced run's
    xplane; None where there is no trace or no such span in any call."""
    loaded = load_run(run)
    return None if loaded is None else median_wall_per_call(loaded[1], name)


def launch_gaps(module_events, window):
    """``[(start, end)]`` of ``window`` in which no program was executing."""
    return trace_reduce.idle_gaps(
        trace_reduce.busy_union(trace_reduce.clip(module_events, window)),
        window)


def gap_share(module_events, window):
    """Percent of ``window`` in which no program was executing."""
    span = window[1] - window[0]
    if span <= 0:
        return None
    return 100.0 * sum(e - s for s, e in launch_gaps(module_events, window)) \
        / span


def mark_fresh_fences(host_spans):
    """``host_spans`` with the first ``stage.fence`` inside each
    ``bench_call`` renamed to ``FRESH_FENCE``: ``run.py`` fences the fresh
    input before it starts the clock, and the call's own fences follow."""
    firsts = {min(spans) for spans in spans_by_call(host_spans, FENCE)
              if spans}
    return [(s, e, FRESH_FENCE if n == FENCE and (s, e) in firsts else n)
            for s, e, n in host_spans]


def gap_table(module_events, host_spans, window):
    """Between-program idle by what the host was doing: ``[[label, seconds
    per call, gaps per call, longest gap in seconds]]``, largest first. The
    label is the innermost ``stage.*`` span open at the gap's midpoint, else
    ``in_call`` / ``between_calls`` (``trace_reduce.label_gap``)."""
    n_calls = max(len(calls_of(host_spans)), 1)
    stages = [h for h in mark_fresh_fences(host_spans)
              if h[2] == CALL or h[2].startswith("stage.")]
    rows = {}
    for gap in launch_gaps(module_events, window):
        row = rows.setdefault(trace_reduce.label_gap(gap, stages),
                              [0, 0, 0])
        ns = gap[1] - gap[0]
        row[0] += ns
        row[1] += 1
        row[2] = max(row[2], ns)
    return sorted(([label, total / 1e9 / n_calls, count / n_calls,
                    longest / 1e9]
                   for label, (total, count, longest) in rows.items()),
                  key=lambda r: -r[1])
