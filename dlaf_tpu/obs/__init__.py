"""dlaf_tpu.obs — structured tracing, metrics, and logging.

The observability layer ISSUE 1 calls for (and SURVEY §5 maps from the
reference's pika-delegated profiling): one subsystem under three knobs,
layered like every other :class:`dlaf_tpu.config.Configuration` field
(default < user struct < env < ``--dlaf:`` CLI):

* ``DLAF_LOG`` (``Configuration.log``) — leveled structured logging
  (debug/info/warning/error/off), :mod:`dlaf_tpu.obs.logging`.
* ``DLAF_METRICS_PATH`` (``Configuration.metrics_path``) — JSON-lines
  artifact receiving span records, metrics snapshots, and log events
  (:mod:`dlaf_tpu.obs.sinks`; schema validated by
  ``python -m dlaf_tpu.obs.validate``). Setting it turns the tracer and
  the metrics registry on.
* ``DLAF_TRACE_DIR`` (``Configuration.trace_dir``) — ``jax.profiler``
  trace directory: obs starts (and owns) a profiler session there, and
  trace-time :func:`named_span` phases land in compiled-program op
  metadata. Live host spans carry ``jax.profiler.TraceAnnotation`` names
  onto the timeline of *any* session, this one or a caller's.

Cost contract: with all three unset, every instrumented call site
resolves to a module-level no-op singleton — no allocation, one attribute
read — so the instrumentation in comm/algorithms/eigensolver hot paths is
free when off (verified by tests/test_obs.py).
"""

from __future__ import annotations

import atexit
import os
import time
from typing import Optional

from . import accuracy as accuracy
from . import exporter as exporter
from . import flight as _flight
from . import logging as _logging
from . import metrics as _metrics
from . import sinks as _sinks
from . import slo as _slo
from . import telemetry as telemetry
from . import trace as _trace
from ._state import LOG_LEVELS, STATE, current_rank
from .context import (current_trace, new_span_id, new_trace_id,
                      single_trace_id, trace_context, trace_matches)
from .flight import FlightRecorder
from .logging import Logger, get_logger
from .metrics import (NOOP_COUNTER, NOOP_GAUGE, NOOP_HISTOGRAM, NOOP_WINDOW,
                      Counter, Gauge, Histogram, Registry, SlidingWindow,
                      prometheus_text, quantile)
from .sinks import (SCHEMA_VERSION, JsonlSink,
                    accuracy_record_to_history_line, append_history_line,
                    expand_rank_template, read_history_records, read_records,
                    validate_file, validate_history_records, validate_records)
from .trace import (NOOP_CTX, NOOP_SPAN, Span, current_span, entry_span,
                    named_span, scoped_step, span, start_profiler,
                    stop_profiler, traced_step_count)

__all__ = [
    "configure", "enabled", "metrics_active", "span", "entry_span",
    "named_span", "scoped_step", "traced_step_count",
    "current_span", "counter", "gauge", "histogram", "registry",
    "get_logger", "emit_event", "emit_metrics_snapshot", "flush",
    "prometheus_text", "prometheus_snapshot_text", "validate_file",
    "validate_records", "read_records", "Span", "Counter", "Gauge",
    "Histogram", "Registry", "Logger", "JsonlSink", "SCHEMA_VERSION",
    "NOOP_SPAN", "NOOP_CTX", "NOOP_COUNTER", "NOOP_GAUGE", "NOOP_HISTOGRAM",
    "NOOP_WINDOW",
    "LOG_LEVELS", "start_profiler", "stop_profiler", "telemetry",
    "set_rank", "current_rank", "expand_rank_template",
    "append_history_line", "read_history_records", "validate_history_records",
    "accuracy", "accuracy_record_to_history_line",
    # ISSUE 13: live operational telemetry
    "trace_context", "current_trace", "new_trace_id", "new_span_id",
    "single_trace_id", "trace_matches", "observe_latency", "quantile",
    "SlidingWindow", "FlightRecorder", "exporter",
    # ISSUE 14: device-timeline attribution
    "devtrace",
]


def __getattr__(name: str):
    # lazy submodule: ``obs.devtrace`` is an offline analysis engine
    # (ISSUE 14) never needed on the record-emitting hot path, and an
    # eager import here would trip runpy's found-in-sys.modules warning
    # on every ``python -m dlaf_tpu.obs.devtrace`` invocation
    if name == "devtrace":
        import importlib

        return importlib.import_module(".devtrace", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def configure(log_level: str = "info", metrics_path: str = "",
              trace_dir: str = "", program_telemetry: bool = False,
              metrics_port: int = 0, flight_recorder: int = 0) -> None:
    """(Re)configure the layer — called by ``config.initialize()`` with the
    resolved knobs, or lazily from the env by the first logging call in a
    process that never initializes the runtime.

    Reconfiguring with a different ``metrics_path`` closes the old sink
    (its file stays, a complete artifact); counters persist across
    reconfiguration within a process — they are process-lifetime
    accumulators, like the reference's performance counters.

    ``metrics_path`` may carry a ``%r`` placeholder, replaced by the
    process rank (``jax.process_index()``) so each host of a multi-host
    run appends to its own artifact instead of interleaving one file;
    merge them with ``python -m dlaf_tpu.obs.aggregate``.

    ``program_telemetry`` (the ``DLAF_PROGRAM_TELEMETRY`` knob) arms the
    AOT/jit instrumentation in :mod:`dlaf_tpu.obs.telemetry` — compile
    walls, retrace counters, and HBM gauges from the library's cached
    program sites. Off (default), every telemetry call site is a
    zero-cost passthrough.

    ``metrics_port`` (``DLAF_METRICS_PORT``, ISSUE 13) starts the live
    ``/metrics`` + ``/healthz`` exporter (:mod:`dlaf_tpu.obs.exporter`)
    as a daemon thread on 127.0.0.1 — AND turns the registry on even
    without a sink, so a scrape-only deployment records. 0 (default):
    no thread, no socket. ``flight_recorder``
    (``DLAF_FLIGHT_RECORDER``) arms a bounded in-memory ring of the
    last N sink records, dumped atomically to
    ``<metrics_path>.flight.jsonl`` on incident triggers
    (:mod:`dlaf_tpu.obs.flight`); it needs a sink (the ring captures
    the sink's record stream) and warns once when armed without one.
    """
    level = str(log_level or "info").strip().lower()
    if level not in LOG_LEVELS:
        raise ValueError(f"DLAF_LOG={log_level!r}: must be one of "
                         f"{tuple(LOG_LEVELS)}")
    STATE.log_level = level
    STATE.log_level_num = LOG_LEVELS[level]
    metrics_path = _sinks.expand_rank_template(metrics_path or "")
    if STATE.sink is not None and STATE.sink.path != metrics_path:
        emit_metrics_snapshot()
        STATE.sink.close()
        STATE.sink = None
    if metrics_path and STATE.sink is None:
        STATE.sink = _sinks.JsonlSink(metrics_path)
    STATE.trace_dir = trace_dir or ""
    port = int(metrics_port or 0)
    if port < 0:
        raise ValueError(f"DLAF_METRICS_PORT={metrics_port!r}: must be "
                         ">= 0 (0 = exporter off)")
    STATE.metrics_on = STATE.sink is not None or port > 0
    STATE.annotate = bool(trace_dir)
    STATE.telemetry_on = bool(program_telemetry)
    if STATE.registry is None and (STATE.metrics_on or STATE.annotate
                                   or STATE.telemetry_on):
        STATE.registry = _metrics.Registry()
    # live exporter lifecycle: restart on a port change, stop on 0
    if port != STATE.exporter_port:
        exporter.stop()
        STATE.exporter_port = 0
        if port > 0:
            exporter.start(port)
            STATE.exporter_port = port
    # flight recorder: a ring of the knob's size over the sink stream
    cap = int(flight_recorder or 0)
    if cap < 0:
        raise ValueError(f"DLAF_FLIGHT_RECORDER={flight_recorder!r}: must "
                         "be >= 0 (0 = recorder off; N = ring depth)")
    if cap > 0 and STATE.sink is not None:
        if STATE.flight is None or STATE.flight.capacity != cap:
            STATE.flight = _flight.FlightRecorder(cap)
    else:
        if cap > 0:
            get_logger("obs").warning_once(
                ("flight_no_sink",),
                "DLAF_FLIGHT_RECORDER is set but DLAF_METRICS_PATH is "
                "not: the flight ring captures the sink's record stream, "
                "so the recorder stays unarmed")
        STATE.flight = None
    if (STATE.metrics_on or STATE.annotate or STATE.telemetry_on) \
            and not STATE.atexit_registered:
        STATE.atexit_registered = True
        atexit.register(_shutdown)
    STATE.configured = True


def set_rank(rank: int) -> None:
    """Pin the rank stamped onto JSONL records (and ``%r`` expansions).
    :func:`dlaf_tpu.comm.multihost.initialize_multihost` calls this right
    after ``jax.distributed.initialize`` — a ``%r`` metrics path resolved
    before the distributed runtime came up would have labeled every host
    rank 0."""
    STATE.rank = int(rank)


def _shutdown() -> None:
    """Process exit: flush a final metrics snapshot, stop the profiler,
    and shut the live exporter down so artifacts are complete even when
    drivers forget to call flush()."""
    try:
        emit_metrics_snapshot()
    finally:
        _trace.stop_profiler()
        exporter.stop()
        STATE.exporter_port = 0
        if STATE.sink is not None:
            STATE.sink.close()


def enabled() -> bool:
    """True when any observability output is active."""
    return STATE.metrics_on or STATE.annotate


def metrics_active() -> bool:
    """Fast-path gate for instrumentation call sites (one attribute read)."""
    return STATE.metrics_on


def registry() -> Registry:
    """The process registry (created on first use — usable directly even
    with the sinks off, e.g. for tests or embedding applications)."""
    if STATE.registry is None:
        STATE.registry = _metrics.Registry()
    return STATE.registry


def counter(name: str, **labels):
    """Registry counter handle, or the no-op singleton when metrics are
    off (zero per-call allocation at disabled call sites)."""
    if not STATE.metrics_on:
        return NOOP_COUNTER
    return STATE.registry.counter(name, **labels)


def gauge(name: str, **labels):
    if not STATE.metrics_on:
        return NOOP_GAUGE
    return STATE.registry.gauge(name, **labels)


def histogram(name: str, **labels):
    if not STATE.metrics_on:
        return NOOP_HISTOGRAM
    return STATE.registry.histogram(name, **labels)


def emit_event(rtype: str, **payload) -> None:
    """Append a free-form record (e.g. ``bench_result``) to the JSONL
    artifact; no-op when the sink is off."""
    if STATE.sink is not None:
        rec = {"type": rtype}
        rec.update(payload)
        STATE.sink.write(rec)


def emit_metrics_snapshot() -> None:
    """Write the registry's current state as one ``metrics`` record."""
    if STATE.sink is not None and STATE.registry is not None:
        snap = STATE.registry.snapshot()
        if snap:
            STATE.sink.write({"type": "metrics", "metrics": snap})


def flush() -> None:
    """Snapshot metrics now (drivers call this at the end of a run so the
    artifact is complete without relying on interpreter shutdown)."""
    emit_metrics_snapshot()


def prometheus_snapshot_text() -> str:
    """Prometheus text exposition of the live registry — and the
    documented zero-allocation no-op ("") when :func:`metrics_active` is
    false, matching the discipline of every other obs entry point: with
    metrics off there is nothing worth snapshotting (a registry may
    still exist from an annotate/telemetry-only configuration, but its
    exposition is not a metrics product). Pinned by
    tests/test_live_telemetry.py (ISSUE 13 satellite)."""
    if not STATE.metrics_on or STATE.registry is None:
        return ""
    return prometheus_text(STATE.registry.snapshot())


def observe_latency(op: str, seconds: float, bucket: str = "") -> None:
    """Feed one end-to-end latency into the rolling-window SLO tracker
    (:mod:`dlaf_tpu.obs.slo`): the ``dlaf_serve_latency_seconds{op,
    bucket}`` histogram (+ exemplar trace ID when called under a
    request-scoped :func:`trace_context`), the
    ``dlaf_serve_latency_window{op,bucket,q}`` gauges, and the
    ``dlaf_slo_breach_total{op}`` burn counter against
    ``DLAF_SLO_P99_MS``. No-op when metrics are off."""
    if not STATE.metrics_on:
        return
    _slo.observe(str(op), float(seconds), bucket=str(bucket))


def _reset_for_tests() -> None:
    """Tear the layer back to the unconfigured default (tests only)."""
    try:
        # a test that left the process trace live must not leak it into
        # the rest of the session (it would record everything until exit)
        _trace.stop_profiler()
    except Exception:
        pass
    if STATE.sink is not None:
        STATE.sink.close()
    exporter.stop()
    STATE.sink = None
    STATE.metrics_on = False
    STATE.annotate = False
    STATE.trace_dir = ""
    STATE.registry = None
    STATE.configured = False
    STATE.log_level = "info"
    STATE.log_level_num = LOG_LEVELS["info"]
    STATE.telemetry_on = False
    STATE.rank = None
    STATE.flight = None
    STATE.exporter_port = 0
    _slo.set_clock(None)
    telemetry._reset_for_tests()
    _logging.reset_once()
