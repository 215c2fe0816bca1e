"""Event sinks and the JSONL artifact schema.

Two output formats, per the observability design (ISSUE 1):

* **JSON lines** (:class:`JsonlSink`) — one self-describing event object
  per line, append-only, the same artifact convention as the repo's
  ``BENCH_*.json`` round files. Everything the tracer/metrics/logger emit
  flows through here when ``DLAF_METRICS_PATH`` is set.
* **Prometheus text exposition** (:func:`prometheus_text`, over a registry
  snapshot) — for scraping; see :mod:`dlaf_tpu.obs.metrics`.

Schema (version 1). Every record carries ``v`` (int schema version),
``type`` (str) and ``ts`` (float, unix seconds). Per type:

``span``
    ``name`` str, ``dur_s`` finite float >= 0, ``depth`` int >= 0,
    ``parent`` str or null, ``attrs`` object. Optional ``flops`` (finite
    number) and ``gflops`` (finite number, derived = flops / dur_s / 1e9).
    Optional ``fenced: false`` marks spans whose wall clock is host
    trace+dispatch only (async JAX work, no device fence inside the
    region) — such records never carry ``gflops``.
``metrics``
    ``metrics``: list of snapshot entries — ``name`` str, ``kind``
    "counter" | "gauge" | "histogram", ``labels`` object; counters/gauges
    carry finite ``value``; histograms carry ``count``/``sum``/``min``/
    ``max`` and ``buckets`` (list of [le, count]).
``log``
    ``level`` str, ``logger`` str, ``msg`` str, ``fields`` object.
``bench_result``
    ``payload`` object (free-form; bench.py's measurement line).
``program``
    Program-telemetry record (:mod:`dlaf_tpu.obs.telemetry`, the
    ``DLAF_PROGRAM_TELEMETRY`` knob): ``site`` str, ``event``
    "compile" | "retrace", finite ``compile_s`` >= 0 (compile events;
    optional ``trace_s``), optional ``hbm`` object of finite byte gauges
    (``args``/``output``/``temp``/``peak`` from
    ``compiled.memory_analysis()``), ``attrs`` object.
``accuracy``
    Numerical-quality record (:mod:`dlaf_tpu.obs.accuracy`, the
    ``DLAF_ACCURACY`` knob; docs/accuracy.md): ``site`` str, ``metric``
    str, ``platform`` str, ``n``/``nb`` non-negative ints, ``dtype``
    str, ``attrs`` object; ``value`` finite >= 0 — or null with
    ``nonfinite: true``, the corruption signal the accuracy gate treats
    as an automatic regression. Budgeted metrics additionally carry
    finite ``bound_ratio = value / (c * n * eps_eff)`` >= 0 plus the
    ``c``/``eps_eff`` they were normalized with (informational metrics,
    e.g. the D&C deflation fraction, omit all three); a record may not
    carry both ``bound_ratio`` and ``nonfinite``.

``resilience``
    Resilience-layer record (:mod:`dlaf_tpu.health.policy` /
    ``.circuit`` / ``.resume`` and the serve queue's overload path;
    docs/robustness.md): ``site`` str, ``event`` one of
    ``retry`` | ``give_up`` | ``deadline`` | ``circuit_open`` |
    ``circuit_half_open`` | ``circuit_close`` | ``shed`` | ``expired`` |
    ``checkpoint`` | ``preempt`` | ``resume``, ``attrs`` object;
    ``retry``/``give_up``/``deadline`` events carry a non-negative int
    ``attempt`` and ``retry`` a finite ``delay_s >= 0`` (the
    deterministic backoff actually applied). The
    ``--require-resilience`` CI obligation: >= 1 ``retry`` or ``resume``
    record (the recovery actually exercised), AND no
    ``dlaf_circuit_state`` gauge left at the open value (2) in the LAST
    metrics snapshot — an artifact that ends with a tripped breaker must
    fail the gate, not scrape as healthy.

``serve``
    Serving-layer record (:mod:`dlaf_tpu.serve`, docs/serving.md), two
    events: ``dispatch`` — one batched bucket dispatch (``op`` str,
    ``bucket_n`` int >= 1, ``nrhs`` int >= 0, ``dtype`` str, ``lanes``
    int in [0, batch], ``batch`` int >= 1, ``cache`` "hit" | "miss",
    finite ``dispatch_s`` >= 0) — and ``request`` — one served request
    (``op`` str, ``n`` int >= 1, ``bucket_n`` >= n, ``dtype`` str,
    finite ``queue_s``/``total_s`` >= 0, ``attrs`` object). The
    ``--require-serve`` CI obligation (a WARMED steady-state serving
    artifact): >= 1 dispatch with >= 2 occupied lanes, every dispatch a
    cache hit (zero misses — the post-warmup contract), >= 1 request
    with finite latency, >= 1 ``accuracy`` record from site ``serve``
    with finite value AND bound_ratio, and no
    ``dlaf_retrace_total{site=serve.*}`` counter at >= 2 (a serve
    program traced twice = an evicted/cold bucket recompiled
    mid-stream).

``flight_trigger``
    Header record of a flight-recorder dump (:mod:`dlaf_tpu.obs.flight`,
    the ``DLAF_FLIGHT_RECORDER`` knob): ``reason`` one of
    :data:`FLIGHT_REASONS`, ``dump_seq`` int >= 1, ``records`` int >= 0
    (ring depth at the dump), ``attrs`` object. It appears only in the
    standalone ``<metrics_path>.flight.jsonl`` incident artifact — the
    ``--require-flight`` CI obligation: >= 1 ``flight_trigger`` record
    AND >= 1 ordinary record after it (an incident dump with no
    pre-trigger context captured nothing worth gating on).

``devtrace``
    Device-timeline attribution summary (:mod:`dlaf_tpu.obs.devtrace`,
    ISSUE 14; docs/observability.md device-time attribution): ``trace``
    non-empty str (the profiler artifact's basename), finite
    ``device_busy_s``/``attributed_s`` >= 0, ``coverage`` finite in
    [0, 1] (attributed / total device busy), ``join``
    "annotation" | "rebase" (how phases were matched), ``phases`` object
    of per-phase cells — finite ``busy_s``/``wall_s`` >= 0 (a NaN wall
    is a schema error: the "no NaN walls" leg of ``--require-devtrace``),
    ``categories`` object of finite seconds, optional finite ``flops``/
    ``measured_gflops`` (the measured-MFU join) — and ``attrs`` object.

``measured_overlap``
    Measured comm/compute overlap for one (``algo``, ``axis``) — the
    device-timeline counterpart of the structural
    ``dlaf_comm_overlapped_total`` trace-time counters: non-empty
    ``algo``/``axis`` strs (``axis`` is ``"all"`` when the trace carries
    no replica-group metadata — Chrome traces do not), finite
    ``collective_s``/``overlapped_s``/``mxu_busy_s`` >= 0 with
    ``overlapped_s <= collective_s`` (every field phase-scoped:
    ``mxu_busy_s`` is the MXU time attributed to THIS algo, so
    ``overlapped_s / mxu_busy_s`` is a meaningful ratio),
    ``overlap_frac`` finite in [0, 1],
    ``kinds`` object of finite per-collective-kind seconds, ``attrs``
    object. Emitted only for phases with POSITIVE attributed collective
    time, so an artifact whose trace attributed zero collectives carries
    no such record and fails ``--require-devtrace``.

Every record additionally carries an optional ``rank`` (int >= 0,
``jax.process_index()``) — stamped by the sink once the rank is known, so
multi-host artifacts merge per rank (``python -m dlaf_tpu.obs.aggregate``;
``DLAF_METRICS_PATH`` accepts a ``%r`` per-rank template so ranks never
interleave one file) — and optional trace correlation (ISSUE 13,
:mod:`dlaf_tpu.obs.context`): ``trace_id`` (non-empty str for
request-scoped records, non-empty list of non-empty strs for
batch-scoped ones — a dispatch, its retries, its compiles) and
``span_id`` (non-empty str, one per batch dispatch), both stamped by the
sink from the active ``obs.trace_context``. ``serve`` dispatch records
may carry a ``stages`` object of finite non-negative stage walls
(``compose_s``/``program_s``/``fetch_s``/``unpad_s``) — joined to member
requests via ``span_id`` by ``obs.aggregate --trace`` (the per-request
waterfall).

:func:`validate_file` is the single schema owner consumed by tests and the
CI gate (``python -m dlaf_tpu.obs.validate``): it rejects unparsable lines,
missing fields, and non-finite numerics (a NaN GFlop/s must fail the tier,
not scrape as a number). The append-only bench history
(``.bench_history.jsonl``) has its own line schema, also owned here
(:func:`validate_history_records`, :func:`append_history_line` — the
validator CLI's ``--history`` mode): a malformed or non-finite history
line must fail loudly, not silently skew a gate baseline.
"""

from __future__ import annotations

import json
import math
import threading
import time
from typing import Optional

SCHEMA_VERSION = 1

KNOWN_TYPES = ("span", "metrics", "log", "bench_result", "program",
               "accuracy", "serve", "resilience", "flight_trigger",
               "devtrace", "measured_overlap", "schedule", "critpath",
               "whatif", "fleet")

#: Documented attribution-coverage floor of ``--require-devtrace``
#: (docs/observability.md device-time attribution): a devtrace record
#: must attribute at least this fraction of total device busy time to
#: algorithm phases — below it, the per-phase walls describe a minority
#: of the timeline and must not gate (or pass) anything.
DEVTRACE_COVERAGE_FLOOR = 0.5

#: Documented coverage floor of ``--require-critpath`` (docs/
#: observability.md critical-path attribution): a critpath record must
#: join at least this fraction of the scheduled programs' device busy
#: time to per-step scopes — below it the per-step walls, gaps and bound
#: classifications describe a minority of the step timeline and must not
#: gate (or pass) anything.
CRITPATH_COVERAGE_FLOOR = 0.5

#: Bound vocabulary of critpath step/program classification
#: (obs.critpath.BOUNDS, duplicated here so validation never imports the
#: joiner).
CRITPATH_BOUNDS = ("panel", "bulk", "comm", "copy", "gap")

#: What-if scenario vocabulary (obs.critpath projections).
WHATIF_SCENARIOS = ("collectives_free", "gaps_closed", "panel_free",
                    "copies_free")

#: The resilience record's event vocabulary (schema above).
RESILIENCE_EVENTS = ("retry", "give_up", "deadline", "circuit_open",
                     "circuit_half_open", "circuit_close", "shed",
                     "expired", "checkpoint", "preempt", "resume",
                     "drain")

#: The flight recorder's trigger vocabulary (docs/observability.md live
#: operations; trigger sites in :mod:`dlaf_tpu.obs.flight`).
FLIGHT_REASONS = ("breaker_open", "overload_shed",
                  "factorization_exhausted", "accuracy_breach",
                  "healthz_failure", "slo_breach_burst",
                  "fleet_worker_down")

#: The fleet record's event vocabulary (docs/fleet.md; emitted by
#: :class:`dlaf_tpu.fleet.router.Router` — the router is the ONLY
#: writer, so the fleet audit trail is a single ordered decision log).
#: ``route``/``redispatch``/``handback`` are ticket-scoped (carry
#: ``seq`` + the active trace context); the rest are membership-scoped.
FLEET_EVENTS = ("route", "redispatch", "handback", "worker_up",
                "worker_dead", "heartbeat_timeout", "draining",
                "drained", "probe", "ticket_lost")


def expand_rank_template(path: str) -> str:
    """Resolve a ``%r`` per-rank placeholder in a metrics path — but ONLY
    when the rank is already known (:func:`dlaf_tpu.obs._state.
    current_rank`'s non-forcing resolution). Before any backend exists the
    template is returned unexpanded: forcing ``jax.process_index()`` here
    would initialize the local backend, and on a multi-host worker that
    happens exactly where it must not — before ``initialize_multihost``'s
    ``jax.distributed.initialize`` (which both breaks bring-up and pins
    rank 0 on every host). The sink expands the deferred template at
    first write instead, and ``initialize_multihost`` re-configures with
    the authoritative rank."""
    if "%r" not in path:
        return path
    from ._state import current_rank

    rank = current_rank()
    return path if rank is None else path.replace("%r", str(rank))


class JsonlSink:
    """Append-only JSON-lines writer; thread-safe, line-buffered so a
    killed process still leaves a readable prefix."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._f = None

    def write(self, record: dict) -> None:
        record.setdefault("v", SCHEMA_VERSION)
        record.setdefault("ts", time.time())
        if "rank" not in record:
            # stamp the process rank once known (lazy: resolving it must
            # not force a jax import from a bare log call)
            from ._state import current_rank

            rank = current_rank()
            if rank is not None:
                record["rank"] = rank
        # request-scoped trace correlation (ISSUE 13): the active
        # obs.trace_context's trace_id/span_id land on EVERY record type
        # written under it — one ContextVar read when no context is live
        from .context import record_stamp

        record_stamp(record)
        from ._state import STATE

        if STATE.flight is not None:
            # flight ring capture, pre-serialization and pre-file-write:
            # the moments before an incident survive a lost sink file
            STATE.flight.capture(record)
        line = json.dumps(record, default=str)
        with self._lock:
            if self._f is None:
                if "%r" in self.path:
                    # deferred %r template (configure() could not resolve
                    # the rank without forcing backend init): expand now —
                    # by first write a backend exists for any real run —
                    # and record the resolved path so a later configure()
                    # with the authoritative rank reopens cleanly. If the
                    # rank is STILL unknown (pre-distributed-init log
                    # writes), use a per-process placeholder: claiming
                    # rank 0 would make every late-initializing host of a
                    # shared filesystem append to rank 0's file — the
                    # misattributed interleaving %r exists to prevent.
                    from ._state import current_rank

                    rank = current_rank()
                    import os as _os

                    self.path = self.path.replace(
                        "%r", str(rank) if rank is not None
                        else f"u{_os.getpid()}")
                self._f = open(self.path, "a", buffering=1)
            self._f.write(line + "\n")

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def _validate_span(r: dict, where: str, errors: list) -> None:
    if not isinstance(r.get("name"), str) or not r.get("name"):
        errors.append(f"{where}: span without a name")
    if not _finite(r.get("dur_s")) or r.get("dur_s", -1) < 0:
        errors.append(f"{where}: span dur_s missing/non-finite/negative")
    if not isinstance(r.get("depth"), int) or r.get("depth", -1) < 0:
        errors.append(f"{where}: span depth missing or negative")
    if not isinstance(r.get("attrs", {}), dict):
        errors.append(f"{where}: span attrs must be an object")
    for key in ("flops", "gflops"):
        if key in r and not _finite(r[key]):
            errors.append(f"{where}: span {key} non-finite")
    if r.get("fenced") is False and "gflops" in r:
        # the tracer never derives throughput from unfenced dispatch
        # wall; hold third-party emitters to the same contract
        errors.append(f"{where}: unfenced span must not carry gflops")
    if r.get("name") == "robust_cholesky.attempt":
        # retry spans are the recovery audit trail (docs/robustness.md):
        # each must say WHICH attempt with WHAT shift, or the artifact
        # cannot reconstruct the recovery history
        attrs = r.get("attrs") or {}
        for key in ("attempt", "shift"):
            if not _finite(attrs.get(key)):
                errors.append(
                    f"{where}: retry span missing finite attr {key!r}")


def _validate_program(r: dict, where: str, errors: list) -> None:
    if not isinstance(r.get("site"), str) or not r.get("site"):
        errors.append(f"{where}: program record without a site")
    event = r.get("event")
    if event not in ("compile", "retrace"):
        errors.append(f"{where}: program event must be compile|retrace, "
                      f"got {event!r}")
    if event == "compile":
        # a compile event without a finite compile wall is exactly the
        # kind of silent telemetry hole the knob exists to close
        if not _finite(r.get("compile_s")) or r.get("compile_s", -1) < 0:
            errors.append(f"{where}: program compile_s "
                          "missing/non-finite/negative")
    elif "compile_s" in r and (not _finite(r["compile_s"])
                               or r["compile_s"] < 0):
        # optional on other events, but non-finite numerics are schema
        # errors everywhere (same treatment as trace_s below)
        errors.append(f"{where}: program compile_s non-finite/negative")
    if "trace_s" in r and (not _finite(r["trace_s"]) or r["trace_s"] < 0):
        errors.append(f"{where}: program trace_s non-finite/negative")
    hbm = r.get("hbm")
    if hbm is not None:
        if not isinstance(hbm, dict):
            errors.append(f"{where}: program hbm must be an object")
        else:
            for key, v in hbm.items():
                if not _finite(v):
                    errors.append(f"{where}: program hbm[{key!r}] "
                                  "non-finite")
    if not isinstance(r.get("attrs", {}), dict):
        errors.append(f"{where}: program attrs must be an object")


def _validate_accuracy(r: dict, where: str, errors: list) -> None:
    for key in ("site", "metric", "platform", "dtype"):
        if not isinstance(r.get(key), str) or not r.get(key):
            errors.append(f"{where}: accuracy record without a {key}")
    for key in ("n", "nb"):
        if not isinstance(r.get(key), int) or isinstance(r.get(key), bool) \
                or r.get(key, -1) < 0:
            errors.append(f"{where}: accuracy {key} must be a non-negative "
                          "int")
    value = r.get("value")
    if r.get("nonfinite") is True:
        if value is not None:
            errors.append(f"{where}: nonfinite accuracy record must carry "
                          "value null")
        if "bound_ratio" in r:
            # a NaN estimate has no meaningful budget ratio; carrying one
            # would let a corrupted run scrape as a (finite) number
            errors.append(f"{where}: nonfinite accuracy record must not "
                          "carry bound_ratio")
    elif not _finite(value) or value < 0:
        errors.append(f"{where}: accuracy value missing/non-finite/negative "
                      "(use value null + nonfinite true for corrupted "
                      "estimates)")
    for key in ("bound_ratio", "c", "eps_eff"):
        if key in r and (not _finite(r[key]) or r[key] < 0):
            errors.append(f"{where}: accuracy {key} non-finite/negative")
    if not isinstance(r.get("attrs", {}), dict):
        errors.append(f"{where}: accuracy attrs must be an object")


def _validate_serve(r: dict, where: str, errors: list) -> None:
    event = r.get("event")
    if event not in ("dispatch", "request"):
        errors.append(f"{where}: serve event must be dispatch|request, "
                      f"got {event!r}")
        return
    for key in ("op", "dtype"):
        if not isinstance(r.get(key), str) or not r.get(key):
            errors.append(f"{where}: serve record without a {key}")
    if not isinstance(r.get("bucket_n"), int) \
            or isinstance(r.get("bucket_n"), bool) or r.get("bucket_n", 0) < 1:
        errors.append(f"{where}: serve bucket_n must be a positive int")
    if event == "dispatch":
        lanes, batch = r.get("lanes"), r.get("batch")
        if not isinstance(r.get("nrhs"), int) \
                or isinstance(r.get("nrhs"), bool) or r.get("nrhs", -1) < 0:
            errors.append(f"{where}: serve dispatch nrhs must be a "
                          "non-negative int")
        if not isinstance(batch, int) or isinstance(batch, bool) or batch < 1:
            errors.append(f"{where}: serve dispatch batch must be a "
                          "positive int")
        if not isinstance(lanes, int) or isinstance(lanes, bool) \
                or lanes < 0 or (isinstance(batch, int) and lanes > batch):
            errors.append(f"{where}: serve dispatch lanes must be an int "
                          "in [0, batch]")
        if r.get("cache") not in ("hit", "miss"):
            errors.append(f"{where}: serve dispatch cache must be "
                          f"hit|miss, got {r.get('cache')!r}")
        if not _finite(r.get("dispatch_s")) or r.get("dispatch_s", -1) < 0:
            errors.append(f"{where}: serve dispatch_s "
                          "missing/non-finite/negative")
        stages = r.get("stages")
        if stages is not None:
            if not isinstance(stages, dict):
                errors.append(f"{where}: serve dispatch stages must be an "
                              "object")
            else:
                for key, v in stages.items():
                    if not _finite(v) or v < 0:
                        errors.append(f"{where}: serve dispatch stages"
                                      f"[{key!r}] non-finite/negative")
    else:
        if not isinstance(r.get("n"), int) or isinstance(r.get("n"), bool) \
                or r.get("n", 0) < 1:
            errors.append(f"{where}: serve request n must be a positive int")
        elif isinstance(r.get("bucket_n"), int) \
                and r["bucket_n"] < r["n"]:
            errors.append(f"{where}: serve request bucket_n < n — the "
                          "bucket must be a ceiling")
        for key in ("queue_s", "total_s"):
            if not _finite(r.get(key)) or r.get(key, -1) < 0:
                errors.append(f"{where}: serve request {key} "
                              "missing/non-finite/negative")
    if not isinstance(r.get("attrs", {}), dict):
        errors.append(f"{where}: serve attrs must be an object")


def _validate_resilience(r: dict, where: str, errors: list) -> None:
    if not isinstance(r.get("site"), str) or not r.get("site"):
        errors.append(f"{where}: resilience record without a site")
    event = r.get("event")
    if event not in RESILIENCE_EVENTS:
        errors.append(f"{where}: resilience event must be one of "
                      f"{RESILIENCE_EVENTS}, got {event!r}")
    if event in ("retry", "give_up", "deadline"):
        attempt = r.get("attempt")
        if not isinstance(attempt, int) or isinstance(attempt, bool) \
                or attempt < 0:
            errors.append(f"{where}: resilience {event} record needs a "
                          "non-negative int attempt")
    if event == "retry" and (not _finite(r.get("delay_s"))
                             or r.get("delay_s", -1) < 0):
        errors.append(f"{where}: resilience retry record needs finite "
                      "delay_s >= 0 (the backoff actually applied)")
    if not isinstance(r.get("attrs", {}), dict):
        errors.append(f"{where}: resilience attrs must be an object")


def _validate_fleet(r: dict, where: str, errors: list) -> None:
    """Fleet decision record (docs/fleet.md): ``event`` from
    :data:`FLEET_EVENTS`, ``worker`` a non-negative int (the replica the
    decision is ABOUT), and for ticket-scoped events (route, redispatch,
    handback, ticket_lost) the router ticket ``seq`` — those records are
    also trace-stamped so a ticket's full journey joins on trace_id."""
    event = r.get("event")
    if event not in FLEET_EVENTS:
        errors.append(f"{where}: fleet event must be one of "
                      f"{FLEET_EVENTS}, got {event!r}")
    worker = r.get("worker")
    if not isinstance(worker, int) or isinstance(worker, bool) or worker < 0:
        errors.append(f"{where}: fleet record needs a non-negative int "
                      f"worker, got {worker!r}")
    if event in ("route", "redispatch", "handback", "ticket_lost"):
        seq = r.get("seq")
        if not isinstance(seq, int) or isinstance(seq, bool) or seq < 0:
            errors.append(f"{where}: fleet {event} record needs a "
                          f"non-negative int seq, got {seq!r}")
        if not isinstance(r.get("trace_id"), str) or not r.get("trace_id"):
            errors.append(f"{where}: fleet {event} record must be "
                          "trace-stamped (joinable to its request)")
    if not isinstance(r.get("attrs", {}), dict):
        errors.append(f"{where}: fleet attrs must be an object")


def _validate_devtrace(r: dict, where: str, errors: list) -> None:
    if not isinstance(r.get("trace"), str) or not r.get("trace"):
        errors.append(f"{where}: devtrace record without a trace name")
    for key in ("device_busy_s", "attributed_s"):
        if not _finite(r.get(key)) or r.get(key, -1) < 0:
            errors.append(f"{where}: devtrace {key} "
                          "missing/non-finite/negative")
    cov = r.get("coverage")
    if not _finite(cov) or not 0.0 <= cov <= 1.0:
        errors.append(f"{where}: devtrace coverage must be finite in "
                      f"[0, 1], got {cov!r}")
    if r.get("join") not in ("annotation", "rebase"):
        errors.append(f"{where}: devtrace join must be "
                      f"annotation|rebase, got {r.get('join')!r}")
    phases = r.get("phases")
    if not isinstance(phases, dict):
        errors.append(f"{where}: devtrace phases must be an object")
    else:
        for name, cell in phases.items():
            w = f"{where} phase[{name!r}]"
            if not isinstance(cell, dict):
                errors.append(f"{w}: must be an object")
                continue
            # the "no NaN walls" leg: every per-phase wall is finite
            for key in ("busy_s", "wall_s"):
                if not _finite(cell.get(key)) or cell.get(key, -1) < 0:
                    errors.append(f"{w}: {key} "
                                  "missing/non-finite/negative")
            cats = cell.get("categories")
            if not isinstance(cats, dict):
                errors.append(f"{w}: categories must be an object")
            else:
                for cat, v in cats.items():
                    if not _finite(v) or v < 0:
                        errors.append(f"{w}: categories[{cat!r}] "
                                      "non-finite/negative")
            for key in ("flops", "measured_gflops"):
                if key in cell and (not _finite(cell[key])
                                    or cell[key] < 0):
                    errors.append(f"{w}: {key} non-finite/negative")
    if not isinstance(r.get("attrs", {}), dict):
        errors.append(f"{where}: devtrace attrs must be an object")


def _validate_measured_overlap(r: dict, where: str, errors: list) -> None:
    for key in ("algo", "axis"):
        if not isinstance(r.get(key), str) or not r.get(key):
            errors.append(f"{where}: measured_overlap record without "
                          f"a {key}")
    for key in ("collective_s", "overlapped_s", "mxu_busy_s"):
        if not _finite(r.get(key)) or r.get(key, -1) < 0:
            errors.append(f"{where}: measured_overlap {key} "
                          "missing/non-finite/negative")
    if _finite(r.get("collective_s")) and _finite(r.get("overlapped_s")) \
            and r["overlapped_s"] > r["collective_s"]:
        errors.append(f"{where}: measured_overlap overlapped_s > "
                      "collective_s (overlap cannot exceed the "
                      "collective time it overlaps)")
    frac = r.get("overlap_frac")
    if not _finite(frac) or not 0.0 <= frac <= 1.0:
        errors.append(f"{where}: measured_overlap overlap_frac must be "
                      f"finite in [0, 1], got {frac!r}")
    kinds = r.get("kinds")
    if kinds is not None:
        if not isinstance(kinds, dict):
            errors.append(f"{where}: measured_overlap kinds must be an "
                          "object")
        else:
            for kind, v in kinds.items():
                if not _finite(v) or v < 0:
                    errors.append(f"{where}: measured_overlap kinds"
                                  f"[{kind!r}] non-finite/negative")
    if not isinstance(r.get("attrs", {}), dict):
        errors.append(f"{where}: measured_overlap attrs must be an "
                      "object")


def _validate_schedule(r: dict, where: str, errors: list) -> None:
    for key in ("site", "module"):
        if not isinstance(r.get(key), str) or not r.get(key):
            errors.append(f"{where}: schedule record without a {key}")
    ops = r.get("ops")
    if not isinstance(ops, list) or not ops:
        errors.append(f"{where}: schedule record without ops")
        return
    for j, entry in enumerate(ops):
        if (not isinstance(entry, list) or len(entry) != 4
                or not isinstance(entry[0], str)
                or not isinstance(entry[1], str)
                or not isinstance(entry[2], int)
                or not isinstance(entry[3], str)):
            errors.append(f"{where}: schedule ops[{j}] must be "
                          "[instr, algo, step, phase]")
            break
    algos = r.get("algos")
    if not isinstance(algos, dict) or not algos:
        errors.append(f"{where}: schedule record without algos summary")


def _validate_critpath(r: dict, where: str, errors: list) -> None:
    if not isinstance(r.get("trace"), str) or not r.get("trace"):
        errors.append(f"{where}: critpath record without a trace name")
    if not isinstance(r.get("algo"), str) or not r.get("algo"):
        errors.append(f"{where}: critpath record without an algo")
    cov = r.get("coverage")
    if not _finite(cov) or not 0.0 <= cov <= 1.0:
        errors.append(f"{where}: critpath coverage must be finite in "
                      f"[0, 1], got {cov!r}")
    if r.get("join") not in ("annotation", "rebase"):
        errors.append(f"{where}: critpath join must be "
                      f"annotation|rebase, got {r.get('join')!r}")
    for key in ("n_runs", "n_steps"):
        if not isinstance(r.get(key), int) or isinstance(r.get(key), bool) \
                or r.get(key, 0) < 1:
            errors.append(f"{where}: critpath {key} must be a positive "
                          "int")
    for key in ("wall_s", "gap_total_s", "critical_path_s"):
        if not _finite(r.get(key)) or r.get(key, -1) < 0:
            errors.append(f"{where}: critpath {key} "
                          "missing/non-finite/negative")
    if r.get("bound") not in CRITPATH_BOUNDS:
        errors.append(f"{where}: critpath bound must be one of "
                      f"{CRITPATH_BOUNDS}, got {r.get('bound')!r}")
    steps = r.get("steps")
    if not isinstance(steps, list) or not steps:
        errors.append(f"{where}: critpath record without steps")
        return
    for s in steps:
        if not isinstance(s, dict):
            errors.append(f"{where}: critpath step entries must be "
                          "objects")
            break
        w = f"{where} step[{s.get('step')!r}]"
        if not isinstance(s.get("step"), int):
            errors.append(f"{w}: missing step index")
        if s.get("empty"):
            continue
        # the "no NaN walls" leg: every per-step wall is finite
        for key in ("wall_s", "panel_s", "bulk_s", "comm_s",
                    "comm_exposed_s", "copy_s", "idle_s", "gap_after_s"):
            if key == "gap_after_s" and key not in s:
                continue  # the last step has no following boundary
            if not _finite(s.get(key)) or s.get(key, -1) < 0:
                errors.append(f"{w}: {key} missing/non-finite/negative")
        if s.get("bound") not in CRITPATH_BOUNDS:
            errors.append(f"{w}: bound must be one of "
                          f"{CRITPATH_BOUNDS}, got {s.get('bound')!r}")


def _validate_whatif(r: dict, where: str, errors: list) -> None:
    if not isinstance(r.get("algo"), str) or not r.get("algo"):
        errors.append(f"{where}: whatif record without an algo")
    if r.get("scenario") not in WHATIF_SCENARIOS:
        errors.append(f"{where}: whatif scenario must be one of "
                      f"{WHATIF_SCENARIOS}, got {r.get('scenario')!r}")
    for key in ("saved_s", "wall_s", "projected_wall_s"):
        if not _finite(r.get(key)) or r.get(key, -1) < 0:
            errors.append(f"{where}: whatif {key} "
                          "missing/non-finite/negative")
    if _finite(r.get("wall_s")) and _finite(r.get("projected_wall_s")) \
            and r["projected_wall_s"] > r["wall_s"] + 1e-12:
        errors.append(f"{where}: whatif projected_wall_s > wall_s "
                      "(removing work cannot slow the run)")
    pct = r.get("wall_pct")
    if not _finite(pct) or not 0.0 <= pct <= 100.0:
        errors.append(f"{where}: whatif wall_pct must be finite in "
                      f"[0, 100], got {pct!r}")


def _validate_flight_trigger(r: dict, where: str, errors: list) -> None:
    if r.get("reason") not in FLIGHT_REASONS:
        errors.append(f"{where}: flight_trigger reason must be one of "
                      f"{FLIGHT_REASONS}, got {r.get('reason')!r}")
    for key in ("dump_seq", "records"):
        if not isinstance(r.get(key), int) or isinstance(r.get(key), bool) \
                or r.get(key, -1) < 0:
            errors.append(f"{where}: flight_trigger {key} must be a "
                          "non-negative int")
    if not isinstance(r.get("attrs", {}), dict):
        errors.append(f"{where}: flight_trigger attrs must be an object")


def _validate_trace_stamp(r: dict, where: str, errors: list) -> None:
    """Optional trace correlation fields, any record type: ``trace_id``
    a non-empty str (request scope) or non-empty list of non-empty strs
    (batch scope); ``span_id`` a non-empty str."""
    tid = r.get("trace_id")
    if tid is not None:
        if isinstance(tid, str):
            if not tid:
                errors.append(f"{where}: trace_id must be non-empty")
        elif isinstance(tid, list):
            if not tid or any(not isinstance(t, str) or not t for t in tid):
                errors.append(f"{where}: trace_id list must be non-empty "
                              "with non-empty string members")
        else:
            errors.append(f"{where}: trace_id must be a string or a list "
                          f"of strings, got {type(tid).__name__}")
    sid = r.get("span_id")
    if sid is not None and (not isinstance(sid, str) or not sid):
        errors.append(f"{where}: span_id must be a non-empty string")


def _validate_metrics(r: dict, where: str, errors: list) -> None:
    entries = r.get("metrics")
    if not isinstance(entries, list):
        errors.append(f"{where}: metrics record without a metrics list")
        return
    for i, m in enumerate(entries):
        w = f"{where} metric[{i}]"
        if not isinstance(m.get("name"), str) or not m.get("name"):
            errors.append(f"{w}: missing name")
        kind = m.get("kind")
        if kind not in ("counter", "gauge", "histogram"):
            errors.append(f"{w}: bad kind {kind!r}")
        elif kind == "histogram":
            for key in ("count", "sum"):
                if not _finite(m.get(key)):
                    errors.append(f"{w}: histogram {key} non-finite")
        elif not _finite(m.get("value")):
            errors.append(f"{w}: {kind} value non-finite")
        if not isinstance(m.get("labels", {}), dict):
            errors.append(f"{w}: labels must be an object")


def validate_records(records, require_spans=False, require_gflops=False,
                     require_collectives=False, require_retries=False,
                     require_fallbacks=False, require_comm_overlap=False,
                     require_dc_batch=False, require_bt_overlap=False,
                     require_telemetry=False, require_accuracy=False,
                     require_serve=False, require_resilience=False,
                     require_flight=False, require_devtrace=False,
                     require_critpath=False,
                     require_fleet=False) -> list:
    """Validate parsed records; returns a list of error strings (empty =
    valid). ``require_*`` add the CI smoke-tier artifact obligations:
    at least one span, at least one span with finite derived gflops,
    collective byte counters in some metrics snapshot, at least one
    ``robust_cholesky.attempt`` retry span (with its attempt/shift
    attrs — the fault-injection smoke), a positive
    ``dlaf_fallback_total`` counter, (``require_comm_overlap``)
    positive finite ``dlaf_comm_overlapped_total{algo,axis}`` counters
    plus finite per-axis ``dlaf_comm_collective_bytes_total`` for BOTH
    mesh axes — the comm look-ahead audit trail (docs/comm_overlap.md) —,
    (``require_dc_batch``) a positive finite
    ``dlaf_dc_merges_total{mode="batched"}`` counter (the level-batched
    D&C audit trail, docs/eigensolver_perf.md), and
    (``require_bt_overlap``) a positive finite
    ``dlaf_comm_overlapped_total`` counter whose algo label starts with
    ``bt_`` (the pipelined back-transform's hoisted collectives), and
    (``require_telemetry``) the program-telemetry audit trail
    (docs/observability.md): >= 1 finite compile-seconds observation,
    finite HBM accounting, and retrace evidence — each leg satisfiable
    by EITHER a metrics snapshot (``dlaf_compile_seconds`` histogram /
    ``dlaf_hbm_bytes`` gauge / ``dlaf_retrace_total`` counter) or the
    per-event ``program`` records, so a run killed before the final
    snapshot landed still validates on its record trail — and
    (``require_accuracy``) at least one ``accuracy`` record with a finite
    value AND a finite ``bound_ratio`` (the DLAF_ACCURACY audit trail,
    docs/accuracy.md: an informational-only or all-nonfinite artifact
    must not satisfy the accuracy obligation), and (``require_serve``)
    the warmed steady-state serving obligation (docs/serving.md): >= 1
    ``serve`` dispatch record with >= 2 occupied lanes, ZERO dispatch
    records with ``cache: miss``, >= 1 request record with finite
    latency, >= 1 accuracy record from site ``serve`` (finite value +
    bound_ratio), and no serve-site retrace evidence at count >= 2 (a
    ``dlaf_retrace_total{site=serve.*}`` counter >= 2, or two program
    retrace records for one serve site — either means a bucket program
    recompiled mid-stream, the exact latency cliff warmup exists to
    prevent), and (``require_resilience``) the resilience audit trail
    (docs/robustness.md): >= 1 ``resilience`` record proving recovery
    actually ran (event ``retry`` or ``resume``), and NO
    ``dlaf_circuit_state`` gauge still at the open value (2) in the last
    metrics snapshot — a run that ended with a breaker tripped failed,
    whatever else it recorded — and (``require_flight``) the
    flight-recorder incident obligation (docs/observability.md): >= 1
    ``flight_trigger`` record with a known reason AND >= 1 ordinary
    (pre-trigger) record, so an incident dump that captured no context
    fails the drill — and (``require_devtrace``) the device-timeline
    attribution obligation (ISSUE 14, docs/observability.md): >= 1
    ``measured_overlap`` record with finite ``overlap_frac`` and
    POSITIVE attributed collective time (a trace that attributed zero
    collectives measured nothing about comm/compute overlap), and >= 1
    ``devtrace`` record with attribution coverage >=
    :data:`DEVTRACE_COVERAGE_FLOOR` (the schema validation above
    already rejects NaN phase walls unconditionally) — and
    (``require_critpath``)
    the per-step critical-path attribution obligation (ISSUE 16,
    docs/observability.md): >= 1 ``critpath`` record with >= 1 step and
    join coverage >= :data:`CRITPATH_COVERAGE_FLOOR` (below the floor
    the per-step walls/gaps/bounds describe a minority of the scheduled
    timeline), and >= 1 ``whatif`` projection record (the headroom
    ranking the attribution exists to produce) — and (``require_fleet``)
    the multi-replica zero-loss obligation (docs/fleet.md): >= 1
    ``fleet`` record with event ``route`` (the router actually routed),
    ZERO ``ticket_lost`` records (a lost ticket is the exact failure the
    fleet tier exists to prevent — any occurrence REJECTS the artifact),
    and every ``worker_dead`` whose reason is not ``drained`` (an
    ungraceful death) must be answered by >= 1 ``redispatch`` record
    somewhere in the artifact — a crash with no failover is a silent
    at-least-once violation."""
    errors = []
    n_spans = n_gflops = n_coll = n_retries = n_fallbacks = 0
    n_dc_batched = n_bt_overlap = n_accuracy = 0
    n_compile_obs = n_hbm = n_retrace = 0
    n_serve_batched = n_serve_miss = n_serve_requests = 0
    n_serve_accuracy = 0
    n_resilience_proof = 0
    n_flight_triggers = n_flight_context = 0
    n_overlap_proof = n_devtrace_covered = 0
    n_critpath_covered = n_whatif = 0
    n_fleet_routes = n_fleet_redispatch = n_fleet_lost = 0
    n_fleet_ungraceful_dead = 0
    devtrace_coverages = []
    critpath_coverages = []
    circuit_state = {}                # site -> latest gauge value seen
    serve_retrace_sites = {}          # serve.* site -> trace evidence count
    overlap_axes, byte_axes = set(), set()
    for i, r in enumerate(records):
        where = f"record {i}"
        if not isinstance(r, dict):
            errors.append(f"{where}: not an object")
            continue
        rtype = r.get("type")
        if rtype not in KNOWN_TYPES:
            errors.append(f"{where}: unknown type {rtype!r}")
            continue
        if not _finite(r.get("ts")):
            errors.append(f"{where}: missing/non-finite ts")
        if r.get("v") != SCHEMA_VERSION:
            errors.append(f"{where}: schema version {r.get('v')!r} != "
                          f"{SCHEMA_VERSION}")
        if "rank" in r and (not isinstance(r["rank"], int)
                            or isinstance(r["rank"], bool)
                            or r["rank"] < 0):
            errors.append(f"{where}: rank must be a non-negative int, "
                          f"got {r['rank']!r}")
        _validate_trace_stamp(r, where, errors)
        if rtype != "flight_trigger":
            n_flight_context += 1
        if rtype == "flight_trigger":
            _validate_flight_trigger(r, where, errors)
            if r.get("reason") in FLIGHT_REASONS:
                n_flight_triggers += 1
        elif rtype == "devtrace":
            _validate_devtrace(r, where, errors)
            if _finite(r.get("coverage")):
                devtrace_coverages.append(float(r["coverage"]))
                if r["coverage"] >= DEVTRACE_COVERAGE_FLOOR:
                    n_devtrace_covered += 1
        elif rtype == "measured_overlap":
            _validate_measured_overlap(r, where, errors)
            if _finite(r.get("overlap_frac")) \
                    and _finite(r.get("collective_s")) \
                    and r["collective_s"] > 0:
                n_overlap_proof += 1
        elif rtype == "schedule":
            _validate_schedule(r, where, errors)
        elif rtype == "critpath":
            _validate_critpath(r, where, errors)
            if _finite(r.get("coverage")):
                critpath_coverages.append(float(r["coverage"]))
                if r["coverage"] >= CRITPATH_COVERAGE_FLOOR \
                        and isinstance(r.get("n_steps"), int) \
                        and r["n_steps"] >= 1:
                    n_critpath_covered += 1
        elif rtype == "whatif":
            _validate_whatif(r, where, errors)
            n_whatif += 1
        elif rtype == "fleet":
            _validate_fleet(r, where, errors)
            event = r.get("event")
            if event == "route":
                n_fleet_routes += 1
            elif event == "redispatch":
                n_fleet_redispatch += 1
            elif event == "ticket_lost":
                n_fleet_lost += 1
            elif event == "worker_dead" \
                    and (r.get("attrs") or {}).get("reason") != "drained":
                n_fleet_ungraceful_dead += 1
        elif rtype == "program":
            _validate_program(r, where, errors)
            if r.get("event") == "compile" and _finite(r.get("compile_s")):
                n_compile_obs += 1
            # program records are first-class telemetry evidence for ALL
            # three --require-telemetry legs: a run killed before the
            # final metrics snapshot landed still wrote its audit trail
            if r.get("event") == "retrace":
                n_retrace += 1
                site = r.get("site")
                if isinstance(site, str) and site.startswith("serve."):
                    serve_retrace_sites[site] = \
                        serve_retrace_sites.get(site, 0) + 1
            hbm = r.get("hbm")
            if isinstance(hbm, dict) and hbm \
                    and all(_finite(v) for v in hbm.values()):
                n_hbm += 1
        elif rtype == "accuracy":
            _validate_accuracy(r, where, errors)
            if _finite(r.get("value")) and _finite(r.get("bound_ratio")):
                n_accuracy += 1
                if r.get("site") == "serve":
                    n_serve_accuracy += 1
        elif rtype == "resilience":
            _validate_resilience(r, where, errors)
            if r.get("event") in ("retry", "resume"):
                n_resilience_proof += 1
        elif rtype == "serve":
            _validate_serve(r, where, errors)
            if r.get("event") == "dispatch":
                if isinstance(r.get("lanes"), int) and r["lanes"] >= 2 \
                        and r.get("cache") == "hit":
                    n_serve_batched += 1
                if r.get("cache") == "miss":
                    n_serve_miss += 1
            elif r.get("event") == "request" \
                    and _finite(r.get("total_s")):
                n_serve_requests += 1
        elif rtype == "span":
            _validate_span(r, where, errors)
            n_spans += 1
            if _finite(r.get("gflops")):
                n_gflops += 1
            if r.get("name") == "robust_cholesky.attempt" and \
                    (r.get("attrs") or {}).get("attempt", 0) >= 1:
                # attempt 0 is the plain factorization; only a shifted
                # RE-attempt proves the recovery path ran
                n_retries += 1
        elif rtype == "metrics":
            _validate_metrics(r, where, errors)
            for m in r.get("metrics") or []:
                if not isinstance(m, dict):
                    continue
                # histogram checks come BEFORE the finite-value guard:
                # histograms carry count/sum, never a 'value'
                if m.get("name") == "dlaf_compile_seconds" \
                        and m.get("kind") == "histogram" \
                        and isinstance(m.get("count"), int) \
                        and m["count"] >= 1 and _finite(m.get("sum")):
                    n_compile_obs += 1
                if not _finite(m.get("value")):
                    continue
                if m.get("name") == "dlaf_comm_collective_bytes_total" \
                        and m["value"] > 0:
                    n_coll += 1
                    axis = (m.get("labels") or {}).get("axis")
                    if axis:
                        byte_axes.add(axis)
                if m.get("name") == "dlaf_comm_overlapped_total" \
                        and m["value"] > 0:
                    labels = m.get("labels") or {}
                    if labels.get("algo") and labels.get("axis"):
                        overlap_axes.add(labels["axis"])
                        if str(labels["algo"]).startswith("bt_"):
                            n_bt_overlap += 1
                if m.get("name") == "dlaf_dc_merges_total" \
                        and m["value"] > 0 \
                        and (m.get("labels") or {}).get("mode") == "batched":
                    n_dc_batched += 1
                if m.get("name") == "dlaf_fallback_total" and m["value"] > 0:
                    n_fallbacks += 1
                if m.get("name") == "dlaf_circuit_state":
                    # records are ordered, so this ends at the LAST
                    # snapshot's value per site — the state the run
                    # finished in
                    site = (m.get("labels") or {}).get("site", "")
                    circuit_state[site] = float(m["value"])
                if m.get("name") == "dlaf_hbm_bytes":
                    n_hbm += 1
                if m.get("name") == "dlaf_retrace_total" and m["value"] >= 1:
                    n_retrace += 1
                    site = (m.get("labels") or {}).get("site", "")
                    if str(site).startswith("serve.") and m["value"] >= 2:
                        serve_retrace_sites[site] = max(
                            serve_retrace_sites.get(site, 0),
                            int(m["value"]))
        elif rtype == "log":
            if not isinstance(r.get("msg"), str):
                errors.append(f"{where}: log without msg")
    if require_spans and n_spans == 0:
        errors.append("artifact contains no span records")
    if require_gflops and n_gflops == 0:
        errors.append("artifact contains no span with finite derived gflops")
    if require_collectives and n_coll == 0:
        errors.append("artifact contains no positive "
                      "dlaf_comm_collective_bytes_total counter")
    if require_retries and n_retries == 0:
        errors.append("artifact contains no robust_cholesky.attempt "
                      "retry span (attempt >= 1)")
    if require_fallbacks and n_fallbacks == 0:
        errors.append("artifact contains no positive dlaf_fallback_total "
                      "counter")
    if require_dc_batch and n_dc_batched == 0:
        errors.append("artifact contains no positive "
                      "dlaf_dc_merges_total{mode=batched} counter")
    if require_bt_overlap and n_bt_overlap == 0:
        errors.append("artifact contains no positive "
                      "dlaf_comm_overlapped_total counter with a bt_* algo")
    if require_telemetry:
        if n_compile_obs == 0:
            errors.append("artifact contains no finite compile-seconds "
                          "observation (program record or "
                          "dlaf_compile_seconds histogram)")
        if n_hbm == 0:
            errors.append("artifact contains no finite HBM accounting "
                          "(dlaf_hbm_bytes gauge or program-record hbm)")
        if n_retrace == 0:
            errors.append("artifact contains no retrace evidence "
                          "(dlaf_retrace_total counter >= 1 or program "
                          "retrace record)")
    if require_accuracy and n_accuracy == 0:
        errors.append("artifact contains no accuracy record with finite "
                      "value and bound_ratio")
    if require_serve:
        if n_serve_batched == 0:
            errors.append("artifact contains no batched serve dispatch "
                          "(dispatch record with lanes >= 2, cache hit)")
        if n_serve_miss > 0:
            errors.append(f"artifact contains {n_serve_miss} serve "
                          "dispatch(es) with cache miss — a warmed "
                          "steady-state stream must be all hits")
        if n_serve_requests == 0:
            errors.append("artifact contains no serve request record with "
                          "finite latency")
        if n_serve_accuracy == 0:
            errors.append("artifact contains no per-request accuracy "
                          "record (site serve, finite value+bound_ratio)")
        hot = sorted(s for s, c in serve_retrace_sites.items() if c >= 2)
        if hot:
            errors.append("serve bucket program(s) retraced mid-stream "
                          f"(count >= 2): {hot}")
    if require_resilience:
        if n_resilience_proof == 0:
            errors.append("artifact contains no resilience retry/resume "
                          "record (recovery never exercised)")
        open_sites = sorted(s for s, v in circuit_state.items() if v >= 2)
        if open_sites:
            errors.append("circuit breaker(s) left open at artifact end "
                          f"(dlaf_circuit_state >= 2): {open_sites}")
    if require_flight:
        if n_flight_triggers == 0:
            errors.append("artifact contains no flight_trigger record "
                          "with a known reason (no incident dump)")
        if n_flight_context == 0:
            errors.append("flight artifact carries no pre-trigger context "
                          "records (the ring captured nothing)")
    if require_devtrace:
        if n_overlap_proof == 0:
            errors.append("artifact contains no measured_overlap record "
                          "with finite overlap_frac and positive "
                          "attributed collective time (the device "
                          "timeline attributed no collectives)")
        if n_devtrace_covered == 0:
            got = (f" (got {['%.3f' % c for c in devtrace_coverages]})"
                   if devtrace_coverages else "")
            errors.append("artifact contains no devtrace record with "
                          "attribution coverage >= "
                          f"{DEVTRACE_COVERAGE_FLOOR}{got}")
    if require_critpath:
        if n_critpath_covered == 0:
            got = (f" (got {['%.3f' % c for c in critpath_coverages]})"
                   if critpath_coverages else "")
            errors.append("artifact contains no critpath record with "
                          ">= 1 step and join coverage >= "
                          f"{CRITPATH_COVERAGE_FLOOR}{got}")
        if n_whatif == 0:
            errors.append("artifact contains no whatif projection record "
                          "(critpath attribution produced no headroom "
                          "ranking)")
    if require_fleet:
        if n_fleet_routes == 0:
            errors.append("artifact contains no fleet route record (the "
                          "router never dispatched anything)")
        if n_fleet_lost > 0:
            errors.append(f"artifact contains {n_fleet_lost} fleet "
                          "ticket_lost record(s) — the zero-loss "
                          "contract (docs/fleet.md) is violated")
        if n_fleet_ungraceful_dead > 0 and n_fleet_redispatch == 0:
            errors.append(f"artifact contains {n_fleet_ungraceful_dead} "
                          "ungraceful fleet worker death(s) but no "
                          "redispatch record — failover never ran")
    if require_comm_overlap:
        if not {"row", "col"} <= overlap_axes:
            errors.append("artifact lacks positive finite "
                          "dlaf_comm_overlapped_total{algo,axis} counters "
                          f"for both mesh axes (got {sorted(overlap_axes)})")
        if not {"row", "col"} <= byte_axes:
            errors.append("artifact lacks finite per-axis "
                          "dlaf_comm_collective_bytes_total for both mesh "
                          f"axes (got {sorted(byte_axes)})")
    return errors


def read_records(path: str) -> list:
    """Parse a JSONL artifact; raises ValueError on an unparsable line."""
    records = []
    with open(path) as f:
        for ln, raw in enumerate(f, 1):
            raw = raw.strip()
            if not raw:
                continue
            try:
                records.append(json.loads(raw))
            except ValueError as e:
                raise ValueError(f"{path}:{ln}: unparsable JSON ({e})")
    return records


def validate_file(path: str, **require) -> list:
    """Errors for the artifact at ``path`` (empty list = schema-valid)."""
    try:
        records = read_records(path)
    except (OSError, ValueError) as e:
        return [str(e)]
    return validate_records(records, **require)


# ---------------------------------------------------------------------------
# History line schemas (.bench_history.jsonl / .accuracy_history.jsonl)
# ---------------------------------------------------------------------------
# Bare measurement lines (no v/type/ts envelope — the bench file predates
# the obs schema), but schema-owned HERE — ONE validating reader
# parameterized by ``kind`` — so scripts/bench_gate.py and
# scripts/accuracy_gate.py read through the same code path and never
# silently ingest a malformed or non-finite entry (ISSUE 8 satellite: no
# second bespoke history parser).

#: ``kind`` -> (numeric fields, string fields): numeric fields must be
#: finite; string fields must be non-empty strings.
HISTORY_KINDS = {
    "bench": (("gflops", "t", "n", "nb"),
              ("variant", "platform", "dtype", "ts", "source")),
    "accuracy": (("value", "bound_ratio", "n", "nb"),
                 ("site", "metric", "platform", "dtype", "ts", "source")),
}

#: Backward-compatible aliases for the original bench-only schema names.
HISTORY_NUMERIC_FIELDS, HISTORY_STRING_FIELDS = HISTORY_KINDS["bench"]


def validate_history_line(line: dict, kind: str = "bench") -> list:
    """Error strings for ONE history measurement line (empty = valid)."""
    errors = []
    if not isinstance(line, dict):
        return [f"{kind} history line is not an object"]
    numeric, strings = HISTORY_KINDS[kind]
    for key in numeric:
        if not _finite(line.get(key)):
            errors.append(f"{kind} history field {key!r} missing/non-finite "
                          f"(got {line.get(key)!r})")
    for key in strings:
        if not isinstance(line.get(key), str) or not line.get(key):
            errors.append(f"{kind} history field {key!r} missing/empty")
    return errors


def validate_history_records(records, kind: str = "bench") -> list:
    errors = []
    for i, line in enumerate(records):
        for e in validate_history_line(line, kind):
            errors.append(f"entry {i}: {e}")
    return errors


def read_history_records(path: str, kind: str = "bench") -> list:
    """Parse + validate an append-only measurement history; raises
    ValueError on an unparsable or schema-invalid line (loud by contract:
    a bad line would otherwise skew every gate baseline derived from the
    file)."""
    records = read_records(path)
    errors = validate_history_records(records, kind)
    if errors:
        raise ValueError(f"{path}: invalid {kind} history: "
                         + "; ".join(errors[:5])
                         + (f" (+{len(errors) - 5} more)"
                            if len(errors) > 5 else ""))
    return records


def append_history_line(path: str, line: dict, kind: str = "bench") -> dict:
    """Validate + append one measurement line to a history log (the
    single write path — scripts/measure_common routes through here).
    Raises ValueError instead of writing a line the readers would have
    to reject."""
    errors = validate_history_line(line, kind)
    if errors:
        raise ValueError(f"refusing to append invalid {kind} history line: "
                         + "; ".join(errors))
    with open(path, "a") as f:
        f.write(json.dumps(line) + "\n")
    return line


def accuracy_record_to_history_line(rec: dict) -> Optional[dict]:
    """Project one ``accuracy`` JSONL record onto the accuracy-history
    line shape (the ``--fresh`` ingestion of scripts/accuracy_gate.py —
    shared here so the gate and any future appender agree on the
    mapping). Returns None for records that carry no gateable budget
    (informational metrics without ``bound_ratio``); a nonfinite record
    maps to ``bound_ratio: inf`` — NOT JSON-appendable, by design: the
    gate must trip on it, never archive it."""
    if rec.get("type") != "accuracy":
        return None
    if rec.get("nonfinite") is True:
        value = ratio = float("inf")
    elif _finite(rec.get("value")) and _finite(rec.get("bound_ratio")):
        value, ratio = rec["value"], rec["bound_ratio"]
    else:
        return None
    return {"site": rec.get("site"), "metric": rec.get("metric"),
            "platform": rec.get("platform"), "dtype": rec.get("dtype"),
            "n": rec.get("n"), "nb": rec.get("nb"),
            "value": value, "bound_ratio": ratio}
