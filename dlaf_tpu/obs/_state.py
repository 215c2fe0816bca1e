"""Shared mutable state of the observability layer.

One module-level :data:`STATE` object, mutated only by
:func:`dlaf_tpu.obs.configure` (driven by ``config.initialize()``) and by
the lazy env-var fallback for processes that use the library without ever
initializing the runtime. Every hot-path check in the tracer/metrics/logger
is a read of one attribute here — no locks, no dict lookups — so call sites
stay allocation-free when observability is off.
"""

from __future__ import annotations

import os

#: DLAF_LOG levels, lowest first. "off" silences everything.
LOG_LEVELS = {"debug": 10, "info": 20, "warning": 30, "error": 40, "off": 99}


class _ObsState:
    __slots__ = ("configured", "log_level", "log_level_num", "metrics_on",
                 "annotate", "trace_dir", "sink", "registry",
                 "profiler_started", "atexit_registered", "telemetry_on",
                 "rank", "flight", "exporter_port")

    def __init__(self):
        self.configured = False
        self.log_level = "info"
        self.log_level_num = LOG_LEVELS["info"]
        self.metrics_on = False          # counters/spans record + JSONL sink
        # trace dir set: spans and named scopes on, obs owns a profiler
        # session (a live span annotates any session, with or without this)
        self.annotate = False
        self.trace_dir = ""              # jax.profiler trace output dir
        self.sink = None                 # type: Optional[object]  # JsonlSink
        self.registry = None             # type: Optional[object]  # Registry
        self.profiler_started = False
        self.atexit_registered = False
        self.telemetry_on = False        # DLAF_PROGRAM_TELEMETRY knob
        self.rank = None                 # type: Optional[int]  # process rank
        self.flight = None               # type: Optional[object]  # recorder
        self.exporter_port = 0           # DLAF_METRICS_PORT in effect (0=off)


STATE = _ObsState()


def ensure_env_defaults() -> None:
    """Lazy fallback: pick up ``DLAF_LOG``/``DLAF_METRICS_PATH``/
    ``DLAF_TRACE_DIR`` straight from the environment when nothing has
    called :func:`dlaf_tpu.obs.configure` yet (library use without
    ``config.initialize()``). A later real configure() overrides this."""
    if STATE.configured:
        return
    from . import configure

    level = os.environ.get("DLAF_LOG", "info")
    if str(level).strip().lower() not in LOG_LEVELS:
        # this path is reached from informational log calls deep inside
        # library code (a knob-resolution notice, a native-load warning):
        # a misspelled env var must not turn those into a crash. The
        # explicit config.initialize() path still rejects bad values.
        import sys

        print(f"dlaf_tpu[warning] obs: DLAF_LOG={level!r} is not one of "
              f"{tuple(LOG_LEVELS)}; using 'info'", file=sys.stderr,
              flush=True)
        level = "info"
    def _int_env(name):
        raw = os.environ.get(name, "").strip()
        try:
            val = int(raw) if raw else 0
        except ValueError:
            val = -1
        if val < 0:
            import sys

            # same stance as the DLAF_LOG fallback above: a malformed
            # (or negative — configure() rejects those too) env var on
            # this lazy path warns instead of crashing a bare log call;
            # config.initialize() still rejects it loudly
            print(f"dlaf_tpu[warning] obs: {name}={raw!r} is not a "
                  "non-negative int; using 0 (off)", file=sys.stderr,
                  flush=True)
            return 0
        return val

    configure(log_level=level,
              metrics_path=os.environ.get("DLAF_METRICS_PATH", ""),
              trace_dir=os.environ.get("DLAF_TRACE_DIR", ""),
              program_telemetry=os.environ.get(
                  "DLAF_PROGRAM_TELEMETRY", "").strip().lower()
              in ("1", "true", "yes", "on"),
              metrics_port=_int_env("DLAF_METRICS_PORT"),
              flight_recorder=_int_env("DLAF_FLIGHT_RECORDER"))


def current_rank():
    """The process rank for record stamping: the rank an owner pinned via
    :func:`dlaf_tpu.obs.set_rank` (``initialize_multihost`` does), else
    ``jax.process_index()`` — but only once jax is imported AND a backend
    already exists. A bare log write must neither import jax nor trigger
    backend initialization (a process that creates a backend takes the
    chip; that is never a side effect of logging); records written
    before the backend comes up simply carry no ``rank`` field (optional
    by schema). ``xla_bridge._backends`` has no public spelling:
    ``jax.extend.backend.backends()`` would initialize them."""
    if STATE.rank is not None:
        return STATE.rank
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge

    if not xla_bridge._backends:
        return None         # no live backend: process_index would init one
    try:
        STATE.rank = int(jax.process_index())
    except Exception:
        return None
    return STATE.rank
