"""Wall-clock timing and phase profiling.

TPU-native counterpart of the reference's ``common::Timer``
(``common/timer.h``). Phase profiling is now a thin veneer over the
:mod:`dlaf_tpu.obs` span tracer: each ``phase(...)`` region is an obs span
(structured JSONL record + duration histogram when ``DLAF_METRICS_PATH``
is set, and a ``jax.profiler.TraceAnnotation`` of the phase's name on
whatever profiler timeline is being recorded) while the familiar
``report()`` {name: seconds} aggregation is kept for existing callers.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

from .. import obs


class Timer:
    """Elapsed-seconds timer (reference ``common::Timer``)."""

    def __init__(self):
        self._t0 = time.perf_counter()

    def reset(self) -> None:
        self._t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0


class PhaseTimer:
    """Named phase timings for multi-stage algorithms (eigensolver pipeline).

    Use ``with phases.phase("stage.reduction_to_band"): ...``; ``report()``
    returns {name: seconds}. Phase names should stay distinct from the
    algorithms' own entry-span names (hence the ``stage.`` prefix in the
    pipeline) — a fenced stage wall-time span sharing a name with an
    unfenced dispatch-time entry span would aggregate two different
    populations under one histogram. Phases are obs spans, so with
    observability configured
    they also land in the JSONL artifact and on profiler timelines. When
    ``profile_dir`` is set (the pre-obs knob), a ``jax.profiler`` trace is
    additionally started for the timer's lifetime — even if the obs layer
    itself is off — preserving the original contract.
    """

    def __init__(self, profile_dir: Optional[str] = None):
        self.times: dict[str, float] = {}
        self.profile_dir = profile_dir
        self._tracing = False

    @contextlib.contextmanager
    def phase(self, name: str, **attrs):
        # keep the span name constant across repeats (one histogram per
        # phase, aggregable durations) and put per-call context — run
        # index and the like — in span attrs instead
        from ..obs._state import STATE

        if self.profile_dir is not None and STATE.trace_dir \
                and STATE.trace_dir != self.profile_dir:
            # jax.profiler supports one trace per process: the obs layer's
            # DLAF_TRACE_DIR wins and this timer's directory stays empty —
            # say so rather than silently dropping the requested output
            obs.get_logger("timer").warning_once(
                ("profile_dir_superseded", self.profile_dir),
                f"profile_dir={self.profile_dir!r} superseded by "
                f"DLAF_TRACE_DIR={STATE.trace_dir!r}; the trace lands there",
                profile_dir=self.profile_dir, trace_dir=STATE.trace_dir)
        if self.profile_dir is not None and not STATE.trace_dir:
            # pre-obs contract: this timer owns a jax.profiler trace. Only
            # when the obs layer has no trace dir of its own — otherwise
            # the spans below start/annotate exactly one process trace
            # (a second start_trace would fail).
            if not self._tracing and obs.start_profiler(self.profile_dir):
                # claimed via the obs layer's single-owner protocol, so a
                # later configure(trace_dir=...) mid-phase (lazy config
                # init inside an algorithm call) can't start_trace again
                # over this live trace
                self._tracing = True
            # a live span even with the obs layer off: the span is what
            # labels the profiler timeline (it emits nothing without a
            # sink or registry)
            sp = obs.Span(name, **attrs)
        else:
            sp = obs.span(name, **attrs)
        with sp:
            # t0 after span entry: one-time jax.profiler.start_trace cost
            # (possibly hundreds of ms, paid by the first phase) stays out
            # of the reported per-phase seconds, as pre-obs
            t0 = time.perf_counter()
            yield
            self.times[name] = self.times.get(name, 0.0) \
                + time.perf_counter() - t0

    def stop(self) -> None:
        from ..obs._state import STATE

        if self._tracing:
            # routed through the obs layer so its profiler_started flag
            # clears with the trace (we claimed it at start)
            obs.stop_profiler()
            self._tracing = False
        elif self.profile_dir is not None \
                and STATE.trace_dir == self.profile_dir:
            # the obs layer started the profiler on this timer's behalf
            # (profile_dir doubles as the obs trace dir); stopping here
            # keeps the pre-obs contract that stop() lands the trace files
            obs.stop_profiler()

    def report(self) -> dict[str, float]:
        return dict(self.times)
