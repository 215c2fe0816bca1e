"""Span-based tracer: nested host-side spans + device-timeline names.

A span measures host wall clock around a region (an algorithm entry, a
pipeline stage, a timed miniapp run) and, whenever it is live (metrics
sink on or a trace dir set), also enters a
``jax.profiler.TraceAnnotation`` of the same name: whatever profiler
session is running -- obs's own (``DLAF_TRACE_DIR``), a ``PhaseTimer``'s,
the benchmark's, an operator's ``jax.profiler.trace`` -- sees the span on
the clock it shares with the device lines. Without a session the
annotation is a flag test. Builders that run at *trace time* (the
unrolled per-``k`` loops) use :func:`named_span` instead — a
``jax.named_scope`` whose cost is paid once at trace time and whose names
land in the compiled program's op metadata (the device timeline), never
in the runtime hot path.

Nesting is tracked per-thread; each emitted span record carries its
``depth`` and ``parent`` so ``scripts/profile_summary.py`` can rebuild the
call tree from the flat JSONL. Spans given ``flops`` derive GFlop/s at
exit — the per-step records BENCH rounds previously reverse-engineered
from stdout.

When observability is off, :func:`span`/:func:`named_span` return
module-level no-op singletons: zero per-call allocation (ISSUE 1
acceptance criterion).
"""

from __future__ import annotations

import threading
import time

from ._state import STATE


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attr(self, key, value) -> None:
        pass


#: Singletons for the disabled fast path. NOOP_CTX doubles as the
#: trace-time named_span no-op.
NOOP_SPAN = _NoopSpan()
NOOP_CTX = NOOP_SPAN

_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class Span:
    """Reentrant context manager: one Span object per region entry (the
    same name may be nested or repeated freely)."""

    __slots__ = ("name", "attrs", "flops", "fenced", "t0", "dur_s", "depth",
                 "parent", "_ann")

    def __init__(self, name: str, flops=None, fenced=True, **attrs):
        self.name = name
        self.attrs = attrs
        self.flops = flops
        self.fenced = fenced
        self.t0 = None
        self.dur_s = None
        self._ann = None

    def set_attr(self, key, value) -> None:
        """Attach/override an attribute after entry (e.g. a route resolved
        mid-region)."""
        self.attrs[key] = value

    def __enter__(self):
        st = _stack()
        self.depth = len(st)
        self.parent = st[-1].name if st else None
        st.append(self)
        # *starting* a session stays tied to DLAF_TRACE_DIR; the
        # annotation labels any session, whoever started it
        _maybe_start_profiler()
        import jax

        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur_s = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        self._ann = None
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        elif self in st:       # exotic exit order; keep the stack sane
            st.remove(self)
        self._emit()
        return False

    def _emit(self) -> None:
        if STATE.registry is not None:
            STATE.registry.histogram("dlaf_span_seconds",
                                     span=self.name).observe(self.dur_s)
        if STATE.sink is None:
            return
        rec = {"type": "span", "name": self.name, "dur_s": self.dur_s,
               "depth": self.depth, "parent": self.parent,
               "attrs": self.attrs}
        if not self.fenced:
            rec["fenced"] = False
        if self.flops is not None:
            rec["flops"] = float(self.flops)
            # derive GFlop/s only when the region's wall is honest work
            # (fenced): an unfenced span around async JAX dispatch would
            # report dispatch time as throughput — numbers past hardware
            # peak that then outrank the real ones in summaries
            if self.fenced and self.dur_s > 0:
                rec["gflops"] = float(self.flops) / self.dur_s / 1e9
        STATE.sink.write(rec)


def span(name: str, flops=None, fenced=True, **attrs):
    """A host-side span, or the no-op singleton when observability is off.

    ``flops``: flop count of the region — the emitted record then carries
    derived ``gflops`` (only when ``fenced``; callers whose region does not
    block on device completion pass ``fenced=False`` so the record keeps
    the flop model but never a dispatch-time throughput). Other keyword
    arguments become the span's attrs.
    """
    if not (STATE.metrics_on or STATE.annotate):
        return NOOP_SPAN
    return Span(name, flops=flops, fenced=fenced, **attrs)


def entry_span(name: str, attrs_fn):
    """Algorithm-entry span: unfenced (the library dispatches async work;
    device completion is the caller's fence, so no derived gflops), with
    lazily built attrs — ``attrs_fn`` is a zero-argument callable returning
    the attr dict (``flops`` allowed as a key) that is never invoked when
    observability is off, keeping flop models and attr strings off the
    disabled path (the cost contract). While the metrics sink is on it
    also counts the call, ``dlaf_entry_calls_total{entry}``: the base of
    ``dlaf_entry_programs_total{entry}`` (device programs an entry
    dispatches, counted at its dispatch sites)."""
    if not (STATE.metrics_on or STATE.annotate):
        return NOOP_SPAN
    if STATE.metrics_on:
        STATE.registry.counter("dlaf_entry_calls_total", entry=name).inc()
    kw = dict(attrs_fn())
    return Span(name, flops=kw.pop("flops", None), fenced=False, **kw)


def named_span(name: str):
    """Trace-time phase name for code inside ``jit``/``shard_map``: a
    ``jax.named_scope`` (op-metadata names on the device timeline, zero
    runtime cost) when observability is on; the no-op singleton otherwise.
    """
    if not (STATE.metrics_on or STATE.annotate):
        return NOOP_CTX
    import jax

    return jax.named_scope(name)


def scoped_step(name: str, fn, steps: int = 1):
    """Wrap a ``lax.scan`` step body so every op it traces carries the
    ``name`` scope. A scan body is traced ONCE for all iterations, so the
    scope can carry no step index — the critpath joiner reconstructs the
    per-iteration timeline from occurrence order instead (one execution
    of the body's instruction set per iteration; the one-traced-body
    limitation, docs/observability.md). ``steps`` is the scan's trip
    count: while the body is traced, :func:`traced_step_count` returns it
    (times any enclosing scoped body's), so trace-time counters such as
    the collectives' count per EXECUTED step. Only the first trace of a
    wrapped body counts — when ``lax.scan`` traces a body again (a
    weakly typed carry), the count inside is 0, never double. Zero-cost
    pass-through when observability is off (``named_span`` returns the
    no-op singleton)."""
    if not (STATE.metrics_on or STATE.annotate):
        return fn
    traced = False

    def wrapped(*args):
        nonlocal traced
        outer = traced_step_count()
        _tls.step_count = 0 if traced else outer * steps
        traced = True
        try:
            with named_span(name):
                return fn(*args)
        finally:
            _tls.step_count = outer

    return wrapped


def traced_step_count() -> int:
    """How many times the code being traced on this thread executes per
    run of its program: the product of the trip counts of the enclosing
    :func:`scoped_step` bodies, 1 outside any."""
    return getattr(_tls, "step_count", 1)


def current_span():
    """Innermost live Span of this thread, or None (attrs can be attached
    to it from helper layers without plumbing the object through)."""
    st = _stack()
    return st[-1] if st else None


def start_profiler(path: str) -> bool:
    """Start THE process-wide ``jax.profiler`` trace at ``path`` unless
    some owner (an obs span via ``DLAF_TRACE_DIR``, or a
    ``PhaseTimer(profile_dir=...)``) already claimed it; returns whether
    this call started it. The single ``STATE.profiler_started`` flag is
    the ownership protocol — every start/stop goes through here and
    :func:`stop_profiler` so two owners can never double-start the one
    trace jax allows per process.

    The python-call tracer is disabled (``python_tracer_level=0``): it
    floods the trace with ~1M ``$builtins isinstance``-grade events per
    unrolled build, and the Chrome-trace converter CAPS total events at
    ~1e6 — on a large traced run the flood evicts the XLA thunk events
    that device-time attribution (ISSUE 14, :mod:`dlaf_tpu.obs.
    devtrace`) exists to read. Host TraceMe events (our
    ``TraceAnnotation`` span mirrors) and the device op events are host-
    tracer products and survive."""
    if STATE.profiler_started:
        return False
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    # perfetto trace alongside the xplane: a gzipped JSON this container
    # can post-process WITHOUT tensorboard (scripts/profile_summary.py)
    jax.profiler.start_trace(path, create_perfetto_trace=True,
                             profiler_options=opts)
    STATE.profiler_started = True
    return True


def _maybe_start_profiler() -> None:
    """Start the process trace when a trace dir is configured (the
    green-field hook SURVEY §5 calls for); stopped by
    :func:`stop_profiler` (atexit-registered by configure)."""
    if STATE.trace_dir:
        start_profiler(STATE.trace_dir)


def stop_profiler() -> None:
    if STATE.profiler_started:
        import jax

        jax.profiler.stop_trace()
        STATE.profiler_started = False
        # the process trace is over: retire the trace config too, or the
        # next span in a long-lived process (pytest, a library caller)
        # silently starts a NEW trace into the same — possibly dead —
        # directory and keeps it open until interpreter exit. A fresh
        # configure(trace_dir=...) re-arms tracing explicitly.
        STATE.trace_dir = ""
        STATE.annotate = False
