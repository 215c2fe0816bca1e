"""Program telemetry: compile walls, retrace counts, HBM footprints.

The ``DLAF_PROGRAM_TELEMETRY`` knob (``Configuration.program_telemetry``,
layered like every other config field) arms a small AOT/jit
instrumentation layer that the algorithm entry points and the library's
cached-program sites route through. Three signals, per ``site`` label:

* ``dlaf_compile_seconds{site}`` — histogram of XLA compile wall per
  compiled program (trace wall recorded separately on the ``program``
  record). Today these numbers are buried in one-off probe scripts
  (``scripts/compile_scaling.py``); the library now owns the plumbing
  and the script calls it.
* ``dlaf_retrace_total{site}`` — counter of traces (first trace = 1; a
  higher count is a retrace). This finally makes the documented
  "trace-time comm counters add again on retrace" caveat *detectable*:
  the collective byte counters are per-program models, and
  ``dlaf_retrace_total`` says how many programs contributed.
* ``dlaf_hbm_bytes{what=args|output|temp|code|peak,site}`` — gauges from
  ``compiled.memory_analysis()`` (the allocator's own accounting; the
  OOM-vs-fit oracle of the round-4 probe sessions).

Each compile additionally emits a ``program`` JSONL record (schema:
:mod:`dlaf_tpu.obs.sinks`) carrying the same numbers, so artifacts keep
per-program detail that gauges (last-write-wins) cannot.

Two call styles:

* :func:`call` — ambient instrumentation for library call sites:
  ``telemetry.call(site, jitted, *args, **static_kwargs)``. Off (the
  default), it is a pure passthrough to ``jitted(*args, **kwargs)`` —
  same callable, same program caches, bitwise no-op. On, the site runs
  through a keyed AOT ``lower()``/``compile()`` with the walls and the
  memory analysis recorded once per distinct program (keyed on the
  jitted callable + input avals/shardings + static kwargs; invalidated
  with the config program caches).
* :func:`aot_compile` — the explicit probe API: always measures,
  records only when the knob is on. ``scripts/compile_scaling.py`` is a
  thin CLI over this.

Builders whose traced bodies the library re-enters per group (e.g. the
level-batched D&C secular dispatch) instead call :func:`count_retrace`
from *inside* the traced body — a trace-time increment, zero runtime
cost, exactly the comm-counter discipline.
"""

from __future__ import annotations

import time
from typing import Any, NamedTuple, Optional

from ._state import STATE

#: ``memory_analysis()`` attribute -> gauge label. ``peak`` is derived:
#: args + output + temp - alias (the est_live the probe scripts printed).
_MEMORY_FIELDS = {
    "argument_size_in_bytes": "args",
    "output_size_in_bytes": "output",
    "temp_size_in_bytes": "temp",
    "alias_size_in_bytes": "alias",
    "generated_code_size_in_bytes": "code",
}

#: AOT program cache for :func:`call`: (site, id(fn), arg key) ->
#: (fn, compiled). fn is held strongly so id() cannot be recycled under a
#: live key. Cleared with the config program caches (knob changes rebuild
#: the underlying jitted callables, and these executables with them) and
#: LRU-bounded at :data:`MAX_PROGRAMS`: the underlying builder lru_caches
#: are bounded (32-64), and without a bound here every builder eviction
#: would pin its dead jitted callable + XLA executable forever in a
#: long-lived telemetry-on process.
_PROGRAMS: dict = {}

MAX_PROGRAMS = 256

_registered = False


class _CacheHandle:
    """config.register_program_cache adapter for the AOT program cache."""

    @staticmethod
    def cache_clear() -> None:
        _PROGRAMS.clear()


def _ensure_registered() -> None:
    global _registered
    if not _registered:
        _registered = True
        from ..config import register_program_cache

        register_program_cache(_CacheHandle)


def active() -> bool:
    """Fast-path gate (one attribute read) for instrumented sites."""
    return STATE.telemetry_on


def _registry():
    if STATE.registry is None:
        from .metrics import Registry

        STATE.registry = Registry()
    return STATE.registry


def count_retrace(site: str) -> None:
    """One trace of ``site``'s program happened (callable from inside a
    traced body — the increment runs at trace time, like the comm byte
    counters). No-op when the knob is off."""
    if not STATE.telemetry_on:
        return
    _registry().counter("dlaf_retrace_total", site=site).inc()
    if STATE.sink is not None:
        STATE.sink.write({"type": "program", "site": site,
                          "event": "retrace", "attrs": {}})


class AotProgram(NamedTuple):
    """Result of :func:`aot_compile`: the compiled executable plus the
    measured walls and the memory analysis (None where the backend
    offers none)."""

    compiled: Any
    trace_s: float
    compile_s: float
    memory: Optional[dict]


def memory_analysis_dict(compiled) -> Optional[dict]:
    """``compiled.memory_analysis()`` as a plain dict of byte counts
    (``args``/``output``/``temp``/``alias``/``code`` + derived ``peak``),
    or None when the backend provides no analysis."""
    try:
        m = compiled.memory_analysis()
    except Exception:
        return None
    if m is None:
        return None
    out = {}
    for field, label in _MEMORY_FIELDS.items():
        v = getattr(m, field, None)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[label] = float(v)
    if not out:
        return None
    out["peak"] = (out.get("args", 0.0) + out.get("output", 0.0)
                   + out.get("temp", 0.0) - out.get("alias", 0.0))
    return out


def record_compile(site: str, *, compile_s: float,
                   trace_s: Optional[float] = None,
                   memory: Optional[dict] = None, **attrs) -> None:
    """Record one compiled program: compile-seconds histogram, HBM
    gauges, and a ``program`` JSONL record. No-op when the knob is off
    (the explicit probe API measures regardless and only *records*
    through here)."""
    if not STATE.telemetry_on:
        return
    reg = _registry()
    reg.histogram("dlaf_compile_seconds", site=site).observe(compile_s)
    if memory:
        for what in ("args", "output", "temp", "code", "peak"):
            if what in memory:
                reg.gauge("dlaf_hbm_bytes", what=what,
                          site=site).set(memory[what])
    if STATE.sink is not None:
        rec = {"type": "program", "site": site, "event": "compile",
               "compile_s": float(compile_s), "attrs": dict(attrs)}
        if trace_s is not None:
            rec["trace_s"] = float(trace_s)
        if memory:
            rec["hbm"] = {k: float(v) for k, v in memory.items()}
        STATE.sink.write(rec)


def aot_compile(site: str, jitted, *args, **kwargs) -> AotProgram:
    """Timed ``jitted.lower(*args, **kwargs).compile()`` + memory
    analysis — THE plumbing the probe scripts used to hand-roll. Always
    measures (it is an explicit call); feeds the registry/artifact only
    when the knob is on. ``args`` may be concrete arrays or
    ``jax.ShapeDtypeStruct`` specs (no execution happens here)."""
    t0 = time.perf_counter()
    lowered = jitted.lower(*args, **kwargs)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    memory = memory_analysis_dict(compiled)
    count_retrace(site)
    record_compile(site, compile_s=t2 - t1, trace_s=t1 - t0, memory=memory)
    record_schedule(site, compiled)
    return AotProgram(compiled, t1 - t0, t2 - t1, memory)


def record_schedule(site: str, compiled) -> None:
    """Record the per-step HLO schedule of a compiled program so the
    critpath joiner (``obs.critpath``) can attribute device intervals to
    ``<algo>.step<k>.<phase>`` scopes offline.  Emits one ``schedule``
    record per program carrying step scopes; silent no-op when the sink
    is off, the program has no step scopes, or the backend refuses to
    render optimized HLO text."""
    if not (STATE.telemetry_on and STATE.sink is not None):
        return
    try:
        hlo_text = compiled.as_text()
    except Exception:  # backend without text rendering — never fail the compile
        return
    from . import critpath

    rec = critpath.schedule_record(site, hlo_text)
    if rec is not None:
        STATE.sink.write(rec)


def _arg_key(x):
    # arrays key on their program-relevant identity (aval + sharding —
    # two layouts of one shape are different programs); everything else
    # is a static and keys on its value
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("aval", tuple(x.shape), str(x.dtype),
                getattr(x, "sharding", None))
    return x


def call(site: str, fn, *args, **kwargs):
    """Run ``fn(*args, **kwargs)`` with program telemetry.

    Knob off: ``fn(*args, **kwargs)`` — the identical jitted callable,
    its own caches, bitwise no-op (the instrumented sites cost one
    attribute read). Knob on: the call is served by an AOT-compiled
    executable keyed on (site, fn, input avals/shardings, static
    kwargs); the first call per key records the trace/compile walls, a
    retrace count, and the HBM gauges. ``kwargs`` must be the jitted
    callable's *static* keyword arguments (they are baked into the
    compiled program); dynamic operands go positionally.
    """
    if not STATE.telemetry_on:
        return fn(*args, **kwargs)
    lower = getattr(fn, "lower", None)
    if lower is None:
        return fn(*args, **kwargs)    # not a jitted callable; nothing to AOT
    try:
        key = (site, id(fn), tuple(_arg_key(a) for a in args),
               tuple(sorted(kwargs.items())))
        hash(key)
    except TypeError:
        return fn(*args, **kwargs)    # unhashable statics; stay uninstrumented
    _ensure_registered()
    entry = _PROGRAMS.get(key)
    if entry is None:
        prog = aot_compile(site, fn, *args, **kwargs)
        while len(_PROGRAMS) >= MAX_PROGRAMS:
            _PROGRAMS.pop(next(iter(_PROGRAMS)))     # LRU: oldest first
        _PROGRAMS[key] = entry = (fn, prog.compiled)
    else:
        # keep insertion order ≈ recency so the bound evicts cold programs
        _PROGRAMS[key] = _PROGRAMS.pop(key)
    return entry[1](*args)


def _reset_for_tests() -> None:
    _PROGRAMS.clear()
