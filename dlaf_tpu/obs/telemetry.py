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

Knob off and the metrics sink on, :func:`call` stays the passthrough and
only *remembers*, once per site, what it dispatched (the jitted callable,
the arguments' avals with their shardings, the static kwargs). A reader
that wants the program asks for it afterwards, and pays for it then:
:func:`programs` (the sites), :func:`compiled` (``lower().compile()``: the
persistent cache's executable; sets the ``dlaf_hbm_bytes`` gauges) and
:func:`phase_table` (``instruction -> phase`` from the executable's
``op_name`` scopes, :mod:`dlaf_tpu.obs.scopes`; sets
``dlaf_phase_instructions{site, phase}``). The benchmark's ``phase_ms.*``,
``program_temp_gib`` and ``program_code_mib`` read these.

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

#: site -> :class:`_Handle`: what :func:`call` dispatched there first (the
#: metrics sink on), or compiled there last (the knob on). Bounded and
#: cleared like ``_PROGRAMS``: a handle holds its jitted callable alive.
_HANDLES: dict = {}

_registered = False


class _CacheHandle:
    """config.register_program_cache adapter for the AOT program cache."""

    @staticmethod
    def cache_clear() -> None:
        _PROGRAMS.clear()
        _HANDLES.clear()


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


def _set_hbm_gauges(site: str, memory: Optional[dict]) -> None:
    """``dlaf_hbm_bytes{what, site}`` from a :func:`memory_analysis_dict`."""
    reg = _registry()
    for what in ("args", "output", "temp", "code", "peak"):
        if memory and what in memory:
            reg.gauge("dlaf_hbm_bytes", what=what,
                      site=site).set(memory[what])


def record_compile(site: str, *, compile_s: float,
                   trace_s: Optional[float] = None,
                   memory: Optional[dict] = None, **attrs) -> None:
    """Record one compiled program: compile-seconds histogram, HBM
    gauges, and a ``program`` JSONL record. No-op when the knob is off
    (the explicit probe API measures regardless and only *records*
    through here)."""
    if not STATE.telemetry_on:
        return
    _registry().histogram("dlaf_compile_seconds",
                          site=site).observe(compile_s)
    _set_hbm_gauges(site, memory)
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


def _aval(x):
    """What ``lower()`` needs of one positional argument: an array's shape,
    dtype, weak type and, where the array is committed to it, sharding
    (the four-chip solve lowers to the program that ran only with its
    operands' shardings; an uncommitted scalar beside them follows them,
    as it did in the call); anything else is handed on as it is."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        import jax

        sharding = getattr(x, "sharding", None) \
            if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding,
            weak_type=getattr(x, "weak_type", False))
    return x


class _Handle:
    """What one site dispatched, enough to lower the same program again;
    the executable, the phases its tree emits and the phase table are
    filled in on demand and kept."""

    __slots__ = ("fn", "args", "kwargs", "compiled", "expected", "table")

    def __init__(self, fn, args, kwargs, compiled=None):
        self.fn = fn
        self.args = tuple(_aval(a) for a in args)
        self.kwargs = dict(kwargs)
        self.compiled = compiled
        self.expected = None        # phases of the lowered module's scopes
        self.table = None


def _remember(site: str, fn, args, kwargs, compiled=None) -> None:
    """Keep ``site``'s handle (None for a callable that cannot be lowered,
    so that the next call's lookup finds the site all the same)."""
    _ensure_registered()
    _HANDLES.pop(site, None)
    while len(_HANDLES) >= MAX_PROGRAMS:
        _HANDLES.pop(next(iter(_HANDLES)))
    _HANDLES[site] = (_Handle(fn, args, kwargs, compiled)
                      if hasattr(fn, "lower") else None)


def call(site: str, fn, *args, **kwargs):
    """Run ``fn(*args, **kwargs)`` with program telemetry.

    Knob off: ``fn(*args, **kwargs)`` — the identical jitted callable,
    its own caches, bitwise no-op (the instrumented sites cost one
    attribute read; two while the metrics sink is on, plus one dict lookup:
    the site's first call then remembers what it dispatched, for
    :func:`compiled` / :func:`phase_table`, and no later call builds
    anything). Knob on: the call is served by an AOT-compiled
    executable keyed on (site, fn, input avals/shardings, static
    kwargs); the first call per key records the trace/compile walls, a
    retrace count, and the HBM gauges. ``kwargs`` must be the jitted
    callable's *static* keyword arguments (they are baked into the
    compiled program); dynamic operands go positionally.
    """
    if not STATE.telemetry_on:
        if STATE.metrics_on and site not in _HANDLES:
            _remember(site, fn, args, kwargs)
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
        _remember(site, fn, args, kwargs, compiled=prog.compiled)
    else:
        # keep insertion order ≈ recency so the bound evicts cold programs
        _PROGRAMS[key] = _PROGRAMS.pop(key)
    return entry[1](*args)


def programs() -> list:
    """The sites whose dispatched program :func:`compiled` can hand out, in
    the order they were first called."""
    return [site for site, handle in _HANDLES.items() if handle is not None]


def _lower(handle: _Handle):
    """The handle's program lowered again (jit's trace cache makes it the
    trace that ran), with the phases its scopes name noted on the way."""
    from . import scopes

    lowered = handle.fn.lower(*handle.args, **handle.kwargs)
    if handle.expected is None:
        try:
            handle.expected = frozenset(scopes.phases_of_text(
                lowered.as_text(debug_info=True)))
        except Exception:       # a dialect without locations: nothing named
            handle.expected = frozenset()
    return lowered


def compiled(site: str):
    """The executable ``site`` dispatched, or None for a site no call
    remembered. Built on demand (``lower().compile()``: seconds from the
    persistent cache, minutes without; never paid by a call) and kept; sets
    ``dlaf_hbm_bytes{what, site}`` from its ``memory_analysis()``."""
    handle = _HANDLES.get(site)
    if handle is None:
        return None
    if handle.compiled is None:
        handle.compiled = _lower(handle).compile()
        _set_hbm_gauges(site, memory_analysis_dict(handle.compiled))
    return handle.compiled


def phases_of_hlo(hlo_text: str) -> tuple:
    """``(phases, operands, counts)`` of a compiled module's text:
    ``phases`` maps an instruction to the phase of its own ``op_name``
    scopes or, for a fusion the compiler left without metadata (a
    multi-output fusion it assembled itself), to the phase most of the
    instructions fused into it carry; ``operands`` lists the operand
    instructions of those that still have none; ``counts`` is instructions
    by phase."""
    from . import scopes

    phases, operands, counts, fusions, majority = {}, {}, {}, {}, {}
    for comp, rows in scopes.computations(hlo_text):
        inside = {}
        for name, op_name, rest in rows:
            scope = scopes.parse(op_name) if op_name else None
            if scope is not None and scope.phase is not None:
                phases[name] = scope.phase
                inside[scope.phase] = inside.get(scope.phase, 0) + 1
            else:
                operands[name] = scopes.operand_names(rest)
                called = scopes.called_computation(rest)
                if called is not None:
                    fusions[name] = called
        if inside:
            majority[comp] = max(inside, key=inside.get)
    for name, called in fusions.items():
        if called in majority:
            phases[name] = majority[called]
            del operands[name]
    for phase in phases.values():
        counts[phase] = counts.get(phase, 0) + 1
    return phases, operands, counts


def phase_table(site: str) -> Optional[dict]:
    """Which phase each instruction of ``site``'s executable belongs to:
    ``{"site", "module", "phases": {instruction: phase}, "operands":
    {instruction: [operand instructions]}`` (of the instructions that
    carry no phase of their own: a compiler's ``copy`` takes its
    operand's), ``"counts": {phase: instructions}, "stale"}``, from the
    ``op_name`` scopes of the compiled text (:func:`phases_of_hlo`,
    :mod:`dlaf_tpu.obs.scopes`).
    Sets ``dlaf_phase_instructions{site, phase}`` for every phase the
    tree's own scopes name. ``stale``: the tree names phases and the
    executable carries none, because it came from a persistent cache an
    older tree wrote (jax keeps metadata out of the cache key): a reader
    must then report nothing rather than a wrong split. None for a site
    no call remembered."""
    handle = _HANDLES.get(site)
    if handle is None:
        return None
    if handle.table is None:
        from . import scopes

        text = compiled(site).as_text()
        if handle.expected is None:
            _lower(handle)
        phases, operands, counts = phases_of_hlo(text)
        reg = _registry()
        for phase in sorted(handle.expected | set(counts)):
            reg.gauge("dlaf_phase_instructions", site=site,
                      phase=phase).set(counts.get(phase, 0))
        handle.table = {
            "site": site, "module": scopes.module_name(text),
            "phases": phases, "operands": operands, "counts": counts,
            "stale": bool(handle.expected) and not phases}
    return handle.table


def _reset_for_tests() -> None:
    _PROGRAMS.clear()
    _HANDLES.clear()
