"""Shape-bucketed AOT program service (docs/serving.md).

PR 7's keyed AOT cache (:func:`dlaf_tpu.obs.telemetry.call`) grown into
an explicit API: a :class:`ProgramService` holds one AOT-compiled,
donated, vmapped program per :class:`ProgramSpec` bucket key
``(op, batch, n, nrhs, nb, dtype, uplo/side/op/diag, with_info,
donate)`` and serves it warm —

* :meth:`ProgramService.warmup` pre-compiles a bucket set (the server
  bring-up step; compiles land in jax's persistent compile cache —
  ``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache`` — so a RESTARTED server warms from
  disk instead of from XLA);
* :meth:`ProgramService.pin` / :meth:`ProgramService.evict` manage
  residency under the ``DLAF_SERVE_CACHE_BYTES`` LRU byte budget
  (pinned programs are never evicted; cost = ``memory_analysis()`` peak
  where the backend reports one, an aval-derived estimate otherwise);
* every lookup counts ``dlaf_serve_cache_total{event=hit|miss|warmup|
  evict|pin, op}`` and the live footprint lands on
  ``dlaf_serve_cache_bytes``; compiles route through
  :func:`dlaf_tpu.obs.telemetry.aot_compile` under a PER-BUCKET site
  (``serve.<op>.<bucket>``), so with ``DLAF_PROGRAM_TELEMETRY=1`` each
  bucket gets its own compile-seconds/HBM/retrace series — and
  "``dlaf_retrace_total{site=serve.*}`` stays 1 per site" IS the
  steady-state zero-retrace pin (a value of 2 means an evicted bucket
  recompiled, exactly what the CI evict drill must surface).

The module-level default service (:func:`get_service`) is registered
with the config program caches: a knob change that invalidates traced
decisions drops the compiled programs with it.
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Optional

import numpy as np

from .. import obs
from ..config import get_configuration, register_program_cache


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One bucket program's identity — THE cache key (ISSUE 11:
    ``(bucket_n, nb, dtype, uplo/side/op)`` plus the lane count, rhs
    width, info flag, and donation, each of which changes the compiled
    program)."""

    op: str                 # "cholesky" | "solve" | "eigh"
    batch: int              # lanes per dispatch (B)
    n: int                  # bucket matrix dimension (the shape ceiling)
    nb: int                 # block size (bucket-key member; see batched.py)
    dtype: str              # numpy dtype name
    uplo: str = "L"
    side: str = "L"         # solve only
    transa: str = "N"       # solve only: op(A)
    diag: str = "N"         # solve only
    nrhs: int = 0           # solve only: rhs free-axis width
    with_info: bool = True
    donate: bool = False

    @property
    def site(self) -> str:
        """Per-bucket telemetry site label (bounded cardinality: one per
        cached program)."""
        extra = (f".{self.side}{self.uplo}{self.transa}{self.diag}"
                 f".r{self.nrhs}" if self.op == "solve"
                 else f".{self.uplo}")
        return (f"serve.{self.op}.b{self.batch}n{self.n}nb{self.nb}"
                f".{self.dtype}{extra}"
                + (".info" if self.with_info else "")
                + (".don" if self.donate else ""))

    def to_wire(self) -> dict:
        """JSON-safe form (fleet warmup handoff, docs/fleet.md): every
        field is a JSON scalar."""
        return dataclasses.asdict(self)

    @classmethod
    def from_wire(cls, doc: dict) -> "ProgramSpec":
        """Inverse of :meth:`to_wire`: a wire-round-tripped spec is
        ``==`` to the original."""
        return cls(**doc)


def cholesky_spec(*, batch: int, n: int, nb: int, dtype: str,
                  uplo: str = "L", with_info: bool = True,
                  donate: bool = False) -> ProgramSpec:
    return ProgramSpec(op="cholesky", batch=int(batch), n=int(n),
                       nb=int(nb), dtype=str(dtype), uplo=uplo,
                       with_info=bool(with_info), donate=bool(donate))


def solve_spec(*, batch: int, n: int, nrhs: int, nb: int, dtype: str,
               side: str = "L", uplo: str = "L", transa: str = "N",
               diag: str = "N", with_info: bool = True,
               donate: bool = False) -> ProgramSpec:
    return ProgramSpec(op="solve", batch=int(batch), n=int(n), nb=int(nb),
                       dtype=str(dtype), uplo=uplo, side=side,
                       transa=transa, diag=diag, nrhs=int(nrhs),
                       with_info=bool(with_info), donate=bool(donate))


def eigh_spec(*, batch: int, n: int, nb: int, dtype: str, uplo: str = "L",
              with_info: bool = True, donate: bool = False) -> ProgramSpec:
    return ProgramSpec(op="eigh", batch=int(batch), n=int(n), nb=int(nb),
                       dtype=str(dtype), uplo=uplo,
                       with_info=bool(with_info), donate=bool(donate))


def program_builder(spec: ProgramSpec):
    """``(batched fn, arg ShapeDtypeStructs, donate_argnums)`` for one
    bucket spec — the UNJITTED vmapped program, shared with the
    graphcheck traced matrix (analysis/graphcheck.py serve specs) so the
    audited programs are the served programs."""
    import functools

    import jax

    from ..algorithms import batched as bt

    dt = np.dtype(spec.dtype)
    b_, n = spec.batch, spec.n
    a_st = jax.ShapeDtypeStruct((b_, n, n), dt)
    if spec.op == "cholesky":
        fn = jax.vmap(functools.partial(bt.cholesky_one, uplo=spec.uplo,
                                        nb=spec.nb,
                                        with_info=spec.with_info))
        return fn, (a_st,), ((0,) if spec.donate else ())
    if spec.op == "solve":
        rhs_shape = ((b_, n, spec.nrhs) if spec.side == "L"
                     else (b_, spec.nrhs, n))
        b_st = jax.ShapeDtypeStruct(rhs_shape, dt)
        al_st = jax.ShapeDtypeStruct((b_,), dt)
        fn = jax.vmap(functools.partial(bt.solve_one, side=spec.side,
                                        uplo=spec.uplo, op=spec.transa,
                                        diag=spec.diag,
                                        with_info=spec.with_info))
        return fn, (a_st, b_st, al_st), ((1,) if spec.donate else ())
    if spec.op == "eigh":
        fn = jax.vmap(functools.partial(bt.eigh_one, uplo=spec.uplo,
                                        with_info=spec.with_info))
        return fn, (a_st,), ((0,) if spec.donate else ())
    raise ValueError(f"unknown serve op {spec.op!r}")


def _estimate_bytes(spec: ProgramSpec, memory: Optional[dict]) -> int:
    """Residency cost of one cached program: the allocator's own peak
    when the backend reports a memory analysis, else the summed
    argument+output aval bytes (a deliberate UNDER-estimate — the budget
    stays a budget, not a precise allocator model)."""
    if memory and math.isfinite(memory.get("peak", float("nan"))):
        return max(int(memory["peak"]), 1)
    _, args, _ = program_builder(spec)
    arg_bytes = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                    for a in args)
    return max(2 * arg_bytes, 1)


@dataclasses.dataclass
class _Entry:
    compiled: object
    nbytes: int
    compile_s: float
    pinned: bool = False


class ProgramService:
    """Keyed AOT program cache with warmup/pin/evict under an LRU byte
    budget (see module docstring). Thread-safe: a serving front end
    submits from request threads."""

    def __init__(self, cache_bytes: Optional[int] = None):
        #: insertion order ≈ recency (moved-to-end on hit) — the LRU order
        self._entries: dict = {}
        self._lock = threading.RLock()
        self._cache_bytes = cache_bytes
        self._stats = {"hits": 0, "misses": 0, "warmups": 0, "pins": 0,
                       "evictions": 0, "compiles": 0, "compile_s": 0.0}

    # -- residency -------------------------------------------------------

    def _budget(self) -> int:
        if self._cache_bytes is not None:
            return int(self._cache_bytes)
        return int(get_configuration().serve_cache_bytes)

    def _bytes(self) -> int:
        return sum(e.nbytes for e in self._entries.values())

    #: stats key -> metric label (the singular event name on the counter)
    _EVENTS = {"hits": "hit", "misses": "miss", "warmups": "warmup",
               "pins": "pin", "evictions": "evict"}

    def _count(self, event: str, spec: ProgramSpec) -> None:
        self._stats[event] += 1
        if obs.metrics_active():
            obs.counter("dlaf_serve_cache_total", event=self._EVENTS[event],
                        op=spec.op).inc()
            obs.gauge("dlaf_serve_cache_bytes").set(float(self._bytes()))

    def _evict_for_budget(self) -> None:
        budget = self._budget()
        if budget <= 0:
            return
        while self._bytes() > budget:
            victim = next((s for s, e in self._entries.items()
                           if not e.pinned), None)
            if victim is None:
                obs.get_logger("serve").warning_once(
                    ("serve_cache_all_pinned", budget),
                    f"serve program cache exceeds its {budget}-byte "
                    "budget but every program is pinned; nothing evicted",
                    budget=budget, bytes=self._bytes())
                return
            self._evict_locked(victim)

    def _evict_locked(self, spec: ProgramSpec) -> None:
        del self._entries[spec]
        self._count("evictions", spec)

    # -- compile / lookup ------------------------------------------------

    def _compile(self, spec: ProgramSpec) -> _Entry:
        import jax

        fn, args, donate = program_builder(spec)
        jitted = jax.jit(fn, donate_argnums=donate)
        prog = obs.telemetry.aot_compile(spec.site, jitted, *args)
        self._stats["compiles"] += 1
        self._stats["compile_s"] += prog.compile_s
        return _Entry(compiled=prog.compiled,
                      nbytes=_estimate_bytes(spec, prog.memory),
                      compile_s=prog.compile_s)

    def get(self, spec: ProgramSpec, *, _event: str = "misses"):
        """The compiled executable for ``spec`` — compiling on a miss
        (counted ``miss``; ``warmup``/``pin`` compiles count their own
        events) and refreshing LRU recency on a hit."""
        with self._lock:
            entry = self._entries.get(spec)
            if entry is not None:
                self._entries[spec] = self._entries.pop(spec)   # recency
                self._count("hits", spec)
                return entry.compiled
            entry = self._compile(spec)
            self._entries[spec] = entry
            self._count(_event, spec)
            self._evict_for_budget()
            return entry.compiled

    def run(self, spec: ProgramSpec, *args):
        """Dispatch ``args`` through the bucket program (the batched
        entry points' call path). Donation-capability warnings are
        silenced the way every library dispatch silences them: the
        donated buffer is service-owned."""
        from ..matrix.tiling import quiet_donation

        prog = self.get(spec)
        with quiet_donation():
            return prog(*args)

    # -- explicit residency API -----------------------------------------

    def warmup(self, *specs: ProgramSpec) -> dict:
        """Pre-compile every missing spec (counted ``warmup``, never
        ``miss``); returns ``{spec: compile_seconds}`` (0.0 for already-
        warm entries). The server bring-up step: after warmup, an
        in-bucket request stream is all hits and never retraces."""
        walls = {}
        for spec in specs:
            with self._lock:
                if spec in self._entries:
                    walls[spec] = 0.0
                    continue
                with obs.span("serve.warmup", op=spec.op, site=spec.site):
                    entry = self._compile(spec)
                self._entries[spec] = entry
                self._count("warmups", spec)
                self._evict_for_budget()
                walls[spec] = entry.compile_s
        return walls

    def pin(self, *specs: ProgramSpec) -> None:
        """Exempt ``specs`` from LRU eviction (compiling any that are
        missing, counted ``pin``)."""
        for spec in specs:
            with self._lock:
                entry = self._entries.get(spec)
                if entry is None:
                    entry = self._compile(spec)
                    self._entries[spec] = entry
                entry.pinned = True
                self._count("pins", spec)
                self._evict_for_budget()

    def unpin(self, *specs: ProgramSpec) -> None:
        with self._lock:
            for spec in specs:
                entry = self._entries.get(spec)
                if entry is not None:
                    entry.pinned = False

    def evict(self, spec: ProgramSpec) -> bool:
        """Drop one cached program (pinned or not — an explicit evict is
        an operator decision). Returns False when it was not resident."""
        with self._lock:
            if spec not in self._entries:
                return False
            self._evict_locked(spec)
            return True

    # -- introspection ---------------------------------------------------

    def stats(self) -> dict:
        """Counters + live footprint: ``hits``/``misses``/``warmups``/
        ``pins``/``evictions``/``compiles``/``compile_s`` plus
        ``entries``/``bytes``/``pinned`` and the derived ``hit_rate``
        (hits / (hits + misses); 1.0 when nothing missed — the
        steady-state target after warmup)."""
        with self._lock:
            served = self._stats["hits"] + self._stats["misses"]
            return dict(self._stats, entries=len(self._entries),
                        bytes=self._bytes(),
                        pinned=sum(e.pinned
                                   for e in self._entries.values()),
                        hit_rate=(self._stats["hits"] / served
                                  if served else 1.0))

    def specs(self) -> tuple:
        with self._lock:
            return tuple(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    # config.register_program_cache protocol: knob changes invalidate the
    # traced routes baked into these executables
    cache_clear = clear


_SERVICE: Optional[ProgramService] = None
_SERVICE_LOCK = threading.Lock()


def get_service() -> ProgramService:
    """The process-default program service (what the batched entry
    points and ``serve.Queue`` use unless handed an explicit one)."""
    global _SERVICE
    if _SERVICE is None:
        with _SERVICE_LOCK:
            if _SERVICE is None:
                svc = ProgramService()
                register_program_cache(svc)
                _SERVICE = svc
    return _SERVICE


def warmup(*specs: ProgramSpec) -> dict:
    """``get_service().warmup(*specs)`` — the one-line server bring-up."""
    return get_service().warmup(*specs)


def _reset_for_tests() -> None:
    if _SERVICE is not None:
        _SERVICE.clear()
        _SERVICE._stats.update(hits=0, misses=0, warmups=0, pins=0,
                               evictions=0, compiles=0, compile_s=0.0)
