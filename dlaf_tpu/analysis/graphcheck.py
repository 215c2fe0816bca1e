"""Jaxpr graph auditor: semantic invariants over every builder's traced
program (docs/static_analysis.md).

Traces every factorization/solve/eigensolver builder — unrolled and scan
forms, local and distributed, both uplos, the knob combos that change
program structure — abstractly (``jax.make_jaxpr`` over
``ShapeDtypeStruct`` args on a virtual CPU mesh: no compile, no
execution; the same trick as ``scripts/mfu_table.py``) and audits each
program for the invariant classes whose violation is a silent
scale-or-correctness bug:

``graph-conditional-collective``
    A collective under ``cond``/``while`` executes on a data-dependent
    subset of ranks. Since every builder is one SPMD program traced
    once, rank-variance of the collective schedule can ONLY enter
    through conditional execution — on multihost meshes this is the
    deadlock class (arXiv:2112.09017 keeps its collectives
    program-order-uniform for exactly this reason). ``scan`` bodies are
    fine: the trip count is a trace-time constant, equal on all ranks.

``graph-host-callback``
    ``pure_callback``/``io_callback``/``debug_callback``/infeed/outfeed
    inside a hot-path program stalls the device pipeline on a host
    round trip every step.

``graph-precision-demotion``
    A non-weak f64/c128 value converted to f32/bf16/f16/c64 inside a
    program traced on the NATIVE route (mxu/ozaki slicing and the mixed
    f32-seed solver are the gated exceptions — the auditor pins those
    knobs off, so any demotion it sees is silent precision loss).

``graph-dead-carry`` / ``graph-dead-output``
    A scan carry slot the body never reads and passes through unchanged
    (a dropped carry left by a refactor — it costs HBM every iteration
    and hides a value someone meant to use), or stacked scan outputs
    nobody consumes (per-iteration work thrown away).

``graph-hbm-blowup``
    Any eqn materializing an intermediate larger than ``hbm_factor``
    times the whole program's input bytes (broadcast-then-reduce
    temporaries — the class behind the session-4d N=16384 OOM).

Audited under a pinned native configuration with ``DLAF_*`` env scrubbed
(restored after), so the result is deterministic regardless of the
caller's environment.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
from typing import Callable, List, Optional, Sequence, Tuple

from . import depgraph
from .findings import Finding

#: Demotion targets: landing one of these from a non-weak f64/c128 value
#: loses mantissa silently.
_NARROW = {"float32", "bfloat16", "float16", "complex64"}
_WIDE = {"float64", "complex128"}

#: Default materialized-intermediate budget, as a multiple of the traced
#: program's total input bytes. The legit builders peak well under 4x
#: (the bulk trailing product and the gathered transposed panels are
#: each <= the local storage); 8x only trips on genuinely materialized
#: broadcast temporaries.
DEFAULT_HBM_FACTOR = 8.0


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One traced program to audit. ``build`` returns ``(fn, args)``
    with args as ShapeDtypeStructs; nothing is compiled."""

    name: str
    build: Callable[[], Tuple[Callable, Tuple]]
    #: no-callback rule applies (all current builders are hot paths)
    hot_path: bool = True
    #: precision-demotion rule applies (traced with the native knobs
    #: pinned, so every demotion is unexpected)
    native_route: bool = True


@contextlib.contextmanager
def pinned_native_config():
    """Scrub ``DLAF_*`` env and pin the knobs that steer trace-time
    routes to their native/serialized choices, so the audited programs
    are deterministic and the precision rule has no gated exceptions in
    scope. On exit the env is restored and the caller's ACTIVE config is
    re-installed (re-layered over the restored env) — a caller that had
    installed a struct config programmatically keeps it."""
    import dlaf_tpu.config as config

    prev = dataclasses.replace(config.get_configuration())
    saved = {k: os.environ.pop(k) for k in list(os.environ)
             if k.startswith("DLAF_")}
    try:
        config.initialize(config.Configuration(
            f64_gemm="native", f64_trsm="native", qr_panel="geqrf",
            cholesky_trailing="loop", cholesky_lookahead="0",
            comm_lookahead="0", dc_level_batch="0", bt_lookahead="0",
            hegst_impl="blocked", dist_step_mode="unrolled",
            # panel_impl pinned to the XLA route so the precision-
            # demotion and route audits keep auditing the native path;
            # the fused route gets its OWN f32 traced-program entries
            # (program_specs *.fpanel variants, built with an explicit
            # panel_fused=True)
            panel_impl="xla", log="off"))
        yield
    finally:
        os.environ.update(saved)
        config.initialize(prev)


def _require_devices(count: int) -> None:
    import jax

    have = len(jax.devices())
    if have < count:
        raise RuntimeError(
            f"graphcheck needs >= {count} devices for its virtual meshes "
            f"but the jax platform has {have}; run via `python -m "
            f"dlaf_tpu.analysis` (which forces an 8-device virtual CPU "
            f"platform) or set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=8 before the first jax import")


def program_specs(rows: int = 2, cols: int = 2, n: int = 24, nb: int = 4,
                  ) -> List[ProgramSpec]:
    """The audited program matrix. Sizes are tiny (tracing cost only —
    the invariants are size-independent program structure); the grid is
    the 2x2 virtual mesh every structural test pin uses."""
    import jax
    import jax.numpy as jnp

    _require_devices(rows * cols)
    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.common.index2d import (GlobalElementSize, GridSize2D,
                                         TileElementSize)
    from dlaf_tpu.matrix.distribution import Distribution
    from dlaf_tpu.matrix.tiling import storage_tile_grid

    grid = Grid(rows, cols)
    dist = Distribution(GlobalElementSize(n, n), TileElementSize(nb, nb),
                        grid_size=GridSize2D(rows, cols))
    str_, stc, _, _ = storage_tile_grid(dist)
    f64 = jnp.float64
    st = jax.ShapeDtypeStruct((str_, stc, nb, nb), f64)
    loc = jax.ShapeDtypeStruct((n, n), f64)
    alpha = jax.ShapeDtypeStruct((), f64)

    specs: List[ProgramSpec] = []

    def add(name, make):
        specs.append(ProgramSpec(name=name, build=make))

    # ---- local Cholesky (unrolled trailing forms + scan form) ----
    from dlaf_tpu.algorithms.cholesky import (_build_dist_cholesky,
                                              _build_dist_cholesky_scan,
                                              _cholesky_local,
                                              _cholesky_local_scan)

    for uplo in ("L", "U"):
        for trailing in ("loop", "biggemm"):
            for la in (False, True):
                add(f"cholesky.local.{trailing}.{uplo}.la{int(la)}",
                    lambda uplo=uplo, trailing=trailing, la=la: (
                        lambda x: _cholesky_local.__wrapped__(
                            x, uplo=uplo, nb=nb, trailing=trailing,
                            lookahead=la), (loc,)))
        add(f"cholesky.local_scan.{uplo}.la1",
            lambda uplo=uplo: (
                lambda x: _cholesky_local_scan.__wrapped__(
                    x, uplo=uplo, nb=nb, lookahead=True), (loc,)))

    # ---- distributed Cholesky (unrolled + scan, knob combos) ----
    for uplo in ("L", "U"):
        for la, comm in ((False, False), (True, True)):
            add(f"cholesky.dist.{uplo}.la{int(la)}.comm{int(comm)}",
                lambda uplo=uplo, la=la, comm=comm: (
                    _build_dist_cholesky(dist, grid.mesh, uplo, False,
                                         True, lookahead=la, comm_la=comm),
                    (st,)))
        add(f"cholesky.dist_scan.{uplo}.la1",
            lambda uplo=uplo: (
                _build_dist_cholesky_scan(dist, grid.mesh, uplo,
                                          lookahead=True), (st,)))
    add("cholesky.dist.L.la1.comm1.info",
        lambda: (_build_dist_cholesky(dist, grid.mesh, "L", False, True,
                                      lookahead=True, comm_la=True,
                                      with_info=True), (st,)))

    # ---- fused Pallas panel route (panel_impl="fused"; f32 — the route's
    # supported dtype, so the precision rule sees no wide values to
    # demote). Built with an EXPLICIT panel_fused=True: the pinned
    # native config above keeps the knob itself on "xla", these specs
    # audit the fused programs the TPU auto resolution emits. ----
    f32 = jnp.float32
    st32 = jax.ShapeDtypeStruct((str_, stc, nb, nb), f32)
    loc32 = jax.ShapeDtypeStruct((n, n), f32)
    alpha32 = jax.ShapeDtypeStruct((), f32)
    for uplo in ("L", "U"):
        add(f"cholesky.local.fpanel.{uplo}.la1",
            lambda uplo=uplo: (
                lambda x: _cholesky_local.__wrapped__(
                    x, uplo=uplo, nb=nb, trailing="loop", lookahead=True,
                    panel_fused=True, panel_interpret=True), (loc32,)))
        add(f"cholesky.dist.fpanel.{uplo}.la1.comm1",
            lambda uplo=uplo: (
                _build_dist_cholesky(dist, grid.mesh, uplo, False, True,
                                     lookahead=True, comm_la=True,
                                     panel_fused=True), (st32,)))
    add("cholesky.dist_scan.fpanel.L.la1",
        lambda: (_build_dist_cholesky_scan(dist, grid.mesh, "L",
                                           lookahead=True,
                                           pallas_interpret=True,
                                           panel_fused=True), (st32,)))

    # ---- fused STEP route (step_impl="fused"; f32 — ONE pallas_call
    # per strip-bearing blocked step, docs/pallas_panel.md "Fused step
    # kernel"). Built with an EXPLICIT step_fused=True like the fpanel
    # specs above: the pinned native config keeps the knob itself on
    # "xla", these audit the fused-step programs the TPU auto
    # resolution emits (tests/test_fused_step.py pins the per-step
    # kernel count and the comm-overlap independence on this route). ----
    for uplo in ("L", "U"):
        add(f"cholesky.local.fstep.{uplo}.la1",
            lambda uplo=uplo: (
                lambda x: _cholesky_local.__wrapped__(
                    x, uplo=uplo, nb=nb, trailing="loop", lookahead=True,
                    step_fused=True, panel_interpret=True), (loc32,)))
        add(f"cholesky.dist.fstep.{uplo}.la1.comm1",
            lambda uplo=uplo: (
                _build_dist_cholesky(dist, grid.mesh, uplo, False, True,
                                     lookahead=True, comm_la=True,
                                     step_fused=True), (st32,)))
    add("cholesky.local_scan.fstep.L.la1",
        lambda: (
            lambda x: _cholesky_local_scan.__wrapped__(
                x, uplo="L", nb=nb, lookahead=True, step_fused=True,
                panel_interpret=True), (loc32,)))
    add("cholesky.dist_scan.fstep.L.la1",
        lambda: (_build_dist_cholesky_scan(dist, grid.mesh, "L",
                                           lookahead=True,
                                           pallas_interpret=True,
                                           step_fused=True), (st32,)))

    # ---- distributed triangular solve / multiply ----
    from dlaf_tpu.algorithms.triangular import (_build_dist_mult,
                                                _build_dist_mult_scan,
                                                _build_dist_solve,
                                                _build_dist_solve_scan)

    for side, uplo, op in (("L", "L", "N"), ("R", "U", "C")):
        add(f"solve.dist.{side}{uplo}{op}",
            lambda side=side, uplo=uplo, op=op: (
                _build_dist_solve(dist, dist, grid.mesh, side, uplo, op,
                                  "N", "float64"), (st, st, alpha)))
        add(f"solve.dist_scan.{side}{uplo}{op}.la1.comm1",
            lambda side=side, uplo=uplo, op=op: (
                _build_dist_solve_scan(dist, dist, grid.mesh, side, uplo,
                                       op, "N", "float64", lookahead=True,
                                       comm_la=True), (st, st, alpha)))
    add("solve.dist.fpanel.LLN",
        lambda: (_build_dist_solve(dist, dist, grid.mesh, "L", "L", "N",
                                   "N", "float32", panel_fused=True,
                                   panel_interpret=True),
                 (st32, st32, alpha32)))
    add("solve.dist_scan.fpanel.LLN.la1",
        lambda: (_build_dist_solve_scan(dist, dist, grid.mesh, "L", "L",
                                        "N", "N", "float32",
                                        lookahead=True, panel_fused=True,
                                        panel_interpret=True),
                 (st32, st32, alpha32)))
    add("mult.dist.LLN",
        lambda: (_build_dist_mult(dist, dist, grid.mesh, "L", "L", "N",
                                  "N", "float64"), (st, st, alpha)))
    add("mult.dist_scan.LLN",
        lambda: (_build_dist_mult_scan(dist, dist, grid.mesh, "L", "L",
                                       "N", "N", "float64"),
                 (st, st, alpha)))

    # ---- distributed HEGST (blocked two-sided update) ----
    from dlaf_tpu.algorithms.gen_to_std import _build_dist_hegst

    for uplo in ("L", "U"):
        for la, comm in ((False, False), (True, True)):
            add(f"hegst.dist.{uplo}.la{int(la)}.comm{int(comm)}",
                lambda uplo=uplo, la=la, comm=comm: (
                    _build_dist_hegst(dist, grid.mesh, uplo, lookahead=la,
                                      comm_la=comm), (st, st)))
    add("hegst.dist.fpanel.L.la1.comm1",
        lambda: (_build_dist_hegst(dist, grid.mesh, "L", lookahead=True,
                                   comm_la=True, panel_fused=True,
                                   panel_interpret=True), (st32, st32)))

    # ---- reduction to band (local + dist, unrolled + scan) ----
    from dlaf_tpu.eigensolver.reduction_to_band import (
        _build_dist_red2band, _build_dist_red2band_scan, _red2band_local,
        _red2band_local_scan)

    add("red2band.local",
        lambda: (lambda x: _red2band_local.__wrapped__(x, nb=nb), (loc,)))
    add("red2band.local_scan",
        lambda: (lambda x: _red2band_local_scan.__wrapped__(x, nb=nb),
                 (loc,)))
    for comm in (False, True):
        add(f"red2band.dist.comm{int(comm)}",
            lambda comm=comm: (
                _build_dist_red2band(dist, grid.mesh, "float64", nb,
                                     comm_la=comm), (st,)))
    add("red2band.dist_scan",
        lambda: (_build_dist_red2band_scan(dist, grid.mesh, "float64", nb),
                 (st,)))

    # ---- back-transforms ----
    from dlaf_tpu.eigensolver.back_transform import (_build_dist_bt_b2t,
                                                     _build_dist_bt_r2b,
                                                     _build_dist_bt_r2b_scan)

    npan = max(-(-n // nb) - 1, 0)
    taus = jax.ShapeDtypeStruct((npan, nb), f64)
    for la in (False, True):
        add(f"bt_r2b.dist.la{int(la)}",
            lambda la=la: (_build_dist_bt_r2b(dist, dist, grid.mesh, nb,
                                              la=la), (st, taus, st)))
    add("bt_r2b.dist_scan.la1",
        lambda: (_build_dist_bt_r2b_scan(dist, dist, grid.mesh, nb,
                                         la=True), (st, taus, st)))
    n_sweeps = max(n - 2, 0)
    n_steps = -(-max(n - 1, 1) // nb)
    add("bt_b2t.dist",
        lambda: (_build_dist_bt_b2t(dist, grid.mesh, b=nb, cplx=False,
                                    n_sweeps=n_sweeps),
                 (jax.ShapeDtypeStruct((n_sweeps, n_steps, nb), f64),
                  jax.ShapeDtypeStruct((n_sweeps, n_steps), f64),
                  jax.ShapeDtypeStruct((n,), f64), st)))

    # ---- serve batched bucket programs (ISSUE 11, docs/serving.md):
    # the vmapped forms the program service compiles, built through the
    # SAME builder the service uses (serve.programs.program_builder) so
    # the audited programs are the served programs; f64 on the pinned
    # native config, with_info on (the serving default). ----
    from dlaf_tpu.serve.programs import (cholesky_spec, eigh_spec,
                                         program_builder, solve_spec)

    serve_specs = [
        cholesky_spec(batch=3, n=n, nb=nb, dtype="float64", uplo="L"),
        cholesky_spec(batch=3, n=n, nb=nb, dtype="float64", uplo="U"),
        solve_spec(batch=3, n=n, nrhs=nb, nb=nb, dtype="float64",
                   side="L", uplo="L", transa="N", diag="N"),
        solve_spec(batch=3, n=n, nrhs=nb, nb=nb, dtype="float64",
                   side="R", uplo="U", transa="C", diag="N"),
        eigh_spec(batch=3, n=n, nb=nb, dtype="float64", uplo="L"),
    ]
    for sspec in serve_specs:
        tag = (f"{sspec.side}{sspec.uplo}{sspec.transa}"
               if sspec.op == "solve" else sspec.uplo)
        add(f"serve.{sspec.op}.batched.{tag}",
            lambda sspec=sspec: program_builder(sspec)[:2])
    return specs


# ---------------------------------------------------------------------------
# Checks over one traced program
# ---------------------------------------------------------------------------

def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    import numpy as np

    return math.prod(int(d) for d in shape) * np.dtype(dtype).itemsize \
        if shape else np.dtype(dtype).itemsize


def _path_str(path) -> str:
    return "/".join(f"{name}.{label}" for name, label in path) or "top"


def audit_jaxpr(name: str, closed_jaxpr, *, hot_path: bool = True,
                native_route: bool = True,
                hbm_factor: float = DEFAULT_HBM_FACTOR) -> List[Finding]:
    """All graph findings for one traced program (see module docstring
    for the rule catalog)."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    findings: List[Finding] = []

    for coll in depgraph.collectives(jaxpr):
        if coll.conditional:
            findings.append(Finding(
                "graph-conditional-collective", name,
                f"{coll.kind} over {coll.axes} executes conditionally "
                f"(path {_path_str(coll.path)}) — rank-varying collective "
                f"schedules deadlock SPMD meshes",
                key_detail=f"{name}|{coll.kind}|{','.join(coll.axes)}"))

    if hot_path:
        for path, e in depgraph.callbacks(jaxpr):
            findings.append(Finding(
                "graph-host-callback", name,
                f"{e.primitive.name} inside hot-path program "
                f"(path {_path_str(path)}) — stalls the device on a host "
                f"round trip",
                key_detail=f"{name}|{e.primitive.name}"))

    if native_route:
        for path, e in depgraph.iter_eqns(jaxpr):
            if e.primitive.name != "convert_element_type":
                continue
            old = e.invars[0].aval
            new = str(e.params.get("new_dtype"))
            if (str(getattr(old, "dtype", "")) in _WIDE
                    and not getattr(old, "weak_type", False)
                    and new in _NARROW):
                findings.append(Finding(
                    "graph-precision-demotion", name,
                    f"{old.dtype}->{new} conversion on the native route "
                    f"(path {_path_str(path)}, shape "
                    f"{tuple(getattr(old, 'shape', ()))}) — silent "
                    f"mantissa loss outside the gated mxu/mixed routes",
                    key_detail=f"{name}|{old.dtype}->{new}"))

    for path, e in depgraph.iter_eqns(jaxpr):
        if e.primitive.name != "scan":
            continue
        for slot in depgraph.scan_carry_slots(e):
            if slot.dead:
                findings.append(Finding(
                    "graph-dead-carry", name,
                    f"scan carry slot {slot.index} (path "
                    f"{_path_str(path)}) is never read and passes "
                    f"through unchanged — a dropped carry",
                    key_detail=f"{name}|carry{slot.index}|{_path_str(path)}"))
        for idx in depgraph.dropped_outputs(e):
            findings.append(Finding(
                "graph-dead-output", name,
                f"scan stacked output {idx} (path {_path_str(path)}) is "
                f"computed every iteration and never consumed",
                key_detail=f"{name}|ys{idx}|{_path_str(path)}"))

    def _hbm_walk(sub_jaxpr, input_bytes, path):
        # inside a shard_map body every aval is PER-SHARD, so the budget
        # denominator must be the body's own (per-shard) input bytes —
        # comparing against the global program inputs would slacken the
        # rule by the mesh size on exactly the distributed builders
        for e in sub_jaxpr.eqns:
            for ov in e.outvars:
                nbytes = _aval_bytes(getattr(ov, "aval", None))
                if nbytes > hbm_factor * input_bytes:
                    findings.append(Finding(
                        "graph-hbm-blowup", name,
                        f"{e.primitive.name} materializes {nbytes} bytes "
                        f"— {nbytes / input_bytes:.1f}x the enclosing "
                        f"program's {input_bytes} input bytes (path "
                        f"{_path_str(path)}, budget {hbm_factor}x)",
                        key_detail=f"{name}|{e.primitive.name}|"
                                   f"{nbytes // input_bytes}x"))
            for label, sub in depgraph.subjaxprs(e):
                sub_bytes = input_bytes
                if "shard_map" in e.primitive.name:
                    sub_bytes = max(sum(_aval_bytes(v.aval)
                                        for v in sub.invars), 1)
                _hbm_walk(sub, sub_bytes,
                          path + ((e.primitive.name, label),))

    _hbm_walk(jaxpr, max(sum(_aval_bytes(v.aval)
                             for v in jaxpr.invars), 1), ())
    return findings


def run(hbm_factor: float = DEFAULT_HBM_FACTOR,
        specs: Optional[Sequence[ProgramSpec]] = None) -> List[Finding]:
    """Trace + audit every spec under the pinned native config. A spec
    that fails to trace is itself a finding (``graph-trace-error``) —
    the auditor must fail loudly, not skip silently."""
    with pinned_native_config():
        if specs is None:
            specs = program_specs()
        findings: List[Finding] = []
        for spec in specs:
            try:
                fn, args = spec.build()
                jaxpr = depgraph.trace(fn, *args)
            except Exception as e:   # noqa: BLE001 — converted to finding
                findings.append(Finding(
                    "graph-trace-error", spec.name,
                    f"builder failed to trace: {type(e).__name__}: {e}",
                    key_detail=f"{spec.name}|{type(e).__name__}"))
                continue
            findings.extend(audit_jaxpr(
                spec.name, jaxpr, hot_path=spec.hot_path,
                native_route=spec.native_route, hbm_factor=hbm_factor))
    return findings
