"""Reusable jaxpr dependency/traversal library (docs/static_analysis.md).

The repo's hardest invariants — look-ahead overlap, collective
independence from the bulk trailing product, callback-free hot paths —
are properties of the *traced program*, not of any single execution.
Until this module existed, each test file that pinned one of them grew
its own jaxpr walker (producer maps, transitive closures, shard_map body
extraction); this is the shared vocabulary those pins — and the
:mod:`dlaf_tpu.analysis.graphcheck` auditor — are written in.

Everything here operates on traced jaxprs only: :func:`jax.make_jaxpr`
over ``ShapeDtypeStruct`` arguments (abstract eval — no compile, no
execution, the same trick ``scripts/mfu_table.py`` uses for its virtual-
mesh ICI traces), so the whole library runs on any host, accelerator or
not.

Terminology: an *eqn list* is the flat ``jaxpr.eqns`` of one (sub)jaxpr.
Closure/position/dependency queries are *flat* — they see one eqn list
and treat control-flow eqns (scan, cond, pjit, ...) as opaque nodes.
:func:`iter_eqns` is the *recursive* walk that descends into every
sub-jaxpr and reports the control-flow path it took to reach each eqn.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, Sequence, Tuple, Union

import jax
from jax import core as jax_core_legacy  # DropVar / Tracer still live here
from jax.extend import core as jax_core

from ..obs import scopes

#: Cross-device collective primitives, as spelled in this jax line's
#: jaxprs (``lax.psum`` -> ``psum``; ``bcast``'s mask+psum realization is
#: therefore counted as a psum, which is exactly what the program runs).
COLLECTIVE_PRIMS = frozenset({
    "psum", "all_gather", "all_to_all", "ppermute", "reduce_scatter",
    "psum_scatter", "pmax", "pmin",
})

#: Host-callback / host-transfer primitives that must never appear in a
#: hot-path program: each one stalls the device on a host round trip
#: (the class of bug ``jax.transfer_guard`` catches dynamically; here it
#: is pinned statically on the traced program).
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed", "host_callback_call",
})

#: Control-flow primitives whose sub-jaxprs execute *conditionally* — a
#: collective under one of these can run on a subset of ranks only,
#: which on SPMD hardware is the deadlock class (every rank must issue
#: every collective in the same order). ``scan`` is NOT here: its trip
#: count is a trace-time constant, identical on every rank.
CONDITIONAL_PRIMS = frozenset({"cond", "while"})

Predicate = Callable[[jax_core.JaxprEqn], bool]


def _as_predicate(pred: Union[str, Predicate]) -> Predicate:
    """Accept a primitive name as shorthand for an eqn predicate."""
    if isinstance(pred, str):
        name = pred
        return lambda e: e.primitive.name == name
    return pred


# ---------------------------------------------------------------------------
# Tracing entry points
# ---------------------------------------------------------------------------

def trace(fn, *args) -> jax_core.ClosedJaxpr:
    """Trace ``fn`` abstractly (``jax.make_jaxpr``) — args may be real
    arrays or ``jax.ShapeDtypeStruct`` placeholders; nothing compiles or
    executes."""
    return jax.make_jaxpr(fn)(*args)


def _jaxpr_of(obj) -> jax_core.Jaxpr:
    """The plain ``Jaxpr`` behind a ClosedJaxpr / Jaxpr."""
    return getattr(obj, "jaxpr", obj)


def shard_map_body(fn_or_jaxpr, *args) -> list:
    """Eqn list of the single ``shard_map`` body of a traced program.

    Accepts either an already-traced (Closed)Jaxpr, or a callable plus
    its (abstract) arguments. Exactly one shard_map eqn must exist at
    the top level — the shape of every distributed builder in this repo.
    """
    if callable(fn_or_jaxpr):
        fn_or_jaxpr = trace(fn_or_jaxpr, *args)
    jaxpr = _jaxpr_of(fn_or_jaxpr)
    matches = [e for e in jaxpr.eqns if "shard_map" in e.primitive.name]
    if len(matches) != 1:
        raise ValueError(
            f"expected exactly one shard_map eqn, found {len(matches)} "
            f"among {[e.primitive.name for e in jaxpr.eqns]}")
    inner = matches[0].params["jaxpr"]
    return list(_jaxpr_of(inner).eqns)


def scan_eqns(eqns: Sequence) -> list:
    """All ``lax.scan`` eqns among ``eqns`` (flat — no descent)."""
    return [e for e in eqns if e.primitive.name == "scan"]


def scan_body(eqns: Sequence, index: int = 0) -> list:
    """Body eqn list of the ``index``-th scan among ``eqns``.

    The scan builders telescope their k-loop into segments — one scan
    eqn per segment; ``index`` selects which segment's body to inspect
    (the pins use the first).
    """
    scans = scan_eqns(eqns)
    if not scans:
        raise ValueError("no scan in traced program")
    return list(_jaxpr_of(scans[index].params["jaxpr"]).eqns)


# ---------------------------------------------------------------------------
# Flat dependency queries
# ---------------------------------------------------------------------------

def producers(eqns: Sequence) -> dict:
    """Map each output var to the eqn that produces it (within ``eqns``)."""
    out = {}
    for e in eqns:
        for v in e.outvars:
            out[v] = e
    return out


def closure(eqns: Sequence, seed_vars) -> list:
    """Transitive producer closure of ``seed_vars`` within ``eqns``:
    every eqn whose outputs the seeds (transitively) depend on. Literals
    terminate the walk; vars produced outside ``eqns`` (jaxpr inputs,
    outer-scope consts) have no producer here and terminate it too."""
    prods = producers(eqns)
    seen: set = set()
    todo = list(seed_vars)
    out = []
    while todo:
        v = todo.pop()
        if isinstance(v, jax_core.Literal):
            continue
        e = prods.get(v)
        if e is None or id(e) in seen:
            continue
        seen.add(id(e))
        out.append(e)
        todo.extend(e.invars)
    return out


def depends_on(eqns: Sequence, eqn_or_index, pred: Union[str, Predicate],
               ) -> bool:
    """True iff the eqn (given directly or by flat index) transitively
    depends — through producers within ``eqns`` — on an eqn matching
    ``pred`` (a predicate or a primitive name)."""
    e = eqns[eqn_or_index] if isinstance(eqn_or_index, int) else eqn_or_index
    pred = _as_predicate(pred)
    return any(pred(d) for d in closure(eqns, e.invars))


def positions(eqns: Sequence, pred: Union[str, Predicate]) -> list:
    """Flat emission-order indices of eqns matching ``pred`` (predicate
    or primitive name). Emission order is what XLA's scheduler sees —
    the pins on "collective emitted BEFORE the bulk product" compare
    exactly these indices."""
    pred = _as_predicate(pred)
    return [i for i, e in enumerate(eqns) if pred(e)]


def is_bulk_dot(e, rank: int = 4) -> bool:
    """The bulk trailing product of every distributed builder under test
    is the only ``dot_general`` with a ``rank``-D (tile-pair grid)
    output; panel solves, strips and W/M products are lower-rank. The
    local builders' bulk is the square 2-D trailing dot — pass
    ``rank=2`` and filter by shape at the call site."""
    return (e.primitive.name == "dot_general"
            and len(e.outvars[0].aval.shape) == rank)


# ---------------------------------------------------------------------------
# Recursive walk
# ---------------------------------------------------------------------------

def subjaxprs(eqn) -> Iterator[Tuple[str, jax_core.Jaxpr]]:
    """(label, jaxpr) pairs for every sub-jaxpr of ``eqn``'s params —
    scan/pjit/shard_map bodies, cond branches, while cond/body, custom
    call rules — discovered generically so new primitives keep walking."""
    for key, val in eqn.params.items():
        if isinstance(val, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
            yield key, _jaxpr_of(val)
        elif isinstance(val, (tuple, list)):
            for i, item in enumerate(val):
                if isinstance(item, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                    yield f"{key}[{i}]", _jaxpr_of(item)


def iter_eqns(eqns_or_jaxpr, path: Tuple[Tuple[str, str], ...] = (),
              ) -> Iterator[Tuple[Tuple[Tuple[str, str], ...],
                                  jax_core.JaxprEqn]]:
    """Depth-first walk over every eqn, descending into all sub-jaxprs.

    Yields ``(path, eqn)`` where ``path`` is a tuple of
    ``(primitive_name, param_label)`` frames for each enclosing
    control-flow eqn — e.g. a collective traced inside a cond branch
    inside a shard_map body arrives with path
    ``(("shard_map", "jaxpr"), ("cond", "branches[1]"))``.
    """
    if not isinstance(eqns_or_jaxpr, (list, tuple)):
        eqns_or_jaxpr = _jaxpr_of(eqns_or_jaxpr).eqns
    for e in eqns_or_jaxpr:
        yield path, e
        for label, sub in subjaxprs(e):
            yield from iter_eqns(sub.eqns,
                                 path + ((e.primitive.name, label),))


def path_has_conditional(path) -> bool:
    """True if any frame of an :func:`iter_eqns` path is a conditionally-
    executed control-flow primitive (cond branch / while body)."""
    return any(name in CONDITIONAL_PRIMS for name, _ in path)


# ---------------------------------------------------------------------------
# Collective / callback enumeration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective eqn of a traced program, with the static facts a
    schedule-uniformity or traffic audit needs."""

    kind: str                 #: primitive name (psum, all_gather, ...)
    axes: Tuple[str, ...]     #: mesh axis names it communicates over
    shape: Tuple[int, ...]    #: operand shape
    dtype: str                #: operand dtype name
    path: Tuple               #: iter_eqns control-flow path
    eqn: jax_core.JaxprEqn = dataclasses.field(compare=False, repr=False)

    @property
    def conditional(self) -> bool:
        return path_has_conditional(self.path)

    @property
    def nbytes(self) -> int:
        import numpy as np

        size = 1
        for d in self.shape:
            size *= int(d)
        return size * np.dtype(self.dtype).itemsize


def _collective_axes(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def is_collective(e) -> bool:
    return e.primitive.name in COLLECTIVE_PRIMS


def collectives(eqns_or_jaxpr, descend: bool = True) -> list:
    """Enumerate collectives as :class:`Collective` records, in emission
    order. ``descend=False`` restricts to the given flat eqn list."""
    walk = (iter_eqns(eqns_or_jaxpr) if descend
            else (((), e) for e in eqns_or_jaxpr))
    out = []
    for path, e in walk:
        if is_collective(e):
            aval = e.invars[0].aval
            out.append(Collective(
                kind=e.primitive.name, axes=_collective_axes(e),
                shape=tuple(aval.shape), dtype=str(aval.dtype),
                path=path, eqn=e))
    return out


def callbacks(eqns_or_jaxpr) -> list:
    """Every host-callback/transfer eqn in the program (recursive walk),
    as (path, eqn) pairs — must be empty for hot-path programs."""
    return [(path, e) for path, e in iter_eqns(eqns_or_jaxpr)
            if e.primitive.name in CALLBACK_PRIMS]


def contains_primitive(eqns_or_jaxpr, names) -> bool:
    """True if any eqn (recursive) has a primitive named in ``names``."""
    if isinstance(names, str):
        names = {names}
    names = set(names)
    return any(e.primitive.name in names for _, e in iter_eqns(eqns_or_jaxpr))


# ---------------------------------------------------------------------------
# Scan carry analysis
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CarrySlot:
    """One carry slot of a scan eqn: whether the body reads it, whether
    it passes through unchanged, and whether the stacked/final outputs
    are consumed by the outer program."""

    index: int          #: carry position (0-based, after num_consts)
    read: bool          #: some body eqn consumes the carry invar
    passthrough: bool   #: body outvar is the same var as the invar
    out_dropped: bool   #: the outer scan outvar for this slot is DropVar

    @property
    def dead(self) -> bool:
        """A slot the body never reads and never rewrites: it does no
        work across iterations — a closed-over constant in disguise (or
        a dropped carry left behind by a refactor)."""
        return not self.read and self.passthrough


def scan_carry_slots(scan_eqn) -> list:
    """Analyze every carry slot of one scan eqn (see :class:`CarrySlot`)."""
    body = _jaxpr_of(scan_eqn.params["jaxpr"])
    num_consts = scan_eqn.params["num_consts"]
    num_carry = scan_eqn.params["num_carry"]
    carry_invars = body.invars[num_consts:num_consts + num_carry]
    carry_outvars = body.outvars[:num_carry]
    consumed = set()
    for e in body.eqns:
        for v in e.invars:
            if not isinstance(v, jax_core.Literal):
                consumed.add(id(v))
    # a carry returned at a *different* position still flows somewhere
    # (check every occurrence — a var can be passthrough at its own slot
    # AND feed a later slot, which is a read)
    out_ids = [id(getattr(v, "val", v)) for v in carry_outvars]
    slots = []
    for i, (iv, ov) in enumerate(zip(carry_invars, carry_outvars)):
        read = id(iv) in consumed or any(
            oid == id(iv) and j != i for j, oid in enumerate(out_ids))
        slots.append(CarrySlot(
            index=i, read=read,
            passthrough=getattr(ov, "val", ov) is iv,
            out_dropped=isinstance(scan_eqn.outvars[i], jax_core_legacy.DropVar)))
    return slots


def dropped_outputs(scan_eqn) -> list:
    """Indices of stacked (ys) outputs of ``scan_eqn`` nobody consumes
    (DropVar in the outer eqn): per-iteration work the program computes
    and throws away."""
    num_carry = scan_eqn.params["num_carry"]
    return [i for i, v in enumerate(scan_eqn.outvars[num_carry:])
            if isinstance(v, jax_core_legacy.DropVar)]


# ---------------------------------------------------------------------------
# Per-step scope structure (ISSUE 16)
# ---------------------------------------------------------------------------

def step_scope_of(eqn) -> Tuple[str, int, str] | None:
    """``(algo, step, phase)`` of an eqn's innermost step scope, from its
    traced name stack — or ``None`` for unscoped eqns (and for eqns under
    a phase with no step marker).  The per-step ``named_scope`` convention
    every pipelined builder annotates with (``<algo>.step<k>.<phase>``,
    obs.named_span) and the index-free scan form (``<algo>.scanstep``,
    obs.scoped_step) are parsed by :mod:`dlaf_tpu.obs.scopes`, as
    obs.critpath's HLO side is: the jaxpr name stack and the compiled
    op_name metadata carry the same scopes, so the static structure here
    and the measured timeline there join on the same keys.  Scan-body
    scopes carry no index and report step ``-1``; phase defaults to
    ``other`` (the scope names only the step)."""
    scope = scopes.parse(
        str(getattr(eqn.source_info, "name_stack", "") or ""))
    if scope is None or scope.step is None:
        return None
    return (scope.algo, scope.step, scope.phase or "other")


def step_groups(eqns: Sequence) -> dict:
    """Group a flat eqn list by step scope: ``{(algo, step, phase):
    [eqn, ...]}`` in emission order.  Unscoped eqns are omitted."""
    out: dict = {}
    for e in eqns:
        key = step_scope_of(e)
        if key is not None:
            out.setdefault(key, []).append(e)
    return out


def step_edges(eqns: Sequence) -> set:
    """Inter-group dependency edges of the step structure.

    ``(src, dst)`` is present when some eqn in group ``dst`` transitively
    depends — through producers within ``eqns`` — on an eqn in group
    ``src``.  This is the static step DAG the critpath joiner's
    critical-path model walks with measured walls; tests pin the
    lookahead property on it (panel k+1 must NOT depend on bulk k).
    """
    groups = step_groups(eqns)
    owner = {id(e): key for key, evs in groups.items() for e in evs}
    edges: set = set()
    for key, evs in groups.items():
        seeds = [v for e in evs for v in e.invars]
        for d in closure(eqns, seeds):
            src = owner.get(id(d))
            if src is not None and src != key:
                edges.add((src, key))
    return edges


def step_structure(eqns_or_jaxpr) -> dict:
    """Export the static per-step phase structure of a traced program:
    ``{"groups": {key: n_eqns}, "edges": [...], "algos": {algo:
    {"steps": K, "scan": bool}}}`` with keys rendered as
    ``"<algo>.step<k>.<phase>"`` strings (scan: ``"<algo>.scanstep.
    <phase>"``) — the depgraph-side mirror of obs.critpath's measured
    schedule, JSON-ready for tooling."""
    if hasattr(eqns_or_jaxpr, "eqns"):
        eqns = list(eqns_or_jaxpr.eqns)
    elif hasattr(eqns_or_jaxpr, "jaxpr"):
        eqns = list(eqns_or_jaxpr.jaxpr.eqns)
    else:
        eqns = list(eqns_or_jaxpr)
    groups = step_groups(eqns)
    edges = step_edges(eqns)

    def render(key) -> str:
        algo, step, phase = key
        stem = f"{algo}.scanstep" if step < 0 else f"{algo}.step{step:03d}"
        return f"{stem}.{phase}"

    algos: dict = {}
    for algo, step, _phase in groups:
        a = algos.setdefault(algo, {"steps": 0, "scan": False})
        if step < 0:
            a["scan"] = True
        else:
            a["steps"] = max(a["steps"], step + 1)
    return {
        "groups": {render(k): len(v) for k, v in sorted(groups.items())},
        "edges": sorted((render(a), render(b)) for a, b in edges),
        "algos": algos,
    }
