"""The one parser of the library's trace-time scopes.

Builders name what they emit with :func:`dlaf_tpu.obs.named_span` /
:func:`dlaf_tpu.obs.scoped_step` (``jax.named_scope``: the names land in
the jaxpr's name stack and in the compiled program's ``op_name`` metadata,
``jit(f)/while/body/closed_call/red2band.scanstep/red2band.panel/mul``, at
no runtime cost). One component of such a path is a scope of ours when it
reads

    <algo>[.step<k> | .scanstep | .rowchunk][.<phase>]

(``algo`` and ``phase`` lower case) with a marker, a phase or both: ``cholesky.step003.panel``,
``trsm.scanstep``, ``red2band.w``; or is one of :data:`BARE_PHASES`
(``layout``: the tile-layout moves of ``matrix/tiling.py:on_global``,
which belong to no algorithm). ``.step<k>`` / ``.scanstep`` / ``.rowchunk``
are step markers and never phases: they say which step an operation
belongs to (``-1`` inside a body traced once for all its iterations), the
phase says what the step was doing. The innermost phase wins, and so does
the innermost step marker, so a panel chain hoisted into step ``k``'s
scope and tagged ``<algo>.step<k+1>.panel`` is step ``k+1``'s, and a
jitted helper called under two phases keeps each call site's.

Read by ``obs.critpath`` (``schedule`` records), ``obs.telemetry``
(``phase_table``) and ``analysis.depgraph`` (the jaxpr's name stacks).
"""

from __future__ import annotations

import functools
import re
from typing import Iterator, NamedTuple, Optional

#: Phases that stand alone as a path component, without an algorithm.
BARE_PHASES = frozenset({"layout"})

_COMPONENT = re.compile(
    r"([a-z][a-z0-9_]*)(?:\.(?:step(\d+)|(scanstep|rowchunk)))?"
    r"(?:\.([a-z][a-z0-9_]*))?")
_HEAD = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.MULTILINE)
_OPERANDS = re.compile(r"(?:^| )[a-z][a-z0-9\-]*\(([^)]*)\)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.\-]+) ")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


class Scope(NamedTuple):
    """What a scope path says of one operation. ``step``: ``k`` of the
    innermost ``.step<k>``, ``-1`` under a ``.scanstep`` / ``.rowchunk``
    body, None without a marker. ``phase``: the innermost phase token, None
    where the path holds step markers only. ``algo``: that of the phase's
    component (``""`` for a bare phase), else of the marker's."""

    algo: str
    step: Optional[int]
    phase: Optional[str]


def _component(text: str):
    """``(algo, step or None, phase or None)`` of one path component that
    is a scope, else None."""
    if text in BARE_PHASES:
        return "", None, text
    m = _COMPONENT.fullmatch(text)
    if m is None:
        return None
    algo, k, marker, phase = m.groups()
    if k is None and marker is None and phase is None:
        return None                 # a bare word: a primitive, not a scope
    return algo, (int(k) if k is not None else -1 if marker else None), phase


@functools.lru_cache(maxsize=1 << 16)
def parse(path: str) -> Optional[Scope]:
    """The :class:`Scope` of a ``/``-joined scope path (an ``op_name``, a
    jaxpr name stack), or None where no component is a scope of ours."""
    parts = path.split("/")
    if "." not in path and not BARE_PHASES.intersection(parts):
        return None
    found = [c for c in map(_component, reversed(parts)) if c]
    if not found:
        return None
    phased = next((c for c in found if c[2] is not None), None)
    marked = next((c for c in found if c[1] is not None), None)
    lead = phased or marked
    step = lead[1] if lead[1] is not None else marked[1] if marked else None
    return Scope(lead[0], step, lead[2])


def module_name(hlo_text: str) -> str:
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else ""


def instructions(hlo_text: str) -> Iterator[tuple]:
    """``(instruction name, op_name or "", the text after " = ")`` of every
    instruction line of a compiled module's text, nested computations
    included (instruction names are unique in a module)."""
    for _name, rows in computations(hlo_text):
        yield from rows


def computations(hlo_text: str) -> Iterator[tuple]:
    """``(computation name, [(instruction name, op_name or "", text after
    " = ")])`` of every computation of a compiled module's text, in order
    (``%fused_computation.7 (p: f32[4]) -> f32[4] {`` ... ``}``)."""
    name, rows = None, []
    for line in hlo_text.splitlines():
        if line.startswith("}"):
            if name is not None:
                yield name, rows
            name, rows = None, []
        elif line.endswith("{") and not line.startswith(" "):
            head = _COMPUTATION.match(line)
            name, rows = (head.group(1) if head else None), []
        elif name is not None:
            head = _HEAD.match(line)
            if head is not None:
                rest = line[head.end():]
                meta = _OP_NAME.search(rest)
                rows.append((head.group(1),
                             meta.group(1) if meta else "", rest))


def called_computation(rest: str) -> Optional[str]:
    """The computation a fusion instruction calls (``calls=%name``)."""
    found = _CALLS.search(rest)
    return found.group(1) if found else None


def operand_names(rest: str) -> list:
    """The operand instructions of one instruction, in order, from its text
    after ``" = "`` (``type opcode(%a, %b), attributes``)."""
    found = _OPERANDS.search(rest)
    return _OPERAND.findall(found.group(1)) if found else []


def phases_of_text(text: str) -> set:
    """Every phase token some named location of ``text`` carries: what a
    lowered module (``as_text(debug_info=True)``: ``loc("cholesky.scanstep/
    cholesky.panel/mul"(...))``) says the tree that traced it emits. A
    function's qualified name is a named location too; ``Class.method`` and
    ``f.<locals>.g`` are no scopes of ours (an algorithm is lower case)."""
    out = set()
    for path in set(re.findall(r'loc\("([^"\n<]*)"', text)):
        if path.endswith(".py"):
            continue                # a source file's location
        scope = parse(path)
        if scope is not None and scope.phase is not None:
            out.add(scope.phase)
    return out
