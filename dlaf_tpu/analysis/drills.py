"""Seeded-bad programs that MUST trip the analysis gate.

Mirrors the bench/accuracy-gate injection drills (scripts/bench_gate.py
``--inject-slowdown``, scripts/accuracy_gate.py ``--inject``): a checker
whose failure mode has never been demonstrated is not a gate. Each drill
builds a program (or source snippet) carrying exactly one violation; CI
runs ``python -m dlaf_tpu.analysis --drill <name>`` and requires exit 1
with the expected rule named in the log (docs/static_analysis.md).

The graph drills trace real shard_map/jit programs on the virtual mesh —
the same trace path the auditor uses on the production builders — so a
drill that stops tripping means the CHECK broke, not the drill.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

from . import depgraph, graphcheck, lint
from .findings import Finding


def _x64():
    """The graph drills trace f64 programs like the production builders;
    without x64 the placeholders silently truncate to f32 and the
    precision drill would audit the wrong program."""
    import jax

    jax.config.update("jax_enable_x64", True)


def _mesh22():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    graphcheck._require_devices(4)
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("row", "col"))


def _rank_varying_collective() -> List[Finding]:
    """A psum only rank-row-0 executes (``lax.cond`` on ``axis_index``):
    the SPMD deadlock class graph-conditional-collective exists for."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def body(x):
        return lax.cond(lax.axis_index("row") == 0,
                        lambda v: lax.psum(v, "col"),
                        lambda v: v, x)

    fn = jax.shard_map(body, mesh=_mesh22(), in_specs=P("row", "col"),
                           out_specs=P("row", "col"), check_vma=False)
    sds = jax.ShapeDtypeStruct((8, 8), jnp.float64)
    return graphcheck.audit_jaxpr("drill.rank_varying_collective",
                                  depgraph.trace(fn, sds))


def _host_callback() -> List[Finding]:
    """A ``pure_callback`` spliced into a hot-path program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def fn(x):
        y = jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return x + y

    sds = jax.ShapeDtypeStruct((8, 8), jnp.float64)
    return graphcheck.audit_jaxpr("drill.host_callback",
                                  depgraph.trace(fn, sds))


def scan_keeping_passthrough(body, init, length: int):
    """``lax.scan(body, init, None, length)`` bound BENEATH ``lax.scan``'s
    own clean-up: the installed JAX forwards a carry its body passes
    through unchanged out of the scan at trace time, so a dead carry
    never reaches a jaxpr traced from ``lax.scan`` itself. A scan eqn
    built by a transformation (or by hand, as here) can still hold one,
    which is the program graph-dead-carry exists to catch."""
    import jax
    from jax import lax

    flat, tree = jax.tree_util.tree_flatten(init)

    def flat_body(*carry):
        new, y = body(jax.tree_util.tree_unflatten(tree, carry), None)
        return (*jax.tree_util.tree_leaves(new), y)

    outs = lax.scan_p.bind(
        *flat, jaxpr=jax.make_jaxpr(flat_body)(*flat), length=length,
        reverse=False, linear=(False,) * len(flat), unroll=1, num_consts=0,
        num_carry=len(flat), _split_transpose=False)
    return (jax.tree_util.tree_unflatten(tree, outs[:len(flat)]),
            outs[len(flat)])


def _dropped_carry() -> List[Finding]:
    """A scan carrying a slot its body never reads (and stacking an
    output nobody consumes): the dropped-carry refactor residue."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        def body(carry, _):
            a, dropped = carry
            a = a * 1.5
            return (a, dropped), a.sum()

        (a, _), _ys = scan_keeping_passthrough(body, (x, x + 1.0), 4)
        return a

    sds = jax.ShapeDtypeStruct((8, 8), jnp.float64)
    return graphcheck.audit_jaxpr("drill.dropped_carry",
                                  depgraph.trace(fn, sds))


def _hbm_blowup() -> List[Finding]:
    """A broadcast-then-reduce temporary 64x the program's input bytes —
    the materialized-intermediate OOM class."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        big = jnp.broadcast_to(x, (64,) + x.shape) * 2.0
        return big.sum(axis=0)

    sds = jax.ShapeDtypeStruct((16, 16), jnp.float64)
    return graphcheck.audit_jaxpr("drill.hbm_blowup",
                                  depgraph.trace(fn, sds))


def _precision_demotion() -> List[Finding]:
    """An f64 operand silently demoted to f32 for the product."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        y = x.astype(jnp.float32)
        return (y @ y).astype(jnp.float64)

    sds = jax.ShapeDtypeStruct((8, 8), jnp.float64)
    return graphcheck.audit_jaxpr("drill.precision_demotion",
                                  depgraph.trace(fn, sds))


#: Seeded-bad source snippet for the lint drill: one violation per rule,
#: in a path that puts it under the traced-layer scoping
#: (dlaf_tpu/algorithms/). The bare suppression on the last function is
#: itself the violation for lint-suppression-reason. (The suppression
#: scanner reads real COMMENT tokens only, so this string literal's
#: embedded marker is invisible when THIS file is linted.)
LINT_DRILL_PATH = "dlaf_tpu/algorithms/_lint_drill.py"
LINT_DRILL_SOURCE = '''\
import os

import jax
import numpy as np

from dlaf_tpu import obs


def resolved_bad_knob():
    return os.environ.get("DLAF_TOTALLY_UNREGISTERED_KNOB", "0")


def _build_bad(dist, mesh):
    def fn(storage):
        obs.counter("dlaf_bad_steps_total", mode="bad").inc()
        return np.abs(storage)
    return fn


@jax.jit
def _bad_local(a):
    host = jax.device_get(a)
    print("peek:", host[0, 0])
    return a


def suppressed_without_reason():
    return os.environ.get("DLAF_OTHER_KNOB")  # dlaf: disable=lint-unregistered-knob
'''


def _lint_violation() -> List[Finding]:
    return lint.lint_source(LINT_DRILL_SOURCE, LINT_DRILL_PATH)


#: drill name -> (runner, rules the run MUST report)
DRILLS: Dict[str, Tuple[Callable[[], List[Finding]], Tuple[str, ...]]] = {
    "rank_varying_collective": (_rank_varying_collective,
                                ("graph-conditional-collective",)),
    "host_callback": (_host_callback, ("graph-host-callback",)),
    "dropped_carry": (_dropped_carry,
                      ("graph-dead-carry", "graph-dead-output")),
    "hbm_blowup": (_hbm_blowup, ("graph-hbm-blowup",)),
    "precision_demotion": (_precision_demotion,
                           ("graph-precision-demotion",)),
    "lint_violation": (_lint_violation,
                       ("lint-unregistered-knob",
                        "lint-unguarded-traced-metric",
                        "lint-np-in-traced", "lint-host-sync",
                        "lint-suppression-reason")),
}


def run(name: str) -> Tuple[List[Finding], Tuple[str, ...]]:
    """Run one drill; returns (findings, rules that must appear)."""
    if name not in DRILLS:
        raise KeyError(f"unknown drill {name!r}; have {sorted(DRILLS)}")
    runner, expected = DRILLS[name]
    if name != "lint_violation":
        _x64()
    return runner(), expected
