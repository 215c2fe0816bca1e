"""Collective verbs over mesh axes, usable inside ``shard_map``.

TPU-native counterpart of the reference's L4 async tile collectives
(``communication/kernels/{broadcast,all_reduce,reduce,p2p,p2p_allsum}.h``).
The reference wraps nonblocking MPI calls in sender adaptors, serialized
per-communicator by ``Pipeline`` and polled from a dedicated "mpi" thread pool
(``sender/transform_mpi.h:56-98``). On TPU all of that machinery collapses
into XLA collectives over ICI: ordering is XLA program order inside the traced
step, overlap is XLA's latency hiding, and there is nothing to poll.

Each verb takes an ``axis`` (``'row'`` or ``'col'`` — see
:mod:`dlaf_tpu.comm.grid`). Broadcast *along* the row axis communicates among
ranks of the same grid column (the reference's column communicator) and vice
versa. Source/destination ranks must be trace-time constants, which they are
in the per-``k`` factorization loops (the loop is unrolled at trace time).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .grid import COL_AXIS, ROW_AXIS  # re-export for convenience  # noqa: F401
from .. import obs


#: Trace-time payload-corruption hook, installed ONLY by
#: ``health.inject.corrupt_collective`` (fault-injection drills); None in
#: production, so the cost is one module-attribute check per traced
#: collective. The hook sees (kind, axis, payload) and returns the —
#: possibly poisoned — payload.
_INJECT_HOOK = None


def _maybe_inject(kind: str, axis: str, x):
    if _INJECT_HOOK is None:
        return x
    return _INJECT_HOOK(kind, axis, x)


def _record(kind: str, axis: str, x) -> None:
    """Per-collective accounting (the per-kind/per-axis byte counters
    arXiv:2112.09017 credits its ICI tuning to): payload element count ×
    itemsize, attributed to the mesh axis, per EXECUTED step. Shapes/dtypes
    are static even for traced operands, so this costs nothing at run time
    — counts accumulate when a program is TRACED (once per compiled
    program). A ``lax.scan`` body is traced once for all its iterations, so
    a collective inside one counts the enclosing scan's trip count
    (``obs.traced_step_count()``, set by the ``obs.scoped_step`` wrapper
    every distributed scan builder passes its body through): the scan form
    and the unrolled form of one algorithm then report the same per-call
    traffic, which is the per-program traffic model the tuning sessions
    need. A ``while_loop``/``fori_loop`` body would still count once
    whatever its trip count; none holds a collective today (the loops of
    ``tile_ops/`` and ``eigensolver/tridiag_solver.py`` are local). With
    metrics off this is one attribute read and a return."""
    if not obs.metrics_active():
        return
    steps = obs.traced_step_count()
    nbytes = int(x.size) * x.dtype.itemsize if hasattr(x, "size") else 0
    obs.counter("dlaf_comm_collective_count_total",
                kind=kind, axis=axis).inc(steps)
    obs.counter("dlaf_comm_collective_bytes_total",
                kind=kind, axis=axis).inc(steps * nbytes)


def this_rank(axis: str):
    """This device's coordinate along ``axis`` (reference ``Communicator::rank``)."""
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    """Number of ranks along ``axis`` (reference ``Communicator::size``)."""
    return lax.axis_size(axis)


def bcast(x, axis: str, src: int):
    """Broadcast ``x`` from rank ``src`` along ``axis``
    (reference ``scheduleSendBcast``/``scheduleRecvBcast``,
    ``kernels/broadcast.h:62-115``).

    Two implementations (config knob ``bcast_impl``):

    * ``"psum"`` (default) — mask-then-psum: contributions from non-source
      ranks are zeroed, so the all-reduce returns exactly the source
      value. On a TPU ring this lowers to one all-reduce over ICI; XLA
      fuses the masking. For axis size p and payload V it moves
      ~2V(p-1)/p per link (reduce-scatter + all-gather) — within 2x of
      the V(p-1)/p one-to-all lower bound, the right shape for the
      bandwidth-bound panel broadcasts.
    * ``"tree"`` — binomial doubling over ``ppermute`` rounds: ceil(log2 p)
      serialized collective-permutes, each moving the full payload on
      disjoint links. ~log2(p) link latencies vs the ring's ~2(p-1), at
      log2(p)x the per-link traffic — the candidate winner for SMALL
      payloads (diagonal tiles) where hop latency dominates. (A one-hop
      multicast is not expressible: XLA collective-permute requires
      unique sources AND destinations.)

    First multi-chip access must A/B the two on real ICI (round-2 review
    carried this); the knob makes both measurable with the same programs.
    """
    from ..config import get_configuration

    _record("bcast", axis, x)
    x = _maybe_inject("bcast", axis, x)
    if get_configuration().bcast_impl == "tree":
        return _bcast_tree(x, axis, src)
    mask = (this_rank(axis) == src).astype(x.dtype)
    return lax.psum(x * mask, axis)


def _bcast_tree(x, axis: str, src: int):
    """Binomial-tree broadcast: at round r (r = 1, 2, 4, ...), ranks
    ``src .. src+r-1`` (cyclically) send to ``src+r .. src+2r-1`` in one
    ``ppermute`` with disjoint pairs. Handles non-power-of-2 axis sizes."""
    p = axis_size(axis)
    dist = (this_rank(axis) - src) % p
    val = x
    r = 1
    while r < p:
        npairs = min(r, p - r)
        perm = [((src + i) % p, (src + i + r) % p) for i in range(npairs)]
        sent = lax.ppermute(val, axis, perm=perm)
        take = (dist >= r) & (dist < min(2 * r, p))
        val = jnp.where(take, sent, val)
        r *= 2
    return val


def bcast2d(x, owner_r: int, owner_c: int):
    """Broadcast ``x`` from the single rank ``(owner_r, owner_c)`` to the
    whole 2D mesh in ONE collective (the diagonal-tile broadcast of every
    blocked factorization step — reference ``cholesky/impl.h:215-219``).

    Replaces the two-hop ``bcast(bcast(x, 'row', r), 'col', c)``: under the
    default mask+psum realization the two hops are two serialized
    all-reduces on the step critical path; here the payload is masked to
    the owning rank and ONE ``psum`` over BOTH mesh axes delivers it —
    XLA lowers this to a single all-reduce over the combined replica
    groups. Bitwise-identical to the two-hop form: either way the result
    is the owner's value plus exact zeros (the same masked-add discipline,
    including the ``-0.0 + 0.0 -> +0.0`` flattening any psum with more
    than one participant performs).

    ``bcast_impl="tree"`` has no 2-axis fusion (ppermute pairs live on one
    axis), so it keeps the two-hop binomial trees.

    Accounting: recorded once per axis under kind ``"bcast2d"`` so the
    per-axis byte counters see the same per-axis payload the two-hop form
    charged; the injection hook fires once (kind ``"bcast2d"``), and
    ``health.inject.corrupt_collective("bcast", ...)`` matches it too.
    """
    from ..config import get_configuration

    _record("bcast2d", ROW_AXIS, x)
    _record("bcast2d", COL_AXIS, x)
    x = _maybe_inject("bcast2d", ROW_AXIS, x)
    if get_configuration().bcast_impl == "tree":
        return _bcast_tree(_bcast_tree(x, ROW_AXIS, owner_r),
                           COL_AXIS, owner_c)
    mask = ((this_rank(ROW_AXIS) == owner_r)
            & (this_rank(COL_AXIS) == owner_c)).astype(x.dtype)
    return lax.psum(x * mask, (ROW_AXIS, COL_AXIS))


def record_overlapped(algo: str, axis: str, n: int = 1) -> None:
    """Trace-time accounting of HOISTED collectives (``comm_lookahead``,
    docs/comm_overlap.md): each collective a distributed builder emits
    BEFORE the preceding step's bulk trailing product — i.e. a transfer
    XLA can run on the ICI while the MXU grinds the bulk gemms — bumps
    ``dlaf_comm_overlapped_total{algo,axis}`` once per compiled program.
    Same trace-time semantics as the byte counters above."""
    if obs.metrics_active() and n:
        obs.counter("dlaf_comm_overlapped_total", algo=algo,
                    axis=axis).inc(n)


def all_reduce(x, axis: str, op: str = "sum"):
    """All-reduce along ``axis`` (reference ``scheduleAllReduce``,
    ``kernels/all_reduce.h:67-138``). The rooted :func:`reduce` lowers
    through here, so its traffic is accounted under this kind too."""
    _record("all_reduce", axis, x)
    x = _maybe_inject("all_reduce", axis, x)
    if op == "sum":
        return lax.psum(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    raise ValueError(f"unsupported reduce op {op!r}")


def reduce(x, axis: str, root: int, op: str = "sum"):
    """Reduce to ``root`` (reference ``scheduleReduceRecvInPlace`` +
    ``scheduleReduceSend``, ``kernels/reduce.h:36-124``).

    SPMD realization: the reduction runs as an all-reduce (one XLA
    collective; there is no partial-reduce primitive), and non-root ranks
    get ZEROS — the reference's contract defines only the root's output
    tile, and zeroing makes accidental reads of non-root results surface
    in tests instead of silently working and then breaking under a real
    rooted implementation.
    """
    full = all_reduce(x, axis, op)
    return jnp.where(this_rank(axis) == root, full,
                     jnp.zeros_like(full))


def send_recv(x, axis: str, src: int, dst: int):
    """Point-to-point move of ``x`` from ``src`` to ``dst`` along ``axis``
    (reference ``scheduleSend``/``scheduleRecv``, ``kernels/p2p.h:34-105``).

    Returns the sent value on ``dst``; other ranks get zeros. Lowered to an
    XLA collective-permute (one ICI hop for neighbours).
    """
    _record("send_recv", axis, x)
    return lax.ppermute(x, axis, perm=[(src, dst)])


def all_sum_p2p(x, axis: str):
    """Sum over an axis intended for the 2-rank case (reference
    ``scheduleAllSumP2P``, ``kernels/p2p_allsum.h:39-60``: a send/recv pair
    plus local add). XLA's psum already specializes the 2-rank ring."""
    _record("all_sum_p2p", axis, x)
    return lax.psum(x, axis)


def all_gather(x, axis: str, *, tiled: bool = False, concat_axis: int = 0):
    """Gather ``x`` from every rank along ``axis``; result has a new leading
    axis of size ``axis_size``, or is concatenated along array axis
    ``concat_axis`` when ``tiled``. Used by panel broadcast to give every rank
    the full panel (reference ``broadcast_panel.h`` achieves the same with
    per-tile bcasts)."""
    _record("all_gather", axis, x)
    x = _maybe_inject("all_gather", axis, x)
    return lax.all_gather(x, axis, axis=concat_axis, tiled=tiled)


def all_to_all(x, axis: str, *, split_axis: int, concat_axis: int):
    """Tiled all-to-all along ``axis`` (the layout-transpose verb of the
    distributed chase back-transform, eigensolver/back_transform.py: each
    rank scatters ``split_axis`` slices and concatenates the received
    ones along ``concat_axis``). The reference pipelines per-tile sends
    instead (``bt_band_to_tridiag/impl.h``); on ICI one all_to_all moves
    V(p-1)/p per link in a single collective. Accounted and injectable
    like every other verb."""
    _record("all_to_all", axis, x)
    x = _maybe_inject("all_to_all", axis, x)
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def barrier_value(x, axis: str):
    """Order-enforcing no-op: returns ``x`` after a reduction over a token.

    The reference fences benchmark timing with ``MPI_Barrier``
    (``miniapp_cholesky.cpp:134-146``); inside one traced program XLA order
    suffices, so this exists for cross-program fencing in miniapps.
    """
    z = jnp.zeros((), x.dtype)
    _record("barrier", axis, z)
    token = lax.psum(z, axis)
    return x + token
