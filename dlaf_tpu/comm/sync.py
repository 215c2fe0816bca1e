"""Blocking host-side communication tier.

TPU-native counterpart of the reference's third comm tier, the blocking
``sync::`` wrappers (``communication/sync/broadcast.h:28-76``,
``sync/reduce.h``, ``sync/all_reduce.h``, ``sync/basic.h:28-164``,
``functions_sync.h``): used by tests and result checking, never by
algorithm hot paths.

In the reference every rank owns only its shard, so checking a result
means blocking MPI traffic (gather-by-broadcast, reduce to a master
rank). Under the single-controller SPMD model the host process already
addresses every shard; the blocking tier therefore becomes *device→host*
movement rather than rank→rank movement: pull shards with
``jax.device_get`` (which blocks until the producing computation is
done) and combine on host with numpy. The verbs keep the reference's
names and its "tests/checks only" role — algorithm hot paths use the
compiled ICI collectives in :mod:`dlaf_tpu.comm.collectives` instead,
exactly as the reference splits ``sync::`` from the async sender tier.

Rank→rank p2p (``sync::basic::send_to/receive_from``) has no residue
here: there is no second controller to exchange with, and host code can
read any shard directly via ``gather_shards``.
"""

from __future__ import annotations

import numpy as np


from ..common.sync import hard_fence
from ..matrix import memory

__all__ = ["gather", "gather_shards", "all_reduce", "reduce", "barrier"]


def gather(mat) -> np.ndarray:
    """Blocking gather of a distributed ``Matrix`` to one host array.

    The reference test suite's ``matrix_local.h`` gather: every rank
    broadcasts its tiles (``sync::broadcast``) until all ranks hold the
    global matrix. Here: one blocking device→host pull of the tile
    storage, then the inverse block-cyclic re-tile on host.
    ``Matrix.to_numpy`` delegates to this.
    """
    from ..matrix import tiling

    return np.asarray(
        tiling.tiles_to_global(memory.fetch(mat.storage), mat.dist))


def gather_shards(x) -> list[np.ndarray]:
    """Per-rank host copies of a sharded array, in device order
    (the blocking analog of each rank reading its local part;
    reference ``sync::basic::receive_from`` at the test master)."""
    if hasattr(x, "addressable_shards"):
        return [memory.fetch(s.data) for s in x.addressable_shards]
    return [memory.fetch(x) if hasattr(x, "devices") else np.asarray(x)]


def all_reduce(values, op: str = "sum"):
    """Blocking host fold of per-rank partial values
    (reference ``sync::allReduceInPlace``, ``sync/all_reduce.h``)."""
    ops = {"sum": np.sum, "max": np.max, "min": np.min,
           "prod": np.prod}
    if op not in ops:
        raise ValueError(f"unsupported reduce op {op!r}")
    return ops[op](np.stack([np.asarray(v) for v in values]), axis=0)


def reduce(values, root: int = 0, op: str = "sum"):
    """Blocking reduce "to ``root``" (reference ``sync::reduce``,
    ``sync/reduce.h``). The host plays every rank, so the result is the
    same object regardless of ``root``; the argument is kept for
    call-site parity with the reference's signature."""
    del root
    return all_reduce(values, op)


#: Blocking completion fence (reference ``MPI_Barrier`` in the miniapp
#: timing protocol, ``miniapp_cholesky.cpp:134-146``); see
#: :func:`dlaf_tpu.common.sync.hard_fence` for what the fence does.
barrier = hard_fence
