"""Real device synchronization for timing fences.

:func:`hard_fence` calls ``jax.Array.block_until_ready`` and then reads one
element of the array back to the host, so a timed region ends only when the
producing computation has run and its result has been seen by the host. The
readback is a single-element transfer — noise next to any timed region worth
measuring.

Reference analog: the fenced-timing protocol ``waitLocalTiles()`` +
``MPI_Barrier`` around every benchmark region (miniapp_cholesky.cpp:134-146).

Note: the readback pulls one element from the first *addressable shard*,
a one-device array, whatever the array's sharding. All shards of one array
are defined by the same launched program, so completion of any output buffer
implies the program ran; per-device skew is bounded by the program itself.
Indexing the sharded array instead (``x[(0, ..., 0)]``) is a gather over
every device of its mesh — on a 2x2 v5e result eight scalar transfers, a
concatenate and a four-device gather program, 4.8 ms of host time per fence
against 2.0 ms for the shard (PERF.md, PR 27) — and on a multi-controller run
the global element (0, ..., 0) may live on a device this process cannot
address at all.
"""

from __future__ import annotations

import numpy as np

from .. import obs

__all__ = ["hard_fence"]


def hard_fence(*arrays):
    """Block until every given array's producing computation has run.

    Accepts jax Arrays (or anything with ``block_until_ready``); numpy
    arrays and ``None`` pass through untouched. Returns the single argument
    (or the tuple) for call-site chaining.
    """
    # one host span per fence (not per array): on a profiler timeline it
    # labels the device's idle time around the readback
    with obs.span("stage.fence", fenced=False):
        for x in arrays:
            if x is None:
                continue
            if hasattr(x, "block_until_ready"):
                x.block_until_ready()
                if getattr(x, "size", 0):
                    # tiny readback of a value that depends on the array,
                    # from one local shard (module docstring)
                    shards = getattr(x, "addressable_shards", None)
                    src = shards[0].data if shards else x
                    np.asarray(src[(0,) * src.ndim])
    return arrays[0] if len(arrays) == 1 else arrays
