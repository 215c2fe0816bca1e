"""dlaf_tpu.serve — batched many-problem serving layer (docs/serving.md).

The production front end for the batch-small-problems regime (ROADMAP
item 1, ISSUE 11): millions of small/medium factorize/solve/EVP requests
served at MXU-bound — not dispatch/compile-bound — throughput. Three
surfaces:

* **Batched entry points** (:mod:`dlaf_tpu.algorithms.batched`,
  re-exported here): ``cholesky_batched`` / ``solve_batched`` /
  ``eigh_batched`` over a leading batch axis — one vmapped, donated
  program per shape bucket, per-element ``info`` vectors.
* **Program service** (:mod:`.programs`): the shape-bucketed AOT cache —
  ``warmup(spec, ...)`` pre-compiles a bucket set, ``pin``/``evict``
  manage residency under the ``DLAF_SERVE_CACHE_BYTES`` LRU budget,
  hit/miss/evict/compile metrics per bucket, persistent-compile-cache
  integration (``JAX_COMPILATION_CACHE_DIR``, else
  ``<checkout>/.jax_cache``) so a restarted server warms from disk.
* **Request queue** (:mod:`.queue`): buckets incoming (shape, dtype)
  requests to the nearest ceiling, pads, dispatches the cached program
  when a batch fills or the deadline expires, unpads — each request
  carrying a span, a ``serve`` JSONL record, and (under
  ``DLAF_ACCURACY``) an accuracy record, so the existing validator and
  CI gates cover the serving path end to end (``--require-serve``).
"""

from __future__ import annotations

from ..algorithms.batched import (cholesky_batched, eigh_batched,  # noqa: F401
                                  solve_batched)
from .programs import (ProgramService, ProgramSpec, cholesky_spec,  # noqa: F401
                       eigh_spec, get_service, program_builder, solve_spec,
                       warmup)
from .queue import (OPS, Queue, Request, Ticket, bucket_ceiling,  # noqa: F401
                    rhs_ceiling)

__all__ = [
    "OPS", "ProgramService", "ProgramSpec", "Queue", "Request", "Ticket",
    "bucket_ceiling", "cholesky_batched", "cholesky_spec", "eigh_batched",
    "eigh_spec", "get_service", "program_builder", "rhs_ceiling",
    "solve_batched", "solve_spec", "warmup",
]
