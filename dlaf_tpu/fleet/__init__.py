"""dlaf_tpu.fleet — multi-replica serve tier with failover
(docs/fleet.md, ROADMAP item 3).

The jump from "a server" to "a service": a :class:`~.router.Router`
front tier shards bucketed requests across N :class:`~.worker.
FleetWorker` replicas — each one the existing single-process serve
stack (``serve.Queue`` + ``ProgramService``), warm-started from the
persistent compile cache — over the zero-new-deps
length-prefixed-JSON transport of :mod:`.transport`.

Robustness contract (the headline, docs/fleet.md):

* every accepted request gets a durable router-owned
  :class:`~.router.FleetTicket`; worker death re-dispatches
  unacknowledged tickets to siblings (at-least-once, never dropped);
* liveness is heartbeat-based with clock-injectable timeouts
  (:mod:`.membership`) so drills replay deterministically;
* routing is breaker-aware per worker (``fleet.worker{k}`` sites,
  half-open probe re-admission);
* SIGTERM drains gracefully (``Queue.drain()`` handback, zero
  re-dispatches), SIGKILL exercises failover;
* every decision lands as a schema-validated ``fleet`` JSONL record
  (``python -m dlaf_tpu.obs.validate --require-fleet``) and worker
  death trips the flight recorder (reason ``fleet_worker_down``).
"""

from __future__ import annotations

from .membership import Membership  # noqa: F401
from .router import (DISPATCH_SITE, FleetTicket, RemoteError,  # noqa: F401
                     Router, worker_site)
from .transport import (MAX_FRAME_BYTES, TransportClosed,  # noqa: F401
                        TransportIdle, recv_msg, send_msg)


def __getattr__(name: str):
    # .worker is exposed lazily so ``python -m dlaf_tpu.fleet.worker``
    # does not import it twice (runpy warns when the -m target is
    # already in sys.modules) — same pattern as ``obs.devtrace``.
    if name in ("FleetWorker", "connect_worker"):
        from . import worker
        return getattr(worker, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "DISPATCH_SITE", "FleetTicket", "FleetWorker", "MAX_FRAME_BYTES",
    "Membership", "RemoteError", "Router", "TransportClosed",
    "TransportIdle", "connect_worker", "recv_msg", "send_msg",
    "worker_site",
]
