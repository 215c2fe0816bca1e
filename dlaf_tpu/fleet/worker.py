"""Fleet worker: one serve replica behind the router (docs/fleet.md).

A :class:`FleetWorker` wraps the existing per-replica stack — one
:class:`~dlaf_tpu.serve.queue.Queue` over one
:class:`~dlaf_tpu.serve.programs.ProgramService`, warm-started from the
jax persistent compile cache (placed by ``config.initialize()``) exactly
like a single-process server — and speaks the length-prefixed JSON
protocol of :mod:`.transport` back to the router over one connect-back
socket.

The protocol loop is deliberately SINGLE-THREADED: a wedged dispatch
blocks the pong too, so the router's heartbeat timeout observes real
unresponsiveness, not just socket liveness. Deadline-based partial-batch
dispatch still works because every incoming message AND every idle tick
is a queue clock edge (``queue.poll()``), preserving the
no-background-thread determinism of the serve layer.

Message kinds (router -> worker): ``submit`` (one wire request + router
ticket seq + trace id), ``flush``, ``ping``, ``healthz``, ``warmup``
(wire ProgramSpecs), ``drain``. Worker -> router: ``hello``, ``result``
(the ACK — a ticket is only ever router-owned until this arrives),
``pong``, ``healthz``, ``warmed``, ``draining``, ``drained`` (carrying
the handback seq list).

Shutdown contract (docs/fleet.md): SIGTERM (or a router ``drain``)
triggers the GRACEFUL path — stop admission, absorb any submits already
in the socket buffer as unstarted handbacks, let the synchronous
in-flight dispatch finish (it already has, by single-threadedness),
``Queue.drain()`` the undispatched remainder, send results + the
``drained`` handback, exit 0. SIGKILL skips all of that and exercises
the router's failover path instead.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import sys
from typing import Optional

from .. import obs
from ..health.errors import DrainedError
from ..serve.programs import ProgramSpec
from ..serve.queue import Queue, Request, array_to_wire
from . import transport

#: Socket timeout of the protocol loop — the idle-tick cadence at which
#: the worker polls its queue's deadlines and checks the drain flag.
IDLE_TICK_S = 0.05


class FleetWorker:
    """One worker's protocol loop over an already-connected socket
    (module docstring). ``queue`` defaults to a fresh config-driven
    :class:`~dlaf_tpu.serve.queue.Queue`; tests inject one with a fake
    clock / tiny batch."""

    def __init__(self, sock: socket.socket, worker: int,
                 queue: Optional[Queue] = None,
                 idle_tick_s: float = IDLE_TICK_S):
        self.sock = sock
        self.worker = int(worker)
        self.queue = queue if queue is not None else Queue()
        self.idle_tick_s = float(idle_tick_s)
        self._tickets: dict = {}        # router seq -> serve Ticket
        self._draining = False
        self._killed = False

    # -- external control (signal handler / tests) ------------------------

    def request_drain(self) -> None:
        """Arm the graceful-drain path; honored at the next loop tick
        (the SIGTERM handler calls this — nothing async-unsafe here)."""
        self._draining = True

    def kill(self) -> None:
        """SIGKILL stand-in for in-process drill workers: drop the
        connection with no drain, no handback, unacked tickets and all —
        the router must detect the EOF and fail over."""
        self._killed = True
        try:
            self.sock.close()
        except OSError:
            pass

    # -- the loop ---------------------------------------------------------

    def serve(self) -> None:
        """Run the protocol loop until drain completes or the router
        goes away. Sends ``hello`` first (the router learns this
        worker's index and pid from it, never from connection order)."""
        self.sock.settimeout(self.idle_tick_s)
        self._send({"kind": "hello", "worker": self.worker,
                    "pid": os.getpid()})
        try:
            while True:
                if self._draining:
                    self._drain()
                    return
                try:
                    msg = transport.recv_msg(self.sock, idle_ok=True)
                except transport.TransportIdle:
                    # idle tick = queue clock edge: deadline-expired
                    # partial batches dispatch here, results ack here
                    self._poll_safely()
                    self._pump()
                    continue
                self._handle(msg)
                self._pump()
        except (transport.TransportClosed, OSError):
            # the router went away (or this worker was kill()ed) — there
            # is nobody left to report to, so exit the loop cleanly; the
            # docstring's "until ... the router goes away" contract
            return
        finally:
            if not self._killed:
                try:
                    self.sock.close()
                except OSError:
                    pass

    # -- message handling -------------------------------------------------

    def _handle(self, msg: dict) -> None:
        kind = msg.get("kind")
        if kind == "submit":
            self._submit(msg)
        elif kind == "flush":
            try:
                self.queue.flush()
            except Exception:
                pass            # failed tickets are poisoned; _pump acks
        elif kind == "ping":
            self._poll_safely()
            self._send({"kind": "pong", "worker": self.worker})
        elif kind == "healthz":
            self._send({"kind": "healthz", "worker": self.worker,
                        "payload": obs.exporter.healthz_payload()})
        elif kind == "warmup":
            specs = [ProgramSpec.from_wire(d) for d in msg.get("specs", [])]
            walls = self.queue.service.warmup(*specs)
            self._send({"kind": "warmed", "worker": self.worker,
                        "compile_s": float(sum(walls.values()))})
        elif kind == "drain":
            self._draining = True

    def _submit(self, msg: dict) -> None:
        seq = int(msg["seq"])
        req = Request.from_wire(msg["req"])
        # sweep OTHER buckets' deadlines first so a failure there (whose
        # tickets are all mapped) cannot masquerade as this submit's
        self._poll_safely()
        try:
            ticket = self.queue.submit(req, trace_id=msg.get("trace_id"))
            self._tickets[seq] = ticket
        except Exception as e:
            # shed (OverloadError) or this bucket's inline dispatch
            # failed after the worker's own retries: ack the structured
            # cause — the router treats a processed-and-failed request
            # as final (at-least-once applies to LOST tickets only)
            self._send_error(seq, e)

    def _poll_safely(self) -> None:
        try:
            self.queue.poll()
        except Exception:
            pass                # poisoned tickets are acked by _pump

    # -- result pump ------------------------------------------------------

    def _pump(self) -> None:
        """Ack every resolved ticket (result or structured error) back
        to the router; drained tickets are NOT error-acked — the drain
        handback owns them."""
        for seq in [s for s, t in self._tickets.items()
                    if t.done or t.error is not None]:
            ticket = self._tickets[seq]
            if ticket.done:
                out = ticket._result
                arrays = (list(out) if isinstance(out, tuple) else [out])
                self._send({"kind": "result", "seq": seq, "ok": True,
                            "worker": self.worker,
                            "arrays": [array_to_wire(a) for a in arrays],
                            "info": ticket.info,
                            "queue_s": ticket.queue_s,
                            "total_s": ticket.total_s})
            elif isinstance(ticket.error, DrainedError):
                continue
            else:
                self._send_error(seq, ticket.error)
            del self._tickets[seq]

    def _send_error(self, seq: int, exc: BaseException) -> None:
        self._send({"kind": "result", "seq": seq, "ok": False,
                    "worker": self.worker,
                    "error": {"type": type(exc).__name__,
                              "message": str(exc)}})

    def _send(self, msg: dict) -> None:
        transport.send_msg(self.sock, msg)

    # -- graceful drain ---------------------------------------------------

    def _drain(self) -> None:
        """The SIGTERM / router-``drain`` path (module docstring)."""
        self._send({"kind": "draining", "worker": self.worker})
        # absorb submits already in the socket buffer: admission is
        # stopped, so they are unstarted by definition -> handback
        handback = []
        idle = 0
        while idle < 2:
            try:
                msg = transport.recv_msg(self.sock, idle_ok=True)
            except (transport.TransportIdle, transport.TransportClosed,
                    OSError):
                idle += 1
                continue
            if msg.get("kind") == "submit":
                handback.append(int(msg["seq"]))
            elif msg.get("kind") == "ping":
                self._send({"kind": "pong", "worker": self.worker})
        # the synchronous in-flight dispatch (if any) already completed;
        # ack its results, then hand back the undispatched remainder
        self._pump()
        drained = {id(t) for _, t in self.queue.drain()}
        for seq in [s for s, t in self._tickets.items()
                    if id(t) in drained]:
            handback.append(seq)
            del self._tickets[seq]
        self._pump()            # drain() may have raced a done ticket
        self._send({"kind": "drained", "worker": self.worker,
                    "handback": sorted(handback)})
        try:
            self.sock.close()
        except OSError:
            pass


def connect_worker(port: int, worker: int, host: str = "127.0.0.1",
                   queue: Optional[Queue] = None,
                   idle_tick_s: float = IDLE_TICK_S) -> FleetWorker:
    """Dial the router and wrap the connection (shared by the subprocess
    entry point below and the in-process drill workers in tests)."""
    sock = socket.create_connection((host, int(port)))
    return FleetWorker(sock, worker, queue=queue, idle_tick_s=idle_tick_s)


def main(argv=None) -> int:
    """``python -m dlaf_tpu.fleet.worker --connect HOST:PORT --worker K``
    — the real-subprocess worker (CI chaos drill, bench fleet arm).
    Stamps ``obs.set_rank(K)`` BEFORE any sink write so a ``%r``
    metrics-path template lands each worker's records in its own shard,
    and installs the SIGTERM graceful-drain handler."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--connect", required=True,
                        help="router address, HOST:PORT")
    parser.add_argument("--worker", required=True, type=int,
                        help="this worker's fleet index (also its obs "
                        "rank for %%r path templates)")
    args = parser.parse_args(argv)
    host, port = args.connect.rsplit(":", 1)
    obs.set_rank(args.worker)
    w = connect_worker(int(port), args.worker, host=host)
    signal.signal(signal.SIGTERM, lambda *_: w.request_drain())
    try:
        w.serve()
    finally:
        obs.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
