"""Request-queue front end over the bucketed program service
(docs/serving.md).

:class:`Queue` accepts singleton requests (one ``(n, n)`` problem each),
buckets them by ``(op, dtype, uplo/side/op/diag, bucket ceiling)``,
identity-pads each problem to the bucket ceiling, dispatches the warm
vmapped bucket program when a batch fills — or when the oldest pending
request exceeds the ``DLAF_SERVE_DEADLINE_MS`` deadline — and unpads the
results back to request shape.

Determinism contract: the queue runs NO background thread. Deadlines are
evaluated against the injected ``clock`` at ``submit``/``poll``/
``flush`` calls, so which requests share a dispatch is a pure function
of the submission sequence and the clock values — testable to the lane.

Padding contract (probed + pinned in tests/test_serve.py):

* **lane padding** (a non-full dispatch): missing lanes are identity
  matrices (zero rhs for the solve). Lanes of the batched programs are
  bitwise independent, so pad lanes are provably inert — real-lane
  results are bitwise identical at every occupancy, and the pad lanes
  themselves factor to the singleton-builder identity result (info 0).
* **shape padding** (``n_req < bucket_n``): the problem is embedded in
  an identity border (``[[A, 0], [0, I]]``; zero rhs rows/cols; the
  eigh border is ``c*I`` with ``c`` strictly above the Gershgorin
  bound of the stored triangle's hermitian expansion — an upper bound
  on the spectral radius, so the pad eigenvalues sort strictly last
  and the real pairs are the leading ``n_req``).
  The padded region stays exactly zero/identity, but the real block is
  ulp-level — NOT bitwise — against the exact-size program (the
  backend's lowering is shape-dependent); the per-request accuracy
  records bound the effect against the analytic budget.

Every request carries a span and, under ``DLAF_ACCURACY``, a
per-request ``accuracy`` record (site ``serve``); every dispatch and
request lands as a ``serve`` JSONL record so the validator's
``--require-serve`` covers the serving path end to end
(docs/observability.md).

Trace correlation (ISSUE 13, docs/observability.md live operations):
``submit`` stamps one ``trace_id`` per request (``Ticket.trace_id``)
and each batch dispatch draws one ``span_id``; the dispatch runs under
a batch-scope ``obs.trace_context`` (member-ID list + span_id) so the
dispatch record, the policy engine's retry/breaker records, and any
program compile it triggers are all joinable from any member ID, while
the per-request records (request, span, accuracy, SLO latency
exemplar) re-enter request scope with the single ID. The dispatch
record's ``stages`` object (compose/program/fetch/unpad walls) plus
the request's ``queue_s`` is the per-request waterfall
``obs.aggregate --trace <id>`` renders. Request completions feed
``obs.observe_latency`` (the rolling-window SLO gauges + breach
counter), the queue registers itself on the live ``/healthz`` endpoint
at construction, and an admission shed trips the flight recorder.

Resilience (PR 12, docs/robustness.md):

* **Admission control** (``DLAF_SERVE_MAX_DEPTH`` / ``DLAF_SERVE_SHED``):
  total pending depth is bounded; at the bound a submit either sheds fast
  with a structured :class:`~dlaf_tpu.health.errors.OverloadError` (shed
  counted per bucket, ``dlaf_serve_shed_total``) or — shed off —
  force-dispatches the fullest bucket as backpressure. Either way depth
  provably never exceeds the bound (queue memory is bounded under
  overload; bench.py's ``overload`` arm certifies shed rate + p99 at 2x
  capacity).
* **Per-request deadlines** (``Request.deadline_s``): at dispatch
  composition, requests whose wait exceeded their deadline are cancelled
  with a :class:`~dlaf_tpu.health.errors.DeadlineExceededError` cause
  (counted ``dlaf_deadline_exceeded_total{site="serve.queue"}`` +
  per-bucket ``expired``) instead of riding a batch whose result nobody
  will read.
* **Retried, breaker-guarded dispatch**: each batch dispatch runs under
  the shared :mod:`dlaf_tpu.health.policy` engine
  (``DLAF_SERVE_RETRY_ATTEMPTS``/``DLAF_SERVE_RETRY_BACKOFF_MS``) behind
  a per-bucket circuit breaker (:mod:`dlaf_tpu.health.circuit`,
  ``dlaf_circuit_state{site}``) — a transient failure retries before any
  ticket is poisoned; sustained failure opens the breaker and fails
  later dispatches fast instead of re-running a broken program.
* :meth:`Queue.stats` snapshots per-bucket depth / in-flight / shed /
  expired counts and breaker states (also exported as gauges and printed
  by ``scripts/profile_summary.py``'s serve section).
"""

from __future__ import annotations

import base64
import dataclasses
import functools
import itertools
import threading
import time
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..common.asserts import dlaf_assert
from ..config import (get_configuration, parse_serve_buckets,
                      register_program_cache)
from ..health import circuit as _circuit
from ..health.errors import (DeadlineExceededError, DrainedError,
                             OverloadError)
from ..health.policy import RetryPolicy, with_policy
from .programs import (ProgramService, cholesky_spec, eigh_spec,
                       get_service, solve_spec)

#: ops the queue serves, with their singular per-request result shapes
OPS = ("cholesky", "solve", "eigh")


def resolve_buckets() -> tuple:
    """The configured explicit ceilings (may be empty = pure
    power-of-two policy)."""
    return parse_serve_buckets(get_configuration().serve_buckets)


def bucket_ceiling(n: int, buckets: tuple = None) -> int:
    """Deterministic ceiling for a request dimension: the smallest
    configured bucket >= n, else (no bucket fits / no explicit list)
    the next power of two >= max(n, 8) — every shape is servable, an
    unconfigured one just lands in a colder bucket."""
    n = int(n)
    dlaf_assert(n >= 1, f"bucket_ceiling: n must be >= 1, got {n}")
    if buckets is None:
        buckets = resolve_buckets()
    for b in buckets:
        if b >= n:
            return b
    return 1 << max(int(n) - 1, 7).bit_length()


def rhs_ceiling(free: int) -> int:
    """Ceiling for the solve's rhs FREE-axis width: the next power of
    two >= free. Deliberately NOT the ``serve_buckets`` list — those are
    MATRIX-size ceilings, and rounding a 1-column rhs up to the smallest
    configured matrix bucket would multiply the rhs work/traffic by
    ``bucket/nrhs``; the pow2 policy bounds the padding waste at 2x
    while still sharing programs across nearby widths."""
    free = int(free)
    dlaf_assert(free >= 1, f"rhs_ceiling: free must be >= 1, got {free}")
    return 1 << (free - 1).bit_length()


# ---------------------------------------------------------------------------
# Wire codec (fleet ticket handoff, docs/fleet.md): requests must cross a
# process boundary as JSON — the fleet transport is length-prefixed JSON
# over local sockets, zero new deps — so arrays ride as base64(raw bytes)
# + dtype + shape. Defined HERE (not in dlaf_tpu.fleet) because the
# request owns its serialization and serve must not import fleet.
# ---------------------------------------------------------------------------

def array_to_wire(a) -> dict:
    """One ndarray as a JSON-safe dict (dtype name + shape + base64 of
    the C-contiguous raw bytes — exact, no text round-trip loss)."""
    a = np.ascontiguousarray(np.asarray(a))
    return {"dtype": a.dtype.name, "shape": list(a.shape),
            "data": base64.b64encode(a.tobytes()).decode("ascii")}


def array_from_wire(doc: dict) -> np.ndarray:
    """Inverse of :func:`array_to_wire` (a writable copy — frombuffer
    views are read-only and serve results are caller-owned)."""
    flat = np.frombuffer(base64.b64decode(doc["data"]),
                         dtype=np.dtype(doc["dtype"]))
    return flat.reshape(tuple(int(s) for s in doc["shape"])).copy()


@dataclasses.dataclass
class Request:
    """One serving request: ``op`` in :data:`OPS`, ``a`` the ``(n, n)``
    problem (triangle semantics per op), ``b`` the rhs for the solve
    (``(n, nrhs)`` side='L', ``(nrhs, n)`` side='R'), ``alpha`` the
    solve scale. ``rid`` is stamped by the queue when left None.
    ``deadline_s`` (None = no deadline) bounds the QUEUE WAIT: a request
    still pending ``deadline_s`` seconds after submit is cancelled at
    dispatch composition with a
    :class:`~dlaf_tpu.health.errors.DeadlineExceededError` cause."""

    op: str
    a: Any
    b: Any = None
    uplo: str = "L"
    side: str = "L"
    transa: str = "N"
    diag: str = "N"
    alpha: float = 1.0
    rid: Optional[int] = None
    deadline_s: Optional[float] = None

    def to_wire(self) -> dict:
        """JSON-safe form for the fleet ticket handoff (docs/fleet.md):
        arrays via :func:`array_to_wire`, scalars as-is. Round-trips
        exactly through :meth:`from_wire`."""
        return {"op": self.op, "a": array_to_wire(self.a),
                "b": None if self.b is None else array_to_wire(self.b),
                "uplo": self.uplo, "side": self.side,
                "transa": self.transa, "diag": self.diag,
                "alpha": float(self.alpha), "rid": self.rid,
                "deadline_s": self.deadline_s}

    @classmethod
    def from_wire(cls, doc: dict) -> "Request":
        return cls(op=str(doc["op"]), a=array_from_wire(doc["a"]),
                   b=(None if doc.get("b") is None
                      else array_from_wire(doc["b"])),
                   uplo=str(doc.get("uplo", "L")),
                   side=str(doc.get("side", "L")),
                   transa=str(doc.get("transa", "N")),
                   diag=str(doc.get("diag", "N")),
                   alpha=float(doc.get("alpha", 1.0)),
                   rid=doc.get("rid"),
                   deadline_s=doc.get("deadline_s"))


class Ticket:
    """Handle returned by :meth:`Queue.submit`. ``done`` flips when the
    request's batch dispatched; :meth:`result` returns the unpadded
    per-request output as HOST (numpy) arrays — the dispatch fetches the
    whole batch once, so per-ticket results are zero-cost views — and
    raises RuntimeError while still queued. ``info`` is the per-element
    info value (int) once done."""

    def __init__(self, request: Request, submitted: float,
                 trace_id: Optional[str] = None):
        self.request = request
        self.submitted = submitted
        self.done = False
        self.error: Optional[BaseException] = None
        self.info: Optional[int] = None
        self.queue_s: Optional[float] = None
        self.total_s: Optional[float] = None
        # request-scoped trace correlation (ISSUE 13): one ID per
        # request, stamped by obs.trace_context onto every record the
        # request's causal chain emits — `obs.aggregate --trace <id>`
        # joins them back together. An adopted trace_id (the fleet
        # worker passing through its router ticket's ID) keeps the
        # cross-process chain joinable from either side.
        self.trace_id = trace_id or obs.new_trace_id()
        self._result = None

    def result(self):
        if self.error is not None:
            # the request was not served: expired before dispatch, or the
            # batch it rode in failed to dispatch (compile error, OOM,
            # open breaker, ...) — surface the cause instead of "queued"
            what = ("expired before dispatch"
                    if isinstance(self.error, DeadlineExceededError)
                    else "drained undispatched"
                    if isinstance(self.error, DrainedError)
                    else "batch dispatch failed")
            raise RuntimeError(
                f"request {self.request.rid}: {what} "
                f"({type(self.error).__name__})") from self.error
        if not self.done:
            raise RuntimeError(
                f"request {self.request.rid} is still queued; Queue.flush() "
                "forces dispatch of partial batches")
        return self._result


@dataclasses.dataclass(frozen=True)
class _BucketKey:
    op: str
    n: int            # bucket ceiling
    nrhs: int         # rhs ceiling (0 for non-solve)
    dtype: str
    uplo: str
    side: str
    transa: str
    diag: str


# ---------------------------------------------------------------------------
# Padding / unpadding (host side — shapes are request-sized, tiny)
# ---------------------------------------------------------------------------

def _pad_a(req: Request, bn: int) -> np.ndarray:
    a = np.asarray(req.a)
    n = a.shape[0]
    if n == bn:
        return a
    out = np.zeros((bn, bn), a.dtype)
    out[:n, :n] = a
    if req.op == "eigh":
        # pad eigenvalues must sort strictly AFTER every real one so the
        # leading n pairs are the request's. max|A| alone does NOT bound
        # the spectrum (rho(A) can reach n*max|A| — e.g. the all-ones
        # matrix); use the Gershgorin/inf-norm bound of the hermitian
        # expansion of the STORED triangle (the only data the op reads)
        tri = np.tril(a) if req.uplo == "L" else np.triu(a)
        k = -1 if req.uplo == "L" else 1
        herm = tri + np.conj(np.tril(tri, k) if req.uplo == "L"
                             else np.triu(tri, k)).T
        c = 1.0 + float(np.abs(herm).sum(axis=1).max(initial=0.0))
    else:
        c = 1.0
    out[range(n, bn), range(n, bn)] = c
    return out


def _pad_b(req: Request, bn: int, brhs: int) -> np.ndarray:
    b = np.asarray(req.b)
    shape = (bn, brhs) if req.side == "L" else (brhs, bn)
    if b.shape == shape:
        return b
    out = np.zeros(shape, b.dtype)
    out[:b.shape[0], :b.shape[1]] = b
    return out


def _pad_lane(key: _BucketKey):
    """The inert pad-lane operands for one unfilled batch slot."""
    dt = np.dtype(key.dtype)
    a = np.eye(key.n, dtype=dt)
    if key.op != "solve":
        return (a,)
    shape = (key.n, key.nrhs) if key.side == "L" else (key.nrhs, key.n)
    return a, np.zeros(shape, dt)


def _unpad(req: Request, key: _BucketKey, lane_out):
    """Slice one lane's bucket-shaped outputs back to request shape."""
    n = np.asarray(req.a).shape[0]
    if req.op == "cholesky":
        return lane_out[:n, :n]
    if req.op == "solve":
        rows, cols = np.asarray(req.b).shape
        return lane_out[:rows, :cols]
    w, v = lane_out
    return w[:n], v[:n, :n]


# ---------------------------------------------------------------------------
# Per-dispatch accuracy probes (exact residuals — bucket problems are
# small by regime, so the O(n^3) check is cheap next to the solve)
# ---------------------------------------------------------------------------

@register_program_cache
@functools.lru_cache(maxsize=64)
def _residual_prog(op: str, shapes, dtype: str, uplo: str, side: str,
                   transa: str, diag: str):
    dt = np.dtype(dtype)

    def _fro(x):
        return jnp.sqrt(jnp.sum(jnp.abs(x) ** 2, axis=(-2, -1)))

    def _herm(a):
        if uplo == "L":
            return jnp.tril(a) + jnp.conj(jnp.tril(a, -1)).swapaxes(-1, -2)
        return jnp.triu(a) + jnp.conj(jnp.triu(a, 1)).swapaxes(-1, -2)

    tiny = jnp.asarray(np.finfo(dt.type(0).real.dtype).tiny)
    if op == "cholesky":
        def run(a, fac):
            ah = _herm(a)
            tri = jnp.tril(fac) if uplo == "L" else jnp.triu(fac)
            ll = (tri @ jnp.conj(tri).swapaxes(-1, -2) if uplo == "L"
                  else jnp.conj(tri).swapaxes(-1, -2) @ tri)
            return _fro(ll - ah) / jnp.maximum(_fro(ah), tiny)
    elif op == "solve":
        # vmapped bodies see ONE lane: a (n,n), b/x (n,nrhs), alpha scalar
        def run(a, b, alpha, x):
            tri = jnp.tril(a) if uplo == "L" else jnp.triu(a)
            if diag == "U":
                eye = jnp.eye(tri.shape[-1], dtype=tri.dtype)
                tri = jnp.where(eye.astype(bool), eye, tri)
            if transa != "N":
                tri = tri.swapaxes(-1, -2)
                if transa == "C":
                    tri = jnp.conj(tri)
            lhs = tri @ x if side == "L" else x @ tri
            rhs = alpha * b
            return _fro(lhs - rhs) / jnp.maximum(_fro(rhs), tiny)
    else:   # eigh
        def run(a, w, v):
            ah = _herm(a)
            r = ah @ v - v * w[None, :]
            return _fro(r) / jnp.maximum(_fro(ah), tiny)

    return jax.jit(jax.vmap(run))


#: op -> (accuracy metric label, analytic tolerance factor c) — the c
#: constants the existing estimator family uses for the same metrics
#: (docs/accuracy.md).
_ACCURACY = {"cholesky": ("cholesky_residual", 60.0),
             "solve": ("trsm_residual", 60.0),
             "eigh": ("eigen_residual", 200.0)}


# ---------------------------------------------------------------------------
# The queue
# ---------------------------------------------------------------------------

class Queue:
    """Bucketing/padding/deadline front end (module docstring).

    ``batch``/``deadline_s``/``buckets`` default to the
    ``DLAF_SERVE_BATCH``/``DLAF_SERVE_DEADLINE_MS``/``DLAF_SERVE_BUCKETS``
    knobs; ``clock`` (default ``time.monotonic``) is injectable so
    deadline behavior is deterministic under test."""

    def __init__(self, service: Optional[ProgramService] = None, *,
                 batch: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 buckets: Optional[tuple] = None,
                 clock: Callable[[], float] = time.monotonic,
                 max_depth: Optional[int] = None,
                 shed: Optional[bool] = None,
                 retry_attempts: Optional[int] = None,
                 retry_backoff_s: Optional[float] = None):
        cfg = get_configuration()
        self.service = service if service is not None else get_service()
        self.batch = int(batch if batch is not None else cfg.serve_batch)
        dlaf_assert(self.batch >= 1, f"Queue: batch must be >= 1, got "
                    f"{self.batch}")
        self.deadline_s = float(cfg.serve_deadline_ms / 1e3
                                if deadline_s is None else deadline_s)
        self.buckets = (tuple(buckets) if buckets is not None
                        else resolve_buckets())
        self.clock = clock
        self.max_depth = int(max_depth if max_depth is not None
                             else cfg.serve_max_depth)
        dlaf_assert(self.max_depth >= 0, f"Queue: max_depth must be >= 0, "
                    f"got {self.max_depth}")
        self.shed = bool(cfg.serve_shed if shed is None else shed)
        self.retry_attempts = int(retry_attempts if retry_attempts
                                  is not None else cfg.serve_retry_attempts)
        dlaf_assert(self.retry_attempts >= 1, "Queue: retry_attempts must "
                    f"be >= 1, got {self.retry_attempts}")
        self.retry_backoff_s = float(
            cfg.serve_retry_backoff_ms / 1e3 if retry_backoff_s is None
            else retry_backoff_s)
        self._pending: dict = {}          # _BucketKey -> [(req, ticket)]
        self._rid = itertools.count()
        # one lock over submit/poll/flush: the service below is already
        # thread-safe, but bucket fill/pop must be atomic too or two
        # request threads filling the same bucket double-pop it
        self._lock = threading.RLock()
        self.dispatches = 0
        self.requests = 0
        self._in_flight = 0               # dispatches currently executing
        self._counts: dict = {}           # _BucketKey -> {shed, expired}
        # expose this queue on the live /healthz endpoint (weakref, no
        # unregister protocol) — LAST, after every field stats() reads
        # exists: a scrape thread may call stats() the instant the queue
        # is visible, and a half-constructed queue answering /healthz
        # with an AttributeError would fabricate a healthz_failure
        # flight dump on a perfectly clean run
        obs.exporter.register_queue(self)

    # -- submission ------------------------------------------------------

    def _key(self, req: Request) -> _BucketKey:
        a = np.asarray(req.a)
        dlaf_assert(req.op in OPS,
                    f"Queue: op must be one of {OPS}, got {req.op!r}")
        dlaf_assert(a.ndim == 2 and a.shape[0] == a.shape[1],
                    f"Queue: request 'a' must be square (n, n), got "
                    f"{a.shape}")
        bn = bucket_ceiling(a.shape[0], self.buckets)
        nrhs = 0
        if req.op == "solve":
            b = np.asarray(req.b)
            dlaf_assert(b.ndim == 2, "Queue: solve request needs a 2D rhs")
            dlaf_assert(b.dtype == a.dtype,
                        f"Queue: rhs dtype {b.dtype} != matrix dtype "
                        f"{a.dtype} (one bucket program serves one dtype)")
            solve_dim, free = ((b.shape[0], b.shape[1]) if req.side == "L"
                               else (b.shape[1], b.shape[0]))
            dlaf_assert(solve_dim == a.shape[0],
                        f"Queue: rhs solve dimension {solve_dim} != "
                        f"n={a.shape[0]}")
            nrhs = rhs_ceiling(free)
        return _BucketKey(op=req.op, n=bn, nrhs=nrhs,
                          dtype=np.dtype(a.dtype).name, uplo=req.uplo,
                          side=req.side, transa=req.transa, diag=req.diag)

    def _bucket_counts(self, key: _BucketKey) -> dict:
        return self._counts.setdefault(
            key, {"shed": 0, "expired": 0, "dispatches": 0, "failures": 0,
                  "drained": 0})

    def _admit(self, key: _BucketKey) -> None:
        """Admission control (lock held): at the ``max_depth`` bound,
        shed this submit with OverloadError, or — shed off — dispatch the
        fullest bucket inline (backpressure) until there is room. Depth
        therefore provably never exceeds ``max_depth``."""
        if not self.max_depth:
            return
        while self.pending() >= self.max_depth:
            if self.shed:
                counts = self._bucket_counts(key)
                counts["shed"] += 1
                if obs.metrics_active():
                    obs.counter("dlaf_serve_shed_total", op=key.op,
                                bucket_n=key.n).inc()
                obs.emit_event("resilience", site="serve.queue",
                               event="shed",
                               attrs={"op": key.op, "bucket_n": key.n,
                                      "depth": self.pending(),
                                      "max_depth": self.max_depth})
                # a shed burst is an incident: dump the flight ring
                # (the shed record above is already in it); the
                # recorder's per-reason cooldown means the FIRST shed
                # of a burst dumps and the next thousand do not
                from ..obs import flight
                flight.trigger("overload_shed", op=key.op,
                               bucket_n=key.n, depth=self.pending(),
                               max_depth=self.max_depth)
                raise OverloadError(self.pending(), self.max_depth,
                                    op=key.op, bucket_n=key.n)
            fullest = max((k for k, v in self._pending.items() if v),
                          key=lambda k: len(self._pending[k]),
                          default=None)
            if fullest is None:
                return          # nothing pending: the bound cannot bind
            try:
                self._dispatch(fullest)
            except Exception:
                # the inline dispatch failed for ANOTHER bucket's batch:
                # its tickets already carry the cause (poisoned by
                # _dispatch) and its lanes were popped either way, so
                # room was made — that failure belongs to those tickets,
                # not to THIS submit, which must still be admitted
                pass

    def submit(self, req: Request,
               trace_id: Optional[str] = None) -> Ticket:
        """Enqueue one request; dispatches its bucket immediately when
        the batch fills, and sweeps OTHER buckets' expired deadlines
        (the no-background-thread discipline: submission is the clock
        edge). At the ``max_depth`` admission bound the submit sheds
        (:class:`~dlaf_tpu.health.errors.OverloadError`, no ticket
        created — a shed request is never stranded) or applies
        backpressure, per the ``shed`` knob. ``trace_id`` (optional)
        makes the ticket adopt an existing trace — the fleet worker
        passes its router ticket's ID through so the whole
        cross-process chain joins on one ID."""
        with self._lock:
            now = self.clock()
            key = self._key(req)          # validate BEFORE admission
            self._admit(key)
            if req.rid is None:
                req.rid = next(self._rid)
            ticket = Ticket(req, now, trace_id)
            lanes = self._pending.setdefault(key, [])
            lanes.append((req, ticket))
            self.requests += 1
            if obs.metrics_active():
                obs.counter("dlaf_serve_requests_total", op=req.op).inc()
                obs.gauge("dlaf_serve_depth", op=key.op,
                          bucket_n=key.n).set(float(len(lanes)))
            if len(lanes) >= self.batch:
                self._dispatch(key)
            self.poll(now)
            return ticket

    def poll(self, now: Optional[float] = None) -> int:
        """Dispatch every bucket whose OLDEST pending request has
        exceeded the deadline; returns the number of dispatches."""
        with self._lock:
            now = self.clock() if now is None else now
            n = 0
            for key in [k for k, lanes in self._pending.items()
                        if lanes and now - lanes[0][1].submitted
                        >= self.deadline_s]:
                self._dispatch(key)
                n += 1
            return n

    def flush(self) -> int:
        """Dispatch every pending bucket regardless of fill or deadline
        (shutdown / end-of-stream); returns the number of dispatches."""
        with self._lock:
            n = 0
            for key in [k for k, lanes in self._pending.items() if lanes]:
                self._dispatch(key)
                n += 1
            return n

    def drain(self) -> list:
        """Cancel every UNDISPATCHED pending request (graceful shutdown:
        stop serving without running partial batches nobody will wait
        for) and return the ``(request, ticket)`` pairs, in submission
        order per bucket. The explicit API the fleet worker's drain path
        uses instead of reaching into ``_pending`` (docs/fleet.md) —
        drained requests were never started, so handing them back to the
        router for resubmission elsewhere is always safe.

        Each drained ticket is poisoned with a structured
        :class:`~dlaf_tpu.health.errors.DrainedError` (``result()``
        names the cause instead of claiming "still queued"), counted
        per bucket (``stats()['drained']``,
        ``dlaf_serve_drained_total{op}``), and emits one ``resilience``
        ``drain`` record under the ticket's trace ID — stats, records,
        and metrics stay in exact agreement (pinned in
        tests/test_serve.py)."""
        with self._lock:
            drained = []
            for key in [k for k, lanes in self._pending.items() if lanes]:
                lanes = self._pending.pop(key)
                counts = self._bucket_counts(key)
                if obs.metrics_active():
                    obs.gauge("dlaf_serve_depth", op=key.op,
                              bucket_n=key.n).set(0.0)
                for req, ticket in lanes:
                    ticket.error = DrainedError("serve.queue", req.rid,
                                                op=key.op, bucket_n=key.n)
                    counts["drained"] += 1
                    if obs.metrics_active():
                        obs.counter("dlaf_serve_drained_total",
                                    op=key.op).inc()
                    with obs.trace_context(trace_id=ticket.trace_id):
                        obs.emit_event(
                            "resilience", site="serve.queue", event="drain",
                            attrs={"rid": req.rid, "op": key.op,
                                   "bucket_n": key.n})
                    drained.append((req, ticket))
            return drained

    def pending(self) -> int:
        return sum(len(v) for v in self._pending.values())

    def stats(self) -> dict:
        """Operational snapshot (docs/serving.md; printed by
        scripts/profile_summary.py's serve section): totals — pending
        depth, in-flight dispatch count (nonzero only when read from
        WITHIN the dispatching thread, e.g. service hooks or probes —
        the single submit/poll/flush lock serializes outside readers
        past the dispatch), requests/dispatches, shed/
        expired totals, the ``max_depth``/``shed`` admission config —
        plus a per-bucket table keyed by the bucket program's site label:
        depth, shed, expired, and the bucket breaker's state ("closed" |
        "half_open" | "open"; None = the bucket never dispatched)."""
        with self._lock:
            buckets = {}
            for key in set(self._pending) | set(self._counts):
                counts = self._counts.get(key) or {}
                site = self._spec(key).site
                buckets[site] = {
                    "depth": len(self._pending.get(key, [])),
                    "shed": counts.get("shed", 0),
                    "expired": counts.get("expired", 0),
                    "dispatches": counts.get("dispatches", 0),
                    "failures": counts.get("failures", 0),
                    "drained": counts.get("drained", 0),
                    "breaker": _circuit.peek(site),
                }
            return {
                "pending": self.pending(),
                "in_flight": self._in_flight,
                "requests": self.requests,
                "dispatches": self.dispatches,
                "shed": sum(b["shed"] for b in buckets.values()),
                "expired": sum(b["expired"] for b in buckets.values()),
                "drained": sum(b["drained"] for b in buckets.values()),
                "max_depth": self.max_depth,
                "shed_policy": "shed" if self.shed else "backpressure",
                "buckets": buckets,
            }

    # -- warmup sugar ----------------------------------------------------

    def _spec(self, key: _BucketKey):
        if key.op == "cholesky":
            return cholesky_spec(batch=self.batch, n=key.n,
                                 nb=_default_nb(key.n), dtype=key.dtype,
                                 uplo=key.uplo, with_info=True, donate=True)
        if key.op == "solve":
            return solve_spec(batch=self.batch, n=key.n, nrhs=key.nrhs,
                              nb=_default_nb(key.n), dtype=key.dtype,
                              side=key.side, uplo=key.uplo,
                              transa=key.transa, diag=key.diag,
                              with_info=True, donate=True)
        return eigh_spec(batch=self.batch, n=key.n, nb=_default_nb(key.n),
                         dtype=key.dtype, uplo=key.uplo, with_info=True,
                         donate=True)

    def warmup_specs(self, requests) -> tuple:
        """The exact ProgramSpecs a stream of ``requests`` will dispatch
        through — ``service.warmup(*queue.warmup_specs(sample))`` warms
        precisely the buckets the production stream hits."""
        return tuple({self._spec(self._key(r)): None for r in requests})

    def warmup(self, requests) -> dict:
        return self.service.warmup(*self.warmup_specs(requests))

    # -- dispatch --------------------------------------------------------

    def _dispatch(self, key: _BucketKey) -> None:
        lanes = self._pending.pop(key)
        if obs.metrics_active():
            obs.gauge("dlaf_serve_depth", op=key.op,
                      bucket_n=key.n).set(0.0)
        self._in_flight += 1
        try:
            if self._dispatch_lanes(key, lanes):
                self._bucket_counts(key)["dispatches"] += 1
        except Exception as e:
            self._bucket_counts(key)["failures"] += 1
            # a failed dispatch (compile error, OOM, exhausted retries,
            # open breaker, ...) must not strand its tickets as
            # silently-forever-"queued": poison them with the cause —
            # result() re-raises it — and let the exception reach the
            # submitting caller. Tickets already cancelled (expiry) keep
            # their own, more precise cause.
            for _, ticket in lanes:
                if ticket.error is None and not ticket.done:
                    ticket.error = e
            raise
        finally:
            self._in_flight -= 1

    def _expire_lanes(self, key: _BucketKey, lanes: list, now: float
                      ) -> list:
        """Cancel requests whose queue wait exceeded their deadline (the
        dispatch-composition cancellation point: an expired request must
        not ride a batch whose answer nobody will read); returns the
        still-live lanes."""
        live = []
        for req, ticket in lanes:
            waited = now - ticket.submitted
            if req.deadline_s is not None and waited > req.deadline_s:
                err = DeadlineExceededError("serve.queue", waited,
                                            req.deadline_s)
                ticket.error = err
                self._bucket_counts(key)["expired"] += 1
                if obs.metrics_active():
                    obs.counter("dlaf_deadline_exceeded_total",
                                site="serve.queue").inc()
                with obs.trace_context(trace_id=ticket.trace_id):
                    obs.emit_event(
                        "resilience", site="serve.queue", event="expired",
                        attrs={"rid": req.rid, "op": key.op,
                               "bucket_n": key.n,
                               "waited_s": float(waited),
                               "deadline_s": float(req.deadline_s)})
            else:
                live.append((req, ticket))
        return live

    def _dispatch_lanes(self, key: _BucketKey, lanes: list) -> bool:
        """Returns whether a program actually ran — an all-expired batch
        does not count as a dispatch anywhere (stats, records, metrics
        all stay consistent)."""
        lanes = self._expire_lanes(key, lanes, self.clock())
        if not lanes:
            return False        # everything expired: nothing to run
        reqs = [r for r, _ in lanes]
        tickets = [t for _, t in lanes]
        spec = self._spec(key)
        resident = spec in self.service.specs()
        # batch-scope trace context (ISSUE 13): the dispatch's span_id
        # plus the MEMBER trace-ID list stamp every record emitted below
        # — the dispatch record, the policy engine's retry/breaker
        # records, any program compile the batch triggers — so one
        # request ID finds its whole dispatch by membership
        span_id = obs.new_span_id()
        member_ids = [t.trace_id for t in tickets]
        with obs.trace_context(trace_id=member_ids, span_id=span_id):
            return self._dispatch_traced(key, reqs, tickets, spec,
                                         resident, span_id)

    def _dispatch_traced(self, key: _BucketKey, reqs: list, tickets: list,
                         spec, resident: bool, span_id: str) -> bool:
        t0 = self.clock()
        # assemble the padded batch (host: request shapes are serve-small)
        a_batch = np.stack(
            [_pad_a(r, key.n) for r in reqs]
            + [_pad_lane(key)[0]] * (self.batch - len(reqs)))
        args = [a_batch]
        if key.op == "solve":
            b_batch = np.stack(
                [_pad_b(r, key.n, key.nrhs) for r in reqs]
                + [_pad_lane(key)[1]] * (self.batch - len(reqs)))
            alpha = np.array([np.dtype(key.dtype).type(r.alpha)
                              for r in reqs]
                             + [np.dtype(key.dtype).type(1.0)]
                             * (self.batch - len(reqs)))
            args += [b_batch, alpha]
        t_compose = self.clock()
        # dispatch + compile run under the shared policy engine behind
        # the bucket's circuit breaker: a transient failure (e.g. an
        # inject.fail_dispatch drill, a flaky device link) retries before any
        # ticket is poisoned; consecutive attempt failures open the
        # breaker and later dispatches fail fast (CircuitOpenError)
        breaker = _circuit.breaker(spec.site, clock=self.clock)
        policy = RetryPolicy(max_attempts=self.retry_attempts,
                             backoff_base_s=self.retry_backoff_s)

        def _attempt():
            from ..health import inject

            inject.maybe_fail_dispatch()
            return self.service.run(spec, *args)

        with obs.span("serve.dispatch", op=key.op, bucket_n=key.n,
                      nrhs=key.nrhs, lanes=len(reqs), batch=self.batch,
                      dtype=key.dtype, cache="hit" if resident else "miss"):
            out = with_policy(spec.site, _attempt, policy=policy,
                              breaker=breaker, clock=self.clock)
        t_prog = self.clock()
        dev_outs, infos = _split_outputs(key.op, out)
        # ONE device->host fetch per dispatch, then zero-cost numpy views
        # per ticket: per-lane device slicing would cost a dispatch per
        # request — the exact overhead this layer exists to amortize —
        # and serving results are host-bound by regime. The fetch is also
        # the fence, so the per-request latency records are honest.
        lane_outs = (tuple(np.asarray(o) for o in dev_outs)
                     if isinstance(dev_outs, tuple) else np.asarray(dev_outs))
        t1 = self.clock()
        infos_np = np.asarray(infos) if infos is not None else None
        # unpad every lane BEFORE the dispatch record so the record's
        # stages object covers the whole waterfall the requests ride
        for i, (req, ticket) in enumerate(zip(reqs, tickets)):
            ticket._result = _unpad(req, key, _lane(key.op, lane_outs, i))
            ticket.info = int(infos_np[i]) if infos_np is not None else None
            ticket.queue_s = max(t0 - ticket.submitted, 0.0)
            ticket.total_s = max(t1 - ticket.submitted, 0.0)
            ticket.done = True
        t_unpad = self.clock()
        self.dispatches += 1
        if obs.metrics_active():
            obs.counter("dlaf_serve_dispatch_total", op=key.op).inc()
            obs.histogram("dlaf_serve_dispatch_seconds",
                          op=key.op).observe(t1 - t0)
        obs.emit_event("serve", event="dispatch", op=key.op,
                       bucket_n=key.n, nrhs=key.nrhs, dtype=key.dtype,
                       lanes=len(reqs), batch=self.batch,
                       cache="hit" if resident else "miss",
                       dispatch_s=float(t1 - t0),
                       stages={"compose_s": float(t_compose - t0),
                               "program_s": float(t_prog - t_compose),
                               "fetch_s": float(t1 - t_prog),
                               "unpad_s": float(t_unpad - t1)})
        residuals = self._residuals(key, reqs, args, dev_outs)
        for i, (req, ticket) in enumerate(zip(reqs, tickets)):
            n_req = int(np.asarray(req.a).shape[0])
            attrs = {"rid": req.rid,
                     **({"info": ticket.info}
                        if ticket.info is not None else {})}
            # request-scope trace context: these records carry the ONE
            # member trace ID (overriding the surrounding batch scope)
            # while keeping the dispatch's span_id as the join key
            with obs.trace_context(trace_id=ticket.trace_id,
                                   span_id=span_id):
                obs.emit_event("serve", event="request", op=key.op,
                               n=n_req, bucket_n=key.n, dtype=key.dtype,
                               queue_s=float(ticket.queue_s),
                               total_s=float(ticket.total_s), attrs=attrs)
                # per-request span record (unfenced-wall convention does
                # not apply: total_s ends at the dispatch's host
                # materialization, a real fence) — the request-granular
                # audit trail next to the typed serve record
                obs.emit_event("span", name="serve.request",
                               dur_s=float(ticket.total_s), depth=0,
                               parent=None,
                               attrs={"op": key.op, "n": n_req,
                                      "bucket_n": key.n, **attrs})
                # rolling-window SLO tracking: the histogram records the
                # exemplar trace ID from this request-scoped context
                obs.observe_latency(f"serve.{key.op}", ticket.total_s,
                                    bucket=str(key.n))
                if residuals is not None:
                    metric, c = _ACCURACY[key.op]
                    obs.accuracy.emit(
                        "serve", metric, residuals[i], n=n_req,
                        nb=_default_nb(key.n), c=c,
                        dtype=np.dtype(key.dtype),
                        of=_lane_array(dev_outs),
                        attrs={"op": key.op, "rid": req.rid,
                               "bucket_n": key.n})
        return True

    def _residuals(self, key, reqs, args, lane_outs):
        """Per-real-lane residual vector under DLAF_ACCURACY, else None
        (the hot path computes nothing)."""
        if not obs.accuracy.enabled():
            return None
        shapes = tuple(tuple(np.asarray(a).shape) for a in args)
        prog = _residual_prog(key.op, shapes, key.dtype, key.uplo,
                              key.side, key.transa, key.diag)
        if key.op == "cholesky":
            vals = prog(args[0], lane_outs)
        elif key.op == "solve":
            vals = prog(args[0], args[1], args[2], lane_outs)
        else:
            vals = prog(args[0], lane_outs[0], lane_outs[1])
        return np.asarray(vals)[:len(reqs)]


def _default_nb(n: int) -> int:
    from ..algorithms.batched import default_nb

    return default_nb(n)


def _split_outputs(op: str, out):
    """(lane outputs, info vector or None) from one dispatch result."""
    if op == "eigh":
        if len(out) == 3:
            w, v, info = out
            return (w, v), info
        return out, None
    if isinstance(out, tuple):
        return out[0], out[1]
    return out, None


def _lane(op: str, lane_outs, i: int):
    if op == "eigh":
        return lane_outs[0][i], lane_outs[1][i]
    return lane_outs[i]


def _lane_array(lane_outs):
    """A representative device array for platform/eps attribution."""
    return lane_outs[1] if isinstance(lane_outs, tuple) else lane_outs
