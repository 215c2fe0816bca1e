"""Tests for ISSUE 18: the multi-replica fleet serve tier
(dlaf_tpu.fleet, docs/fleet.md).

Covers: the length-prefixed JSON transport (round-trip, oversize
refusal, idle vs EOF), Request/ProgramSpec wire round-trips, membership
state transitions at injected-clock edges, router fan-out correctness
against numpy, bucket co-location, the SIGKILL failover drill (worker
death -> every unacked ticket re-dispatched, zero loss), the
heartbeat-timeout drill (wedged worker -> suspect + forced-open breaker
-> re-dispatch -> half-open probe re-admission), the seeded
``inject.fail_fleet_dispatch`` drills (transient fault retries into the
SAME worker; sustained fault opens the breaker and re-routes to the
sibling), the warm-sibling retrace pin (re-dispatched bucket lands on a
warm program: retrace counter stays at first-compile), the
failover-disabled must-trip (``ticket_lost`` records + structured
``WorkerLostError`` + ``--require-fleet`` REJECTS), the graceful drain
contract (handback, ZERO re-dispatches), the ``fleet`` record schema +
``require_fleet`` validator obligations, and the aggregated fleet
``/healthz`` view.
"""

import gc
import os
import socket
import sys
import threading
import time
import weakref

import numpy as np
import pytest

import dlaf_tpu.config as C
from dlaf_tpu import health, obs
from dlaf_tpu.fleet import (Router, TransportClosed, TransportIdle,
                            connect_worker, recv_msg, send_msg,
                            worker_site)
from dlaf_tpu.fleet.membership import Membership
from dlaf_tpu.fleet.router import RemoteError, _bucket_of
from dlaf_tpu.health import inject
from dlaf_tpu.health.errors import FleetUnavailableError, WorkerLostError
from dlaf_tpu.obs.sinks import FLEET_EVENTS, validate_records
from dlaf_tpu.serve import (ProgramService, Queue, Request, cholesky_spec,
                            solve_spec)
from dlaf_tpu.serve import programs as serve_programs
from dlaf_tpu.serve.queue import array_from_wire, array_to_wire


@pytest.fixture(autouse=True)
def fleet_reset():
    """Each test leaves the default config, an empty default service,
    and closed breakers behind (mirrors test_serve.serve_reset)."""
    yield
    for key in ("DLAF_METRICS_PATH", "DLAF_PROGRAM_TELEMETRY",
                "DLAF_SERVE_BUCKETS", "DLAF_SERVE_BATCH",
                "DLAF_SERVE_DEADLINE_MS", "DLAF_FLEET_WORKERS",
                "DLAF_FLEET_FAILOVER", "DLAF_FLEET_HEARTBEAT_MS",
                "DLAF_FLEET_HEARTBEAT_TIMEOUT_MS",
                "DLAF_FLEET_RETRY_ATTEMPTS", "DLAF_FLIGHT_RECORDER"):
        os.environ.pop(key, None)
    obs._reset_for_tests()
    obs.telemetry._reset_for_tests()
    serve_programs._reset_for_tests()
    health.circuit.reset()
    C.finalize()
    C.initialize()


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _hpd(n, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n)).astype(dtype)
    return (x @ x.T + n * np.eye(n)).astype(dtype)


def _check_chol(ticket):
    a = np.asarray(ticket.request.a)
    fac = np.tril(ticket.result())
    np.testing.assert_allclose(fac @ fac.T,
                               np.tril(a) + np.tril(a, -1).T,
                               atol=1e-10 * len(a))


class _Fleet:
    """In-process drill fleet: a router with an injected clock + N
    worker protocol loops on daemon threads, each its own Queue over a
    SHARED ProgramService (the in-process stand-in for the shared
    persistent compile cache — docs/fleet.md warm-sibling contract)."""

    def __init__(self, n_workers=2, batch=1, router_kw=None, clock=None,
                 service=None):
        self.clock = clock if clock is not None else _FakeClock()
        self.router = Router(clock=self.clock, port=0,
                             **(router_kw or {}))
        self.service = service if service is not None else ProgramService()
        self.workers = []
        for k in range(n_workers):
            q = Queue(self.service, batch=batch, deadline_s=1e9,
                      buckets=(16,))
            w = connect_worker(self.router.port, k, queue=q,
                               idle_tick_s=0.01)
            threading.Thread(target=w.serve, daemon=True).start()
            self.workers.append(w)
        deadline = time.monotonic() + 10
        while len(self.router.stats()["workers"]) < n_workers:
            assert time.monotonic() < deadline, "workers never connected"
            self.router.poll()
            time.sleep(0.005)

    def close(self):
        self.router.close()


def _fleet_records(path):
    return [r for r in obs.read_records(path) if r.get("type") == "fleet"]


# ---------------------------------------------------------------------------
# Transport framing
# ---------------------------------------------------------------------------

class TestTransport:
    def test_round_trip(self):
        a, b = socket.socketpair()
        try:
            msg = {"kind": "submit", "seq": 7, "req": {"op": "cholesky"},
                   "unicode": "π≤1"}
            send_msg(a, msg)
            assert recv_msg(b) == msg
        finally:
            a.close()
            b.close()

    def test_eof_raises_closed(self):
        a, b = socket.socketpair()
        a.close()
        try:
            with pytest.raises(TransportClosed):
                recv_msg(b)
        finally:
            b.close()

    def test_idle_timeout_raises_idle_between_frames(self):
        a, b = socket.socketpair()
        try:
            b.settimeout(0.01)
            with pytest.raises(TransportIdle):
                recv_msg(b, idle_ok=True)
            # the stream is intact after an idle tick: a frame sent
            # afterwards still parses
            send_msg(a, {"kind": "ping"})
            assert recv_msg(b, idle_ok=True) == {"kind": "ping"}
        finally:
            a.close()
            b.close()

    def test_mid_frame_timeout_keeps_reading(self):
        import struct
        a, b = socket.socketpair()
        try:
            b.settimeout(0.01)
            payload = b'{"kind": "pong"}'
            a.sendall(struct.pack(">I", len(payload)) + payload[:4])

            def finish():
                time.sleep(0.05)       # several idle ticks mid-frame
                a.sendall(payload[4:])

            threading.Thread(target=finish, daemon=True).start()
            assert recv_msg(b, idle_ok=True) == {"kind": "pong"}
        finally:
            a.close()
            b.close()

    def test_oversize_frame_refused_both_ways(self, monkeypatch):
        from dlaf_tpu.fleet import transport
        monkeypatch.setattr(transport, "MAX_FRAME_BYTES", 64)
        a, b = socket.socketpair()
        try:
            with pytest.raises(ValueError, match="frame"):
                transport.send_msg(a, {"blob": "x" * 128})
            # a corrupt/oversize length prefix kills the stream on recv
            import struct
            a.sendall(struct.pack(">I", 1 << 20))
            with pytest.raises(TransportClosed, match="corrupt"):
                transport.recv_msg(b)
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# Wire codecs
# ---------------------------------------------------------------------------

class TestWire:
    def test_request_round_trip(self):
        a = _hpd(12, dtype=np.float32)
        b = np.ones((12, 3))
        req = Request(op="solve", a=a, b=b, uplo="U", side="L",
                      transa="T", diag="N", alpha=2.0, rid="r1",
                      deadline_s=1.5)
        back = Request.from_wire(req.to_wire())
        np.testing.assert_array_equal(np.asarray(back.a), a)
        np.testing.assert_array_equal(np.asarray(back.b), b)
        assert np.asarray(back.a).dtype == np.float32
        assert (back.op, back.uplo, back.side, back.transa, back.diag,
                back.alpha, back.rid, back.deadline_s) == \
            ("solve", "U", "L", "T", "N", 2.0, "r1", 1.5)

    def test_program_spec_round_trip_is_equal(self):
        spec = solve_spec(batch=4, n=16, nrhs=8, nb=8, dtype="float64",
                          side="R", uplo="U")
        assert spec.from_wire(spec.to_wire()) == spec
        assert spec.from_wire(spec.to_wire()).site == spec.site


# ---------------------------------------------------------------------------
# Membership (pure clock-edge state machine)
# ---------------------------------------------------------------------------

class TestMembership:
    def test_lifecycle_and_timeout_edges(self):
        clock = _FakeClock()
        m = Membership(heartbeat_timeout_s=5.0, clock=clock)
        m.add(0, pid=11)
        m.add(1, pid=22)
        assert m.routable() == [0, 1]
        clock.t = 4.9
        assert m.timed_out(clock.t) == []
        clock.t = 5.1
        m.beat(1)                       # 1 is fresh, 0 went silent
        clock.t = 10.0
        assert m.timed_out(clock.t) == [0]
        assert m.state(0) == "suspect"
        assert m.routable() == [0, 1]   # suspect stays ROUTABLE
        assert m.timed_out(clock.t) == []      # flips only once
        m.beat(0)                       # any message re-ups a suspect
        assert m.state(0) == "up"

    def test_dead_and_draining_are_terminal(self):
        clock = _FakeClock()
        m = Membership(heartbeat_timeout_s=5.0, clock=clock)
        m.add(0)
        m.add(1)
        m.mark_dead(0, "eof")
        m.mark_draining(1)
        m.beat(0)
        m.beat(1)
        assert m.state(0) == "dead" and m.state(1) == "draining"
        assert m.routable() == []
        assert m.states()[0]["reason"] == "eof"


# ---------------------------------------------------------------------------
# Router fan-out (the tentpole happy path)
# ---------------------------------------------------------------------------

class TestRouterDispatch:
    def test_fan_out_results_and_bucket_colocation(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        C.initialize(C.Configuration(metrics_path=path))
        fleet = _Fleet(n_workers=2, batch=1)
        try:
            tickets = [fleet.router.submit(
                Request(op="cholesky", a=_hpd(12, seed=i)))
                for i in range(4)]
            assert fleet.router.join(tickets, timeout_s=60)
            for t in tickets:
                _check_chol(t)
                assert t.info == 0 and t.total_s >= 0.0
            # bucket co-location: one bucket -> one worker
            assert len({t.worker for t in tickets}) == 1
            st = fleet.router.stats()
            assert st["unresolved"] == 0 and st["lost"] == 0
        finally:
            fleet.close()
        obs.flush()
        recs = _fleet_records(path)
        ups = [r for r in recs if r["event"] == "worker_up"]
        routes = [r for r in recs if r["event"] == "route"]
        assert len(ups) == 2 and len(routes) == 4
        # ticket-scoped records are trace-stamped and join the request
        assert all(r.get("trace_id") for r in routes)
        assert sorted(r["seq"] for r in routes) == [0, 1, 2, 3]
        assert validate_records(obs.read_records(path),
                                require_fleet=True) == []

    def test_distinct_buckets_spread_across_workers(self):
        fleet = _Fleet(n_workers=2, batch=1)
        try:
            reqs = [Request(op="cholesky", a=_hpd(12)),
                    Request(op="cholesky", a=_hpd(12).astype(np.float32)),
                    Request(op="cholesky", a=_hpd(12), uplo="U"),
                    Request(op="solve", a=_hpd(12),
                            b=np.ones((12, 2)))]
            assert len({_bucket_of(r) for r in reqs}) == 4
            tickets = [fleet.router.submit(r) for r in reqs]
            assert fleet.router.join(tickets, timeout_s=60)
            assert len({t.worker for t in tickets}) == 2
        finally:
            fleet.close()

    def test_no_workers_fails_fast_and_keeps_nothing(self):
        router = Router(clock=_FakeClock(), port=0)
        try:
            with pytest.raises(FleetUnavailableError):
                router.submit(Request(op="cholesky", a=_hpd(12)))
            assert router.stats()["unresolved"] == 0
        finally:
            router.close()

    def test_worker_acked_failure_is_terminal_remote_error(self):
        """A worker that PROCESSED a request and acked a structured
        failure is final — at-least-once covers lost tickets only."""
        clock = _FakeClock()
        router = Router(clock=clock, port=0)
        try:
            stub = socket.create_connection(("127.0.0.1", router.port))
            stub.settimeout(5.0)
            send_msg(stub, {"kind": "hello", "worker": 0, "pid": 1})
            deadline = time.monotonic() + 10
            while not router.stats()["workers"]:
                assert time.monotonic() < deadline
                router.poll()
                time.sleep(0.005)
            t = router.submit(Request(op="cholesky", a=_hpd(12)))
            msg = recv_msg(stub)
            assert msg["kind"] == "submit" and msg["seq"] == t.seq
            send_msg(stub, {"kind": "result", "seq": t.seq, "ok": False,
                            "worker": 0,
                            "error": {"type": "OverloadError",
                                      "message": "queue full"}})
            assert router.join([t], timeout_s=10)
            with pytest.raises(RuntimeError, match="request failed"):
                t.result()
            assert isinstance(t.error, RemoteError)
            assert t.error.etype == "OverloadError"
            st = router.stats()
            assert st["redispatches"] == 0 and st["lost"] == 0
            stub.close()
        finally:
            router.close()


# ---------------------------------------------------------------------------
# Failover drills (SIGKILL stand-in + heartbeat timeout)
# ---------------------------------------------------------------------------

class TestFailover:
    def test_worker_kill_redispatches_every_unacked_ticket(self, tmp_path):
        """The replica-kill drill: a worker dies holding a full batch of
        unacknowledged tickets; every one re-dispatches to the sibling
        and completes — zero loss, and the artifact proves it."""
        path = str(tmp_path / "m.jsonl")
        C.initialize(C.Configuration(metrics_path=path))
        # batch=8 >> submits: tickets sit undispatched (unacked) in the
        # victim until the kill
        fleet = _Fleet(n_workers=2, batch=8)
        try:
            tickets = [fleet.router.submit(
                Request(op="cholesky", a=_hpd(12, seed=i)))
                for i in range(3)]
            victim = tickets[0].worker
            fleet.workers[victim].kill()          # SIGKILL stand-in
            deadline = time.monotonic() + 10
            while fleet.router.stats()["workers"][victim]["state"] \
                    != "dead":
                assert time.monotonic() < deadline
                fleet.router.poll()
                time.sleep(0.005)
            fleet.router.flush()
            assert fleet.router.join(tickets, timeout_s=60)
            sibling = 1 - victim
            for t in tickets:
                _check_chol(t)
                assert t.worker == sibling and t.redispatched == 1
                assert t.attempts == [victim, sibling]
            st = fleet.router.stats()
            assert st["redispatches"] == 3 and st["lost"] == 0
        finally:
            fleet.close()
        obs.flush()
        recs = _fleet_records(path)
        dead = [r for r in recs if r["event"] == "worker_dead"]
        redis = [r for r in recs if r["event"] == "redispatch"]
        assert len(dead) == 1 and dead[0]["attrs"]["reason"] == "eof"
        assert len(redis) == 3
        assert all(r["attrs"]["from"] == victim for r in redis)
        # a re-dispatch is joinable to its original route by trace_id
        routes = {r["trace_id"]: r for r in recs if r["event"] == "route"}
        assert all(r["trace_id"] in routes for r in redis)
        assert validate_records(obs.read_records(path),
                                require_fleet=True) == []

    def test_worker_death_trips_the_flight_recorder(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        C.initialize(C.Configuration(metrics_path=path,
                                     flight_recorder=64))
        dump = path + ".flight.jsonl"
        fleet = _Fleet(n_workers=2, batch=8)
        try:
            t = fleet.router.submit(Request(op="cholesky", a=_hpd(12)))
            fleet.workers[t.worker].kill()
            deadline = time.monotonic() + 10
            while not os.path.exists(dump):
                assert time.monotonic() < deadline
                fleet.router.poll()
                time.sleep(0.005)
            recs = obs.read_records(dump)
            trig = [r for r in recs if r.get("type") == "flight_trigger"]
            assert trig and trig[-1]["reason"] == "fleet_worker_down"
            assert trig[-1]["attrs"]["unacked"] == 1
            assert trig[-1]["attrs"]["failover"] is True
            assert validate_records(recs, require_flight=True) == []
        finally:
            fleet.close()

    def test_heartbeat_timeout_suspects_reroutes_and_readmits(self):
        """The wedged-worker drill, fully deterministic under the
        injected clock: a silent worker flips suspect, its breaker is
        forced open, its unacked ticket re-dispatches to the sibling;
        after the cooldown the NEXT dispatch probes it half-open and a
        successful ACK closes the breaker (re-admission)."""
        clock = _FakeClock()
        router = Router(clock=clock, port=0, heartbeat_s=1.0,
                        heartbeat_timeout_s=5.0)
        wedged = socket.create_connection(("127.0.0.1", router.port))
        wedged.settimeout(10.0)
        send_msg(wedged, {"kind": "hello", "worker": 0, "pid": 1})
        deadline = time.monotonic() + 10
        while not router.stats()["workers"]:
            assert time.monotonic() < deadline
            router.poll()
            time.sleep(0.005)
        try:
            # the only worker: the ticket lands on the wedge and is
            # never acked
            t1 = router.submit(Request(op="cholesky", a=_hpd(12)))
            assert t1.worker == 0
            assert recv_msg(wedged)["kind"] == "submit"
            # bring up a live sibling, then advance past the timeout
            fleet_q = Queue(ProgramService(), batch=1, deadline_s=1e9,
                            buckets=(16,))
            w1 = connect_worker(router.port, 1, queue=fleet_q,
                                idle_tick_s=0.01)
            threading.Thread(target=w1.serve, daemon=True).start()
            deadline = time.monotonic() + 10
            while len(router.stats()["workers"]) < 2:
                assert time.monotonic() < deadline
                router.poll()
                time.sleep(0.005)
            # a ping edge at t=1.5: the live sibling pongs (fresh beat),
            # the wedge stays silent — so only IT times out at t=6
            clock.t = 1.5
            router.poll()
            deadline = time.monotonic() + 10
            while router.stats()["workers"][1]["last_seen"] < 1.5:
                assert time.monotonic() < deadline, "sibling never ponged"
                router.poll()
                time.sleep(0.005)
            clock.t = 6.0
            router.poll()
            st = router.stats()
            assert st["workers"][0]["state"] == "suspect"
            assert st["workers"][1]["state"] == "up"
            assert st["breakers"][0] == "open"
            assert router.join([t1], timeout_s=60)
            _check_chol(t1)
            assert t1.worker == 1 and t1.redispatched == 1
            # cooldown elapsed: the next same-bucket dispatch is the
            # half-open probe back into worker 0 IF selection prefers it;
            # force preference by draining the sibling first
            router._send(1, {"kind": "drain"})
            deadline = time.monotonic() + 10
            while router.stats()["workers"][1]["state"] != "dead":
                assert time.monotonic() < deadline
                router.poll()
                time.sleep(0.005)
            clock.t = 6.0 + 31.0        # default cooldown 30s
            t2 = router.submit(Request(op="cholesky", a=_hpd(12, seed=9)))
            assert t2.worker == 0
            assert router.stats()["breakers"][0] == "half_open"
            msg = recv_msg(wedged)
            while msg["kind"] != "submit":
                msg = recv_msg(wedged)
            assert msg["seq"] == t2.seq
            # the wedge recovers: its ACK closes the breaker and re-ups
            # the suspect
            send_msg(wedged, {"kind": "result", "seq": t2.seq, "ok": True,
                              "worker": 0,
                              "arrays": [array_to_wire(np.eye(12))],
                              "info": 0, "queue_s": 0.0, "total_s": 0.0})
            assert router.join([t2], timeout_s=10)
            st = router.stats()
            assert st["breakers"][0] == "closed"
            assert st["workers"][0]["state"] == "up"
        finally:
            wedged.close()
            router.close()

    def test_failover_disabled_loses_loudly_and_validator_rejects(
            self, tmp_path):
        """The must-trip leg: with DLAF_FLEET_FAILOVER=0 a worker death
        poisons its unacked tickets with structured WorkerLostError and
        ``ticket_lost`` records — and ``require_fleet`` REJECTS the
        artifact."""
        path = str(tmp_path / "m.jsonl")
        C.initialize(C.Configuration(metrics_path=path))
        fleet = _Fleet(n_workers=2, batch=8,
                       router_kw={"failover": False})
        try:
            tickets = [fleet.router.submit(
                Request(op="cholesky", a=_hpd(12, seed=i)))
                for i in range(2)]
            victim = tickets[0].worker
            fleet.workers[victim].kill()
            assert fleet.router.join(tickets, timeout_s=30)
            for t in tickets:
                with pytest.raises(RuntimeError) as ei:
                    t.result()
                assert isinstance(ei.value.__cause__, WorkerLostError)
            st = fleet.router.stats()
            assert st["lost"] == 2 and st["redispatches"] == 0
        finally:
            fleet.close()
        obs.flush()
        recs = obs.read_records(path)
        lost = [r for r in recs if r.get("type") == "fleet"
                and r["event"] == "ticket_lost"]
        assert len(lost) == 2
        assert all(r["attrs"]["reason"] == "eof" for r in lost)
        errors = validate_records(recs, require_fleet=True)
        assert any("ticket_lost" in e for e in errors), errors
        # the same artifact passes WITHOUT the fleet obligation: the
        # schema itself is valid — only the zero-loss contract is broken
        assert validate_records(recs) == []


# ---------------------------------------------------------------------------
# Seeded dispatch-fault drills (inject.fail_fleet_dispatch)
# ---------------------------------------------------------------------------

class TestInjectedDispatchFaults:
    def test_transient_fault_retries_into_the_same_worker(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        C.initialize(C.Configuration(metrics_path=path))
        fleet = _Fleet(n_workers=2, batch=1)
        try:
            # learn the bucket's preferred worker with no fault injected
            t0 = fleet.router.submit(Request(op="cholesky", a=_hpd(12)))
            assert fleet.router.join([t0], timeout_s=60)
            preferred = t0.worker
            with inject.fail_fleet_dispatch(nth=0, count=1):
                t1 = fleet.router.submit(
                    Request(op="cholesky", a=_hpd(12, seed=5)))
            # one transient fault: attempt 2 lands on the SAME worker
            # (breaker threshold 3 keeps it admitted)
            assert t1.worker == preferred
            assert fleet.router.join([t1], timeout_s=60)
            _check_chol(t1)
        finally:
            fleet.close()
        obs.flush()
        recs = obs.read_records(path)
        retries = [r for r in recs if r.get("type") == "resilience"
                   and r["event"] == "retry"
                   and r["site"] == "fleet.dispatch"]
        assert len(retries) == 1

    def test_sustained_fault_opens_the_breaker_and_reroutes(
            self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        C.initialize(C.Configuration(metrics_path=path))
        fleet = _Fleet(n_workers=2, batch=1)
        try:
            t0 = fleet.router.submit(Request(op="cholesky", a=_hpd(12)))
            assert fleet.router.join([t0], timeout_s=60)
            preferred = t0.worker
            # 3 consecutive faults = the default breaker threshold: the
            # preferred worker's breaker opens mid-policy and attempt 4
            # re-routes to the sibling
            with inject.fail_fleet_dispatch(nth=0, count=3):
                t1 = fleet.router.submit(
                    Request(op="cholesky", a=_hpd(12, seed=5)))
                assert t1.worker == 1 - preferred
                assert fleet.router.stats()["breakers"][preferred] \
                    == "open"
            assert fleet.router.join([t1], timeout_s=60)
            _check_chol(t1)
        finally:
            fleet.close()

    def test_redispatched_bucket_reuses_the_siblings_warm_program(
            self, tmp_path):
        """The warm-failover pin (docs/fleet.md): after both workers are
        warm on a bucket, a kill-and-redispatch must NOT recompile —
        dlaf_retrace_total for the bucket's program site stays at its
        first-compile value (1), i.e. retrace <= 1 per bucket per
        worker over the whole drill."""
        path = str(tmp_path / "m.jsonl")
        C.initialize(C.Configuration(metrics_path=path,
                                     program_telemetry=True))
        fleet = _Fleet(n_workers=2, batch=2)
        try:
            spec = cholesky_spec(batch=2, n=16, nb=16, dtype="float64")
            walls = fleet.router.warmup([spec], timeout_s=300.0)
            assert sorted(walls) == [0, 1]
            site = spec.site
            warm = obs.registry().counter("dlaf_retrace_total",
                                          site=site).snapshot()["value"]
            assert warm == 1        # shared service: ONE compile total
            tickets = [fleet.router.submit(
                Request(op="cholesky", a=_hpd(16, seed=i)))
                for i in range(2)]
            victim = tickets[0].worker
            fleet.workers[victim].kill()
            deadline = time.monotonic() + 10
            while fleet.router.stats()["workers"][victim]["state"] \
                    != "dead":
                assert time.monotonic() < deadline
                fleet.router.poll()
                time.sleep(0.005)
            fleet.router.flush()
            assert fleet.router.join(tickets, timeout_s=60)
            for t in tickets:
                _check_chol(t)
            after = obs.registry().counter("dlaf_retrace_total",
                                           site=site).snapshot()["value"]
            assert after == warm, (warm, after)
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# Graceful drain (SIGTERM twin)
# ---------------------------------------------------------------------------

class TestGracefulDrain:
    def test_drain_hands_back_undispatched_with_zero_redispatches(
            self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        C.initialize(C.Configuration(metrics_path=path))
        fleet = _Fleet(n_workers=2, batch=8)
        try:
            tickets = [fleet.router.submit(
                Request(op="cholesky", a=_hpd(12, seed=i)))
                for i in range(3)]
            victim = tickets[0].worker
            fleet.workers[victim].request_drain()  # SIGTERM stand-in
            deadline = time.monotonic() + 15
            while fleet.router.stats()["workers"][victim]["state"] \
                    != "dead":
                assert time.monotonic() < deadline
                fleet.router.poll()
                time.sleep(0.005)
            fleet.router.flush()
            assert fleet.router.join(tickets, timeout_s=60)
            sibling = 1 - victim
            for t in tickets:
                _check_chol(t)
                assert t.worker == sibling
                assert t.redispatched == 0     # handback, NOT failover
            st = fleet.router.stats()
            assert st["handbacks"] == 3 and st["redispatches"] == 0
            assert st["lost"] == 0
            assert st["workers"][victim]["reason"] == "drained"
        finally:
            fleet.close()
        obs.flush()
        recs = _fleet_records(path)
        events = [r["event"] for r in recs]
        assert events.count("handback") == 3
        assert events.count("redispatch") == 0
        assert events.count("draining") == 1
        assert events.count("drained") == 1
        dead = [r for r in recs if r["event"] == "worker_dead"]
        assert [r["attrs"]["reason"] for r in dead] == ["drained"]
        # graceful death does NOT demand a redispatch record
        assert validate_records(obs.read_records(path),
                                require_fleet=True) == []


# ---------------------------------------------------------------------------
# Record schema + require_fleet obligations
# ---------------------------------------------------------------------------

def _rec(**over):
    base = {"type": "fleet", "v": 1, "ts": 1.0, "event": "route",
            "worker": 0, "seq": 3, "trace_id": "ab12" * 8, "attrs": {}}
    base.update(over)
    return base


def _membership_rec(**over):
    rec = _rec(**over)
    del rec["seq"], rec["trace_id"]
    return rec


class TestSchemaAndValidator:
    def test_valid_records_pass(self):
        ticket_scoped = ("route", "redispatch", "handback", "ticket_lost")
        recs = [_rec(event=e) if e in ticket_scoped
                else _membership_rec(event=e) for e in FLEET_EVENTS]
        assert validate_records(recs) == []

    @pytest.mark.parametrize("over,msg", [
        ({"event": "teleport"}, "fleet event"),
        ({"worker": None}, "worker"),
        ({"worker": -1}, "worker"),
        ({"worker": True}, "worker"),
        ({"seq": None}, "seq"),
        ({"seq": -2}, "seq"),
        ({"trace_id": None}, "trace-stamped"),
        ({"attrs": "x"}, "attrs"),
    ])
    def test_schema_rejections(self, over, msg):
        errors = validate_records([_rec(**over)])
        assert errors and msg in errors[0], errors

    def test_require_fleet_needs_a_route(self):
        errors = validate_records([_membership_rec(event="worker_up")],
                                  require_fleet=True)
        assert any("no fleet route" in e for e in errors), errors

    def test_require_fleet_rejects_any_ticket_lost(self):
        recs = [_rec(), _rec(event="ticket_lost", seq=4)]
        errors = validate_records(recs, require_fleet=True)
        assert any("ticket_lost" in e for e in errors), errors

    def test_require_fleet_demands_failover_after_ungraceful_death(self):
        dead = _membership_rec(event="worker_dead",
                               attrs={"reason": "eof"})
        errors = validate_records([_rec(), dead], require_fleet=True)
        assert any("failover never ran" in e for e in errors), errors
        # answered by a redispatch -> clean
        recs = [_rec(), dead, _rec(event="redispatch", seq=5)]
        assert validate_records(recs, require_fleet=True) == []
        # a DRAINED death demands nothing
        drained = _membership_rec(event="worker_dead",
                                  attrs={"reason": "drained"})
        assert validate_records([_rec(), drained],
                                require_fleet=True) == []

    def test_validate_cli_flag(self, tmp_path):
        from dlaf_tpu.obs import validate as vcli
        good = tmp_path / "good.jsonl"
        import json as _json
        good.write_text(_json.dumps(_rec()) + "\n")
        assert vcli.main([str(good), "--require-fleet"]) == 0
        bad = tmp_path / "bad.jsonl"
        lost = _rec(event="ticket_lost", seq=4)
        bad.write_text(_json.dumps(_rec()) + "\n"
                       + _json.dumps(lost) + "\n")
        assert vcli.main([str(bad), "--require-fleet"]) == 1
        assert vcli.main([str(bad)]) == 0


# ---------------------------------------------------------------------------
# Aggregated health
# ---------------------------------------------------------------------------

class TestFleetHealth:
    def test_healthz_aggregates_worker_payloads(self):
        fleet = _Fleet(n_workers=2, batch=1)
        try:
            view = fleet.router.healthz(timeout_s=30.0)
            assert view["status"] == "ok"
            assert sorted(view["workers"]) == [0, 1]
            for payload in view["workers"].values():
                assert payload["status"] == "ok"
                assert "queues" in payload and "breakers" in payload
            assert view["fleet"]["lost"] == 0
        finally:
            fleet.close()

    def test_router_lands_on_the_exporter_healthz(self):
        fleet = _Fleet(n_workers=1, batch=1)
        try:
            payload = obs.exporter.healthz_payload()
            assert "fleet" in payload
            # [-1]: the most recently registered router (earlier tests'
            # closed routers may not be collected yet)
            assert payload["fleet"][-1]["workers"][0]["state"] == "up"
        finally:
            fleet.close()

    def test_degraded_when_a_worker_is_dead(self):
        fleet = _Fleet(n_workers=2, batch=1)
        try:
            fleet.workers[0].kill()
            deadline = time.monotonic() + 10
            while fleet.router.stats()["workers"][0]["state"] != "dead":
                assert time.monotonic() < deadline
                fleet.router.poll()
                time.sleep(0.005)
            view = fleet.router.healthz(timeout_s=10.0)
            assert view["status"] == "degraded"
        finally:
            fleet.close()

    def test_close_releases_worker_threads_and_healthz_queues(self):
        """Regression: ``Router.close()`` must shutdown() its sockets,
        not just close() them — the reader threads' blocked recv holds
        the open file description, so a bare close() never sends FIN:
        the accept loop, the readers, and every in-process worker loop
        (and therefore its /healthz-registered Queue) leaked forever."""
        before = {t.ident for t in threading.enumerate()}
        fleet = _Fleet(n_workers=2, batch=1)
        queue_refs = [weakref.ref(w.queue) for w in fleet.workers]
        fleet.close()
        deadline = time.monotonic() + 10
        while True:
            leaked = [t for t in threading.enumerate()
                      if t.ident not in before and t.is_alive()]
            if not leaked:
                break
            assert time.monotonic() < deadline, \
                f"fleet threads leaked past close(): {leaked}"
            time.sleep(0.01)
        del fleet
        gc.collect()
        assert [r() for r in queue_refs] == [None, None], \
            "closed fleet's worker queues still reachable (would pin " \
            "dead queues onto /healthz)"
