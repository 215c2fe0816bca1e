#!/usr/bin/env python
"""Driver benchmark: prints ONE JSON line with the headline metric.

Headline config (BASELINE.json #1): miniapp_cholesky, double, N=4096, nb=256,
1x1 local grid, using the reference's fenced-timing protocol and flop model
(``miniapp/miniapp_cholesky.cpp:123-164``): GFLOPS = total_ops(n^3/6, n^3/6)/t.

No absolute baseline exists (the reference publishes no numbers), so ``vs_baseline`` is 1.0 for the first recorded round.

Device contract: this benchmark measures the chip. The platform comes from
JAX's own rules; a run whose platform is not ``tpu`` fails, unless the
caller set ``JAX_PLATFORMS=cpu`` itself (CI's explicit CPU arms, labelled
``[cpu]``). Nothing is probed, retried, re-executed on another platform or
replayed from a history file: every number printed was measured by this run.

One process owns a chip at a time. The parent therefore never creates a JAX
backend; every arm runs in its OWN child process (which also bounds a
pathological compile by a wall-clock timeout without losing the arms that
already landed), one child at a time. An arm whose child exits non-zero
makes the sweep exit non-zero.

All progress goes to stderr; stdout carries exactly one JSON line.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

#: wall-clock cap per variant subprocess: device init + compile (minutes
#: cold, seconds warm via the persistent cache) + 5 timed runs
VARIANT_TIMEOUT_S = int(os.environ.get("DLAF_BENCH_VARIANT_TIMEOUT", "900"))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def expected_platform() -> str:
    """The platform this run has to measure on: the chip, unless the
    caller asked for the CPU in JAX's own spelling. Reads the environment
    only — the sweep's parent never creates a backend."""
    asked = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    return "cpu" if asked == "cpu" else "tpu"


def require_platform() -> str:
    """Child: bring JAX up and insist on :func:`expected_platform`."""
    import jax

    got, want = jax.devices()[0].platform, expected_platform()
    if got != want:
        log(f"bench: JAX came up on {got!r} but this run measures {want!r}"
            + ("" if want == "cpu" else
               " (set JAX_PLATFORMS=cpu yourself for a labelled CPU run)")
            + "; nothing was measured")
        sys.exit(3)
    return got


#: Arms that start several worker processes which each bring up JAX: on one
#: chip they cannot all own the device, so they run on the CPU only.
MULTIPROCESS_ARMS = ("fleet",)


#: eigensolver-pipeline stage arms (ISSUE 6): A/B the level-batched D&C
#: ("tridiag" vs "tridiag+dcb1") and the pipelined reflector-block
#: back-transform ("btr2b" vs "btr2b+btla1"), plus the chase
#: back-transform ("btb2t", its blocked/sweeps A/B rides the existing
#: bt_b2t_impl knob). Plain arms pin their knob to 0 via env so TPU
#: "auto" cannot blur the A/B; results carry a "workload" field so they
#: never take the cholesky headline. The mfu table's stage rows read
#: these labels (scripts/mfu_table.py _FAMILIES).
#: "fpanel" (ISSUE 10): the fused-Pallas-panel A/B arm — an f32 local
#: cholesky pair ("fpanel" pins DLAF_PANEL_IMPL=xla via env so the TPU
#: auto can't blur the comparison, "fpanel+fp1" pins fused; same
#: discipline as the "+la1"/comm arms). Sized off-TPU via
#: DLAF_BENCH_FPANEL_N (the fused kernels run in interpret mode there).
#: "fstep" (ISSUE 19): the fused-STEP A/B arm — the same f32 local
#: cholesky pair with "fstep" pinning DLAF_STEP_IMPL=xla (composed
#: per-op chain) and "fstep+fs1" pinning the one-pallas_call-per-step
#: fused kernel (docs/pallas_panel.md "Fused step kernel"); paired
#: accuracy records ride both arms, and bench_gate holds the pair's
#: presence as a must-trip leg. Sized off-TPU via DLAF_BENCH_FSTEP_N.
#: "serve" (ISSUE 11): the batched serving-layer arm — requests/s and
#: p99 latency of a seeded mixed-shape request stream through
#: serve.Queue over a WARM bucket set, vs a loop of singleton cholesky()
#: calls over the identical problems; results carry workload="serve"
#: (requests/s in the gflops slot, p99 seconds in t — a different
#: metric, so the cholesky headline must never pick it up) plus the
#: batched-vs-singles "speedup" field scripts/bench_gate.py holds to
#: the >= 3x ISSUE-11 floor. Sized via DLAF_BENCH_SERVE_N /
#: DLAF_BENCH_SERVE_REQS.
#: "overload" (ISSUE 12, docs/robustness.md): the overload-protection
#: arm — a burst stream at 2x the queue's DLAF_SERVE_MAX_DEPTH admission
#: bound; records accepted requests/s (gflops slot), p99 latency of the
#: ACCEPTED requests (t slot), shed rate, and the maximum pending depth
#: observed — asserting in-arm that depth never exceeded the bound and
#: no accepted ticket was stranded. workload="overload" keeps it out of
#: every headline. Sized via DLAF_BENCH_SERVE_N / DLAF_BENCH_OVERLOAD_DEPTH.
#: "fleet" (ISSUE 18, docs/fleet.md): the multi-replica serve-tier arm —
#: the same seeded mixed-bucket stream through a fleet Router over ONE
#: real subprocess replica vs DLAF_BENCH_FLEET_WORKERS replicas; the
#: N-vs-1 requests/s ratio rides as the "speedup" field
#: scripts/bench_gate.py holds to the history-free --min-fleet-scaling
#: floor, and a mid-stream SIGKILL leg reports the zero-loss failover
#: cost as "recovery_s". workload="fleet" keeps every number out of the
#: headlines. Sized via DLAF_BENCH_FLEET_N / DLAF_BENCH_FLEET_REQS.
STAGE_BASES = ("tridiag", "btr2b", "btb2t", "fpanel", "fstep", "serve",
               "overload", "fleet")


def _run_fpanel_variant(variant: str, platform: str,
                        workload: str = "fpanel") -> None:
    """Measure one fused-panel ("fpanel", ISSUE 10) or fused-step
    ("fstep", ISSUE 19) A/B arm (f32 local cholesky; the knob was
    pinned by the caller): same artifact/stdout protocol as the other
    arms, a dedicated ``workload`` label so the cholesky headline (a
    different dtype + flop tier) never picks it up. Off-TPU the fused
    route runs the kernels in interpret mode — tiny N keeps that inside
    the sweep budget while still exercising the full routed program."""
    import dlaf_tpu.config as config
    from dlaf_tpu.algorithms.cholesky import cholesky
    from dlaf_tpu.common.index2d import GlobalElementSize, TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix
    from dlaf_tpu.miniapp.generators import hpd_element_fn
    from dlaf_tpu.types import total_ops

    n = int(os.environ.get(f"DLAF_BENCH_{workload.upper()}_N") or
            (os.environ.get("DLAF_BENCH_N", "4096")
             if platform == "tpu" else "256"))
    nb = min(int(os.environ.get("DLAF_BENCH_NB", "256")),
             max(n // 4, 32))    # keep a real multi-step panel chain
    cfg = config.get_configuration()
    log(f"[{variant}] fused-{'step' if workload == 'fstep' else 'panel'} "
        f"arm on {platform}: n={n} nb={nb} "
        f"panel_impl={cfg.panel_impl} step_impl={cfg.step_impl}")
    ref = Matrix.from_element_fn(hpd_element_fn(n, np.float32),
                                 GlobalElementSize(n, n),
                                 TileElementSize(nb, nb), dtype=np.float32)
    flops = total_ops(np.float32, n**3 / 6, n**3 / 6)

    def measure():
        mat = ref.with_storage(ref.storage + 0)
        return cholesky("L", mat, donate=True).storage

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    from measure_common import append_history, best_time

    best_t, last = best_time(measure, reps=3, return_last=True)
    best_g = flops / best_t / 1e9
    log(f"[{variant}] best of 3: {best_t:.4f}s {best_g:.1f} GFlop/s")
    line = append_history(platform, n, nb, best_g, best_t,
                          source="bench.py", variant=variant,
                          dtype="float32", donate=True, workload=workload)
    from dlaf_tpu import obs
    from dlaf_tpu.obs import accuracy

    if accuracy.enabled():
        # paired accuracy record like every timed arm (docs/accuracy.md):
        # a wrong fused-kernel ladder shows up as a bound_ratio jump
        # right next to its GFlop/s number
        out = ref.with_storage(last)
        value = accuracy.cholesky_residual("L", ref, out)
        accuracy.emit("bench", "cholesky_residual", value, n=n, nb=nb,
                      c=60.0, dtype=np.float32, of=last,
                      attrs={"variant": variant})
    obs.emit_event("bench_result", payload=line)
    obs.flush()
    print(json.dumps(line), flush=True)


def _run_serve_variant(variant: str, platform: str) -> None:
    """Measure the serving layer (ISSUE 11, docs/serving.md): a seeded
    mixed-shape stream of Cholesky requests (a) end-to-end through a
    WARM serve.Queue — requests/s in the ``gflops`` history slot, p99
    latency seconds in ``t``; workload="serve" keeps both out of every
    cholesky lookup — and (b) as the ISSUE-11 acceptance ratio: the
    ``cholesky_batched`` entry over the warm bucket program vs a loop of
    singleton ``cholesky()`` calls over the identical problems at the
    same accuracy budget (per-request accuracy records land in this
    child's artifact under DLAF_ACCURACY=1). The entry/singles ratio is
    the ``speedup`` field scripts/bench_gate.py enforces >= 3x; the
    queue's own end-to-end ratio rides as ``queue_speedup``."""
    import dlaf_tpu.config as config
    from dlaf_tpu.algorithms.cholesky import cholesky
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.common.sync import hard_fence
    from dlaf_tpu.matrix.matrix import Matrix
    from dlaf_tpu.obs import quantile
    from dlaf_tpu.serve import Queue, Request, get_service

    bn = int(os.environ.get("DLAF_BENCH_SERVE_N", "64"))
    n_reqs = int(os.environ.get("DLAF_BENCH_SERVE_REQS", "64"))
    batch = config.get_configuration().serve_batch
    rng = np.random.default_rng(bn * 1000 + n_reqs)
    # mixed shapes in the bucket's upper half: real padding traffic, one
    # warm bucket program (the steady-state regime the arm certifies)
    shapes = rng.integers(bn // 2 + 1, bn + 1, size=n_reqs)
    problems = []
    for n in shapes:
        x = rng.standard_normal((n, n))
        problems.append(x @ x.T + n * np.eye(n))
    reqs = [Request(op="cholesky", a=a) for a in problems]
    q = Queue(buckets=(bn,))
    q.warmup(reqs)
    log(f"[{variant}] serve arm on {platform}: bucket={bn} batch={batch} "
        f"requests={n_reqs} (warm: {len(q.service.specs())} programs)")

    def serve_pass():
        tickets = [q.submit(Request(op="cholesky", a=a)) for a in problems]
        q.flush()
        hard_fence(*[t.result() for t in tickets])
        return tickets

    best_t, p99 = float("inf"), float("nan")
    for i in range(3):
        t0 = time.perf_counter()
        tickets = serve_pass()
        t = time.perf_counter() - t0
        # p99 via the shared windowed-quantile estimator's computation
        # (obs.quantile is pinned bit-identical to np.percentile): the
        # SLO gauges, the aggregate request tables, and this arm report
        # THE SAME number for the same latencies (ISSUE 13 satellite)
        lat = [tk.total_s for tk in tickets]
        log(f"[{variant}] queue pass {i}: {t:.4f}s "
            f"{n_reqs / t:.1f} req/s p99 {quantile(lat, 0.99):.4f}s")
        if t < best_t:
            best_t, p99 = t, float(quantile(lat, 0.99))
    rps = n_reqs / best_t

    # the ISSUE-11 acceptance ratio: cholesky_batched (the batched ENTRY
    # over the warm bucket program) vs a loop of singleton cholesky()
    # calls over the identical problems — the queue's end-to-end
    # requests/s above additionally carries padding assembly and the
    # per-request record trail, reported separately
    from dlaf_tpu.serve import cholesky_batched

    padded = []
    for i in range(0, n_reqs, batch):
        chunk = problems[i:i + batch]
        ab = np.broadcast_to(np.eye(bn), (batch, bn, bn)).copy()
        for j, a in enumerate(chunk):
            ab[j, :len(a), :len(a)] = a
        padded.append(ab)
    hard_fence(*cholesky_batched("L", padded[0], with_info=True))   # warm
    best_tb = float("inf")
    for i in range(3):
        t0 = time.perf_counter()
        for ab in padded:
            hard_fence(*cholesky_batched("L", ab, with_info=True))
        t = time.perf_counter() - t0
        log(f"[{variant}] batched-entry pass {i}: {t:.4f}s "
            f"{n_reqs / t:.1f} req/s")
        best_tb = min(best_tb, t)
    rps_batched = n_reqs / best_tb

    # the singles comparator: the public singleton entry over the SAME
    # problems, warmed first (both sides judged warm — the serving claim
    # is about dispatch amortization, not about compile walls)
    mats = [Matrix.from_global(a, TileElementSize(len(a), len(a)))
            for a in problems]

    def singles_pass():
        outs = [cholesky("L", m.with_storage(m.storage + 0), donate=True)
                for m in mats]
        hard_fence(*[o.storage for o in outs])

    singles_pass()                       # warm every distinct shape
    best_ts = float("inf")
    for i in range(3):
        t0 = time.perf_counter()
        singles_pass()
        t = time.perf_counter() - t0
        log(f"[{variant}] singles pass {i}: {t:.4f}s "
            f"{n_reqs / t:.1f} req/s")
        best_ts = min(best_ts, t)
    rps_singles = n_reqs / best_ts
    speedup = rps_batched / rps_singles
    st = get_service().stats()
    log(f"[{variant}] queue {rps:.1f} req/s (p99 {p99:.4f}s); batched "
        f"entry {rps_batched:.1f} vs singles {rps_singles:.1f} req/s -> "
        f"speedup {speedup:.2f}x (queue {rps / rps_singles:.2f}x, cache "
        f"hit rate {st['hit_rate']:.3f})")

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    from measure_common import append_history

    line = append_history(platform, bn, bn, rps, p99, source="bench.py",
                          variant=variant, dtype="float64",
                          workload="serve",
                          extra={"speedup": round(float(speedup), 3),
                                 "batched_rps": round(float(rps_batched), 2),
                                 "singles_rps": round(float(rps_singles),
                                                      2),
                                 "queue_speedup": round(
                                     float(rps / rps_singles), 3),
                                 "requests": n_reqs, "batch": batch,
                                 "hit_rate": st["hit_rate"]})
    from dlaf_tpu import obs

    obs.emit_event("bench_result", payload=line)
    obs.flush()
    print(json.dumps(line), flush=True)


def _run_overload_variant(variant: str, platform: str) -> None:
    """Measure the serving queue's overload protection (ISSUE 12,
    docs/robustness.md): a deterministic burst of 2x the
    ``DLAF_SERVE_MAX_DEPTH`` admission bound per pass — the queue must
    shed the overflow fast (OverloadError), keep pending depth at or
    under the bound, and serve every ACCEPTED request with bounded p99.
    Records accepted requests/s (gflops slot), accepted p99 seconds (t
    slot), the shed rate, and the max observed depth; workload="overload"
    keeps the line out of every headline. The arm FAILS (raises) if depth
    ever exceeds the bound or an accepted ticket is stranded — the
    queue-memory-bounded claim is asserted, not just logged."""
    from dlaf_tpu.health.errors import OverloadError
    from dlaf_tpu.obs import quantile
    from dlaf_tpu.serve import Queue, Request

    bn = int(os.environ.get("DLAF_BENCH_SERVE_N", "32"))
    max_depth = int(os.environ.get("DLAF_BENCH_OVERLOAD_DEPTH", "16"))
    rng = np.random.default_rng(bn * 31 + max_depth)
    n_reqs = 2 * max_depth              # the 2x-capacity burst
    problems = []
    for _ in range(n_reqs):
        n = int(rng.integers(bn // 2 + 1, bn + 1))
        x = rng.standard_normal((n, n))
        problems.append(x @ x.T + n * np.eye(n))
    # batch > max_depth: the bucket cannot drain mid-burst, so the
    # admission bound genuinely binds (arrival faster than dispatch —
    # the overload regime this arm certifies)
    q = Queue(buckets=(bn,), batch=n_reqs, deadline_s=1e9,
              max_depth=max_depth, shed=True)
    q.warmup([Request(op="cholesky", a=problems[0])])
    log(f"[{variant}] overload arm on {platform}: bucket={bn} "
        f"max_depth={max_depth} burst={n_reqs} (2x capacity)")
    best_t, p99 = float("inf"), float("nan")
    shed_total = accepted_total = 0
    max_seen = 0
    for i in range(3):
        tickets, shed = [], 0
        t0 = time.perf_counter()
        for a in problems:
            try:
                tickets.append(q.submit(Request(op="cholesky", a=a)))
            except OverloadError:
                shed += 1
            max_seen = max(max_seen, q.pending())
        q.flush()
        t = time.perf_counter() - t0
        stranded = [tk for tk in tickets
                    if not tk.done and tk.error is None]
        if stranded:
            raise RuntimeError(f"overload arm stranded {len(stranded)} "
                               "accepted ticket(s)")
        if max_seen > max_depth:
            raise RuntimeError(f"overload arm: pending depth {max_seen} "
                               f"exceeded DLAF_SERVE_MAX_DEPTH={max_depth}")
        lat = [tk.total_s for tk in tickets if tk.done]
        shed_total += shed
        accepted_total += len(tickets)
        # shared quantile estimator, not a second hand-rolled p99 (the
        # serve arm has the parity rationale)
        log(f"[{variant}] pass {i}: {t:.4f}s accepted={len(tickets)} "
            f"shed={shed} depth<= {max_seen} "
            f"p99 {quantile(lat, 0.99):.4f}s")
        if t < best_t:
            best_t, p99 = t, float(quantile(lat, 0.99))
    accepted_per_pass = accepted_total // 3
    rps = accepted_per_pass / best_t
    shed_rate = shed_total / (3 * n_reqs)
    st = q.stats()
    log(f"[{variant}] accepted {rps:.1f} req/s (p99 {p99:.4f}s), shed "
        f"rate {shed_rate:.2f}, max depth {max_seen}/{max_depth}, "
        f"queue stats {dict((k, v) for k, v in st.items() if k != 'buckets')}")

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    from measure_common import append_history

    line = append_history(platform, bn, bn, rps, p99, source="bench.py",
                          variant=variant, dtype="float64",
                          workload="overload",
                          extra={"shed_rate": round(float(shed_rate), 3),
                                 "shed": shed_total,
                                 "accepted": accepted_total,
                                 "burst": n_reqs,
                                 "max_depth": max_depth,
                                 "max_depth_seen": max_seen})
    from dlaf_tpu import obs

    obs.emit_event("bench_result", payload=line)
    obs.flush()
    print(json.dumps(line), flush=True)


def _run_fleet_variant(variant: str, platform: str) -> None:
    """Measure the fleet serve tier (ISSUE 18, docs/fleet.md): the SAME
    seeded mixed-bucket cholesky/solve stream through a Router over ONE
    real subprocess replica, then over ``DLAF_BENCH_FLEET_WORKERS``
    replicas sharing the persistent compile cache — requests/s in the
    ``gflops`` history slot, p99 latency seconds in ``t``, and the
    N-vs-1 throughput ratio as the ``speedup`` field
    scripts/bench_gate.py holds to the history-free
    ``--min-fleet-scaling`` floor. The arm then re-runs the stream with
    a mid-flight SIGKILL of the replica holding unacked tickets and
    reports ``recovery_s`` (kill -> every ticket resolved, ZERO lost):
    the replica-kill drill's cost, measured rather than asserted away.
    workload="fleet" keeps all of it out of every headline."""
    import signal
    import subprocess

    from dlaf_tpu import obs
    from dlaf_tpu.fleet import Router
    from dlaf_tpu.obs import quantile
    from dlaf_tpu.serve import Request

    bn = int(os.environ.get("DLAF_BENCH_FLEET_N", "64"))
    n_reqs = int(os.environ.get("DLAF_BENCH_FLEET_REQS", "48"))
    n_workers = int(os.environ.get("DLAF_BENCH_FLEET_WORKERS", "3"))
    # the replica queues bucket by these knobs (children inherit the
    # env); two n-buckets x two ops = four bucket programs, so the
    # router's bucket co-location actually spreads across replicas
    os.environ["DLAF_SERVE_BUCKETS"] = f"{max(bn // 2, 8)},{bn}"
    os.environ["DLAF_SERVE_DEADLINE_MS"] = "60000"
    rng = np.random.default_rng(bn * 7 + n_reqs)
    problems = []
    for i in range(n_reqs):
        n = int(rng.integers(bn // 4 + 1, bn + 1))
        if i % 3 == 2:
            problems.append(dict(
                op="solve",
                a=np.tril(rng.standard_normal((n, n))) + 3 * np.eye(n),
                b=rng.standard_normal((n, 4))))
        else:
            x = rng.standard_normal((n, n))
            problems.append(dict(op="cholesky", a=x @ x.T + n * np.eye(n)))

    router = Router(port=0)
    wenv = dict(os.environ)
    if wenv.get("DLAF_METRICS_PATH"):
        # the replicas must not interleave writes into THIS child's
        # artifact: each gets its own rank-templated shard next to it
        wenv["DLAF_METRICS_PATH"] += ".fleet_w%r.jsonl"
    procs: dict = {}

    def spawn(k):
        procs[k] = subprocess.Popen(
            [sys.executable, "-m", "dlaf_tpu.fleet.worker",
             "--connect", f"127.0.0.1:{router.port}", "--worker", str(k)],
            env=wenv)

    def wait_up(count, timeout_s=180.0):
        deadline = time.monotonic() + timeout_s
        while True:
            states = router.stats()["workers"]
            if sum(1 for m in states.values()
                   if m["state"] == "up") >= count:
                return
            if time.monotonic() > deadline:
                raise RuntimeError(f"fleet replicas not up: {states}")
            router.poll()
            time.sleep(0.05)

    def pass_once():
        tickets = [router.submit(Request(**p)) for p in problems]
        router.flush()
        if not router.join(tickets, timeout_s=VARIANT_TIMEOUT_S):
            raise RuntimeError("fleet stream timed out")
        bad = [t for t in tickets if t.error is not None]
        assert not bad, f"{len(bad)} fleet tickets failed: {bad[0].error}"
        return tickets

    def measure(tag):
        pass_once()                  # warm: compile into the shared cache
        best, p99 = float("inf"), float("nan")
        for i in range(2):
            t0 = time.perf_counter()
            tickets = pass_once()
            t = time.perf_counter() - t0
            lat = [tk.total_s for tk in tickets
                   if isinstance(tk.total_s, (int, float))]
            log(f"[{variant}] {tag} pass {i}: {t:.4f}s "
                f"{n_reqs / t:.1f} req/s")
            if t < best:
                best, p99 = t, float(quantile(lat, 0.99)) if lat \
                    else float("nan")
        return n_reqs / best, p99

    spawn(0)
    wait_up(1)
    log(f"[{variant}] fleet arm on {platform}: bucket={bn} "
        f"requests={n_reqs} replicas=1 then {n_workers}")
    rps_1, _ = measure("1-replica")
    for k in range(1, n_workers):
        spawn(k)
    wait_up(n_workers)
    rps_n, p99_n = measure(f"{n_workers}-replica")
    scaling = rps_n / rps_1

    # the replica-kill recovery leg: strand a partial batch on one
    # replica (no flush yet), SIGKILL it, and clock kill -> last ticket
    tickets = [router.submit(Request(**p)) for p in problems]
    router.poll()
    pending = [t for t in tickets if not t.resolved()]
    recovery_s = 0.0
    if pending:
        victim = pending[0].attempts[-1]
        vpid = router.stats()["workers"][victim]["pid"]
        t_kill = time.perf_counter()
        os.kill(vpid, signal.SIGKILL)
        procs[victim].wait(timeout=60)
        router.flush()
        if not router.join(tickets, timeout_s=VARIANT_TIMEOUT_S):
            raise RuntimeError("fleet kill-recovery stream timed out")
        recovery_s = time.perf_counter() - t_kill
    st = router.stats()
    assert st["lost"] == 0, f"replica kill lost tickets: {st}"
    log(f"[{variant}] fleet {n_workers}x {rps_n:.1f} req/s vs 1x "
        f"{rps_1:.1f} -> scaling {scaling:.2f}x; kill recovery "
        f"{recovery_s:.3f}s ({st['redispatches']} redispatches, 0 lost)")
    router.drain_fleet()
    for p in procs.values():
        if p.poll() is None:
            p.terminate()
            p.wait(timeout=30)
    router.close()

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    from measure_common import append_history

    line = append_history(platform, bn, bn, rps_n, p99_n,
                          source="bench.py", variant=variant,
                          dtype="float64", workload="fleet",
                          extra={"speedup": round(float(scaling), 3),
                                 "rps_1": round(float(rps_1), 2),
                                 "rps_n": round(float(rps_n), 2),
                                 "workers": n_workers,
                                 "requests": n_reqs,
                                 "recovery_s": round(float(recovery_s), 3),
                                 "redispatches": st["redispatches"]})
    obs.emit_event("bench_result", payload=line)
    obs.flush()
    print(json.dumps(line), flush=True)


def _run_stage_variant(variant: str, base: str, mods: set) -> None:
    """Measure one eigensolver-stage arm; same artifact/stdout protocol as
    the cholesky arms (bench_result record + one JSON line)."""
    if base in MULTIPROCESS_ARMS and expected_platform() == "tpu":
        # refused before JAX comes up: nothing to hang on
        log(f"[{variant}] this arm starts several worker processes that each "
            "bring up JAX; on one chip they cannot all own the device. Run "
            "it with JAX_PLATFORMS=cpu. Nothing was measured.")
        sys.exit(3)
    import jax

    import dlaf_tpu.config as config
    from dlaf_tpu.common.sync import hard_fence
    from dlaf_tpu.types import total_ops

    os.environ.setdefault("DLAF_DC_LEVEL_BATCH",
                          "1" if "dcb1" in mods else "0")
    os.environ.setdefault("DLAF_BT_LOOKAHEAD",
                          "1" if "btla1" in mods else "0")
    if base == "fpanel":
        os.environ.setdefault("DLAF_PANEL_IMPL",
                              "fused" if "fp1" in mods else "xla")
    if base == "fstep":
        # plain arm pins the composed chain so TPU "auto" cannot blur
        # the A/B; "+fs1" pins the fused step kernel (ISSUE 19)
        os.environ.setdefault("DLAF_STEP_IMPL",
                              "fused" if "fs1" in mods else "xla")
    config.initialize()
    platform = require_platform()
    if base == "fpanel":
        _run_fpanel_variant(variant, platform)
        return
    if base == "fstep":
        _run_fpanel_variant(variant, platform, workload="fstep")
        return
    if base == "serve":
        _run_serve_variant(variant, platform)
        return
    if base == "overload":
        _run_overload_variant(variant, platform)
        return
    if base == "fleet":
        _run_fleet_variant(variant, platform)
        return
    # stage arms default to a smaller N off-TPU: the local red2band that
    # feeds the bt arm compiles per-panel, and a CPU sweep's budget
    # belongs to the headline arms
    n = int(os.environ.get("DLAF_BENCH_STAGE_N") or
            (os.environ.get("DLAF_BENCH_N", "4096")
             if platform == "tpu" else "1024"))
    nb = int(os.environ.get("DLAF_BENCH_NB", "256"))
    log(f"[{variant}] stage arm on {platform}: n={n} nb={nb}")
    rng = np.random.default_rng(n)
    if base == "tridiag":
        from dlaf_tpu.eigensolver.tridiag_solver import tridiag_solver

        d = rng.standard_normal(n)
        e = rng.standard_normal(n - 1)
        flops = total_ops(np.dtype(np.float64), 2 * n**3 / 3, 2 * n**3 / 3)

        def measure():
            return tridiag_solver(d, e, nb, use_device=True)[1]
    elif base == "btb2t":
        from dlaf_tpu.eigensolver.back_transform import bt_band_to_tridiag
        from dlaf_tpu.eigensolver.band_to_tridiag import band_to_tridiag

        b = min(nb, max(n // 8, 1))
        band = np.zeros((b + 1, n))
        band[0] = rng.standard_normal(n)
        for r in range(1, b + 1):
            band[r, : n - r] = rng.standard_normal(n - r)
        tri = band_to_tridiag(band, b)
        c = rng.standard_normal((n, n))
        flops = total_ops(np.dtype(np.float64), n**3, n**3)

        def measure():
            return bt_band_to_tridiag(tri, c)
    else:   # btr2b
        import jax.numpy as jnp

        from dlaf_tpu.common.index2d import TileElementSize
        from dlaf_tpu.eigensolver.back_transform import bt_reduction_to_band
        from dlaf_tpu.eigensolver.reduction_to_band import reduction_to_band
        from dlaf_tpu.matrix.matrix import Matrix

        x = rng.standard_normal((n, n))
        a = x @ x.T + n * np.eye(n)
        red = reduction_to_band(
            Matrix.from_global(a, TileElementSize(nb, nb)))
        hard_fence(red.matrix.storage)
        c = jnp.asarray(rng.standard_normal((n, n)))
        flops = total_ops(np.dtype(np.float64), n**3, n**3)

        def measure():
            return bt_reduction_to_band(red, c)

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    # the single timing-policy owner (1 warmup + fenced best-of-reps):
    # the stage arms must never drift from the other history entries
    from measure_common import append_history, best_time

    best_t, last = best_time(measure, reps=3, return_last=True)
    best_g = flops / best_t / 1e9
    log(f"[{variant}] best of 3: {best_t:.4f}s {best_g:.1f} GFlop/s")

    line = append_history(platform, n, nb, best_g, best_t,
                          source="bench.py", variant=variant,
                          dtype="float64", workload=base)
    from dlaf_tpu import obs
    from dlaf_tpu.obs import accuracy

    if base == "tridiag" and accuracy.enabled():
        # paired perf+accuracy record (DLAF_ACCURACY, docs/accuracy.md):
        # the D&C eigenvector block's orthogonality defect is the cheap
        # invariant this arm can check without a reference decomposition
        accuracy.emit("bench", "tridiag_orthogonality",
                      accuracy.array_orthogonality(last), n=n, nb=nb,
                      c=200.0, dtype=np.float64, of=last,
                      attrs={"variant": variant})
    obs.emit_event("bench_result", payload=line)
    obs.flush()
    print(json.dumps(line), flush=True)


def _emit_devtrace(variant: str) -> None:
    """Traced bench run (DLAF_TRACE_DIR armed + a metrics sink): stop
    the process trace so the profiler artifact lands, attribute the
    device timeline to this arm's spans (dlaf_tpu.obs.devtrace, ISSUE
    14), and append the devtrace/measured_overlap records to the SAME
    artifact — so a traced bench arm's artifact passes
    ``--require-devtrace`` and feeds ``scripts/perf_diff.py`` with
    measured per-phase device walls next to its bench_result. No-op on
    untraced runs; never fails the measurement (the number already
    landed)."""
    from dlaf_tpu import obs
    from dlaf_tpu.obs._state import STATE

    trace_root = STATE.trace_dir
    if not STATE.profiler_started or STATE.sink is None or not trace_root:
        return
    # NOTHING here may fail the child: the bench_result already flushed,
    # and the parent drops a nonzero-rc child's landed measurement — so
    # the whole post-measurement path (profiler stop, trace parse,
    # attribution, the sink writes themselves) degrades to a log line
    try:
        obs.stop_profiler()        # flush the profiler artifact to disk
        from dlaf_tpu.obs import devtrace

        path = devtrace.newest_trace(trace_root)
        records = obs.read_records(STATE.sink.path)
        report = devtrace.attribute(devtrace.load_trace(path), records)
        for rec in devtrace.records_from_report(report, path):
            obs.emit_event(rec.pop("type"), **rec)
        obs.flush()
    except SystemExit as e:        # newest_trace's empty-dir signal
        log(f"[{variant}] devtrace attribution skipped: {e}")
        return
    except Exception as e:
        log(f"[{variant}] devtrace attribution skipped: {e!r}")
        return
    log(f"[{variant}] devtrace: coverage {report['coverage'] * 100:.1f}%, "
        f"{len(report['overlap'])} measured_overlap record(s)")


def run_variant() -> None:
    """Child: measure ONE trailing variant (env DLAF_BENCH_VARIANT), print
    one JSON line {variant, platform, dtype, n, nb, gflops, t, ts, source,
    donate} on stdout (the exact dict measure_common.append_history wrote
    to .bench_history.jsonl — single schema owner)."""
    variant = os.environ["DLAF_BENCH_VARIANT"]
    dtype_name = os.environ.get("DLAF_BENCH_DTYPE", "float64")
    t_start = time.time()
    import jax

    jax.config.update("jax_enable_x64", True)
    # "<base>+la1" = the same trailing form under the PIPELINED step order
    # (config cholesky_lookahead=1); the plain arm pins lookahead=0 so the
    # pair is a real serialized-vs-pipelined A/B on every platform (the
    # auto knob would silently flip the plain arm on TPU). Explicit env
    # still wins via setdefault.
    base = variant
    la = None
    if variant.endswith("+la1"):
        base, la = variant[: -len("+la1")], "1"
    if base.split("+")[0] in STAGE_BASES:
        parts = base.split("+")
        _run_stage_variant(variant, parts[0], set(parts[1:]))
        _emit_devtrace(variant)
        return
    # the platform check comes BEFORE this child writes its arm's knobs
    # into its own environment: a refused run leaves the process as it
    # found it (a test that calls this entry in-process used to leave
    # DLAF_CHOLESKY_TRAILING / _LOOKAHEAD set for every later test of its
    # worker: tests/test_config.py::test_cholesky_lookahead_knob, PR 30's
    # red tier-1)
    platform = require_platform()
    os.environ.setdefault("DLAF_CHOLESKY_LOOKAHEAD", la or "0")
    os.environ["DLAF_CHOLESKY_TRAILING"] = base

    import dlaf_tpu.config as config

    config.initialize()
    log(f"[{variant}] devices: {jax.devices()} ({time.time() - t_start:.1f}s)")
    if base == "scan" and platform == "tpu":
        # the scan formulation follows the f64_gemm/f64_trsm knobs (it no
        # longer hardwires the MXU route); on TPU the measured scan config
        # is the MXU one, so resolve the knobs the way the product config
        # does there — explicit env still overrides, each knob on its own
        # variable's absence (an explicit DLAF_F64_TRSM alone must not be
        # clobbered)
        os.environ.setdefault("DLAF_F64_GEMM", "mxu")
        os.environ.setdefault("DLAF_F64_TRSM", "mixed")
        config.initialize()
        log(f"[{variant}] tpu: f64_gemm={os.environ['DLAF_F64_GEMM']} "
            f"f64_trsm={os.environ['DLAF_F64_TRSM']}")

    from dlaf_tpu.algorithms.cholesky import cholesky
    from dlaf_tpu.common.index2d import GlobalElementSize, TileElementSize
    from dlaf_tpu.common.sync import hard_fence
    from dlaf_tpu.matrix.matrix import Matrix
    from dlaf_tpu.miniapp.generators import hpd_element_fn
    from dlaf_tpu.types import total_ops

    n = int(os.environ.get("DLAF_BENCH_N", "4096"))
    nb = int(os.environ.get("DLAF_BENCH_NB", "256"))
    dtype = np.dtype(dtype_name).type
    if dtype != np.float64 and base.startswith("ozaki"):
        # "ozaki*" is the emulated-f64 path; for other dtypes it statically
        # falls back to biggemm — keep the label truthful (the lookahead
        # suffix survives the relabel: the step order is orthogonal)
        os.environ["DLAF_CHOLESKY_TRAILING"] = base = "biggemm"
        variant = base + ("+la1" if la else "")
        config.initialize()
    ref = Matrix.from_element_fn(hpd_element_fn(n, dtype),
                                 GlobalElementSize(n, n),
                                 TileElementSize(nb, nb), dtype=dtype)
    best_g, best_t = 0.0, float("inf")
    # 1 warmup (compile) + 4 timed: compiles cost minutes, timed runs cost
    # milliseconds — extra repetitions capture the fast tail for free
    for i in range(5):
        mat = ref.with_storage(ref.storage + 0)
        hard_fence(mat.storage)
        t0 = time.perf_counter()
        # donate: the per-run copy is consumed exactly like the miniapp's
        # (the reference factors mat_a in place); the donated route is the
        # product default and the measured-fastest form (session 4g)
        out = cholesky("L", mat, donate=True)
        hard_fence(out.storage)
        t = time.perf_counter() - t0
        g = total_ops(dtype, n**3 / 6, n**3 / 6) / t / 1e9
        log(f"[{variant}] run {i}: {t:.4f}s {g:.1f} GFlop/s")
        if i > 0 and g > best_g:
            best_g, best_t = g, t
    # append-only measurement log: a measurement that landed is kept
    # whatever happens to the rest of the sweep.
    # measure_common.append_history is the single schema owner; the line it
    # returns (donate=True: this sweep's program aliases its input, a
    # different measured program from pre-donation entries — round-4
    # advisory) is also this child's stdout protocol.
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    from measure_common import append_history

    line = append_history(platform, n, nb, best_g, best_t, source="bench.py",
                          variant=variant, dtype=np.dtype(dtype).name,
                          donate=True)
    from dlaf_tpu.obs import accuracy

    if accuracy.enabled():
        # paired perf+accuracy record for the A/B arm (DLAF_ACCURACY,
        # docs/accuracy.md): probe the LAST timed factor against the
        # retained reference — a bad Ozaki peel or a wrong lookahead mask
        # shows up here as a bound_ratio jump next to its GFlop/s number
        value = accuracy.cholesky_residual("L", ref, out)
        accuracy.emit("bench", "cholesky_residual", value, n=n, nb=nb,
                      c=60.0, dtype=dtype, of=out.storage,
                      attrs={"variant": variant})
    # primary result channel: the obs JSONL artifact (the parent points
    # DLAF_METRICS_PATH at a per-variant file and reads the bench_result
    # record back — structured, alongside this child's spans/counters —
    # instead of scraping the stdout tail). The stdout line stays for
    # humans and as the no-artifact fallback.
    from dlaf_tpu import obs

    obs.emit_event("bench_result", payload=line)
    obs.flush()
    _emit_devtrace(variant)
    print(json.dumps(line), flush=True)


def assemble_headline(results, n, nb):
    """Build the driver's single JSON object from THIS sweep's results:
    the best live cholesky arm, labelled with the platform it ran on. The
    headline is BASELINE config #1 (cholesky); the eigensolver stage arms
    measure different flop models and only ride in the artifact — a sweep
    in which no cholesky arm landed reports nothing (``None``), never a
    stage number under the cholesky label and never a recorded one.
    Reference measurement contract: ``miniapp/miniapp_cholesky.cpp:123-174``.
    """
    chol = [r for r in results if r.get("workload") in (None, "cholesky")]
    if not chol:
        return None
    best = max(chol, key=lambda r: r["gflops"])
    return {
        "metric": (f"miniapp_cholesky {best['dtype']} N={n} nb={nb} "
                   f"local GFlop/s [{best['platform']}] "
                   f"trailing={best['variant']}"),
        "value": best["gflops"],
        "unit": "GFlop/s",
        "vs_baseline": 1.0,
    }


def read_bench_result(path: str):
    """Last ``bench_result`` payload from a child's obs JSONL artifact, or
    None (missing/invalid file, or a child that died before emitting)."""
    try:
        from dlaf_tpu.obs import read_records
    except Exception:
        return None
    try:
        payloads = [r.get("payload") for r in read_records(path)
                    if r.get("type") == "bench_result"]
    except (OSError, ValueError):
        return None
    return payloads[-1] if payloads and isinstance(payloads[-1], dict) \
        else None


def sweep(platform: str) -> None:
    """Parent: run the variant sweep, each variant in a timeout-guarded
    subprocess; print the driver's single JSON line from the best result."""
    # import-only: nothing on this path may create a JAX backend in the
    # parent (tests/test_aux_components.py pins it) — the chip belongs to
    # one process at a time, and that process is the arm's child
    from dlaf_tpu.algorithms.cholesky import VALID_TRAILING

    # an explicit CPU run: the int8-emulation variant has no hardware to
    # win on there
    on_cpu = platform == "cpu"
    pinned = os.environ.get("DLAF_BENCH_TRAILING")
    # measured winner first (ozaki 91-99 GF/s vs xla 37-47 on one v5e
    # chip, 2026-08, hard_fence timing): if the time budget runs out or a
    # later variant hangs, the best measurement has already landed.
    # "+la1" arms re-run a form under the pipelined step order
    # (cholesky_lookahead=1) against the plain serialized arm — the
    # look-ahead A/B the bench artifact must carry on every run.
    # (trailing="xla" delegates the whole factorization to one fused XLA
    # cholesky — no step chain to pipeline, so it has no "+la1" arm; the
    # unrolled-order A/B rides the stepped forms instead)
    # the eigensolver stage A/B arms (tridiag dc_level_batch, btr2b
    # bt_lookahead — ISSUE 6) run LAST: the headline cholesky sweep owns
    # the budget, and the stage pairs are informational artifact rows
    # the fused-panel pair (ISSUE 10) rides after the stage arms: f32,
    # its own workload label, plain arm pinned to panel_impl=xla
    order = ["ozaki", "ozaki+la1", "xla", "scan", "scan+la1",
             "loop", "loop+la1", "biggemm", "biggemm+la1", "invgemm",
             "tridiag", "tridiag+dcb1", "btr2b", "btr2b+btla1", "btb2t",
             "fpanel", "fpanel+fp1", "fstep", "fstep+fs1", "serve",
             "overload", "fleet"]

    def _known(v):
        b = v[: -len("+la1")] if v.endswith("+la1") else v
        return b in VALID_TRAILING or v.split("+")[0] in STAGE_BASES

    variants = [pinned] if pinned else \
        [v for v in order if _known(v)] + \
        [v for v in VALID_TRAILING if v not in order]
    if on_cpu and not pinned:
        # the CPU has fast native f64 — the int8-emulation variant has no
        # hardware to win on there; accelerators keep it leading
        variants = [v for v in variants if not v.startswith("ozaki")]
        variants = sorted(variants, key=lambda v: v != "xla")
    if not on_cpu and not pinned:
        skipped = [v for v in variants if v in MULTIPROCESS_ARMS]
        if skipped:
            log(f"not run on a chip (several worker processes would each "
                f"need the device): {skipped}")
        variants = [v for v in variants if v not in MULTIPROCESS_ARMS]

    budget_s = float(os.environ.get("DLAF_BENCH_BUDGET", "1800"))
    sweep_t0 = time.perf_counter()
    results = []
    failed = []       # arms whose child did not run to a clean end
    import tempfile

    # per-variant obs artifacts: the child's spans, collective byte
    # counters, and its bench_result record (the parent's result channel)
    art_dir = os.environ.get("DLAF_BENCH_OBS_DIR") or tempfile.mkdtemp(
        prefix="dlaf_bench_obs_")
    os.makedirs(art_dir, exist_ok=True)
    log(f"obs artifacts: {art_dir}")
    for vi, variant in enumerate(variants):
        if vi > 0 and time.perf_counter() - sweep_t0 > budget_s:
            log(f"budget {budget_s}s exhausted; skipping {variants[vi:]}")
            break
        if any(r["variant"] == variant for r in results):
            # a child may relabel itself (ozaki -> biggemm when f64 is
            # unavailable); don't re-measure the identical configuration
            log(f"[{variant}] already measured (child relabel); skipping")
            continue
        env = dict(os.environ)
        env["DLAF_BENCH_VARIANT"] = variant
        # every arm's artifact carries a paired accuracy record next to
        # its bench_result (docs/accuracy.md); explicit env still wins
        env.setdefault("DLAF_ACCURACY", "1")
        art = os.path.join(art_dir, f"{variant}.jsonl")
        # the sink appends: drop any artifact from a previous sweep in a
        # reused DLAF_BENCH_OBS_DIR so a child that dies before emitting
        # can't inherit a stale bench_result record
        if os.path.exists(art):
            os.unlink(art)
        env["DLAF_METRICS_PATH"] = art
        try:
            proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                                  env=env, timeout=VARIANT_TIMEOUT_S,
                                  stdout=subprocess.PIPE)
            line = read_bench_result(art)
            if line is None:
                # no artifact (old child, crash before flush): stdout tail
                tail = proc.stdout.decode().strip().splitlines()[-1:]
                if proc.returncode == 0 and tail:
                    try:
                        line = json.loads(tail[0])
                    except ValueError:
                        line = None   # stray non-JSON final line
            if proc.returncode == 0 and line is not None:
                results.append(line)
            else:
                failed.append(variant)
                log(f"[{variant}] child rc={proc.returncode}, no result")
        except subprocess.TimeoutExpired:
            # the measurement may already have landed: the child flushes
            # its bench_result to the line-buffered artifact BEFORE the
            # post-measurement work (accuracy probe, devtrace
            # attribution of a large trace) that can eat the rest of the
            # budget — a timeout there must not discard a landed number
            line = read_bench_result(art)
            if line is not None:
                results.append(line)
                log(f"[{variant}] timed out after {VARIANT_TIMEOUT_S}s "
                    "AFTER its measurement landed; result recovered from "
                    "the artifact")
            else:
                failed.append(variant)
                log(f"[{variant}] timed out after {VARIANT_TIMEOUT_S}s; "
                    "killed (measurements from other variants are "
                    "unaffected)")
        except Exception as e:
            failed.append(variant)
            log(f"[{variant}] failed: {e!r}")
    if not results:
        log("no variant produced a measurement")
        sys.exit(1)
    n = int(os.environ.get("DLAF_BENCH_N", "4096"))
    nb = int(os.environ.get("DLAF_BENCH_NB", "256"))
    result = assemble_headline(results, n, nb)
    if result is None:
        # stage arms alone cannot stand in for the cholesky headline
        log("no cholesky variant produced a measurement")
        sys.exit(1)
    print(json.dumps(result), flush=True)

    chol = [r for r in results if r.get("workload") in (None, "cholesky")]
    best = max(chol, key=lambda r: r["gflops"]) if chol else None
    # informational MXU-tier number (stderr only — the headline metric
    # stays f64 per BASELINE config #1)
    if best is not None and best["dtype"] == "float64" \
            and time.perf_counter() - sweep_t0 < budget_s:
        env = dict(os.environ)
        env["DLAF_BENCH_VARIANT"] = best["variant"]
        env["DLAF_BENCH_DTYPE"] = "float32"
        try:
            proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                                  env=env, timeout=VARIANT_TIMEOUT_S,
                                  stdout=subprocess.PIPE)
            line = proc.stdout.decode().strip().splitlines()[-1:]
            if proc.returncode != 0:
                failed.append(f"{best['variant']}[float32]")
            elif line:
                log(f"[info] float32: {json.loads(line[0])['gflops']} GFlop/s")
        except Exception as e:
            failed.append(f"{best['variant']}[float32]")
            log(f"[info] float32 arm failed: {e!r}")
    if failed:
        # the headline above is live and stays printed; the sweep as a
        # whole did not run clean
        log(f"arms that failed: {failed}")
        sys.exit(1)


def main() -> None:
    if os.environ.get("DLAF_BENCH_VARIANT"):
        run_variant()
        return
    sweep(expected_platform())


if __name__ == "__main__":
    main()
