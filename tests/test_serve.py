"""Tests for ISSUE 11: the batched many-problem serving layer.

Covers: batched-vs-loop-of-singles BITWISE parity for the three batched
entry points (dtype x uplo x occupancy), pad-lane inertness and the
shape-padding budget, program-service cache semantics (hit/miss/warmup/
pin/evict, LRU byte budget, config invalidation), zero-retrace-after-
warmup pinned on ``dlaf_retrace_total``, queue bucket-selection and
deadline determinism (fake clock), the ``serve`` record schema +
``--require-serve`` validator obligation, per-lane
``robust_cholesky_batched`` recovery, the bench serve arm's headline
isolation, the bench-gate serve-speedup leg, and the graphcheck serve
program specs (docs/serving.md).
"""

import functools
import json
import os
import sys

import numpy as np
import pytest

import jax

import dlaf_tpu.config as C
from dlaf_tpu import health, obs
from dlaf_tpu.algorithms import batched as bt
from dlaf_tpu.serve import (ProgramService, Queue, Request, bucket_ceiling,
                            cholesky_batched, cholesky_spec, eigh_batched,
                            eigh_spec, get_service, solve_batched,
                            solve_spec)
from dlaf_tpu.serve import programs as serve_programs

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")
if SCRIPTS not in sys.path:
    sys.path.insert(0, SCRIPTS)


@pytest.fixture(autouse=True)
def serve_reset():
    """Each test leaves the default (unobserved) config and an empty
    default service behind."""
    yield
    for key in ("DLAF_METRICS_PATH", "DLAF_PROGRAM_TELEMETRY",
                "DLAF_ACCURACY", "DLAF_SERVE_BUCKETS", "DLAF_SERVE_BATCH",
                "DLAF_SERVE_DEADLINE_MS", "DLAF_SERVE_CACHE_BYTES"):
        os.environ.pop(key, None)
    obs._reset_for_tests()
    obs.telemetry._reset_for_tests()
    serve_programs._reset_for_tests()
    health.circuit.reset()            # a tripped dispatch breaker must
    C.finalize()                      # not fail-fast later tests' buckets
    C.initialize()


def _hpd(n, seed=0, dtype=np.float64, shift=None):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n)).astype(dtype)
    return (x @ x.T + (n if shift is None else shift)
            * np.eye(n)).astype(dtype)


def _hpd_batch(b, n, dtype=np.float64, seed=0):
    return np.stack([_hpd(n, seed=seed + i, dtype=dtype) for i in range(b)])


def _tri(n, uplo="L", seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n)).astype(dtype)
    t = np.tril(x) if uplo == "L" else np.triu(x)
    return (t + 3 * np.eye(n)).astype(dtype)


def _sym(n, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n)).astype(dtype)
    return ((x + x.T) / 2).astype(dtype)


# ---------------------------------------------------------------------------
# Batched-vs-loop-of-singles bitwise parity (the core contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("uplo", ["L", "U"])
def test_cholesky_batched_bitwise_vs_singles(dtype, uplo):
    """Every lane of a batched dispatch == the B=1 dispatch of the same
    bucket program == the unbatched singleton kernel, bitwise; info
    vector all zero on SPD lanes."""
    svc = ProgramService()
    n, b = 20, 4
    a = _hpd_batch(b, n, dtype=dtype)
    out, info = cholesky_batched(uplo, a, with_info=True, service=svc)
    out = np.asarray(out)
    assert out.shape == (b, n, n) and np.asarray(info).tolist() == [0] * b
    single = jax.jit(functools.partial(
        bt.cholesky_one, uplo=uplo, nb=bt.default_nb(n), with_info=True))
    for i in range(b):
        lane1, info1 = cholesky_batched(uplo, a[i:i + 1], with_info=True,
                                        service=svc)
        np.testing.assert_array_equal(out[i], np.asarray(lane1)[0])
        s_out, s_info = single(a[i])
        np.testing.assert_array_equal(out[i], np.asarray(s_out))
        assert int(np.asarray(info1)[0]) == int(s_info) == 0


@pytest.mark.parametrize("side,uplo,op,diag", [
    ("L", "L", "N", "N"), ("L", "U", "T", "N"),
    ("R", "U", "N", "U"), ("R", "L", "C", "N"),
])
def test_solve_batched_bitwise_vs_singles(side, uplo, op, diag):
    """Batched solve lanes == B=1 dispatches bitwise for every
    side/uplo/op/diag family, and solve the system they claim to."""
    svc = ProgramService()
    n, nrhs, b = 16, 5, 3
    a = np.stack([_tri(n, uplo=uplo, seed=i) for i in range(b)])
    rng = np.random.default_rng(7)
    shape = (b, n, nrhs) if side == "L" else (b, nrhs, n)
    rhs = rng.standard_normal(shape)
    x, info = solve_batched(side, uplo, op, diag, 1.0, a, rhs,
                            with_info=True, service=svc)
    x = np.asarray(x)
    assert np.asarray(info).tolist() == [0] * b
    for i in range(b):
        x1, _ = solve_batched(side, uplo, op, diag, 1.0, a[i:i + 1],
                              rhs[i:i + 1], with_info=True, service=svc)
        np.testing.assert_array_equal(x[i], np.asarray(x1)[0])
        # the solve actually solves: op(T) X = B / X op(T) = B
        t = np.tril(a[i]) if uplo == "L" else np.triu(a[i])
        if diag == "U":
            np.fill_diagonal(t, 1.0)
        t = {"N": t, "T": t.T, "C": t.conj().T}[op]
        lhs = t @ x[i] if side == "L" else x[i] @ t
        np.testing.assert_allclose(lhs, rhs[i], atol=1e-10)


def test_solve_batched_per_lane_alpha():
    """alpha is a traced per-lane vector, never a bucket key: two
    dispatches with different alphas share one program, and each lane
    honors its own scale."""
    svc = ProgramService()
    n, b = 12, 3
    a = np.stack([_tri(n, seed=i) for i in range(b)])
    rhs = np.random.default_rng(1).standard_normal((b, n, 4))
    alphas = np.array([1.0, -2.0, 0.5])
    x = np.asarray(solve_batched("L", "L", "N", "N", alphas, a, rhs,
                                 with_info=False, service=svc))
    for i in range(b):
        np.testing.assert_allclose(np.tril(a[i]) @ x[i],
                                   alphas[i] * rhs[i], atol=1e-10)
    assert svc.stats()["entries"] == 1


@pytest.mark.parametrize("uplo", ["L", "U"])
def test_eigh_batched_bitwise_vs_singles(uplo):
    """Batched eigh lanes == B=1 dispatches == the unbatched singleton
    kernel, bitwise; only the ``uplo`` triangle is read."""
    svc = ProgramService()
    n, b = 16, 3
    a = np.stack([_sym(n, seed=i) for i in range(b)])
    # poison the ignored triangle: the entry must not read it
    poison = np.full((n, n), 1e30)
    a_stored = np.where(np.tril(np.ones((n, n)), 0 if uplo == "L" else n)
                        .astype(bool) if uplo == "L"
                        else np.triu(np.ones((n, n))).astype(bool),
                        a, poison)
    w, v, info = eigh_batched(uplo, a_stored, with_info=True, service=svc)
    w, v = np.asarray(w), np.asarray(v)
    assert np.asarray(info).tolist() == [0] * b
    single = jax.jit(functools.partial(bt.eigh_one, uplo=uplo,
                                       with_info=True))
    for i in range(b):
        w1, v1, _ = eigh_batched(uplo, a_stored[i:i + 1], with_info=True,
                                 service=svc)
        np.testing.assert_array_equal(w[i], np.asarray(w1)[0])
        np.testing.assert_array_equal(v[i], np.asarray(v1)[0])
        sw, sv, _ = single(a_stored[i])
        np.testing.assert_array_equal(w[i], np.asarray(sw))
        np.testing.assert_array_equal(v[i], np.asarray(sv))
        # the decomposition is of the triangle's hermitian expansion
        np.testing.assert_allclose(a[i] @ v[i], v[i] * w[i][None, :],
                                   atol=1e-12 * n)


def test_pad_lanes_inert_and_identity():
    """Occupancy invariance: real-lane results are bitwise unchanged
    whether the other lanes hold problems or identity padding, and the
    pad lanes factor to exactly the singleton identity result."""
    svc = ProgramService()
    n, b = 16, 4
    full = _hpd_batch(b, n)
    padded = full.copy()
    padded[2:] = np.eye(n)
    out_full, _ = cholesky_batched("L", full, with_info=True, service=svc)
    out_pad, info_pad = cholesky_batched("L", padded, with_info=True,
                                         service=svc)
    out_full, out_pad = np.asarray(out_full), np.asarray(out_pad)
    np.testing.assert_array_equal(out_full[:2], out_pad[:2])
    assert np.asarray(info_pad).tolist() == [0] * b
    eye1, _ = cholesky_batched("L", np.eye(n)[None], with_info=True,
                               service=svc)
    for i in (2, 3):
        np.testing.assert_array_equal(out_pad[i], np.asarray(eye1)[0])


def test_batched_info_flags_failed_lanes_only():
    """Per-element info: indefinite lanes report their failing column,
    clean lanes report 0, and the factor bytes of clean lanes match the
    all-clean batch (failure containment across lanes)."""
    svc = ProgramService()
    n = 12
    good = _hpd_batch(3, n)
    mixed = good.copy()
    mixed[1] = _hpd(n, seed=9, shift=-100.0)     # indefinite lane
    out_good, info_good = cholesky_batched("L", good, with_info=True,
                                           service=svc)
    out_mixed, info_mixed = cholesky_batched("L", mixed, with_info=True,
                                             service=svc)
    assert np.asarray(info_good).tolist() == [0, 0, 0]
    infos = np.asarray(info_mixed)
    assert infos[0] == 0 and infos[2] == 0 and infos[1] >= 1
    np.testing.assert_array_equal(np.asarray(out_good)[0],
                                  np.asarray(out_mixed)[0])
    np.testing.assert_array_equal(np.asarray(out_good)[2],
                                  np.asarray(out_mixed)[2])


def test_shape_padding_budgeted_not_bitwise():
    """The queue's identity-border shape padding: the padded region is
    exactly inert and the real block matches the exact-size program at
    ulp level (the documented budget, docs/serving.md)."""
    svc = ProgramService()
    n_req, bn = 13, 16
    a = _hpd(n_req, seed=3)
    ap = np.eye(bn)
    ap[:n_req, :n_req] = a
    out_p, info_p = cholesky_batched("L", ap[None], with_info=True,
                                     service=svc)
    out_s, _ = cholesky_batched("L", a[None], with_info=True, service=svc)
    out_p, out_s = np.asarray(out_p)[0], np.asarray(out_s)[0]
    assert int(np.asarray(info_p)[0]) == 0
    # pad region exactly inert
    np.testing.assert_array_equal(np.tril(out_p)[n_req:, n_req:],
                                  np.eye(bn - n_req))
    assert np.abs(np.tril(out_p)[n_req:, :n_req]).max() == 0.0
    # real block within a few ulp of the exact-size factor
    np.testing.assert_allclose(out_p[:n_req, :n_req], out_s,
                               rtol=0, atol=64 * np.finfo(np.float64).eps
                               * np.abs(out_s).max())


# ---------------------------------------------------------------------------
# Program service: cache semantics
# ---------------------------------------------------------------------------

def _spec(n=12, b=2, **kw):
    kw.setdefault("dtype", "float64")
    kw.setdefault("uplo", "L")
    return cholesky_spec(batch=b, n=n, nb=n, **kw)


def test_cache_hit_miss_and_stats():
    svc = ProgramService()
    spec = _spec()
    a = _hpd_batch(2, 12)
    svc.run(spec, a)                      # miss + compile
    svc.run(spec, a)                      # hit
    st = svc.stats()
    assert st["misses"] == 1 and st["hits"] == 1 and st["compiles"] == 1
    assert st["entries"] == 1 and st["bytes"] > 0
    assert st["hit_rate"] == 0.5


def test_warmup_counts_warmup_not_miss_and_is_idempotent():
    svc = ProgramService()
    spec = _spec()
    walls = svc.warmup(spec)
    assert walls[spec] > 0
    assert svc.warmup(spec)[spec] == 0.0      # already warm
    st = svc.stats()
    assert st["warmups"] == 1 and st["misses"] == 0 and st["compiles"] == 1
    svc.run(spec, _hpd_batch(2, 12))
    st = svc.stats()
    assert st["hits"] == 1 and st["misses"] == 0 and st["hit_rate"] == 1.0


def test_zero_retrace_and_full_hit_rate_after_warmup(tmp_path):
    """The ISSUE-11 steady-state acceptance pin: after warmup, an
    in-bucket stream shows dlaf_retrace_total == 1 per serve site (the
    warmup trace — never a retrace) and cache hit rate == 1.0."""
    C.initialize(C.Configuration(
        metrics_path=str(tmp_path / "m.jsonl"), program_telemetry=True))
    svc = ProgramService()
    spec = _spec(n=14, b=3)
    svc.warmup(spec)
    a = _hpd_batch(3, 14)
    for _ in range(5):
        svc.run(spec, a)
    st = svc.stats()
    assert st["hit_rate"] == 1.0 and st["misses"] == 0
    snap = obs.registry().counter("dlaf_retrace_total",
                                  site=spec.site).snapshot()
    assert snap["value"] == 1, snap
    # an evict forces the recompile the counter exists to expose
    assert svc.evict(spec)
    svc.run(spec, a)
    snap = obs.registry().counter("dlaf_retrace_total",
                                  site=spec.site).snapshot()
    assert snap["value"] == 2, snap
    assert svc.stats()["misses"] == 1


def test_lru_byte_budget_evicts_oldest_unpinned():
    svc = ProgramService(cache_bytes=1)       # everything over budget
    s1, s2 = _spec(n=8), _spec(n=12)
    svc.warmup(s1)
    assert svc.specs() == ()                  # evicted immediately
    st = svc.stats()
    assert st["evictions"] == 1
    # pinned programs are never budget-evicted
    svc.pin(s2)
    assert svc.specs() == (s2,)
    svc.warmup(s1)
    assert s2 in svc.specs()                  # survived; s1 evicted
    assert s1 not in svc.specs()


def test_lru_recency_order():
    """Hits refresh recency: with a budget fitting two programs, the
    least-recently-USED one is evicted, not the oldest-inserted."""
    svc = ProgramService()                    # unbounded while warming
    s1, s2, s3 = _spec(n=8), _spec(n=8, uplo="U"), _spec(n=8, b=2,
                                                         with_info=False)
    svc.warmup(s1, s2)
    e1 = svc._entries[s1].nbytes
    e2 = svc._entries[s2].nbytes
    svc.run(s1, _hpd_batch(2, 8))             # s1 most-recent
    svc._cache_bytes = e1 + e2                # room for exactly two
    svc.warmup(s3)                            # forces one eviction
    assert s2 not in svc.specs()              # LRU victim, not s1
    assert s1 in svc.specs() and s3 in svc.specs()


def test_explicit_evict_and_unpin():
    svc = ProgramService()
    spec = _spec()
    assert svc.evict(spec) is False           # not resident
    svc.pin(spec)
    assert svc.stats()["pins"] == 1
    assert svc.evict(spec) is True            # explicit evict beats pin
    svc.pin(spec)
    svc.unpin(spec)
    svc._cache_bytes = 1
    svc._evict_for_budget()
    assert spec not in svc.specs()            # unpinned -> evictable


def test_config_change_clears_default_service():
    svc = get_service()
    spec = _spec()
    svc.warmup(spec)
    assert spec in svc.specs()
    C.initialize(C.Configuration(serve_batch=5))   # differing config
    assert svc.specs() == ()


def test_spec_site_labels_are_distinct_and_bounded():
    specs = [_spec(n=8), _spec(n=8, b=4), _spec(n=16),
             solve_spec(batch=2, n=8, nrhs=3, nb=8, dtype="float64"),
             eigh_spec(batch=2, n=8, nb=8, dtype="float64"),
             _spec(n=8, donate=True)]
    sites = [s.site for s in specs]
    assert len(set(sites)) == len(sites)
    assert all(s.startswith("serve.") for s in sites)


# ---------------------------------------------------------------------------
# Queue: bucket policy, deadlines, determinism
# ---------------------------------------------------------------------------

def test_bucket_ceiling_policy():
    assert bucket_ceiling(17, (32, 64)) == 32
    assert bucket_ceiling(32, (32, 64)) == 32
    assert bucket_ceiling(33, (32, 64)) == 64
    # above the largest ceiling / no explicit list: next power of two
    assert bucket_ceiling(65, (32, 64)) == 128
    assert bucket_ceiling(5, ()) == 8
    assert bucket_ceiling(100, ()) == 128
    with pytest.raises(Exception):
        bucket_ceiling(0, ())


def test_serve_knob_validation():
    with pytest.raises(ValueError):
        C.initialize(C.Configuration(serve_batch=0))
    with pytest.raises(ValueError):
        C.initialize(C.Configuration(serve_deadline_ms=-1.0))
    with pytest.raises(ValueError):
        C.initialize(C.Configuration(serve_cache_bytes=-5))
    with pytest.raises(ValueError):
        C.initialize(C.Configuration(serve_buckets="64,32"))
    with pytest.raises(ValueError):
        C.initialize(C.Configuration(serve_buckets="a,b"))
    cfg = C.initialize(C.Configuration(serve_buckets="32,64"))
    assert C.parse_serve_buckets(cfg.serve_buckets) == (32, 64)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_queue_full_batch_dispatches_immediately():
    svc = ProgramService()
    clock = _FakeClock()
    q = Queue(svc, batch=3, deadline_s=1e9, buckets=(16,), clock=clock)
    t1 = q.submit(Request(op="cholesky", a=_hpd(12, seed=1)))
    t2 = q.submit(Request(op="cholesky", a=_hpd(14, seed=2)))
    assert not t1.done and q.pending() == 2
    t3 = q.submit(Request(op="cholesky", a=_hpd(16, seed=3)))
    assert t1.done and t2.done and t3.done and q.pending() == 0
    assert q.dispatches == 1
    for t in (t1, t2, t3):
        a = np.asarray(t.request.a)
        fac = np.tril(t.result())
        assert fac.shape == a.shape
        np.testing.assert_allclose(fac @ fac.T,
                                   np.tril(a) + np.tril(a, -1).T,
                                   atol=1e-10 * len(a))
        assert t.info == 0 and t.total_s >= 0.0


def test_queue_deadline_determinism_with_fake_clock():
    svc = ProgramService()
    clock = _FakeClock()
    q = Queue(svc, batch=4, deadline_s=0.05, buckets=(16,), clock=clock)
    t1 = q.submit(Request(op="cholesky", a=_hpd(10)))
    clock.t = 0.049
    assert q.poll() == 0 and not t1.done       # under deadline: holds
    clock.t = 0.051
    assert q.poll() == 1 and t1.done           # expired: dispatches
    assert q.dispatches == 1
    # a submit is also a clock edge for OTHER buckets' deadlines
    t2 = q.submit(Request(op="cholesky", a=_hpd(10, seed=4)))
    clock.t = 0.2
    t3 = q.submit(Request(op="eigh", a=_sym(12)))
    assert t2.done                             # cholesky bucket expired
    assert not t3.done                         # eigh bucket is fresh
    q.flush()
    assert t3.done


def test_queue_bucket_keys_separate_ops_dtypes_and_flags():
    svc = ProgramService()
    q = Queue(svc, batch=8, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    q.submit(Request(op="cholesky", a=_hpd(12)))
    q.submit(Request(op="cholesky", a=_hpd(12).astype(np.float32)))
    q.submit(Request(op="cholesky", a=_hpd(12), uplo="U"))
    q.submit(Request(op="eigh", a=_sym(12)))
    q.submit(Request(op="solve", a=_tri(12),
                     b=np.ones((12, 3))))
    assert len(q._pending) == 5               # five distinct bucket keys
    assert q.flush() == 5


def test_queue_solve_roundtrip_with_rhs_bucketing():
    svc = ProgramService()
    q = Queue(svc, batch=2, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    a1, b1 = _tri(12, seed=1), np.random.default_rng(0).standard_normal(
        (12, 5))
    a2, b2 = _tri(10, seed=2), np.random.default_rng(1).standard_normal(
        (10, 7))
    t1 = q.submit(Request(op="solve", a=a1, b=b1, alpha=2.0))
    t2 = q.submit(Request(op="solve", a=a2, b=b2))
    assert t1.done and t2.done                # same (n=16, rhs=8) bucket
    x1, x2 = t1.result(), t2.result()
    assert x1.shape == b1.shape and x2.shape == b2.shape
    np.testing.assert_allclose(np.tril(a1) @ x1, 2.0 * b1, atol=1e-10)
    np.testing.assert_allclose(np.tril(a2) @ x2, b2, atol=1e-10)


def test_rhs_ceiling_is_pow2_not_matrix_bucket():
    """The rhs free-axis width never rounds to the MATRIX bucket list: a
    1-column rhs in a 512-bucket config would otherwise pay 512x the
    rhs work per solve (review finding on the first cut)."""
    from dlaf_tpu.serve import rhs_ceiling

    assert rhs_ceiling(1) == 1
    assert rhs_ceiling(3) == 4
    assert rhs_ceiling(8) == 8
    assert rhs_ceiling(9) == 16
    svc = ProgramService()
    q = Queue(svc, batch=1, deadline_s=1e9, buckets=(512,),
              clock=_FakeClock())
    t = q.submit(Request(op="solve", a=_tri(12),
                         b=np.ones((12, 1))))
    (spec,) = svc.specs()
    assert spec.n == 512 and spec.nrhs == 1   # not 512
    np.testing.assert_allclose(np.tril(_tri(12)) @ t.result(),
                               np.ones((12, 1)), atol=1e-10)


def test_queue_eigh_shape_pad_recovers_leading_pairs():
    """The eigh shape-padding contract: the pad block's eigenvalues sort
    strictly last, so the leading n_req pairs are the request's — pad
    rows of the returned vectors are exactly zero."""
    svc = ProgramService()
    q = Queue(svc, batch=1, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    a = _sym(11, seed=5)
    t = q.submit(Request(op="eigh", a=a))
    w, v = t.result()
    assert w.shape == (11,) and v.shape == (11, 11)
    ws, vs = np.linalg.eigh(a)
    np.testing.assert_allclose(w, ws, atol=1e-12)
    np.testing.assert_allclose(np.abs(v), np.abs(vs), atol=1e-10)
    np.testing.assert_allclose(a @ v, v * w[None, :], atol=1e-11)


def test_queue_eigh_shape_pad_dominant_eigenvalue():
    """Review-finding regression: the pad constant must dominate the
    SPECTRAL RADIUS, not max|A| — the all-ones matrix (rho = n, max|A|
    = 1) must come back with its dominant eigenpair intact."""
    svc = ProgramService()
    q = Queue(svc, batch=1, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    n = 8
    a = np.ones((n, n))
    t = q.submit(Request(op="eigh", a=a))
    w, v = t.result()
    ws, _ = np.linalg.eigh(a)
    np.testing.assert_allclose(w, ws, atol=1e-12)      # incl. lambda = n
    assert abs(w[-1] - n) < 1e-12
    np.testing.assert_allclose(a @ v, v * w[None, :], atol=1e-11)


def test_ticket_result_before_dispatch_raises():
    svc = ProgramService()
    q = Queue(svc, batch=4, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    t = q.submit(Request(op="cholesky", a=_hpd(8)))
    with pytest.raises(RuntimeError, match="still queued"):
        t.result()


def test_queue_rejects_malformed_requests():
    q = Queue(ProgramService(), batch=2, clock=_FakeClock())
    with pytest.raises(Exception):
        q.submit(Request(op="lu", a=_hpd(8)))
    with pytest.raises(Exception):
        q.submit(Request(op="cholesky", a=np.ones((3, 4))))
    with pytest.raises(Exception):
        q.submit(Request(op="solve", a=_tri(8), b=np.ones((5, 2))))
    with pytest.raises(Exception, match="dtype"):
        # mixed dtypes would poison the whole co-batched dispatch deep
        # inside the compiled executable: reject at submit
        q.submit(Request(op="solve", a=_tri(8).astype(np.float32),
                         b=np.ones((8, 2), np.float64)))


def test_dispatch_failure_poisons_tickets_with_cause():
    """A dispatch-time exception must not strand co-batched requests as
    forever-'queued': every ticket carries the cause, result() re-raises
    it, and the queue is not wedged for later requests."""

    class _BoomService(ProgramService):
        def run(self, spec, *args):
            raise RuntimeError("XLA exploded")

    q = Queue(_BoomService(), batch=2, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    t1 = q.submit(Request(op="cholesky", a=_hpd(8, seed=0)))
    with pytest.raises(RuntimeError, match="XLA exploded"):
        q.submit(Request(op="cholesky", a=_hpd(8, seed=1)))
    assert t1.error is not None and not t1.done
    with pytest.raises(RuntimeError, match="dispatch failed") as exc:
        t1.result()
    assert "XLA exploded" in str(exc.value.__cause__)
    assert q.pending() == 0                   # bucket not wedged


def test_queue_threaded_submits_race_free():
    """Concurrent submits into one bucket must never double-pop it: all
    requests dispatch exactly once and every ticket completes."""
    import threading as _threading

    svc = ProgramService()
    q = Queue(svc, batch=4, deadline_s=1e9, buckets=(16,))
    svc.warmup(*q.warmup_specs([Request(op="cholesky", a=_hpd(12))]))
    tickets, errors = [], []

    def worker(seed):
        try:
            tickets.append(q.submit(Request(op="cholesky",
                                            a=_hpd(12, seed=seed))))
        except Exception as e:               # noqa: BLE001 — recorded
            errors.append(e)

    threads = [_threading.Thread(target=worker, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    q.flush()
    assert errors == []
    assert len(tickets) == 16 and all(t.done for t in tickets)
    assert q.dispatches == 4 and q.pending() == 0


# ---------------------------------------------------------------------------
# Queue.drain() — the explicit graceful-shutdown API (ISSUE 18 satellite)
# ---------------------------------------------------------------------------

def test_queue_drain_returns_undispatched_and_poisons_tickets():
    """drain() hands back every UNDISPATCHED (request, ticket) pair in
    submission order, empties the queue, and poisons each ticket with a
    structured DrainedError — result() names the cause instead of
    claiming "still queued"."""
    from dlaf_tpu.health.errors import DrainedError

    svc = ProgramService()
    q = Queue(svc, batch=4, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    done = q.submit(Request(op="cholesky", a=_hpd(12, seed=9)))
    q.flush()                          # dispatched: NOT drainable
    assert done.done
    reqs = [Request(op="cholesky", a=_hpd(12, seed=i)) for i in range(3)]
    reqs.append(Request(op="eigh", a=_sym(12)))
    tickets = [q.submit(r) for r in reqs]
    assert q.pending() == 4

    drained = q.drain()
    assert q.pending() == 0
    assert [r.rid for r, _ in drained] == [r.rid for r in reqs]
    assert [t for _, t in drained] == tickets
    assert done not in [t for _, t in drained]
    for req, t in drained:
        assert not t.done and isinstance(t.error, DrainedError)
        assert t.error.rid == req.rid and t.error.site == "serve.queue"
        assert t.error.bucket_n == 16
        with pytest.raises(RuntimeError,
                           match="drained undispatched") as ei:
            t.result()
        assert ei.value.__cause__ is t.error
    assert {t.error.op for _, t in drained} == {"cholesky", "eigh"}
    # drained tickets never resurface on later clock edges
    assert q.poll(now=1e12) == 0 and q.flush() == 0
    assert q.drain() == []             # idempotent on an empty queue
    # and the queue still serves fresh work afterwards
    t2 = q.submit(Request(op="cholesky", a=_hpd(12, seed=77)))
    q.flush()
    assert t2.done and np.tril(t2.result()).shape == (12, 12)


def test_queue_drain_stats_records_metrics_agree(tmp_path):
    """One drain, three observers — stats()['drained'], the resilience
    ``drain`` records, and ``dlaf_serve_drained_total{op}`` — must all
    report the SAME counts, joinable per request by trace ID."""
    path = str(tmp_path / "drain.jsonl")
    C.initialize(C.Configuration(metrics_path=path, log="off"))
    svc = ProgramService()
    q = Queue(svc, batch=8, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    tickets = [q.submit(Request(op="cholesky", a=_hpd(12, seed=i)))
               for i in range(3)]
    tickets += [q.submit(Request(op="eigh", a=_sym(12, seed=i)))
                for i in range(2)]

    drained = q.drain()
    assert len(drained) == 5
    st = q.stats()
    assert st["pending"] == 0 and st["drained"] == 5
    by_site = {site: b["drained"] for site, b in st["buckets"].items()
               if b["drained"]}
    assert sorted(by_site.values()) == [2, 3]
    assert all(b["depth"] == 0 for b in st["buckets"].values())

    reg = obs.registry()
    assert reg.counter("dlaf_serve_drained_total",
                       op="cholesky").snapshot()["value"] == 3
    assert reg.counter("dlaf_serve_drained_total",
                       op="eigh").snapshot()["value"] == 2
    depth = [m for m in reg.snapshot()
             if m["name"] == "dlaf_serve_depth"]
    assert depth and all(m["value"] == 0.0 for m in depth)

    obs.flush()
    recs = [r for r in obs.read_records(path)
            if r.get("type") == "resilience" and r.get("event") == "drain"]
    assert len(recs) == 5
    assert all(r["site"] == "serve.queue" for r in recs)
    # records ↔ tickets joined by trace ID, one each, attrs name the rid
    assert ({r["trace_id"] for r in recs}
            == {t.trace_id for _, t in drained})
    by_trace = {r["trace_id"]: r for r in recs}
    for req, t in drained:
        attrs = by_trace[t.trace_id]["attrs"]
        assert attrs == {"rid": req.rid, "op": req.op, "bucket_n": 16}
    assert obs.validate_file(path) == []
    assert len({t.trace_id for _, t in drained}) == 5


# ---------------------------------------------------------------------------
# Records, accuracy, and --require-serve
# ---------------------------------------------------------------------------

def _drive_warm_queue(tmp_path, warm=True, accuracy=True):
    path = str(tmp_path / "serve.jsonl")
    C.initialize(C.Configuration(metrics_path=path, program_telemetry=True,
                                 accuracy="1" if accuracy else "0",
                                 log="off"))
    svc = ProgramService()
    q = Queue(svc, batch=3, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    reqs = [Request(op="cholesky", a=_hpd(12 + 2 * (i % 3), seed=i))
            for i in range(6)]
    if warm:
        q.warmup(reqs)
    for r in reqs:
        q.submit(r)
    q.flush()
    obs.flush()
    return path, svc, q


def test_warmed_queue_artifact_passes_require_serve(tmp_path):
    path, svc, q = _drive_warm_queue(tmp_path)
    assert svc.stats()["misses"] == 0 and svc.stats()["hit_rate"] == 1.0
    errors = obs.validate_file(path, require_serve=True)
    assert errors == []
    recs = obs.read_records(path)
    dispatches = [r for r in recs if r.get("type") == "serve"
                  and r.get("event") == "dispatch"]
    requests = [r for r in recs if r.get("type") == "serve"
                and r.get("event") == "request"]
    assert len(requests) == 6 and q.dispatches == len(dispatches) == 2
    assert all(r["cache"] == "hit" for r in dispatches)
    # per-request span records ride alongside the typed serve records
    spans = [r for r in recs if r.get("type") == "span"
             and r.get("name") == "serve.request"]
    assert len(spans) == 6
    # per-request accuracy records: site serve, finite budget, n = the
    # REQUEST's n (not the bucket ceiling)
    accs = [r for r in recs if r.get("type") == "accuracy"
            and r.get("site") == "serve"]
    assert len(accs) == 6
    assert {r["n"] for r in accs} == {12, 14, 16}
    assert all(r["bound_ratio"] < 1.0 for r in accs)


def test_queue_accuracy_records_for_every_op(tmp_path):
    """Per-request accuracy probes for all three ops (the vmapped
    residual programs see ONE lane each — pinned after the CI smoke
    caught batch-axis indexing in the solve/eigh bodies)."""
    path = str(tmp_path / "acc.jsonl")
    C.initialize(C.Configuration(metrics_path=path, accuracy="1",
                                 log="off"))
    svc = ProgramService()
    q = Queue(svc, batch=2, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    rng = np.random.default_rng(0)
    for i in range(2):
        q.submit(Request(op="cholesky", a=_hpd(12, seed=i)))
    for i in range(2):
        q.submit(Request(op="solve", a=_tri(12, seed=i), alpha=2.0,
                         b=rng.standard_normal((12, 3))))
    for i in range(2):
        q.submit(Request(op="eigh", a=_sym(12, seed=i)))
    q.flush()
    obs.flush()
    accs = [r for r in obs.read_records(path)
            if r.get("type") == "accuracy" and r.get("site") == "serve"]
    assert len(accs) == 6
    by_metric = {r["metric"] for r in accs}
    assert by_metric == {"cholesky_residual", "trsm_residual",
                         "eigen_residual"}
    assert all(r["bound_ratio"] < 1.0 for r in accs)


def test_unwarmed_queue_artifact_fails_require_serve(tmp_path):
    path, svc, _ = _drive_warm_queue(tmp_path, warm=False)
    assert svc.stats()["misses"] >= 1
    errors = obs.validate_file(path, require_serve=True)
    assert any("cache miss" in e for e in errors)


def test_evicted_bucket_recompile_fails_require_serve(tmp_path):
    """The CI evict drill's validator leg: a warm stream interrupted by
    an evict shows a miss dispatch + a twice-traced serve site, and
    --require-serve must reject the artifact."""
    path = str(tmp_path / "drill.jsonl")
    C.initialize(C.Configuration(metrics_path=path, program_telemetry=True,
                                 log="off"))
    svc = ProgramService()
    q = Queue(svc, batch=2, deadline_s=1e9, buckets=(16,),
              clock=_FakeClock())
    sample = [Request(op="cholesky", a=_hpd(12))]
    q.warmup(sample)
    (spec,) = q.warmup_specs(sample)
    q.submit(Request(op="cholesky", a=_hpd(12, seed=1)))
    q.submit(Request(op="cholesky", a=_hpd(12, seed=2)))
    assert svc.evict(spec)
    q.submit(Request(op="cholesky", a=_hpd(12, seed=3)))
    q.submit(Request(op="cholesky", a=_hpd(12, seed=4)))
    assert svc.stats()["misses"] == 1
    obs.flush()
    errors = obs.validate_file(path, require_serve=True)
    assert any("cache miss" in e for e in errors)
    assert any("retraced mid-stream" in e for e in errors)


def test_serve_record_schema_rejections():
    from dlaf_tpu.obs.sinks import validate_records

    def rec(**kw):
        base = {"v": 1, "type": "serve", "ts": 1.0}
        base.update(kw)
        return base

    good_d = rec(event="dispatch", op="cholesky", bucket_n=16, nrhs=0,
                 dtype="float64", lanes=2, batch=4, cache="hit",
                 dispatch_s=0.01)
    good_r = rec(event="request", op="cholesky", n=12, bucket_n=16,
                 dtype="float64", queue_s=0.0, total_s=0.01)
    assert validate_records([good_d, good_r]) == []
    assert validate_records([rec(event="nope")])
    assert validate_records([dict(good_d, cache="warm")])
    assert validate_records([dict(good_d, lanes=9)])       # > batch
    assert validate_records([dict(good_d, dispatch_s=float("nan"))])
    assert validate_records([dict(good_d, nrhs=-1)])
    bad_nrhs = dict(good_d)
    del bad_nrhs["nrhs"]
    assert validate_records([bad_nrhs])
    assert validate_records([dict(good_r, bucket_n=8)])    # < n
    bad = dict(good_r)
    del bad["total_s"]
    assert validate_records([bad])


def test_validator_cli_require_serve_flag(tmp_path):
    from dlaf_tpu.obs.validate import main

    path = str(tmp_path / "x.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"v": 1, "type": "log", "ts": 1.0,
                            "level": "info", "logger": "t", "msg": "m",
                            "fields": {}}) + "\n")
    assert main([path]) == 0
    assert main([path, "--require-serve"]) == 1
    assert main([path, "--require-serve", "--history"]) == 2


# ---------------------------------------------------------------------------
# robust_cholesky_batched: per-lane recovery
# ---------------------------------------------------------------------------

def test_robust_batched_all_clean_is_one_attempt():
    a = _hpd_batch(3, 12)
    res = health.robust_cholesky_batched("L", a)
    assert res.attempts == 1 and res.lane_attempts == (1, 1, 1)
    assert res.shifts == (0.0,) and res.infos[0] == (0, 0, 0)
    for i in range(3):
        fac = np.tril(np.asarray(res.out)[i])
        np.testing.assert_allclose(
            fac @ fac.T, np.tril(a[i]) + np.tril(a[i], -1).T, atol=1e-10)


def test_robust_batched_retries_only_failed_lanes(tmp_path):
    """The per-lane contract: clean lanes keep their attempt-0 factor
    BITWISE (they are never re-dispatched), failed lanes recover under
    a shift, and dlaf_retry_total is attributed per lane."""
    C.initialize(C.Configuration(metrics_path=str(tmp_path / "m.jsonl"),
                                 log="off"))
    svc = ProgramService()
    a = _hpd_batch(4, 12)
    a[1] = _hpd(12, seed=20, shift=-80.0)
    a[3] = _hpd(12, seed=21, shift=-80.0)
    plain, _ = cholesky_batched("L", a.copy(), with_info=True, service=svc)
    res = health.robust_cholesky_batched("L", a, service=svc)
    assert res.attempts >= 2
    assert res.lane_attempts[0] == 1 and res.lane_attempts[2] == 1
    assert res.lane_attempts[1] == res.lane_attempts[3] >= 2
    out = np.asarray(res.out)
    np.testing.assert_array_equal(out[0], np.asarray(plain)[0])
    np.testing.assert_array_equal(out[2], np.asarray(plain)[2])
    for i in (1, 3):
        fac = np.tril(out[i])
        shift = res.shifts[res.lane_attempts[i] - 1]
        target = np.tril(a[i]) + np.tril(a[i], -1).T + shift * np.eye(12)
        np.testing.assert_allclose(fac @ fac.T, target, atol=1e-8)
    for lane in (1, 3):
        snap = obs.registry().counter("dlaf_retry_total",
                                      algo="cholesky_batched",
                                      lane=lane).snapshot()
        assert snap["value"] >= 1, (lane, snap)
    snap0 = obs.registry().counter("dlaf_retry_total",
                                   algo="cholesky_batched",
                                   lane=0).snapshot()
    assert snap0["value"] == 0


def test_robust_batched_single_retry_dispatch_reuses_program():
    """One re-dispatch per attempt through the SAME bucket program: the
    retry must be a cache hit, never a second compile."""
    svc = ProgramService()
    a = _hpd_batch(3, 10)
    a[1] = _hpd(10, seed=30, shift=-50.0)
    health.robust_cholesky_batched("L", a, service=svc)
    st = svc.stats()
    assert st["compiles"] == 1 and st["misses"] == 1 and st["hits"] >= 1


def test_robust_batched_exhaustion_raises():
    a = np.stack([_hpd(8), _hpd(8, seed=40, shift=-30.0)])
    with pytest.raises(health.FactorizationError) as exc:
        health.robust_cholesky_batched("L", a, max_attempts=1)
    assert exc.value.attempts == 1 and exc.value.infos == (1,)


def test_robust_batched_argument_validation():
    a = _hpd_batch(2, 8)
    with pytest.raises(ValueError):
        health.robust_cholesky_batched("L", a, max_attempts=0)
    with pytest.raises(ValueError):
        health.robust_cholesky_batched("L", a, shift=0.0)
    with pytest.raises(ValueError):
        health.robust_cholesky_batched("L", a, shift_growth=1.0)
    with pytest.raises(ValueError):
        health.robust_cholesky_batched("L", _hpd(8))


# ---------------------------------------------------------------------------
# bench serve arm + gate leg (aux pins)
# ---------------------------------------------------------------------------

def test_serve_lines_never_take_cholesky_headline():
    """workload="serve" measures requests/s, not GFlop/s: it must never
    surface as the cholesky headline nor enter its history lookup."""
    import bench

    serve_line = {"variant": "serve", "platform": "cpu",
                  "dtype": "float64", "n": 64, "nb": 64, "gflops": 4000.0,
                  "t": 0.001, "ts": "2026-08-04T00:00:00",
                  "source": "bench.py", "workload": "serve",
                  "speedup": 10.0}
    assert bench.assemble_headline([serve_line], 4096, 256) is None
    chol = {"variant": "loop", "platform": "cpu", "dtype": "float64",
            "n": 4096, "nb": 256, "gflops": 8.0, "t": 1.0,
            "ts": "2026-08-04T00:00:00", "source": "bench.py"}
    head = bench.assemble_headline([serve_line, chol], 4096, 256)
    assert head["value"] == 8.0 and "serve" not in head["metric"]


def test_bench_gate_serve_speedup_leg():
    from bench_gate import run_gate

    hist = []
    mk = lambda speedup: {"variant": "serve", "platform": "cpu",
                          "dtype": "float64", "n": 64, "nb": 64,
                          "gflops": 4000.0, "t": 0.001, "ts": "t",
                          "source": "s", "workload": "serve",
                          "speedup": speedup}
    logs = []
    assert run_gate(hist, [mk(3.5)], tolerance=0.1, min_history=3,
                    best_k=3, log=logs.append) == 0
    assert run_gate(hist, [mk(2.2)], tolerance=0.1, min_history=3,
                    best_k=3, log=logs.append) == 1
    # best-of protocol: one slow pass does not trip a key whose best
    # measurement cleared the floor
    assert run_gate(hist, [mk(2.2), mk(3.1)], tolerance=0.1,
                    min_history=3, best_k=3, log=logs.append) == 0
    # a serve line without the field is not a ratio measurement
    no_field = {k: v for k, v in mk(0).items() if k != "speedup"}
    assert run_gate(hist, [no_field], tolerance=0.1, min_history=3,
                    best_k=3, log=logs.append) == 0
    # a non-serve workload never faces the floor
    other = dict(mk(0.5), workload="fpanel")
    assert run_gate(hist, [other], tolerance=0.1, min_history=3,
                    best_k=3, log=logs.append) == 0
    assert any("ISSUE-11" in line for line in logs)


def test_bench_history_path_env_redirects_append(tmp_path):
    """DLAF_BENCH_HISTORY_PATH redirects the durable history append —
    the CI serve bench run must never mutate the git-tracked baseline
    file with container-local numbers (review finding)."""
    import measure_common

    repo_hist = os.path.join(measure_common.repo_root(),
                             ".bench_history.jsonl")
    before = os.path.getsize(repo_hist)
    redirected = tmp_path / "hist.jsonl"
    os.environ["DLAF_BENCH_HISTORY_PATH"] = str(redirected)
    try:
        line = measure_common.append_history(
            "cpu", 64, 64, 100.0, 0.01, source="test", variant="serve",
            workload="serve", extra={"speedup": 5.0})
    finally:
        os.environ.pop("DLAF_BENCH_HISTORY_PATH", None)
    assert os.path.getsize(repo_hist) == before
    from dlaf_tpu.obs import read_history_records

    (rec,) = read_history_records(str(redirected))
    assert rec["gflops"] == 100.0 and rec["speedup"] == line["speedup"]


def test_committed_history_carries_gating_serve_line():
    """The committed .bench_history.jsonl must hold >= 1 serve line
    whose speedup clears the floor — that line keeps the ISSUE-11
    acceptance enforced in every CI --replay."""
    from dlaf_tpu.obs import read_history_records

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".bench_history.jsonl")
    serve_lines = [r for r in read_history_records(path)
                   if r.get("workload") == "serve"]
    assert serve_lines, "no committed serve history line"
    assert any(r.get("speedup", 0) >= 3.0 for r in serve_lines)


# ---------------------------------------------------------------------------
# graphcheck integration
# ---------------------------------------------------------------------------

def test_graphcheck_traces_serve_batched_programs():
    """The audited program matrix includes the serve bucket programs
    (built through the service's own builder), and they audit clean."""
    from dlaf_tpu.analysis import depgraph, graphcheck

    specs = [s for s in graphcheck.program_specs()
             if s.name.startswith("serve.")]
    names = {s.name for s in specs}
    assert {"serve.cholesky.batched.L", "serve.cholesky.batched.U",
            "serve.solve.batched.LLN", "serve.eigh.batched.L"} <= names
    with graphcheck.pinned_native_config():
        for spec in specs:
            fn, args = spec.build()
            jaxpr = depgraph.trace(fn, *args)
            findings = graphcheck.audit_jaxpr(spec.name, jaxpr)
            assert findings == [], (spec.name, findings)


def test_program_builder_shapes_match_spec():
    from dlaf_tpu.serve import program_builder

    spec = solve_spec(batch=3, n=10, nrhs=4, nb=10, dtype="float32",
                      side="R", donate=True)
    fn, args, donate = program_builder(spec)
    assert [tuple(a.shape) for a in args] == [(3, 10, 10), (3, 4, 10),
                                              (3,)]
    assert donate == (1,)
    spec2 = eigh_spec(batch=2, n=8, nb=8, dtype="float64")
    fn2, args2, donate2 = program_builder(spec2)
    assert [tuple(a.shape) for a in args2] == [(2, 8, 8)]
    assert donate2 == ()
    with pytest.raises(ValueError):
        from dlaf_tpu.serve.programs import ProgramSpec
        program_builder(ProgramSpec(op="lu", batch=1, n=4, nb=4,
                                    dtype="float64"))
