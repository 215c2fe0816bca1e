#!/usr/bin/env python3
"""Smoke run of dlaf_tpu's main path on the chip: the quickest proof that the
system still starts there.

    python chip_smoke.py               # one TPU chip: phases 1-4
    python chip_smoke.py --multichip   # four chips, Grid(2, 2): phase 5 only

One process, no children, default configuration (every knob ``auto``), all
work through the public entry points (``dlaf_tpu.algorithms.cholesky`` /
``triangular_solve``, ``dlaf_tpu.eigensolver.eigensolver``, and the miniapp
CLIs that wrap them). It refuses to start unless JAX's first device is a TPU.

Every result is compared with a plain float64 reference on the host (numpy
only; no code of the library): random probes of ``A - L L^H``, ``A X - B``,
``A Q - Q diag(lam)`` and ``Q^H Q - I`` (O(n^2) each), plus the eigenvalues
against ``numpy.linalg.eigvalsh``. The tolerances are the repo's own budgets
(``c * n * eps_eff``, miniapp/checks.py) written out below: an f32-grade
answer fails every double-precision phase. The miniapps' own
``--check-result`` runs too and is printed, but does not decide.

Any phase that raises or fails its comparison ends the script non-zero. The
last line of stdout is the one JSON object the driver reads.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")

#: Effective epsilons of the tolerances. f64 on a TPU is double-f32
#: emulation (dlaf_tpu/miniapp/checks.py EMULATED_F64_EPS); f32 is native.
EPS_F64_TPU = 2.0 ** -47
EPS_F32 = 2.0 ** -23
#: ``tol = C * n * eps``: 60 for factorizations and solves, 200 for the
#: eigensolver (miniapp_eigensolver.EIGEN_BUDGETS).
C_FACTOR = 60.0
C_EIGEN = 200.0
PROBES = 8

# Sizes of the real run (ISSUE 22 phases 1-5); the tests rehearse the same
# functions at tiny sizes on the CPU. The tile width is the real one
# everywhere; depth (N) is cut only as far as the 1200 s limit of a COLD run
# forces (PERF.md, PR 22: the f64 Cholesky compiles in 291 s at N=4096 on the
# chip, the eigensolver's programs in 243 s at N=2048, and the f32 Cholesky's
# first call took 840 s at N=4096 against 27.5 s at N=1024).
N_CHOLESKY = 4096           # phases 1, 2 (BASELINE config #1) and 5b
N_CHOLESKY_F32 = 1024       # phases 3 and 5c: four blocked steps
N_EIGEN = 2048
NB = 256
NRHS = 256
N_MULTI_TRSM = 8192         # BASELINE config #2


class SmokeFailure(SystemExit):
    """A phase's comparison with the host reference failed."""

    def __init__(self, msg: str):
        print(f"FAILED: {msg}", flush=True)
        super().__init__(1)


def _eps(dtype, platform: str) -> float:
    if np.dtype(dtype) == np.float32:
        return EPS_F32
    return EPS_F64_TPU if platform == "tpu" else float(np.finfo(np.float64).eps)


def _tol(c: float, n: int, dtype, platform: str) -> float:
    return c * n * _eps(dtype, platform)


def _say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(
        f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in kv.items()), flush=True)


def _hold(phase: str, what: str, value: float, tol: float) -> None:
    _say(phase, check=what, value=float(value), tol=float(tol),
         ok=bool(value <= tol))
    if not value <= tol:            # also catches NaN
        raise SmokeFailure(f"{phase}: {what} = {value:.3e} > tol {tol:.3e}")


def _frob(x) -> float:
    return float(np.linalg.norm(x))


def _probe(n: int, seed: int, k: int = PROBES) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, k))


def _timed(phase: str, what: str, call, reps: int = 2):
    """First call (compile + run) and ``reps`` steady calls, fenced, on
    their own lines; returns the last result."""
    from dlaf_tpu.common.sync import hard_fence

    def once():
        t0 = time.perf_counter()
        out = call()
        hard_fence(*(getattr(o, "storage", o) for o in
                     (out if isinstance(out, tuple) else (out,))))
        return out, time.perf_counter() - t0

    out, first = once()
    _say(phase, program=what, first_call_s=first)
    steady = []
    for _ in range(reps):
        out, t = once()
        steady.append(t)
    run = statistics.median(steady)
    _say(phase, program=what, steady_run_s=run, runs=reps,
         compile_s=max(first - run, 0.0))
    return out


#: JAX's own persistent-compile-cache events, counted per phase so a second
#: run in the same checkout shows its hits.
_CACHE_EVENTS = {"hits": 0, "misses": 0}


def _count_cache_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE_EVENTS["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _CACHE_EVENTS["misses"] += 1


def _cache_line(phase: str) -> None:
    _say(phase, compile_cache_hits=_CACHE_EVENTS["hits"],
         compile_cache_misses=_CACHE_EVENTS["misses"])
    _CACHE_EVENTS.update(hits=0, misses=0)


_ROUTE_COUNTERS = ("dlaf_panel_kernel_total", "dlaf_step_kernel_total")
_SEEN: dict = {}


def _counters(names) -> dict:
    """What the named counters gained since the last call (the registry
    is cumulative; a phase reports its own share)."""
    from dlaf_tpu import obs

    out = {}
    for m in obs.registry().snapshot():
        if m.get("name") not in names:
            continue
        labels = ",".join(f"{k}={v}" for k, v in
                          sorted(m.get("labels", {}).items()))
        key = f"{m['name']}{{{labels}}}"
        gained = m["value"] - _SEEN.get(key, 0)
        _SEEN[key] = m["value"]
        if gained:
            out[key] = gained
    return out


def _no_fallback(phase: str) -> None:
    """The smoke path may not degrade anywhere: no kernel gives way to
    another route, no native host kernel to its numpy twin."""
    bad = _counters(("dlaf_fallback_total",))
    _say(phase, dlaf_fallback_total=sum(bad.values()))
    _cache_line(phase)
    if bad:
        raise SmokeFailure(f"{phase}: degraded path taken: {bad}")


def _hpd(n: int) -> np.ndarray:
    from dlaf_tpu.miniapp.generators import hpd_element_fn

    i, j = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    return np.asarray(hpd_element_fn(n, np.float64)(i, j), dtype=np.float64)


def _matrix(a: np.ndarray, nb: int, dtype, grid=None):
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix

    return Matrix.from_global(np.asarray(a, dtype=dtype),
                              TileElementSize(nb, nb), grid=grid)


def _fresh(m):
    return m.with_storage(m.storage + 0)


def _grid_args(grid) -> list:
    return [] if grid is None else ["--grid-rows", str(grid.size.row),
                                    "--grid-cols", str(grid.size.col)]


def _miniapp(phase: str, module: str, argv: list) -> None:
    """The CLI entry a user would call, one timed run with its own
    residual check (its verdict is printed; a failed check exits non-zero
    by itself)."""
    import importlib

    argv = argv + ["--nruns", "1", "--nwarmups", "0", "--check-result", "last"]
    res = importlib.import_module(f"dlaf_tpu.miniapp.{module}").run(argv)
    _say(phase, miniapp=module, argv="'" + " ".join(argv) + "'",
         time_s=float(res[-1]["time_s"]))


# ---------------------------------------------------------------------------
# phases — functions of their sizes; only main() insists on the chip
# ---------------------------------------------------------------------------

def phase_cholesky(phase: str, dtype, n: int, nb: int, platform: str,
                   grid=None):
    """``A = L L^H`` through ``cholesky("L", ...)``; returns host ``A`` and
    the factor (a Matrix) for the solve phase."""
    from dlaf_tpu.algorithms import cholesky

    a = _hpd(n)
    ref = _matrix(a, nb, dtype, grid)
    out = _timed(phase, "cholesky",
                 lambda: cholesky("L", _fresh(ref), donate=True))
    low = np.tril(np.asarray(out.to_numpy(), dtype=np.float64))
    x = _probe(n, seed=1)
    ax = a @ x
    _hold(phase, "|A x - L(L^H x)|/|A x|",
          _frob(ax - low @ (low.T @ x)) / _frob(ax),
          _tol(C_FACTOR, n, dtype, platform))
    _say(phase, **(_counters(_ROUTE_COUNTERS) or {"route_counters": "none"}))
    _no_fallback(phase)
    _miniapp(phase, "miniapp_cholesky",
             ["-m", str(n), "-b", str(nb), "--type",
              "s" if np.dtype(dtype) == np.float32 else "d"] + _grid_args(grid))
    return a, out


def _hold_solve(phase: str, a: np.ndarray, xm, b: np.ndarray,
                platform: str) -> None:
    """``A X = B`` on random combinations of the right-hand sides."""
    x = np.asarray(xm.to_numpy(), dtype=np.float64)
    w = _probe(b.shape[1], seed=3)
    xw = x @ w
    _hold(phase, "|(A X - B) w|/(|A||X w|)",
          _frob(a @ xw - b @ w) / (_frob(a) * _frob(xw)),
          _tol(C_FACTOR, a.shape[0], xm.dtype, platform))
    _no_fallback(phase)


def phase_solve(phase: str, a: np.ndarray, factor, nrhs: int,
                platform: str) -> None:
    """``A X = B`` with the factor: ``L Y = B`` then ``L^H X = Y``."""
    from dlaf_tpu.algorithms import triangular_solve

    nb, dtype = factor.block_size.row, factor.dtype
    b = _probe(a.shape[0], seed=2, k=nrhs)
    bm = _matrix(b, nb, dtype, factor.grid)

    def solve():
        y = triangular_solve("L", "L", "N", "N", 1.0, factor, _fresh(bm),
                             donate_b=True)
        return triangular_solve("L", "L", "C", "N", 1.0, factor, y,
                                donate_b=True)

    xm = _timed(phase, "triangular_solve(LLNN)+triangular_solve(LLCN)", solve)
    _hold_solve(phase, a, xm, b, platform)


def phase_eigensolver(phase: str, n: int, nb: int, platform: str) -> None:
    """Whole Hermitian eigensolver pipeline, double, with the stage walls
    PhaseTimer fences; must run on the native host kernels."""
    from dlaf_tpu.common.timer import PhaseTimer
    from dlaf_tpu.eigensolver import eigensolver

    x = np.random.default_rng(4).standard_normal((n, n))
    a = (x + x.T) / 2
    ref = _matrix(a, nb, np.float64)
    timers = []

    def solve():
        timers.append(PhaseTimer())
        res = eigensolver("L", _fresh(ref), phases=timers[-1], donate=True)
        return res.eigenvectors, res.eigenvalues

    q, lam = _timed(phase, "eigensolver", solve, reps=1)
    _say(phase, stage_walls="first_call",
         **{k: float(v) for k, v in timers[0].report().items()})
    _say(phase, stage_walls="steady",
         **{k: float(v) for k, v in timers[-1].report().items()})
    q = np.asarray(q.to_numpy(), dtype=np.float64)
    lam = np.asarray(lam, dtype=np.float64)
    tol = _tol(C_EIGEN, n, np.float64, platform)
    w = _probe(n, seed=5)
    qw = q @ w
    na = _frob(a)
    _hold(phase, "|A Q w - Q lam w|/(|A||w|)",
          _frob(a @ qw - q @ (lam[:, None] * w)) / (na * _frob(w)), tol)
    _hold(phase, "|Q^H Q w - w|/|w|", _frob(q.T @ qw - w) / _frob(w), tol)
    lam_ref = np.linalg.eigvalsh(a)
    _hold(phase, "max|lam - eigvalsh(A)|/max|lam|",
          float(np.abs(lam - lam_ref).max() / np.abs(lam_ref).max()), tol)
    _no_fallback(phase)
    _miniapp(phase, "miniapp_eigensolver", ["-m", str(n), "-b", str(nb)])


def _spread(phase: str, arr, devices) -> None:
    """The work is really spread: the result's sharding names every
    device, its shards are equal in size, and every device holds bytes."""
    shards = arr.addressable_shards
    on = {s.device for s in shards}
    sizes = {int(np.prod(s.data.shape)) for s in shards}
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in devices]
    _say(phase, shard_devices=len(on), shard_sizes=sorted(sizes),
         bytes_in_use=in_use)
    if on != set(devices) or len(sizes) != 1:
        raise SmokeFailure(f"{phase}: result not spread evenly over "
                           f"{len(devices)} devices: {len(on)} devices, "
                           f"shard sizes {sorted(sizes)}")
    if in_use and not all(in_use) and devices[0].platform != "cpu":
        raise SmokeFailure(f"{phase}: a device holds no bytes: {in_use}")


def phase_multichip(platform: str, devices, n_trsm: int, n_chol: int,
                    n_chol_f32: int, nb: int) -> None:
    """Phase 5: Grid(2, 2) over four devices — distributed triangular
    solve (double) and distributed Cholesky (double, f32)."""
    from dlaf_tpu import config
    from dlaf_tpu.algorithms import triangular_solve
    from dlaf_tpu.comm.grid import Grid

    grid = Grid(2, 2, devices=devices)

    # (a) BASELINE config #2: T X = B, double, 2x2
    phase = "multichip_trsm_f64"
    n = n_trsm
    i, j = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    t = np.tril(1.0 / (1.0 + np.abs(i - j)) + 2.0 * n * (i == j))
    b = _probe(n, seed=6, k=n)
    tm, bm = _matrix(t, nb, np.float64, grid), _matrix(b, nb, np.float64, grid)
    _say(phase, step_mode=config.resolve_step_mode(-(-n // nb), platform))
    xm = _timed(phase, "triangular_solve(LLNN) 2x2",
                lambda: triangular_solve("L", "L", "N", "N", 1.0, tm,
                                         _fresh(bm), donate_b=True))
    _spread(phase, xm.storage, devices)
    _hold_solve(phase, t, xm, b, platform)
    _miniapp(phase, "miniapp_triangular_solver",
             ["-m", str(n), "-n", str(n), "-b", str(nb)] + _grid_args(grid))
    del tm, bm, xm, t, b

    # (b), (c) distributed Cholesky (unrolled whatever dist_step_mode says)
    for phase, dtype, n in (("multichip_cholesky_f64", np.float64, n_chol),
                            ("multichip_cholesky_f32", np.float32,
                             n_chol_f32)):
        # Cholesky does not follow dist_step_mode: unrolled unless
        # cholesky_trailing=scan
        _say(phase, step_mode="scan" if config.get_configuration()
             .cholesky_trailing == "scan" else "unrolled")
        _, out = phase_cholesky(phase, dtype, n, nb, platform, grid=grid)
        _spread(phase, out.storage, devices)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip Grid(2, 2) phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX's first device is {devices[0].platform!r}, "
              "not a TPU; nothing was run", file=sys.stderr)
        return 2
    need = 4 if args.multichip else 1
    if len(devices) != need:
        print(f"chip_smoke: this run needs exactly {need} chip(s), JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    # the route counters only count while the metrics sink is on
    os.makedirs(OUT_DIR, exist_ok=True)
    os.environ.setdefault("DLAF_METRICS_PATH", os.path.join(
        OUT_DIR, "chip_smoke_multichip.jsonl" if args.multichip
        else "chip_smoke.jsonl"))

    import dlaf_tpu
    from dlaf_tpu import obs

    jax.monitoring.register_event_listener(_count_cache_event)
    t0 = time.perf_counter()
    dlaf_tpu.initialize()
    print(f"[device] platform={devices[0].platform} "
          f"kind={devices[0].device_kind!r} count={len(devices)} "
          f"jax={jax.__version__} "
          f"cache_dir={jax.config.jax_compilation_cache_dir}", flush=True)
    platform = devices[0].platform
    if args.multichip:
        phase_multichip(platform, devices, N_MULTI_TRSM, N_CHOLESKY,
                        N_CHOLESKY_F32, NB)
    else:
        a, factor = phase_cholesky("cholesky_f64", np.float64, N_CHOLESKY,
                                   NB, platform)
        phase_solve("solve_f64", a, factor, NRHS, platform)
        del a, factor
        phase_cholesky("cholesky_f32", np.float32, N_CHOLESKY_F32, NB,
                       platform)
        phase_eigensolver("eigensolver_f64", N_EIGEN, NB, platform)
    obs.flush()
    print(f"[total] wall_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
