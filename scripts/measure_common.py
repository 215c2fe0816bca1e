"""Shared measurement protocol for the hardware scripts.

The fenced ``best_time`` here is the measurement contract the bench
artifacts cite: 1 warmup (compile) + ``REPS`` timed iterations, each
bounded by :func:`dlaf_tpu.common.sync.hard_fence`. Scripts must share
this module rather than copying it so the protocol cannot drift between
artifacts.
"""

from __future__ import annotations

import os
import sys
import time

REPS = int(os.environ.get("DLAF_SWEEP_REPS", "4"))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def best_time(fn, *args, reps: int = None, return_last: bool = False):
    """min over ``reps`` fenced timings after one warmup call.
    ``return_last=True`` returns ``(t, out)`` with the last run's output,
    so callers that also validate the result don't pay an extra run."""
    from dlaf_tpu.common.sync import hard_fence

    out = fn(*args)
    hard_fence(*(out if isinstance(out, tuple) else (out,)))
    times = []
    for _ in range(REPS if reps is None else reps):
        t0 = time.perf_counter()
        out = fn(*args)
        hard_fence(*(out if isinstance(out, tuple) else (out,)))
        times.append(time.perf_counter() - t0)
    return (min(times), out) if return_last else min(times)


def append_history(platform: str, n: int, nb: int, gflops: float, t: float,
                   source: str, variant: str = "ozaki",
                   dtype: str = "float64", donate: bool = None,
                   workload: str = None, extra: dict = None):
    """Append one measurement to the git-tracked append-only history log
    and return the line dict (line schema owned by ``dlaf_tpu.obs.sinks``
    — bench.py prints the returned dict rather than rebuilding it), so
    a measurement that landed is kept whatever happens to the rest of
    the sweep. Nothing replays this file: it is a record, not a source
    of headlines.

    The line is schema-validated BEFORE it is written
    (``obs.append_history_line``): a non-finite measurement raises
    ValueError here, loudly, instead of landing in the log and silently
    skewing every later bench-gate baseline. Disk errors stay non-fatal (the measurement survives on
    stdout/artifact)."""
    import time as _time

    line = {"variant": variant, "platform": platform, "dtype": dtype,
            "n": n, "nb": nb, "gflops": round(float(gflops), 2),
            "t": float(t),
            # UTC: mfu_table's PEEL_FIX_TS pre/post-fix cutoff is UTC-anchored
            "ts": _time.strftime("%Y-%m-%dT%H:%M:%S", _time.gmtime()),
            "source": source}
    if donate is not None:
        # the donated program aliases its input (different measured program
        # from the pre-donation entries in this log — round-4 advisory):
        # record the flag so cross-round comparisons can tell them apart
        line["donate"] = bool(donate)
    if workload is not None:
        # non-cholesky workloads (bench.py's eigensolver stage arms carry
        # different flop models): labeled so the cholesky headline and
        # its replayed-history lookup never pick them up
        line["workload"] = str(workload)
    if extra:
        # workload-specific side fields (e.g. the serve arm's
        # batched-vs-singles speedup that scripts/bench_gate.py holds to
        # the ISSUE-11 floor); never part of the required line schema,
        # and never allowed to shadow it
        line = {**{k: v for k, v in extra.items() if k not in line}, **line}
    from dlaf_tpu.obs import append_history_line

    # DLAF_BENCH_HISTORY_PATH redirects the durable log (CI runs the
    # serve bench arm for the speedup gate and must not mutate the
    # git-tracked baseline file with container-local numbers — the gate
    # reads the obs artifact's bench_result records, not the history)
    path = os.environ.get("DLAF_BENCH_HISTORY_PATH") or os.path.join(
        repo_root(), ".bench_history.jsonl")
    try:
        append_history_line(path, line)
    except OSError as e:
        log(f"history append failed: {e!r}")
    return line


def append_accuracy_history(platform: str, site: str, metric: str, n: int,
                            nb: int, value: float, bound_ratio: float,
                            source: str, dtype: str = "float64"):
    """Append one accuracy measurement to the git-tracked append-only
    accuracy history (``.accuracy_history.jsonl`` — the drift baseline of
    ``scripts/accuracy_gate.py``). Line schema owned by
    ``dlaf_tpu.obs.sinks`` (kind="accuracy", the same validating reader
    the gates share); a non-finite value raises here, loudly, instead of
    poisoning every later drift baseline. Disk errors stay non-fatal."""
    import time as _time

    line = {"site": site, "metric": metric, "platform": platform,
            "dtype": dtype, "n": n, "nb": nb, "value": float(value),
            "bound_ratio": float(bound_ratio),
            "ts": _time.strftime("%Y-%m-%dT%H:%M:%S", _time.gmtime()),
            "source": source}
    from dlaf_tpu.obs import append_history_line

    try:
        append_history_line(os.path.join(repo_root(),
                                         ".accuracy_history.jsonl"), line,
                            kind="accuracy")
    except OSError as e:
        log(f"accuracy history append failed: {e!r}")
    return line


def peel(x, s: int):
    """Stacked int8 Ozaki slices + the row scale (micro-kernel input)."""
    import jax.numpy as jnp

    from dlaf_tpu.tile_ops import ozaki as oz

    sa = oz._scale(x, axis=-1)
    return jnp.stack(oz._peel_slices(oz._normalize(x, sa), s)), sa


def cholesky_arm(slices: int, dot: str, *, n: int = 4096,
                 nb: int = 256, source: str, extra_env: dict = None):
    """One config-#1 Cholesky measurement under the given ozaki knobs,
    with the miniapp-grade residual check — THE shared protocol for every
    script's full-cholesky arm (probe-identical by construction, per this
    module's no-copy contract). Returns ``{t, gflops, residual, tol,
    check}``; on a passing TPU run the result is appended to the durable
    history as ``"<source> slices=...,dot=..."``. Knobs are
    restored and config re-initialized on exit."""
    import jax
    import numpy as np

    from dlaf_tpu import config
    from dlaf_tpu.algorithms.cholesky import cholesky
    from dlaf_tpu.common.index2d import GlobalElementSize, TileElementSize
    from dlaf_tpu.matrix.matrix import Matrix
    from dlaf_tpu.miniapp.checks import effective_eps
    from dlaf_tpu.miniapp.generators import hpd_element_fn
    from dlaf_tpu.types import total_ops

    extra_env = dict(extra_env or {})
    key = f"slices={slices},dot={dot}" + "".join(
        f",{k.removeprefix('DLAF_').lower()}={v}"
        for k, v in sorted(extra_env.items()))
    for k, v in extra_env.items():
        os.environ[k] = v
    os.environ["DLAF_CHOLESKY_TRAILING"] = "ozaki"
    os.environ["DLAF_F64_GEMM_SLICES"] = str(slices)
    os.environ["DLAF_OZAKI_DOT"] = dot
    config.initialize()
    try:
        ref = Matrix.from_element_fn(
            hpd_element_fn(n, np.float64), GlobalElementSize(n, n),
            TileElementSize(nb, nb), dtype=np.float64)

        def run(st):
            return cholesky("L", ref.with_storage(st)).storage

        t, last = best_time(run, ref.storage + 0, return_last=True)
        g = total_ops(np.float64, n**3 / 6, n**3 / 6) / t / 1e9
        lfac = np.tril(np.asarray(ref.with_storage(last).to_numpy()))
        aref = np.asarray(ref.to_numpy())
        ah = np.tril(aref) + np.tril(aref, -1).T
        resid = float(np.linalg.norm(lfac @ lfac.T - ah)
                      / np.linalg.norm(ah))
        # judge tolerance from the devices that produced the result
        # (`of=last`), not the process default backend
        eps, _ = effective_eps(np.float64, of=last)
        tol = 60 * n * eps
        out = {"t": float(t), "gflops": float(g), "residual": resid,
               "tol": float(tol), "check": bool(resid < tol)}
        log(f"cholesky N={n} {key}: {t:.4f}s {g:.1f} GF/s "
            f"residual={resid:.3e} tol={tol:.3e} "
            f"({'PASS' if out['check'] else 'FAIL'})")
        if jax.devices()[0].platform == "tpu" and out["check"]:
            append_history("tpu", n, nb, g, t, f"{source} {key}")
            # paired accuracy entry: every durable perf point carries its
            # residual grade, so accuracy_gate's drift baseline grows
            # alongside the bench one (docs/accuracy.md)
            append_accuracy_history("tpu", "cholesky_arm",
                                    "cholesky_residual", n, nb, resid,
                                    resid / tol, f"{source} {key}")
        return out
    finally:
        for k_ in ("DLAF_CHOLESKY_TRAILING", "DLAF_F64_GEMM_SLICES",
                   "DLAF_OZAKI_DOT",
                   *extra_env):
            os.environ.pop(k_, None)
        config.initialize()
