"""Cholesky benchmark driver.

TPU-native counterpart of the reference's ``miniapp/miniapp_cholesky.cpp``:
same fenced-timing protocol (device-sync before and after the factorization —
the analog of ``waitLocalTiles()`` + ``MPI_Barrier``, ``:134-146``), same flop
model (``total_ops(n^3/6, n^3/6)``, ``:149-154``), and the same schema for the
per-run output line (``:157-164``):

    [i] <t>s <gflops>GFlop/s <type><uplo> (m,m) (mb,mb) (gr,gc) <threads> <backend>

Run:  python -m dlaf_tpu.miniapp.miniapp_cholesky -m 4096 -b 256
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from .. import config
from ..common.sync import hard_fence
from ..algorithms.cholesky import cholesky
from ..comm.grid import Grid
from ..common.index2d import GlobalElementSize, TileElementSize
from ..matrix.matrix import Matrix
from ..types import total_ops, type_letter
from .generators import hpd_element_fn
from .options import (CheckIterFreq, add_miniapp_arguments,
                      announce_donation, parse_miniapp_options,
                      select_devices)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-m", "--matrix-size", type=int, default=4096,
                   help="matrix size (reference default 4096)")
    p.add_argument("-b", "--block-size", type=int, default=256,
                   help="tile size (reference default 256)")
    p.add_argument("--uplo", choices=["L", "U"], default="L")
    add_miniapp_arguments(p)
    return p


def run(argv=None) -> list[dict]:
    args, extra = build_parser().parse_known_args(argv)
    config.initialize(argv=extra)
    opts = parse_miniapp_options(args)
    devices = select_devices(opts)

    n, nb = args.matrix_size, args.block_size
    grid = Grid(opts.grid_rows, opts.grid_cols, devices=devices,
                ordering=config.get_configuration().grid_ordering)
    use_grid = None if grid.num_devices == 1 else grid

    size = GlobalElementSize(n, n)
    block = TileElementSize(nb, nb)
    ref = Matrix.from_element_fn(hpd_element_fn(n, opts.dtype), size, block,
                                 grid=use_grid, dtype=opts.dtype)
    backend = devices[0].platform
    threads = os.cpu_count()
    results = []
    from ..common.timer import PhaseTimer

    ptimer = PhaseTimer(config.get_configuration().profile_dir or None)
    try:
        return _timed_runs(args, opts, ref, ptimer, backend, threads, results)
    finally:
        ptimer.stop()


def _timed_runs(args, opts, ref, ptimer, backend, threads, results):
    from .. import obs
    from ..obs import accuracy

    n, nb = args.matrix_size, args.block_size
    flops = total_ops(opts.dtype, n**3 / 6, n**3 / 6)
    announce_donation()   # timed runs consume their input copies
    # --dlaf:check (the DLAF_CHECK knob): drive the robustness path by
    # hand — in-graph info detection, shift-retry recovery, finite guards
    # on input/factor — and report info/attempts per run (docs/
    # robustness.md). Off by default: the guard host-syncs by design, and
    # the robust driver cannot donate the run's input (the original must
    # survive for re-shifted retries), so peak HBM is ~one full-matrix
    # buffer higher than the plain donated path — near the single-chip
    # ceiling (N=16384) run WITHOUT the flag.
    robust = config.get_configuration().check
    if robust:
        from ..health import robust_cholesky
    for run_i in range(-opts.nwarmups, opts.nruns):
        mat = ref.with_storage(ref.storage + 0)   # fresh copy per run (:127-128)
        hard_fence(mat.storage)                   # start fence (:134-136)
        t0 = time.perf_counter()
        # per-step span: fenced device wall per timed run, with the
        # reference flop model attached so the JSONL record derives
        # GFlop/s — the per-step artifact the CI smoke gate validates
        step_span = obs.span("miniapp_cholesky.run", flops=flops,
                             run=run_i, warmup=run_i < 0, n=n, nb=nb,
                             uplo=args.uplo,
                             dtype=np.dtype(opts.dtype).name,
                             grid=f"{opts.grid_rows}x{opts.grid_cols}",
                             backend=backend)
        rec = None
        with step_span, ptimer.phase("cholesky.factor", run=run_i):
            if robust:
                rec = robust_cholesky(args.uplo, mat)
                out = rec.matrix
            else:
                # donate: the reference's cholesky overwrites mat_a in
                # place (factorization/cholesky.h:36); this run's fresh
                # copy is dead after the call, and the freed buffer is
                # what lets N=16384 fit the single chip
                out = cholesky(args.uplo, mat, donate=True)
            hard_fence(out.storage)               # end fence (:142-144)
        t = time.perf_counter() - t0
        gflops = flops / t / 1e9
        if run_i < 0:
            continue
        line = (f"[{run_i}] {t:.6f}s {gflops:.2f}GFlop/s "
                f"{type_letter(opts.dtype)}{args.uplo} ({n}, {n}) ({nb}, {nb}) "
                f"({opts.grid_rows}, {opts.grid_cols}) {threads} {backend}")
        if rec is not None:
            line += (f" info={rec.infos[-1]} attempts={rec.attempts}"
                     f" shifts={list(rec.shifts)}")
        print(line, flush=True)
        results.append({"run": run_i, "time_s": t, "gflops": gflops})
        last = run_i == opts.nruns - 1
        checked = opts.check is CheckIterFreq.ALL or \
            (opts.check is CheckIterFreq.LAST and last)
        if accuracy.enabled() and not checked:
            # accuracy telemetry (DLAF_ACCURACY, docs/accuracy.md): one
            # in-graph residual probe per timed run, OUTSIDE the timed
            # region — the paired perf+accuracy record the accuracy gate
            # consumes. O(n^2) device work; never touches the factor.
            # Checked runs skip this: check_cholesky runs the identical
            # probe and emits the record itself.
            value = accuracy.cholesky_residual(args.uplo, ref, out)
            accuracy.emit(
                "miniapp_cholesky", "cholesky_residual", value, n=n, nb=nb,
                c=60.0, dtype=opts.dtype, of=out.storage,
                attrs={"uplo": args.uplo, "run": run_i,
                       "grid": f"{opts.grid_rows}x{opts.grid_cols}"})
        if checked:
            check_cholesky(args.uplo, ref, out)
    # land the counters (collective bytes, tile ops, span histograms) in
    # the artifact now — not at interpreter exit — so library callers and
    # the CI gate read a complete file as soon as run() returns
    obs.flush()
    return results


def check_cholesky(uplo: str, ref: Matrix, out: Matrix) -> None:
    """Residual check |A - L L^H|_F / |A|_F <= c*n*eps (reference
    ``:379-417``) via the shared device estimator
    (:func:`dlaf_tpu.obs.accuracy.cholesky_residual`) — a stochastic
    O(n^2) probe under DLAF_ACCURACY in {0, 1}, the exact Frobenius
    residual under "full"; no full-matrix host fetch either way (the old
    host numpy recompute gathered both matrices and paid an O(n^3)
    gemm). Stdout keeps the historical ``check:`` line contract."""
    from ..obs import accuracy

    n = ref.size.row
    resid = accuracy.cholesky_residual(uplo, ref, out)
    res = accuracy.emit(
        "miniapp_cholesky", "cholesky_residual", resid, n=n,
        nb=ref.block_size.row, c=60.0, dtype=ref.dtype, of=out.storage,
        attrs={"uplo": uplo, "check": True})
    status = "PASSED" if res.passed else "FAILED"
    print(f"check: {status} residual={resid:.3e} tol={res.tol:.3e}{res.eps_label}", flush=True)
    if not res.passed:
        sys.exit(1)


def main(argv=None) -> int:
    """Console-script entry: run() returns per-run results for
    library callers; exit status must not carry that list."""
    run(argv)
    return 0


if __name__ == "__main__":
    main()
