"""Reduction-to-band benchmark driver.

TPU-native counterpart of the reference's
``miniapp/miniapp_reduction_to_band.cpp`` (204 LoC). Flop model: the
two-sided blocked Householder reduction costs ~4/3 n^3 (muls+adds evenly
split). BASELINE config #4: d, N=16384, nb=512, 4x4 grid.

Run:  python -m dlaf_tpu.miniapp.miniapp_reduction_to_band -m 16384 -b 512 \
          --grid-rows 4 --grid-cols 4
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from .. import config
from ..common.sync import hard_fence
from ..comm.grid import Grid
from ..common.index2d import GlobalElementSize, TileElementSize
from ..eigensolver.reduction_to_band import reduction_to_band
from ..matrix.matrix import Matrix
from ..types import total_ops, type_letter
from .options import (CheckIterFreq, add_miniapp_arguments,
                      announce_donation, parse_miniapp_options,
                      select_devices)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-m", "--matrix-size", type=int, default=4096)
    p.add_argument("-b", "--block-size", type=int, default=256,
                   help="tile size (reference --block-size)")
    p.add_argument("--band-size", type=int, default=-1,
                   help="bandwidth; negative = block-size (reference "
                        "--band-size; must divide block-size; unlike the "
                        "reference this also works distributed). The "
                        "ceil(n/band)-1 panel steps are unrolled at trace "
                        "time below config.resolve_step_mode's threshold "
                        "(dist_step_mode auto: 32 panels on a TPU, 128 "
                        "elsewhere) and run as telescoped lax.scan "
                        "segments from it on, so the compile time stops "
                        "growing with the panel count")
    add_miniapp_arguments(p)
    return p


def run(argv=None) -> list[dict]:
    args, extra = build_parser().parse_known_args(argv)
    config.initialize(argv=extra)
    opts = parse_miniapp_options(args)
    devices = select_devices(opts)

    n, nb = args.matrix_size, args.block_size
    band = nb if args.band_size < 0 else args.band_size
    grid = Grid(opts.grid_rows, opts.grid_cols, devices=devices,
                ordering=config.get_configuration().grid_ordering)
    use_grid = None if grid.num_devices == 1 else grid

    def fn(i, j):  # Hermitian analytic setter
        return np.cos(0.001 * (i * 31 + j * 17)) + np.cos(0.001 * (j * 31 + i * 17))

    ref = Matrix.from_element_fn(fn, GlobalElementSize(n, n),
                                 TileElementSize(nb, nb), grid=use_grid,
                                 dtype=opts.dtype)
    backend = devices[0].platform
    results = []
    announce_donation()   # timed runs consume their input copies
    for run_i in range(-opts.nwarmups, opts.nruns):
        mat = ref.with_storage(ref.storage + 0)
        hard_fence(mat.storage)
        t0 = time.perf_counter()
        # donate: this run's fresh copy is dead after the call (the
        # reference overwrites mat_a with V/R in place); frees one
        # full-matrix HBM buffer — needed headroom at n=16384 single-chip
        red = reduction_to_band(mat, band_size=band, donate=True)
        hard_fence(red.matrix.storage)
        t = time.perf_counter() - t0
        gflops = total_ops(opts.dtype, 2 * n**3 / 3, 2 * n**3 / 3) / t / 1e9
        if run_i < 0:
            continue
        print(f"[{run_i}] {t:.6f}s {gflops:.2f}GFlop/s "
              f"{type_letter(opts.dtype)}L ({n}, {n}) ({nb}, {nb}) "
              f"({opts.grid_rows}, {opts.grid_cols}) {os.cpu_count()} {backend}",
              flush=True)
        results.append({"run": run_i, "time_s": t, "gflops": gflops})
        last = run_i == opts.nruns - 1
        if opts.check is CheckIterFreq.ALL or (opts.check is CheckIterFreq.LAST and last):
            check(ref, red, n, band)
    return results


def check(ref, red, n, band) -> None:
    """Eigenvalues of the band matrix must match the input's (an
    eigenvalue-set comparison — host-computed by construction; recorded
    through the shared accuracy emitter, docs/accuracy.md)."""
    from ..obs import accuracy

    a = ref.to_numpy()
    full = red.matrix.to_numpy()
    bd = np.zeros_like(a)
    for r in range(band + 1):
        d = np.diagonal(full, -r)
        bd += np.diag(d, -r)
        if r:
            bd += np.diag(d.conj(), r)
    w1 = np.linalg.eigvalsh(bd)
    w2 = np.linalg.eigvalsh(a)
    resid = np.abs(w1 - w2).max() / max(np.abs(w2).max(), 1e-30)
    rec = accuracy.emit("miniapp_reduction_to_band", "eigenvalue_drift",
                        resid, n=n, nb=ref.block_size.row, c=100.0,
                        dtype=a.dtype, of=red.matrix.storage,
                        attrs={"band": band, "check": True})
    status = "PASSED" if rec.passed else "FAILED"
    print(f"check: {status} residual={resid:.3e} tol={rec.tol:.3e}{rec.eps_label}", flush=True)
    if not rec.passed:
        sys.exit(1)


def main(argv=None) -> int:
    """Console-script entry: run() returns per-run results for
    library callers; exit status must not carry that list."""
    run(argv)
    return 0


if __name__ == "__main__":
    main()
