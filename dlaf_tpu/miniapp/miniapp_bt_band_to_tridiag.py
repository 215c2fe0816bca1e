"""Back-transformation (band -> tridiag stage) benchmark driver.

TPU-native counterpart of the reference's
``miniapp/miniapp_bt_band_to_tridiag.cpp`` (195 LoC): times the application
of the bulge-chasing Householder vectors to an eigenvector matrix
(``bt_band_to_tridiag``), with the chase itself as untimed setup. Flop
model: sum_s ceil((n-1-s)/b) ~ n^2/(2b) live reflectors of length b applied
to m columns at 4bm real ops each -> muls = adds = n^2 m (the entry span's
model, ``back_transform._bt_b2t_entry_span``).

Run:  python -m dlaf_tpu.miniapp.miniapp_bt_band_to_tridiag -m 4096 -b 128
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from .. import config
from ..common.sync import hard_fence
from ..common.index2d import TileElementSize
from ..comm.grid import Grid
from ..eigensolver.back_transform import bt_band_to_tridiag
from ..eigensolver.band_to_tridiag import band_to_tridiag
from ..matrix.matrix import Matrix
from ..types import total_ops, type_letter
from .miniapp_band_to_tridiag import make_band
from .options import CheckIterFreq, add_miniapp_arguments, parse_miniapp_options, select_devices


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-m", "--matrix-size", type=int, default=4096,
                   help="rows of the band matrix / eigenvector matrix")
    p.add_argument("-n", "--evec-cols", type=int, default=0,
                   help="eigenvector columns (default: matrix size)")
    p.add_argument("-b", "--band-size", type=int, default=128)
    add_miniapp_arguments(p)
    return p


def run(argv=None) -> list[dict]:
    args, extra = build_parser().parse_known_args(argv)
    config.initialize(argv=extra)
    opts = parse_miniapp_options(args)
    devices = select_devices(opts)
    n, b = args.matrix_size, args.band_size
    m = args.evec_cols or n

    band = make_band(n, b, opts.dtype)
    tri = band_to_tridiag(band, b)          # untimed setup (own miniapp)
    rng = np.random.default_rng(1)
    e0 = rng.standard_normal((n, m)).astype(opts.dtype)

    grid = None
    if opts.grid_rows * opts.grid_cols > 1:
        grid = Grid(opts.grid_rows, opts.grid_cols, devices=devices)
    em = Matrix.from_global(e0, TileElementSize(b, b), grid=grid)

    backend = devices[0].platform
    results = []
    for run_i in range(-opts.nwarmups, opts.nruns):
        e_in = em.with_storage(em.storage + 0)
        hard_fence(e_in.storage)
        t0 = time.perf_counter()
        out = bt_band_to_tridiag(tri, e_in)
        hard_fence(out.storage)
        t = time.perf_counter() - t0
        gflops = total_ops(opts.dtype, n**2 * m, n**2 * m) / t / 1e9
        if run_i < 0:
            continue
        print(f"[{run_i}] {t:.6f}s {gflops:.2f}GFlop/s "
              f"{type_letter(opts.dtype)} ({n}, {m}) band={b} "
              f"({opts.grid_rows}, {opts.grid_cols}) {os.cpu_count()} {backend}",
              flush=True)
        results.append({"run": run_i, "time_s": t, "gflops": gflops})
        last = run_i == opts.nruns - 1
        if opts.check is CheckIterFreq.ALL or (opts.check is CheckIterFreq.LAST and last):
            check(tri, e0, out)
    return results


def check(tri, e0, out) -> None:
    """|Q E - out| with the dense Q materialized by applying the reflectors
    to the identity, then one reference gemm (host-computed by
    construction; recorded through the shared accuracy emitter)."""
    from ..obs import accuracy

    n = tri.d.shape[0]
    qmat = np.asarray(bt_band_to_tridiag(tri, np.eye(n, dtype=out.dtype)))
    qe = qmat @ np.asarray(e0, dtype=out.dtype)
    got = out.to_numpy()
    resid = np.linalg.norm(got - qe) / max(np.linalg.norm(qe), 1e-30)
    rec = accuracy.emit("miniapp_bt_band_to_tridiag", "bt_residual", resid,
                        n=n, nb=out.block_size.row, c=100.0,
                        dtype=out.dtype, of=out.storage,
                        attrs={"check": True})
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
