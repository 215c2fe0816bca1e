#!/usr/bin/env python
"""Run-time premium of the telescoped scan builders vs unrolled, on the
8-virtual-device CPU mesh: distributed triangular solve + multiply and
distributed reduction_to_band (done criterion is a
measured premium <= ~1.2x at nt=32, like Cholesky's 1.18x).

Run:  python scripts/dist_scan_premium.py [--nt 32] [--nb 16] [--runs 5]
Self-configures the virtual CPU platform; one JSON line to stdout.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "")
os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def bench(fn, runs):
    fn()  # compile + warm
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nt", type=int, default=32)
    ap.add_argument("--nb", type=int, default=16)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--grid", default="2,4")
    args = ap.parse_args()

    import numpy as np
    import jax

    jax.config.update("jax_enable_x64", True)

    import dlaf_tpu.config as config
    from dlaf_tpu.algorithms.triangular import (triangular_multiply,
                                                triangular_solve)
    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.common.index2d import TileElementSize
    from dlaf_tpu.eigensolver.back_transform import bt_reduction_to_band
    from dlaf_tpu.eigensolver.reduction_to_band import reduction_to_band
    from dlaf_tpu.matrix.matrix import Matrix

    n = args.nt * args.nb
    rng = np.random.default_rng(0)
    a_h = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    b_h = rng.standard_normal((n, n))
    herm_h = rng.standard_normal((n, n))
    herm_h = (herm_h + herm_h.T) / 2
    gr, gc = (int(x) for x in args.grid.split(","))
    grid = Grid(gr, gc)
    ts = TileElementSize(args.nb, args.nb)

    out = {"nt": args.nt, "nb": args.nb, "grid": f"{gr}x{gc}", "cases": {}}
    for mode in ("unrolled", "scan"):
        os.environ["DLAF_DIST_STEP_MODE"] = mode
        config.initialize()
        am = Matrix.from_global(a_h, ts, grid=grid)
        bm = Matrix.from_global(b_h, ts, grid=grid)
        hm = Matrix.from_global(herm_h, ts, grid=grid)

        def run_solve():
            triangular_solve("L", "L", "N", "N", 1.0, am, bm) \
                .storage.block_until_ready()

        def run_mult():
            triangular_multiply("L", "L", "N", "N", 1.0, am, bm) \
                .storage.block_until_ready()

        def run_red2band():
            reduction_to_band(hm).matrix.storage.block_until_ready()

        red = reduction_to_band(hm)

        def run_bt_r2b():
            bt_reduction_to_band(red, bm).storage.block_until_ready()

        for name, fn in (("trsm_LLN", run_solve), ("trmm_LLN", run_mult),
                         ("red2band", run_red2band),
                         ("bt_r2b", run_bt_r2b)):
            t0 = time.perf_counter()
            t = bench(fn, args.runs)
            log(f"{mode} {name}: best {t*1e3:.1f} ms "
                f"(incl. compile {time.perf_counter()-t0:.1f} s)")
            out["cases"].setdefault(name, {})[mode] = t
    for name, d in out["cases"].items():
        d["premium"] = d["scan"] / d["unrolled"]
        log(f"{name}: premium {d['premium']:.2f}x")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
