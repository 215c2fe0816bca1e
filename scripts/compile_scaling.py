#!/usr/bin/env python
"""Compile-time scaling of the trace-unrolled distributed factorization.

Round-1 review item 5: all distributed algorithms unroll the per-k loop at
trace time, so program size grows with the tile count nt; nothing showed
XLA compile time stays sane at BASELINE-scale tile counts (nt = 64-128).
This script AOT-compiles (``jax.jit(...).lower().compile()`` — no
execution) distributed Cholesky on the 8-virtual-device CPU mesh at a
sweep of nt, with and without the persistent compilation cache, and
reports trace time, compile time, and compiled program size.

Run:  python scripts/compile_scaling.py [--nt 16,32,64,128]
(self-configures the virtual CPU platform; results to stderr + one JSON
line to stdout for DESIGN.md).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nt", default="16,32,64,128")
    ap.add_argument("--nb", type=int, default=8,
                    help="tile size (compile cost depends on tile COUNT, "
                         "not tile size; small tiles keep tracing cheap)")
    ap.add_argument("--cache", default="")
    ap.add_argument("--mode", default="unrolled",
                    choices=("unrolled", "scan"),
                    help="step formulation: unrolled per-k trace or the "
                         "lax.scan'd uniform step (O(1) compile)")
    args = ap.parse_args()

    if not os.environ.get("_DLAF_COMPILE_SCALING_CHILD"):
        import subprocess

        from dlaf_tpu.tpu_info import cpu_subprocess_env

        env = cpu_subprocess_env(n_virtual_devices=8)
        env["_DLAF_COMPILE_SCALING_CHILD"] = "1"
        if args.cache:
            # JAX's own spelling: config.initialize() then sets no directory
            env["JAX_COMPILATION_CACHE_DIR"] = args.cache
        rc = subprocess.run([sys.executable] + sys.argv, env=env).returncode
        sys.exit(rc)

    import jax

    jax.config.update("jax_enable_x64", True)

    import numpy as np

    import dlaf_tpu.config as config
    from dlaf_tpu.algorithms.cholesky import (_build_dist_cholesky,
                                              _build_dist_cholesky_scan)
    from dlaf_tpu.comm.grid import Grid
    from dlaf_tpu.common.index2d import (GlobalElementSize, GridSize2D,
                                         RankIndex2D, TileElementSize)
    from dlaf_tpu.matrix.distribution import Distribution
    from dlaf_tpu.matrix.tiling import storage_tile_grid

    config.initialize()
    grid = Grid(2, 4)
    results = []
    for nt in [int(x) for x in args.nt.split(",")]:
        nb = args.nb
        n = nt * nb
        dist = Distribution(size=GlobalElementSize(n, n),
                            block_size=TileElementSize(nb, nb),
                            grid_size=GridSize2D(2, 4),
                            rank=RankIndex2D(0, 0),
                            source_rank=RankIndex2D(0, 0))
        sr, sc, _, _ = storage_tile_grid(dist)
        if args.mode == "scan":
            fn = _build_dist_cholesky_scan(dist, grid.mesh, "L")
        else:
            fn = _build_dist_cholesky(dist, grid.mesh, "L", use_pallas=False,
                                      pallas_interpret=True)
        x = jax.ShapeDtypeStruct((sr, sc, nb, nb), np.float64)
        # the timed lower/compile + memory_analysis plumbing is the
        # library's now (dlaf_tpu.obs.telemetry, ISSUE 7 satellite);
        # with DLAF_PROGRAM_TELEMETRY=1 each point also lands as a
        # program record in the DLAF_METRICS_PATH artifact
        from dlaf_tpu.obs import telemetry

        prog = telemetry.aot_compile(
            f"compile_scaling.{args.mode}", jax.jit(fn), x)
        size = int((prog.memory or {}).get("code", -1))
        row = {"nt": nt, "mode": args.mode,
               "trace_s": round(prog.trace_s, 2),
               "compile_s": round(prog.compile_s, 2), "code_bytes": size}
        results.append(row)
        log(f"nt={nt}: trace {prog.trace_s:.1f}s, compile "
            f"{prog.compile_s:.1f}s, "
            f"code {size / 1e6 if size > 0 else -1:.1f} MB")
    print(json.dumps({"platform": "cpu-mesh8", "nb": args.nb,
                      "cache": bool(args.cache), "rows": results}),
          flush=True)


if __name__ == "__main__":
    main()
