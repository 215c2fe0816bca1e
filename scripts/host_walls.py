#!/usr/bin/env python
"""North-star host-stage walls: the native bulge chase
at n=65536 and the D&C secular-threshold sweep, measured on the CPU
backend. Appends one JSON line per step to stdout as it lands (wedge-proof)
and aborts between steps if the TPU measurement session has started
(``.session4_auto`` appears) — host walls must not contend with silicon
numbers on this 1-core box.

Run:  python scripts/host_walls.py [--skip-chase] [--dnc-n 16384]
"""

import argparse
import json
import os
import resource
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(rec):
    print(json.dumps(rec), flush=True)


def rss_gb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-chase", action="store_true")
    ap.add_argument("--chase-n", type=int, default=65536)
    ap.add_argument("--band", type=int, default=128)
    ap.add_argument("--dnc-n", type=int, default=16384)
    ap.add_argument("--thresholds", default="2048,4096,8192")
    ap.add_argument("--dnc-big", type=int, default=0,
                    help="optional final single D&C run at this n")
    args = ap.parse_args()

    import numpy as np
    import jax

    jax.config.update("jax_enable_x64", True)
    import dlaf_tpu.config as config

    config.initialize()

    if not args.skip_chase:
        from dlaf_tpu.eigensolver.band_to_tridiag import band_to_tridiag

        n, b = args.chase_n, args.band
        rng = np.random.default_rng(0)
        band = rng.standard_normal((b + 1, n))
        band[0] += 2 * b  # diagonally dominant, well-scaled
        log(f"chase n={n} b={b} (native, chase_threads=auto on "
            f"{os.cpu_count()} core(s))")
        t0 = time.perf_counter()
        res = band_to_tridiag(band, b)
        t = time.perf_counter() - t0
        emit({"step": "chase", "n": n, "b": b, "wall_s": round(t, 1),
              "rss_gb": round(rss_gb(), 1), "cores": os.cpu_count(),
              "d0": float(res.d[0])})
        log(f"chase: {t:.0f} s, rss {rss_gb():.1f} GB")

    from dlaf_tpu.eigensolver.tridiag_solver import tridiag_solver

    n = args.dnc_n
    rng = np.random.default_rng(1)
    d = rng.standard_normal(n)
    e = rng.standard_normal(n - 1)
    for thr in [int(x) for x in args.thresholds.split(",") if x]:
        os.environ["DLAF_SECULAR_DEVICE_MIN_K"] = str(thr)
        config.initialize()
        t0 = time.perf_counter()
        w, q = tridiag_solver(d, e, nb=512)
        w = np.asarray(w)
        t = time.perf_counter() - t0
        # sampled residual: a few columns of T q - w q
        cols = [0, n // 2, n - 1]
        qh = np.asarray(q[:, cols])
        tq = d[:, None] * qh
        tq[1:] += e[:, None] * qh[:-1]
        tq[:-1] += e[:, None] * qh[1:]
        resid = float(np.max(np.abs(tq - qh * w[cols][None, :])))
        emit({"step": "dnc", "n": n, "secular_device_min_k": thr,
              "wall_s": round(t, 1), "rss_gb": round(rss_gb(), 1),
              "sampled_resid": resid})
        log(f"dnc n={n} thr={thr}: {t:.0f} s, resid {resid:.1e}")
        del w, q, qh, tq

    if args.dnc_big:
        os.environ.pop("DLAF_SECULAR_DEVICE_MIN_K", None)
        config.initialize()
        n = args.dnc_big
        d = rng.standard_normal(n)
        e = rng.standard_normal(n - 1)
        t0 = time.perf_counter()
        w, q = tridiag_solver(d, e, nb=512)
        np.asarray(w)
        t = time.perf_counter() - t0
        emit({"step": "dnc_big", "n": n, "wall_s": round(t, 1),
              "rss_gb": round(rss_gb(), 1)})
        log(f"dnc n={n}: {t:.0f} s")


if __name__ == "__main__":
    main()
