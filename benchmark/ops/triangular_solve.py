"""``T X = B`` through ``dlaf_tpu.algorithms.triangular_solve``
(miniapp_triangular_solver), ``B`` overwritten by ``X``.

Input: ``T = tril(G, -1) + 2 n I`` and ``B`` standard normal, both from the
seed. ``T = 2n (I + N/2n)`` with ``|N/2n|_2`` about ``1/sqrt(n)``, so its
condition number is under 1.1 at n=8192 — like the miniapp's analytic
triangle (off-diagonal entries below 1, diagonal 2n).
"""

from __future__ import annotations

import numpy as np

import oplib


def build(config: dict, seed: int, devices) -> dict:
    n, nb = config["n"], config["nb"]
    m = config["args"].get("nrhs", n)
    rng = np.random.default_rng(seed)
    t = np.tril(rng.standard_normal((n, n)), -1)
    t[np.diag_indices(n)] = 2.0 * n
    b = rng.standard_normal((n, m))
    grid = oplib.make_grid(config, devices)
    dtype = np.dtype(config["dtype"])
    a = config["args"]
    if (a["side"], a["uplo"], a["op"], a["diag"]) != ("L", "L", "N", "N"):
        raise ValueError("the host reference of this op file is written for "
                         "LLNN only")
    return {"t": t, "b": b, "tm": oplib.matrix(t, nb, dtype, grid),
            "bm": oplib.matrix(b, nb, dtype, grid), "args": a, "seed": seed}


def fresh(state):
    return oplib.fresh(state["bm"])


def call(state, inp, traced=False):
    from dlaf_tpu.algorithms import triangular_solve

    a = state["args"]
    return triangular_solve(a["side"], a["uplo"], a["op"], a["diag"],
                            float(a["alpha"]), state["tm"], inp,
                            donate_b=True)


def host(out):
    return np.asarray(out.to_numpy(), dtype=np.float64)


def check(state, x) -> dict:
    """``|(T X - alpha B) w| / (|T| |X w|)`` on eight seeded combinations of
    the right-hand sides."""
    t, b = state["t"], state["b"]
    w = oplib.probe(b.shape[1], seed=state["seed"] + 3)
    xw = x @ w
    return {"|(T X - B) w|/(|T||X w|)":
            oplib.frob(t @ xw - float(state["args"]["alpha"]) * (b @ w))
            / (oplib.frob(t) * oplib.frob(xw))}


def flops(config: dict) -> float:
    """Left side: ``n^2 m / 2`` additions and as many multiplications
    (miniapp_triangular_solver.cpp)."""
    n = config["n"]
    return float(n) * n * config["args"].get("nrhs", n)
