"""``A = L L^H`` through ``dlaf_tpu.algorithms.cholesky`` (miniapp_cholesky).

Input: ``A = (G + G^T)/2 + n I`` with ``G`` standard normal from the seed:
symmetric, eigenvalues in ``n +- sqrt(2 n)``, so 2-norm condition number
``(n + sqrt(2n)) / (n - sqrt(2n))`` (1.05 at n=4096) — as well conditioned as
the miniapp's ``hpd_element_fn`` (diagonal n, condition number about 1).
"""

from __future__ import annotations

import numpy as np

import oplib


def build(config: dict, seed: int, devices) -> dict:
    n, nb = config["n"], config["nb"]
    g = np.random.default_rng(seed).standard_normal((n, n))
    a = (g + g.T) / 2 + n * np.eye(n)
    ref = oplib.matrix(a, nb, np.dtype(config["dtype"]),
                       oplib.make_grid(config, devices))
    return {"a": a, "ref": ref, "uplo": config["args"]["uplo"], "seed": seed}


def fresh(state):
    return oplib.fresh(state["ref"])


def call(state, inp, traced=False):
    from dlaf_tpu.algorithms import cholesky

    return cholesky(state["uplo"], inp, donate=True)


def host(out):
    return np.tril(np.asarray(out.to_numpy(), dtype=np.float64))


def check(state, low) -> dict:
    """``|A x - L (L^H x)| / |A x|`` on eight seeded probe vectors."""
    a = state["a"]
    x = oplib.probe(a.shape[0], seed=state["seed"] + 1)
    ax = a @ x
    return {"|A x - L(L^H x)|/|A x|":
            oplib.frob(ax - low @ (low.T @ x)) / oplib.frob(ax)}


def flops(config: dict) -> float:
    """The reference's model: ``n^3/6`` additions and as many
    multiplications (miniapp_cholesky.cpp:149-154)."""
    return config["n"] ** 3 / 3.0
