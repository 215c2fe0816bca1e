"""``B = Q^H A Q`` through ``dlaf_tpu.eigensolver.reduction_to_band``
(miniapp_reduction_to_band): a real symmetric matrix reduced to a band of
``args.band_size`` sub-diagonals, the reflectors left below the band.

Input: ``A = (G + G^T)/2`` with ``G`` standard normal from the seed (the
eigensolver cell's matrix): a semicircle spectrum of radius ``sqrt(2 n)``.

The check is numpy float64 on the host and uses no code of the library
(``benchmark/reference/band_reduction.py``: the band read from diagonals
``0 .. band`` of the result and nothing below them, ``Q`` applied one
reflector at a time from the stored tails and taus).
"""

from __future__ import annotations

import numpy as np

import oplib
from reference import band_reduction as ref


def build(config: dict, seed: int, devices) -> dict:
    n, nb = config["n"], config["nb"]
    g = np.random.default_rng(seed).standard_normal((n, n))
    a = (g + g.T) / 2
    mat = oplib.matrix(a, nb, np.dtype(config["dtype"]),
                       oplib.make_grid(config, devices))
    return {"a": a, "ref": mat, "band": config["args"]["band_size"],
            "seed": seed, "lam": np.linalg.eigvalsh(a)}


def fresh(state):
    return oplib.fresh(state["ref"])


def call(state, inp, traced=False):
    from dlaf_tpu.eigensolver import reduction_to_band

    red = reduction_to_band(inp, band_size=state["band"], donate=True)
    return red.matrix, red.taus


def host(out):
    mat, taus = out
    return (np.asarray(mat.to_numpy(), dtype=np.float64),
            np.asarray(taus, dtype=np.float64))


def check(state, out) -> dict:
    """Similarity and orthogonality on eight seeded probe vectors, and the
    miniapp's own check: the band matrix has ``A``'s eigenvalues."""
    red, taus = out
    a, band = state["a"], state["band"]
    b = ref.band_of(red, band)
    x = oplib.probe(a.shape[0], seed=state["seed"] + 7)
    ax = a @ x
    qhx = ref.apply_q(red, taus, band, x, adjoint=True)
    lam_b = np.linalg.eigvalsh(b)
    return {
        "|A x - Q(B(Q^H x))|/|A x|":
            oplib.frob(ax - ref.apply_q(red, taus, band, b @ qhx))
            / oplib.frob(ax),
        "|Q^H Q x - x|/|x|":
            oplib.frob(ref.apply_q(red, taus, band,
                                   ref.apply_q(red, taus, band, x),
                                   adjoint=True) - x) / oplib.frob(x),
        "max|eigvalsh(B) - eigvalsh(A)|/max|lam|":
            float(np.abs(lam_b - state["lam"]).max()
                  / np.abs(state["lam"]).max()),
    }


def flops(config: dict) -> float:
    """The miniapp's model: ``2 n^3 / 3`` additions and as many
    multiplications (miniapp_reduction_to_band.cpp)."""
    return 4.0 * config["n"] ** 3 / 3.0
