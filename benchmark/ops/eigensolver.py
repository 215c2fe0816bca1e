"""All eigenpairs of a real symmetric matrix through
``dlaf_tpu.eigensolver.eigensolver`` (miniapp_eigensolver).

Input: ``A = (G + G^T)/2`` with ``G`` standard normal from the seed (the
matrix chip_smoke.py and ``miniapp.generators.random_hermitian`` use):
spectrum a semicircle of radius ``sqrt(2 n)``; a symmetric matrix's
eigenvalues are perfectly conditioned, its eigenvectors as the gaps allow
(mean gap ``2 sqrt(2n) / n``).
"""

from __future__ import annotations

import contextlib

import numpy as np

import oplib


def build(config: dict, seed: int, devices) -> dict:
    n, nb = config["n"], config["nb"]
    g = np.random.default_rng(seed).standard_normal((n, n))
    a = (g + g.T) / 2
    ref = oplib.matrix(a, nb, np.dtype(config["dtype"]),
                       oplib.make_grid(config, devices))
    return {"a": a, "ref": ref, "uplo": config["args"]["uplo"], "seed": seed,
            "stage_walls": []}


def fresh(state):
    return oplib.fresh(state["ref"])


def _stage_timer():
    """A PhaseTimer whose stages are also annotations in the profiler's
    trace, so that an idle gap can be given the stage that was open."""
    import jax

    from dlaf_tpu.common.timer import PhaseTimer

    class StageTimer(PhaseTimer):
        @contextlib.contextmanager
        def phase(self, name, **attrs):
            with jax.profiler.TraceAnnotation(name), \
                    super().phase(name, **attrs):
                yield

    return StageTimer()


def call(state, inp, traced=False):
    """Untraced: the call a user makes. Traced: with a PhaseTimer, whose
    fences between the stages are part of the tracing overhead."""
    from dlaf_tpu.eigensolver import eigensolver

    if not traced:
        res = eigensolver(state["uplo"], inp, donate=True)
    else:
        timer = _stage_timer()
        res = eigensolver(state["uplo"], inp, phases=timer, donate=True)
        state["stage_walls"].append(
            {k: float(v) for k, v in timer.report().items()})
    return res.eigenvectors, res.eigenvalues


def host(out):
    q, lam = out
    return (np.asarray(q.to_numpy(), dtype=np.float64),
            np.asarray(lam, dtype=np.float64))


def check(state, out) -> dict:
    """Residual and orthogonality on eight seeded probe vectors, and the
    eigenvalues against ``numpy.linalg.eigvalsh``."""
    q, lam = out
    a = state["a"]
    if "lam_ref" not in state:
        state["lam_ref"] = np.linalg.eigvalsh(a)
    w = oplib.probe(a.shape[0], seed=state["seed"] + 5)
    qw = q @ w
    nw = oplib.frob(w)
    return {
        "|A Q w - Q lam w|/(|A||w|)":
            oplib.frob(a @ qw - q @ (lam[:, None] * w))
            / (oplib.frob(a) * nw),
        "|Q^H Q w - w|/|w|": oplib.frob(q.T @ qw - w) / nw,
        "max|lam - eigvalsh(A)|/max|lam|":
            float(np.abs(lam - state["lam_ref"]).max()
                  / np.abs(state["lam_ref"]).max()),
    }


def flops(config: dict) -> float:
    """The miniapp's model: ``5 n^3 / 3`` additions and as many
    multiplications."""
    return 10.0 * config["n"] ** 3 / 3.0
