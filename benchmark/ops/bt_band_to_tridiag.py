"""``Q E`` through ``dlaf_tpu.eigensolver.bt_band_to_tridiag``
(miniapp_bt_band_to_tridiag): the bulge chase's Householder reflectors
applied to an ``(n, evec_cols)`` block of vectors held as a local Matrix.

Input: the band (``args.band_size`` sub-diagonals) of ``(G + G^T)/2`` with
``G`` standard normal from the seed, in lower band storage, and ``E``
standard normal from the same generator. Set-up chases the band once with the
library's own chase (untimed, as the miniapp has it; ``run.py`` reads
``dlaf_fallback_total`` after the window, so a chase that fell back to its
numpy twin makes the run not ``correct``) and keeps the reflectors in host
memory, as the eigensolver pipeline does: every timed call uploads them.

The checks are numpy float64 on the host and use no code of the library:
``SAMPLE`` columns drawn from the seed against the plain reference
(``benchmark/reference/chase_reflectors.py``: one rank-1 update a reflector
in the published order), and the Gram matrix of ALL columns (``Q`` is
orthogonal, so ``out^T out = E^T E``: a wrong column the sample misses moves
it).
"""

from __future__ import annotations

import numpy as np

import oplib
from reference import chase_reflectors as ref

#: columns of E held to the plain reference (65 k rank-1 updates at n=4096)
SAMPLE = 64


def build(config: dict, seed: int, devices) -> dict:
    from dlaf_tpu.eigensolver.band_to_tridiag import band_to_tridiag

    n, nb = config["n"], config["nb"]
    band, m = config["args"]["band_size"], config["args"]["evec_cols"]
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((n, n))
    tri = band_to_tridiag(ref.lower_band((g + g.T) / 2, band), band)
    e = rng.standard_normal((n, m))
    cols = np.sort(rng.choice(m, size=min(SAMPLE, m), replace=False))
    mat = oplib.matrix(e, nb, np.dtype(config["dtype"]),
                       oplib.make_grid(config, devices))
    return {"tri": tri, "ref": mat, "band": band, "cols": cols,
            "want": ref.apply_q(tri.v, tri.tau, e[:, cols], band),
            "gram": e.T @ e}


def fresh(state):
    return oplib.fresh(state["ref"])


def call(state, inp, traced=False):
    from dlaf_tpu.eigensolver import bt_band_to_tridiag

    return bt_band_to_tridiag(state["tri"], inp)


def host(out):
    return np.asarray(out.to_numpy(), dtype=np.float64)


def check(state, out) -> dict:
    want, gram = state["want"], state["gram"]
    return {
        "|out[:, J] - ref(E[:, J])|/|ref(E[:, J])|":
            oplib.frob(out[:, state["cols"]] - want) / oplib.frob(want),
        "|out^T out - E^T E|/|E^T E|":
            oplib.frob(out.T @ out - gram) / oplib.frob(gram),
    }


def flops(config: dict) -> float:
    """``2 n^2 m``: ``sum_s ceil((n - 1 - s) / b)`` ~ ``n^2 / (2 b)`` live
    reflectors of length ``b`` at ``4 b m`` real operations each (``n^2 m``
    multiplications and as many additions: the entry span's model and, since
    PR 39, the miniapp's)."""
    return 2.0 * config["n"] ** 2 * config["args"]["evec_cols"]
