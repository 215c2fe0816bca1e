"""Standard and generalized Hermitian eigensolver drivers.

TPU-native counterpart of the reference's ``eigensolver/eigensolver``
(``api.h:28-31``, ``impl.h:33-78``) and ``gen_eigensolver``
(``api.h:17-21``, ``impl.h:24-35``) — LOCAL only, matching the reference at
this snapshot (its distributed eigensolver does not exist either; SURVEY §2).

Pipeline (reference ``impl.h:33-78``):
  reduction_to_band  ->  band_to_tridiag (host chase)  ->  D&C tridiag solve
  ->  bt_band_to_tridiag  ->  bt_reduction_to_band

Generalized problem ``A x = lambda B x`` (``gen_eigensolver/impl.h:24-35``):
  cholesky(B)  ->  gen_to_std  ->  eigensolver  ->  triangular back-
  substitution of the eigenvectors.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from typing import Optional

from ..common.sync import hard_fence
from ..algorithms.cholesky import cholesky
from ..algorithms.gen_to_std import gen_to_std
from ..algorithms.triangular import triangular_solve
from ..common.asserts import dlaf_assert
from ..common.timer import PhaseTimer
from ..matrix import ops as mops
from ..matrix.matrix import Matrix
from .back_transform import bt_band_to_tridiag, bt_reduction_to_band
from .band_to_tridiag import band_to_tridiag
from .reduction_to_band import extract_band, reduction_to_band
from .tridiag_solver import tridiag_solver


@dataclasses.dataclass
class EigensolverResult:
    """Reference ``EigensolverResult{eigenvalues, eigenvectors}``
    (``api.h:21-24``)."""

    eigenvalues: np.ndarray   # (n,) real, ascending
    eigenvectors: Matrix      # columns are eigenvectors


def eigensolver(uplo: str, a: Matrix,
                phases: Optional[PhaseTimer] = None,
                band_size: int | None = None, *,
                donate: bool = False,
                resume: bool = False) -> EigensolverResult:
    """Eigendecomposition of Hermitian ``a`` stored in ``uplo``
    (reference ``eigensolver::eigensolver``, ``api.h:28-31``).

    The reference is LOCAL-only at this snapshot; here the same pipeline also
    runs distributed (beyond-parity): distributed reduction_to_band, host
    band/tridiag/D&C stages (the reference keeps these on CPU too), then the
    two distributed back-transformations.

    ``phases`` (optional :class:`PhaseTimer`) collects per-stage wall times —
    the per-algorithm phase instrumentation SURVEY §5 calls for.

    ``donate=True`` permits consuming ``a``'s device storage at the first
    stage (the reference pipeline overwrites mat_a throughout); ``a`` must
    not be used afterwards (with ``resume=True`` skipping the first stage,
    ``a`` is simply left untouched).

    **Preemption-safe resume** (docs/robustness.md §5): with
    ``DLAF_RESUME_DIR`` (config ``resume_dir``) set, the pipeline writes an
    atomic versioned stage checkpoint after each of red2band / b2t /
    tridiag / bt_b2t / bt_r2b; ``resume=True`` then skips every stage whose
    manifest matches this run's config/grid/dtype fingerprint and restores
    its payload bitwise, so a preempted multi-minute run continues from the
    last completed boundary and produces the SAME eigenpairs as the
    uninterrupted run (bitwise per stage on the native routes — pinned by
    tests/test_resilience.py and the ci/run.sh kill-and-resume drill).
    A fingerprint mismatch raises :class:`dlaf_tpu.health.errors.
    ResumeError` naming the offending keys; ``resume=True`` without a
    resume dir raises too — a silent full recompute is not a resume.
    """
    dlaf_assert(a.size.row == a.size.col, "eigensolver: square only")
    n = a.size.row
    nb = a.block_size.row
    if n == 0:
        return EigensolverResult(np.zeros(0), a)
    pt = phases if phases is not None else PhaseTimer()
    # per-phase device fences only when timing was requested — they would
    # otherwise serialize stage compile/dispatch against device execution
    fence = (hard_fence if phases is not None
             else (lambda x: None))
    distributed = a.grid is not None and a.grid.num_devices > 1
    from .. import obs
    from ..types import total_ops

    from ..config import resolved_bt_lookahead, resolved_dc_level_batch

    # canonical full-EVP flop model (miniapp_eigensolver): 5n^3/3
    # muls+adds; the five stage spans below nest under this one. The
    # pipeline-throughput knobs (docs/eigensolver_perf.md) ride along so
    # one span record says which trailing-stage formulation ran.
    pipeline_span = obs.entry_span("eigensolver", lambda: dict(
        flops=total_ops(np.dtype(a.dtype), 5 * n**3 / 3, 5 * n**3 / 3),
        n=n, nb=nb, uplo=uplo, dtype=np.dtype(a.dtype).name,
        dc_level_batch=int(resolved_dc_level_batch()),
        bt_lookahead=int(resolved_bt_lookahead()),
        grid=f"{a.dist.grid_size.row}x{a.dist.grid_size.col}"))
    with pipeline_span:
        return _eigensolver_pipeline(uplo, a, pt, fence, distributed,
                                     band_size, donate, n, nb, resume)


def _stage_fingerprint(uplo, a, band_size, n, nb) -> dict:
    """The run identity a stage checkpoint is valid for: shape/layout/
    dtype/grid plus the platform (route autos resolve per backend, and a
    checkpoint must never cross them) plus a content hash of the INPUT —
    two same-shaped runs over different matrices must never trade
    checkpoints (resume would silently return the other run's
    eigenpairs)."""
    import jax

    fp = dict(pipeline="eigensolver", n=int(n), nb=int(nb), uplo=uplo,
              dtype=np.dtype(a.dtype).name,
              band_size=int(band_size) if band_size else 0,
              grid=f"{a.dist.grid_size.row}x{a.dist.grid_size.col}",
              backend=jax.default_backend())
    from ..config import get_configuration

    if get_configuration().resume_dir and jax.process_count() == 1:
        # one host gather of the input, only when checkpointing is
        # armed. Hash the stored triangle only: the other triangle is
        # contractually unread and may hold run-varying garbage.
        import hashlib

        g = np.asarray(a.to_numpy())
        tri = np.tril(g) if uplo == "L" else np.triu(g)
        fp["input_sha"] = hashlib.sha256(
            np.ascontiguousarray(tri).tobytes()).hexdigest()[:16]
    return fp


def _pack_red(red) -> dict:
    from ..matrix.checkpoint import matrix_arrays

    return {**matrix_arrays(red.matrix, "matrix"),
            "taus": np.asarray(red.taus),
            "band": np.asarray(red.band, dtype=np.int64)}


def _load_red(arrays, grid):
    import jax.numpy as jnp

    from ..matrix.checkpoint import matrix_from_arrays
    from .reduction_to_band import BandReduction

    return BandReduction(matrix=matrix_from_arrays(arrays, "matrix", grid),
                         taus=jnp.asarray(arrays["taus"]),
                         band=int(arrays["band"]))


def _pack_tri(tri) -> dict:
    return {"d": np.asarray(tri.d), "e": np.asarray(tri.e),
            "v": np.asarray(tri.v), "tau": np.asarray(tri.tau),
            "phase": np.asarray(tri.phase),
            "band": np.asarray(tri.band, dtype=np.int64)}


def _load_tri(arrays):
    from .band_to_tridiag import TridiagResult

    return TridiagResult(d=arrays["d"], e=arrays["e"], v=arrays["v"],
                         tau=arrays["tau"], phase=arrays["phase"],
                         band=int(arrays["band"]))


def _eigensolver_pipeline(uplo, a, pt, fence, distributed, band_size,
                          donate, n, nb, resume):
    from ..health import resume as hresume
    from ..matrix.checkpoint import matrix_arrays, matrix_from_arrays

    ck = hresume.stage_checkpointer(
        "eigensolver", _stage_fingerprint(uplo, a, band_size, n, nb),
        resume=resume)
    with pt.phase("stage.reduction_to_band"):
        if ck.completed("red2band"):
            red = _load_red(ck.load("red2band"), a.grid)
        else:
            # ``donate`` consumes a's storage at the hermitianize; ah
            # itself is always a fresh copy owned by this driver — donate
            # it to the reduction (one full matrix off peak HBM either
            # way)
            ah = mops.hermitianize(a, uplo, donate=donate)
            red = reduction_to_band(ah, band_size=band_size, donate=True)
            ck.commit("red2band", _pack_red(red))
        fence(red.matrix.storage)
    with pt.phase("stage.band_to_tridiag"):
        if ck.completed("b2t"):
            tri = _load_tri(ck.load("b2t"))
        else:
            band = extract_band(red)
            tri = band_to_tridiag(band, red.band)
            ck.commit("b2t", _pack_tri(tri))
    with pt.phase("stage.tridiag_solver"):
        if ck.completed("tridiag"):
            arrs = ck.load("tridiag")
            lam, z = arrs["lam"], arrs["z"]
        else:
            # distributed: the merge-tree gemms, qc workspaces, and Q run
            # sharded over the grid's mesh (beyond the local-only
            # reference) — the (n, n) merge arrays never have to fit one
            # device's HBM (remaining single-device term: the deflated
            # secular workspace)
            lam, z = tridiag_solver(tri.d, tri.e, nb,
                                    mesh=a.grid.mesh if distributed
                                    else None)
            ck.commit("tridiag", {"lam": np.asarray(lam),
                                  "z": np.asarray(z)})
        fence(z)
    with pt.phase("stage.bt_band_to_tridiag"):
        if ck.completed("bt_b2t"):
            arrs = ck.load("bt_b2t")
            zb = (matrix_from_arrays(arrs, "zb", a.grid) if distributed
                  else arrs["zb"])
        elif distributed:
            # z is a device-resident jax.Array (tridiag_solver keeps Q on
            # device across the merge tree); from_global re-tiles it ON
            # DEVICE — no host materialization between stages (round-1
            # review weak item 4)
            zb = bt_band_to_tridiag(
                tri, Matrix.from_global(z, a.block_size, grid=a.grid,
                                        source_rank=a.dist.source_rank))
            fence(zb.storage)
            ck.commit("bt_b2t", matrix_arrays(zb, "zb"))
        else:
            zb = bt_band_to_tridiag(tri, z)
            fence(zb)
            ck.commit("bt_b2t", {"zb": np.asarray(zb)})
    with pt.phase("stage.bt_reduction_to_band"):
        if ck.completed("bt_r2b"):
            vecs = matrix_from_arrays(ck.load("bt_r2b"), "vecs", a.grid)
        else:
            out = bt_reduction_to_band(red, zb)
            if distributed:
                vecs = out
                fence(vecs.storage)
            else:
                vecs = Matrix.from_global(out, a.block_size, grid=a.grid,
                                          source_rank=a.dist.source_rank)
            ck.commit("bt_r2b", matrix_arrays(vecs, "vecs"))
    return EigensolverResult(lam, vecs)


def gen_eigensolver(uplo: str, a: Matrix, b: Matrix,
                    phases: Optional[PhaseTimer] = None,
                    band_size: int | None = None, *,
                    donate: bool = False) -> EigensolverResult:
    """Generalized problem ``A x = lambda B x`` with Hermitian ``a`` and
    HPD ``b`` (reference ``eigensolver::genEigensolver``, ``api.h:17-21``;
    LOCAL-only in the reference — here every stage also runs distributed).

    ``donate=True`` permits consuming ``a``'s storage; ``b`` is never
    consumed (its factor is formed from an undonated read)."""
    dlaf_assert(a.size == b.size, "gen_eigensolver: A/B size mismatch")
    pt = phases if phases is not None else PhaseTimer()
    fence = (hard_fence if phases is not None
             else (lambda x: None))
    from .. import obs

    pipeline_span = obs.entry_span("gen_eigensolver", lambda: dict(
        n=a.size.row, nb=a.block_size.row, uplo=uplo,
        dtype=np.dtype(a.dtype).name,
        grid=f"{a.dist.grid_size.row}x{a.dist.grid_size.col}"))
    with pipeline_span:
        return _gen_eigensolver_pipeline(uplo, a, b, pt, phases, fence,
                                         band_size, donate)


def _gen_eigensolver_pipeline(uplo, a, b, pt, phases, fence, band_size,
                              donate):
    with pt.phase("stage.cholesky"):
        bf = cholesky(uplo, b)
        fence(bf.storage)
    with pt.phase("stage.gen_to_std"):
        astd = gen_to_std(uplo, a, bf, donate=donate)
        fence(astd.storage)
    # astd is owned by this driver — always donated into the pipeline
    res = eigensolver(uplo, astd, phases=phases, band_size=band_size,
                      donate=True)
    # back-substitute eigenvectors (reference gen_eigensolver/impl.h:24-35):
    # uplo=L: B = L L^H, standard vec y -> x = L^-H y
    # uplo=U: B = U^H U,                x = U^-1 y
    with pt.phase("stage.back_substitution"):
        # res.eigenvectors is owned by this driver — donated into the solve
        if uplo == "L":
            vecs = triangular_solve("L", "L", "C", "N", 1.0, bf,
                                    res.eigenvectors, donate_b=True)
        else:
            vecs = triangular_solve("L", "U", "N", "N", 1.0, bf,
                                    res.eigenvectors, donate_b=True)
        fence(vecs.storage)
    return EigensolverResult(res.eigenvalues, vecs)
