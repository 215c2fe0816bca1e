"""Max-norm of a (triangular part of a) distributed matrix.

TPU-native counterpart of the reference's ``auxiliary::norm``
(``auxiliary/norm/mc.h:29-108``): per-tile ``lange``/``lantr`` partial maxima
folded locally, then reduced across ranks (the reference uses a blocking
``sync::reduce(MPI_MAX)`` to a target rank; here a ``pmax`` over both mesh
axes — every rank gets the result, which XLA DCEs where unused).

Supports norm='M' (max absolute value) over uplo 'L' (lower triangle,
Hermitian use-case) or 'G' (whole matrix), matching the reference's scope.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..config import register_program_cache
from ..comm import collectives as cc
from ..comm.grid import COL_AXIS, ROW_AXIS
from ..matrix.matrix import Matrix
from ..matrix.tiling import storage_tile_grid, tiles_to_global


def _build_dist_norm(dist, mesh, uplo: str):
    nt = dist.nr_tiles
    mb, nb = dist.block_size.row, dist.block_size.col
    Pr, Qc = dist.grid_size.row, dist.grid_size.col
    sr, sc = dist.source_rank.row, dist.source_rank.col
    _, _, ltr, ltc = storage_tile_grid(dist)

    def local_norm(lt):
        rr = (cc.this_rank(ROW_AXIS) - sr) % Pr
        rc = (cc.this_rank(COL_AXIS) - sc) % Qc
        g_rows = jnp.arange(ltr) * Pr + rr          # global tile rows
        g_cols = jnp.arange(ltc) * Qc + rc
        valid = (g_rows[:, None] < nt.row) & (g_cols[None, :] < nt.col)
        if uplo == "L":
            keep_full = valid & (g_rows[:, None] > g_cols[None, :])
            keep_diag = valid & (g_rows[:, None] == g_cols[None, :])
            tril_m = jnp.tril(jnp.ones((mb, nb), dtype=bool))
            mask = (keep_full[:, :, None, None]
                    | (keep_diag[:, :, None, None] & tril_m))
        else:
            mask = valid[:, :, None, None]
        vals = jnp.where(mask, jnp.abs(lt), 0)
        m = jnp.max(vals) if lt.size else jnp.zeros((), vals.dtype)
        m = cc.all_reduce(m, ROW_AXIS, "max")
        m = cc.all_reduce(m, COL_AXIS, "max")
        return m.reshape(1, 1)

    return shard_map(local_norm, mesh=mesh, in_specs=P(ROW_AXIS, COL_AXIS),
                     out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False)


@register_program_cache
@functools.lru_cache(maxsize=64)
def _dist_norm_cached(dist, mesh, uplo):
    return jax.jit(_build_dist_norm(dist, mesh, uplo))


def max_norm(mat: Matrix, uplo: str = "G") -> float:
    """Largest absolute element of ``mat`` (or its lower triangle)."""
    if mat.size.is_empty():
        return 0.0
    if mat.grid is None or mat.grid.num_devices == 1:
        a = tiles_to_global(mat.storage, mat.dist)
        if uplo == "L":
            a = jnp.tril(a)
        return float(jnp.max(jnp.abs(a)))
    out = _dist_norm_cached(mat.dist, mat.grid.mesh, uplo)(mat.storage)
    return float(np.asarray(out).max())
