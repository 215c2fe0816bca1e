"""QR T-factor — the standalone public API (local + distributed).

TPU-native counterpart of the reference's ``factorization/qr``
(``t_factor_impl.h:42-347``; public ``api.h:52,81``): given a panel ``V`` of
``k`` forward columnwise Householder reflectors and their ``taus``, compute
the compact-WY ``T`` factor with ``(I - V T V^H)`` the accumulated product
of the reflectors.

The reference accumulates T with per-tile ``gemv``s and a final ``trmv``
series, all-reducing partial sums over the *column communicator* in the
distributed overload. The TPU-native form uses the closed form
``T^{-1} = diag(1/tau) + strict_upper(V^H V)`` (see ``tile_ops.lapack.
larft``): the only distributed quantity is the small ``k x k`` Gram matrix
``V^H V``, accumulated as rank-local partial products and ``psum``-reduced
along the mesh 'row' axis — the exact analog of the reference's
column-communicator all-reduce — after which every rank finishes the tiny
triangular solve redundantly (replicated T, like the reference's result on
every rank of the column).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..comm import collectives as cc
from ..comm.grid import COL_AXIS, ROW_AXIS
from ..common.asserts import dlaf_assert
from ..config import register_program_cache
from ..matrix.matrix import Matrix
from ..matrix.tiling import storage_tile_grid
from ..tile_ops import blas as tb
from ..tile_ops import lapack as tl


def _t_from_gram(gram, tau):
    """Finish T from the psum'd Gram matrix (small, every rank redundant):
    ``T^{-1} = diag(1/tau) + strict_upper(V^H V)``, zero taus giving zero
    rows/cols (null reflectors, LAPACK semantics)."""
    from jax import lax

    k = tau.shape[-1]
    tau_safe = jnp.where(tau == 0, jnp.ones_like(tau), tau)
    tinv = tb.tri_mask(gram, "U", k=-1) + (1.0 / tau_safe)[..., :, None] \
        * jnp.eye(k, dtype=gram.dtype)
    t = lax.linalg.triangular_solve(tinv, jnp.eye(k, dtype=gram.dtype),
                                    left_side=True, lower=False)
    nz = tau != 0
    return jnp.where(nz[..., :, None] & nz[..., None, :], t,
                     jnp.zeros_like(t))


@register_program_cache
@functools.lru_cache(maxsize=32)
def _dist_t_factor_cached(dist, mesh, dtype_name):
    nt = dist.nr_tiles.row
    mb = dist.block_size.row
    m, k = dist.size.row, dist.size.col
    Pr = dist.grid_size.row
    sr = dist.source_rank.row
    _, _, ltr, _ = storage_tile_grid(dist)

    def prog(lt, taus):
        # rank-local partial Gram over my row tiles of the (single-tile-
        # column) panel; invalid row slots masked out
        rr = (cc.this_rank(ROW_AXIS) - sr) % Pr
        g_rows = jnp.arange(ltr) * Pr + rr
        elem_rows = g_rows[:, None] * mb + jnp.arange(mb)[None, :]
        valid = (elem_rows < m)
        tiles = lt[:, 0]
        # unit-lower-trapezoidal V with implicit ones: global element row r,
        # column c -> keep strictly-lower, inject 1 at r == c
        col = jnp.arange(k)[None, None, :]
        er = elem_rows[:, :, None]
        vv = jnp.where((er > col) & valid[:, :, None], tiles[..., :k], 0)
        vv = vv + jnp.where(er == col, 1.0, 0.0).astype(tiles.dtype)
        part = tb.contract("rab,rad->bd", jnp.conj(vv), vv)
        gram = cc.all_reduce(part, ROW_AXIS)   # the col-communicator allreduce
        # only the grid column owning the panel's single tile column summed
        # real data; everyone else receives its gram (replicated result,
        # like the reference's T on every rank)
        gram = cc.bcast(gram, COL_AXIS, dist.source_rank.col)
        return _t_from_gram(gram, taus)

    fn = shard_map(prog, mesh=mesh, in_specs=(P(ROW_AXIS, COL_AXIS), P()),
                   out_specs=P(), check_vma=False)
    return jax.jit(fn)


def t_factor(v, taus):
    """T factor of the reflector panel ``v`` (reference
    ``factorization::qr::computeTFactor`` local + distributed overloads).

    ``v``: a Matrix whose single block column holds the reflectors (unit
    lower trapezoidal, ones implicit — the stored upper triangle is
    ignored), or a plain (m, k) array; ``taus``: (k,) scaling factors.
    Returns the replicated (k, k) ``T`` as a jax array.
    """
    if not isinstance(v, Matrix):
        arr = jnp.asarray(v)
        return tl.larft(arr, jnp.asarray(taus))
    dlaf_assert(v.dist.nr_tiles.col == 1,
                "t_factor: the reflector panel must be one block column")
    if v.grid is None or v.grid.num_devices == 1:
        from ..matrix.tiling import tiles_to_global

        return tl.larft(tiles_to_global(v.storage, v.dist),
                        jnp.asarray(taus))
    fn = _dist_t_factor_cached(v.dist, v.grid.mesh, np.dtype(v.dtype).name)
    return fn(v.storage, jnp.asarray(taus))
