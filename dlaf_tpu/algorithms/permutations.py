"""Row/column permutations over an index range.

TPU-native counterpart of the reference's ``permutations::permute``
(``permutations/general/api.h:22``, ``impl.h:40-155`` + CUDA gather kernel
``perms.cu:58-120``): out-of-place ``out[i] = in[perm[i]]`` along rows or
columns restricted to a tile range, used by the D&C merge. On TPU the local
form is a single XLA gather (``jnp.take``) — the custom CUDA kernel
disappears.

Distributed form: the reference's kernel operates on LOCAL tiles only; the
Matrix-level distributed permute here is one ``shard_map`` program per call
shape — an ``all_gather`` along the permuted mesh axis restricted to the
slot window covering the affected tile range, followed by a per-rank static
gather (the source positions are trace-time tables indexed by
``lax.axis_index``). Communication is one collective of the affected rows
(O(range x local-extent) per rank, riding ICI); no rank ever materializes
the full matrix and nothing round-trips through the host (the round-3
gather-densify this replaces).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..comm.grid import COL_AXIS, ROW_AXIS
from ..common.asserts import dlaf_assert, dlaf_assert_heavy
from ..config import register_program_cache
from ..matrix.matrix import Matrix
from ..matrix.tiling import global_to_tiles, storage_tile_grid, tiles_to_global


def permute_array(coord: str, perm, arr):
    """``out[i] = in[perm[i]]`` along rows ('Row') or columns ('Col') of a
    plain (device) array — the gather primitive shared by the Matrix-level
    :func:`permute` and the D&C merge assembly (the reference's two callers
    of its permutation kernel, ``perms.cu:58-120``: workspace index sorts
    inside the merge, and matrix-level permutes)."""
    dlaf_assert(coord in ("Row", "Col"), f"bad coord {coord!r}")
    return jnp.take(arr, jnp.asarray(perm), axis=0 if coord == "Row" else 1)


def _gather_tables(nper: int, src: int, lt: int, bsz: int, a0: int, a1: int,
                   perm: np.ndarray, l0: int, w: int):
    """Per-mesh-coordinate gather tables for the distributed permute along
    one axis: for each (mesh coord p, local slot l, intra-tile offset r),
    the flat index into the gathered window ``(nper*w*bsz,)`` of the source
    position, and whether the position is inside the permuted range.

    Storage convention (matrix/tiling.py): slot ``l`` on mesh coordinate
    ``p`` holds global tile ``t = l*nper + (p - src) % nper``; tile ``t``
    lives on coordinate ``(t % nper + src) % nper`` at slot ``t // nper``.
    """
    rp = (np.arange(nper) - src) % nper                       # (nper,)
    t = np.arange(lt)[None, :] * nper + rp[:, None]           # (nper, lt)
    g = (t[:, :, None] * bsz + np.arange(bsz)).reshape(nper, lt * bsz)
    in_range = (g >= a0) & (g < a1)
    s = np.where(in_range,
                 perm[np.clip(g - a0, 0, max(len(perm) - 1, 0))] + a0, 0)
    ts, rs = s // bsz, s % bsz
    ps = (ts % nper + src) % nper
    ls = ts // nper - l0
    idx = np.where(in_range, ps * (w * bsz) + ls * bsz + rs, 0)
    return (jnp.asarray(idx.astype(np.int32)),
            jnp.asarray(in_range))


@register_program_cache
@functools.lru_cache(maxsize=64)
def _dist_permute_cached(dist, mesh, coord: str, l0: int, w: int):
    """jitted shard_map permute program for one (distribution, coord,
    slot-window) shape; the per-call permutation content rides in as the
    table/mask arguments, so distinct permutations of the same range share
    one compiled program."""
    Pr, Qc = dist.grid_size.row, dist.grid_size.col
    _, _, ltr, ltc = storage_tile_grid(dist)
    mb, nb = dist.block_size.row, dist.block_size.col

    def body(t, table, mask):
        if coord == "Row":
            i = jax.lax.axis_index(ROW_AXIS)
            idx, msk = jnp.take(table, i, axis=0), jnp.take(mask, i, axis=0)
            tw = jax.lax.slice_in_dim(t, l0, l0 + w, axis=0)
            g = jax.lax.all_gather(tw, ROW_AXIS)  # (Pr, w, ltc, mb, nb)
            g2 = g.transpose(0, 1, 3, 2, 4).reshape(Pr * w * mb, ltc, nb)
            lf = t.transpose(0, 2, 1, 3).reshape(ltr * mb, ltc, nb)
            new = jnp.where(msk[:, None, None],
                            jnp.take(g2, idx, axis=0), lf)
            return new.reshape(ltr, mb, ltc, nb).transpose(0, 2, 1, 3)
        i = jax.lax.axis_index(COL_AXIS)
        idx, msk = jnp.take(table, i, axis=0), jnp.take(mask, i, axis=0)
        tw = jax.lax.slice_in_dim(t, l0, l0 + w, axis=1)
        g = jax.lax.all_gather(tw, COL_AXIS)      # (Qc, ltr, w, mb, nb)
        g2 = g.transpose(0, 2, 4, 1, 3).reshape(Qc * w * nb, ltr, mb)
        lf = t.transpose(1, 3, 0, 2).reshape(ltc * nb, ltr, mb)
        new = jnp.where(msk[:, None, None], jnp.take(g2, idx, axis=0), lf)
        return new.reshape(ltc, nb, ltr, mb).transpose(2, 0, 3, 1)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(ROW_AXIS, COL_AXIS), P(), P()),
                   out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False)
    return jax.jit(fn)


def permute(coord: str, perm, mat: Matrix, tile_begin: int = 0,
            tile_end: int | None = None) -> Matrix:
    """Permute rows (coord='Row') or columns ('Col') of the element range
    covered by tiles [tile_begin, tile_end); identity elsewhere.

    The distributed path requires a concrete (host) ``perm`` — the gather
    tables are trace-time data, which is what keeps the compiled program
    reusable across permutations of the same range."""
    dlaf_assert(coord in ("Row", "Col"), f"bad coord {coord!r}")
    nb = mat.block_size.row if coord == "Row" else mat.block_size.col
    ext = mat.size.row if coord == "Row" else mat.size.col
    a0 = tile_begin * nb
    a1 = ext if tile_end is None else min(tile_end * nb, ext)
    if a1 <= a0:
        return mat
    distributed = mat.grid is not None and mat.grid.num_devices > 1
    if not distributed:
        g = tiles_to_global(mat.storage, mat.dist)
        idx = jnp.asarray(perm) + a0
        if coord == "Row":
            g = g.at[a0:a1, :].set(permute_array("Row", idx, g))
        else:
            g = g.at[:, a0:a1].set(permute_array("Col", idx, g))
        return mat.with_storage(global_to_tiles(g, mat.dist))
    pm = np.asarray(perm)
    dlaf_assert(pm.ndim == 1 and len(pm) == a1 - a0,
                f"permute: perm length {len(pm)} != range {a1 - a0}")
    dlaf_assert_heavy(pm.min() >= 0 and pm.max() < a1 - a0,
                      "permute: perm indices outside the tile range")
    dist = mat.dist
    nper = dist.grid_size.row if coord == "Row" else dist.grid_size.col
    src = dist.source_rank.row if coord == "Row" else dist.source_rank.col
    _, _, ltr, ltc = storage_tile_grid(dist)
    lt = ltr if coord == "Row" else ltc
    t0, t1 = a0 // nb, -(-a1 // nb)
    l0, w = t0 // nper, (t1 - 1) // nper - t0 // nper + 1
    table, mask = _gather_tables(nper, src, lt, nb, a0, a1, pm, l0, w)
    fn = _dist_permute_cached(dist, mat.grid.mesh, coord, l0, w)
    return mat.with_storage(fn(mat.storage, table, mask))
