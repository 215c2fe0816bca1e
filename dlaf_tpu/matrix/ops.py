"""Whole-matrix structural ops: transpose, hermitianize, triangle merge, copy.

These cover the reference's ``matrix::copy`` (``matrix/copy.h:29``),
``MatrixMirror`` (``matrix/matrix_mirror.h:31-202``) and the implicit
"other-triangle" handling spread through its algorithms. The TPU-native
expression: run the op on the *global view* inside one jit whose inputs and
outputs carry the block-cyclic tile sharding — GSPMD then inserts the
all-to-all/collective-permute traffic for the storage permutation, instead of
hand-written MPI tile exchanges.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..common.asserts import dlaf_assert
from .matrix import Matrix
from .tiling import (global_to_tiles, tiles_to_global, on_global,
                     quiet_donation, donate_argnums_kw)


def _global_op_jit(dist, sharding, fn, donate=False):
    """jit storage->storage running ``fn`` on the global view."""
    kw = dict(donate_argnums_kw(donate, 0))
    if sharding is not None:
        kw.update(in_shardings=sharding, out_shardings=sharding)
    return jax.jit(on_global(fn, dist), **kw)


@functools.lru_cache(maxsize=256)
def _cached_global_op(dist, sharding, name, extra=None, donate=False):
    fns = {
        "transpose": lambda g: jnp.swapaxes(g, 0, 1),
        "conj_transpose": lambda g: jnp.conj(jnp.swapaxes(g, 0, 1)),
        "hermitianize_L": lambda g: _herm(g, "L"),
        "hermitianize_U": lambda g: _herm(g, "U"),
        "tril": lambda g: jnp.tril(g),
        "triu": lambda g: jnp.triu(g),
        "copy": lambda g: g,
    }
    return _global_op_jit(dist, sharding, fns[name], donate)


def _herm(g, uplo):
    tri = jnp.tril(g, -1) if uplo == "L" else jnp.triu(g, 1)
    d = jnp.real(jnp.diagonal(g)) if jnp.iscomplexobj(g) else jnp.diagonal(g)
    return tri + jnp.conj(tri.T) + jnp.diag(d).astype(g.dtype)


def _sharding(mat: Matrix):
    if mat.grid is None or mat.grid.num_devices == 1:
        return None
    return mat.grid.tile_sharding()


def transpose(mat: Matrix, conj: bool = True) -> Matrix:
    """(Conjugate-)transpose; square matrices/blocks keep their distribution."""
    dlaf_assert(mat.size.row == mat.size.col and
                mat.block_size.row == mat.block_size.col,
                "transpose: square matrices only (rectangular lands later)")
    fn = _cached_global_op(mat.dist, _sharding(mat),
                           "conj_transpose" if conj else "transpose")
    return mat.with_storage(fn(mat.storage))


def hermitianize(mat: Matrix, uplo: str, *, donate: bool = False) -> Matrix:
    """Full Hermitian matrix from its stored ``uplo`` triangle
    (the whole-matrix ``hermitian_from``). ``donate=True`` permits
    consuming ``mat``'s storage."""
    fn = _cached_global_op(mat.dist, _sharding(mat), f"hermitianize_{uplo}",
                           donate=donate)
    with quiet_donation():
        return mat.with_storage(fn(mat.storage))


def merge_triangle(new: Matrix, orig: Matrix, uplo: str, *,
                   donate_orig: bool = False) -> Matrix:
    """``uplo`` triangle from ``new``, opposite strict triangle from ``orig``
    (LAPACK in-place update semantics at matrix scope).

    ``new``'s storage is always donated (every caller passes a freshly
    computed intermediate); ``donate_orig=True`` also consumes ``orig``'s
    storage — the final step of an in-place-semantics algorithm entry."""
    fn = _merge_cached(new.dist, _sharding(new), uplo, donate_orig)
    with quiet_donation():
        return new.with_storage(fn(new.storage, orig.storage))


@functools.lru_cache(maxsize=128)
def _merge_cached(dist, sharding, uplo, donate_orig=False):
    def prog(sn, so):
        gn = tiles_to_global(sn, dist)
        go = tiles_to_global(so, dist)
        out = jnp.tril(gn) + jnp.triu(go, 1) if uplo == "L" \
            else jnp.triu(gn) + jnp.tril(go, -1)
        return global_to_tiles(out, dist)

    kw = dict(donate_argnums_kw(True, (0, 1) if donate_orig else (0,)))
    if sharding is not None:
        kw.update(in_shardings=(sharding, sharding), out_shardings=sharding)
    return jax.jit(prog, **kw)


def copy(mat: Matrix) -> Matrix:
    """Fresh storage with identical contents (reference ``matrix::copy``)."""
    return mat.with_storage(mat.storage + 0)


def mirror_to_host(mat: Matrix) -> np.ndarray:
    """Device->host mirror (reference ``MatrixMirror`` D2H side)."""
    return mat.to_numpy()


def mirror_to_device(a: np.ndarray, like: Matrix) -> Matrix:
    """Host->device mirror with ``like``'s layout (MatrixMirror H2D side)."""
    return Matrix.from_global(a, like.block_size, grid=like.grid,
                              source_rank=like.dist.source_rank)
