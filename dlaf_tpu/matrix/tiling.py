"""Layout transforms between global matrices and block-cyclic tile storage.

This is the TPU-native replacement for the reference's per-tile memory model
(``matrix/layout_info.h``, ``memory/``): instead of a pool of individually
allocated tiles, a distributed matrix lives in ONE 4D "tile storage" array of
shape ``(P*ltr, Q*ltc, mb, nb)`` whose leading two axes enumerate tiles in
*rank-major cyclic-permuted* order:

    storage[p*ltr + l_r, q*ltc + l_c] == global tile (l_r*P + (p - src_r)%P,
                                                      l_c*Q + (q - src_c)%Q)

so a plain ``NamedSharding(mesh, P('row','col'))`` over the leading axes gives
each mesh coordinate exactly its block-cyclic local tiles — XLA's block
sharding composed with this static tile permutation *is* the reference's 2D
block-cyclic distribution (``misc/matrix_distribution.md``). Edge tiles are
zero-padded to full ``(mb, nb)``; ranks owning fewer tiles than the max get
all-zero padding tiles.

All transforms are pure jnp functions (jit-able, run on device). The
permutations are trace-time constants derived from :class:`Distribution`,
so the transforms decide at trace time what they emit: an axis whose
permutation is the identity gets no gather, and where both are (no padding
slot can be referenced: every 1x1 grid, and any grid axis with one rank) no
zero tile is appended either. On a 1x1 grid with ``m``, ``n`` whole
multiples of the block the transform is ``reshape`` + ``transpose(0, 2, 1,
3)`` and nothing else; every other distribution traces the gathers it
always did. Values are the same bit for bit either way.

:func:`on_global` lifts a function of the global array to one of tile
storage, so that an entry point's local branch is ONE program (layout in,
work, layout out) instead of three with an f64 hand-off between each.
"""

from __future__ import annotations

import contextlib
import functools
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..types import ceil_div
from .distribution import Distribution
from . import util_distribution as ud


def storage_tile_grid(dist: Distribution) -> tuple[int, int, int, int]:
    """(P*ltr, Q*ltc, ltr, ltc): storage tile-grid extents and the uniform
    per-rank local tile counts (max over ranks, so short ranks are padded)."""
    nt = dist.nr_tiles
    P, Q = dist.grid_size.row, dist.grid_size.col
    ltr = ceil_div(nt.row, P) if nt.row else 0
    ltc = ceil_div(nt.col, Q) if nt.col else 0
    return P * ltr, Q * ltc, ltr, ltc


def _axis_perm(n_tiles: int, grid: int, src: int, lt: int) -> list[int]:
    """storage index -> global tile index (or n_tiles for the zero-pad slot)."""
    perm = []
    for p in range(grid):
        for l in range(lt):
            g = ud.global_tile_from_local_tile(l, grid, p, src)
            perm.append(g if g < n_tiles else n_tiles)
    return perm


def _axis_perm_inv(n_tiles: int, grid: int, src: int, lt: int) -> list[int]:
    """global tile index -> storage index."""
    inv = []
    for g in range(n_tiles):
        p = ud.rank_global_tile(g, grid, src)
        l = ud.local_tile_from_global_tile(g, grid)
        inv.append(p * lt + l)
    return inv


def _axes_in_order(dist: Distribution) -> tuple[bool, bool]:
    """(rows, cols): True where storage order IS global tile order on that
    axis: its permutation is the identity over exactly ``n_tiles`` slots,
    so there is no padding slot and the inverse is the identity too."""
    nt = dist.nr_tiles
    _, _, ltr, ltc = storage_tile_grid(dist)
    return tuple(
        _axis_perm(n, grid, src, lt) == list(range(n))
        for n, grid, src, lt in (
            (nt.row, dist.grid_size.row, dist.source_rank.row, ltr),
            (nt.col, dist.grid_size.col, dist.source_rank.col, ltc)))


def global_to_tiles(a, dist: Distribution):
    """Global ``(m, n)`` array -> tile storage ``(P*ltr, Q*ltc, mb, nb)``."""
    m, n = dist.size.row, dist.size.col
    mb, nb = dist.block_size.row, dist.block_size.col
    nt = dist.nr_tiles
    Sr, Sc, ltr, ltc = storage_tile_grid(dist)
    if not hasattr(a, "devices"):
        # host input: H2D through memory.place (complex-pair fallback for
        # PJRT paths that reject complex128 transfers)
        from . import memory as _memory

        a = _memory.place(np.asarray(a))
    a = jnp.asarray(a)
    # pad to whole tiles, split into the (ntr, ntc, mb, nb) tile grid
    if (nt.row * mb, nt.col * nb) != (m, n):
        a = jnp.pad(a, ((0, nt.row * mb - m), (0, nt.col * nb - n)))
    t = a.reshape(nt.row, mb, nt.col, nb).transpose(0, 2, 1, 3)
    keep_r, keep_c = _axes_in_order(dist)
    if not (keep_r and keep_c):
        # append one zero tile row/col as the target of padding slots, on
        # the axes that have a permutation to apply
        t = jnp.pad(t, ((0, int(not keep_r)), (0, int(not keep_c)),
                        (0, 0), (0, 0)))
    if not keep_r:
        pr = _axis_perm(nt.row, dist.grid_size.row, dist.source_rank.row,
                        ltr)
        t = jnp.take(t, jnp.array(pr, dtype=jnp.int32), axis=0)
    if not keep_c:
        pc = _axis_perm(nt.col, dist.grid_size.col, dist.source_rank.col,
                        ltc)
        t = jnp.take(t, jnp.array(pc, dtype=jnp.int32), axis=1)
    assert t.shape == (Sr, Sc, mb, nb)
    return t


def tiles_to_global(t, dist: Distribution):
    """Tile storage -> global ``(m, n)`` array (inverse of global_to_tiles)."""
    m, n = dist.size.row, dist.size.col
    mb, nb = dist.block_size.row, dist.block_size.col
    nt = dist.nr_tiles
    _, _, ltr, ltc = storage_tile_grid(dist)
    keep_r, keep_c = _axes_in_order(dist)
    t = jnp.asarray(t)
    if not keep_r:
        pr = _axis_perm_inv(nt.row, dist.grid_size.row,
                            dist.source_rank.row, ltr)
        t = jnp.take(t, jnp.array(pr, dtype=jnp.int32), axis=0)
    if not keep_c:
        pc = _axis_perm_inv(nt.col, dist.grid_size.col,
                            dist.source_rank.col, ltc)
        t = jnp.take(t, jnp.array(pc, dtype=jnp.int32), axis=1)
    a = t.transpose(0, 2, 1, 3).reshape(nt.row * mb, nt.col * nb)
    return a[:m, :n]


def on_global(fn, dist: Distribution):
    """Lift ``fn``, a function of the global ``(m, n)`` array, to tile
    storage: ``storage -> global_to_tiles(fn(tiles_to_global(storage)))``,
    to be jitted by the caller as ONE program (the layout moves happen
    inside it, once, next to the work). ``fn`` may return a tuple whose
    first element is the global result; the rest (``info`` scalars) pass
    through."""
    def prog(storage):
        # trace-time phase name of the two layout moves (obs/scopes.py)
        with obs.named_span("layout"):
            a = tiles_to_global(storage, dist)
        out = fn(a)
        with obs.named_span("layout"):
            tiles = global_to_tiles(
                out[0] if isinstance(out, tuple) else out, dist)
        return (tiles,) + out[1:] if isinstance(out, tuple) else tiles

    # the compiled program's name (``jit_<name>`` on a profiler timeline)
    prog.__name__ = f"{getattr(fn, '__name__', 'fn')}_on_tiles"
    return prog


# Donated jit forms of the two layout transforms, shared by the algorithm
# entry points for their internal stage hand-offs (layout -> factorize ->
# layout) and for opt-in input donation (the reference's in-place matrix
# semantics). Donation removes one full-matrix HBM buffer per hand-off —
# at the single-chip ceiling (config #1 N=16384 = 2.1 GB/buffer on a
# 15.75 GB chip) that is the difference between fitting and OOM. No
# config dependence: these never need program-cache invalidation.

@functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
def global_to_tiles_donated(a, dist: Distribution):
    return global_to_tiles(a, dist)


@functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
def tiles_to_global_donated(t, dist: Distribution):
    return tiles_to_global(t, dist)


@contextlib.contextmanager
def quiet_donation():
    """Scope for dispatching donated programs: suppresses jax's
    "Some donated buffers were not usable" warning INSIDE the library's
    own calls only (backends that cannot alias a given buffer — e.g.
    complex128 on XLA:CPU — fall back to a copy, which is exactly the
    pre-donation behavior; per-call noise, not signal). Donation warnings
    from the application's own jax code are left untouched."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def to_global(storage, dist: Distribution, donate: bool):
    """Entry-point helper: tile storage -> global array, optionally
    consuming ``storage`` (the caller's opt-in input donation). Callers
    dispatch inside their own :func:`quiet_donation` scope."""
    if donate:
        return tiles_to_global_donated(storage, dist)
    return tiles_to_global(storage, dist)


def donate_argnums_kw(donate: bool, argnums) -> dict:
    """``jax.jit`` kwargs for an optionally donated build (shared by the
    per-algorithm program caches, which key on the donate flag)."""
    return {"donate_argnums": argnums} if donate else {}


def global_tile_to_storage_index(dist: Distribution, row: int, col: int) -> tuple[int, int]:
    """Storage coordinates of global tile (row, col) — trace-time helper used
    by the per-k algorithm loops."""
    _, _, ltr, ltc = storage_tile_grid(dist)
    pr = ud.rank_global_tile(row, dist.grid_size.row, dist.source_rank.row)
    pc = ud.rank_global_tile(col, dist.grid_size.col, dist.source_rank.col)
    lr = ud.local_tile_from_global_tile(row, dist.grid_size.row)
    lc = ud.local_tile_from_global_tile(col, dist.grid_size.col)
    return pr * ltr + lr, pc * ltc + lc
