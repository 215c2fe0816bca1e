"""Generalized-to-standard eigenproblem transform (HEGST).

TPU-native counterpart of the reference's ``eigensolver/gen_to_std``
(``gen_to_std/api.h:21-23``, ``impl.h:200-740``): given the Cholesky factor of
B, transform ``A x = lambda B x`` to standard form:

    uplo='L':  A <- inv(L) A inv(L)^H        (B = L L^H)
    uplo='U':  A <- inv(U^H) A inv(U)        (B = U^H U)

Two formulations (config knob ``hegst_impl``):

* ``"blocked"`` (default) — the reference's flop discipline (~n^3 real ops):
  per-``k`` two-sided update — hegst on the diagonal block, panel trsm +
  two half-weight hemm's, her2k trailing update exploiting Hermitian
  symmetry, and the trailing triangular solve of the panel realized as
  DEFERRED incremental updates in BOTH forms: at each later step, the
  step's solved row/column fans one gemm into the remaining region — the
  reference's reshuffle ("the tasks of the final huge TRSM have been
  reshuffled to avoid extra communication of the matrix L",
  ``impl.h:330-335``). Distributed, each panel broadcast thereby serves
  the trailing update AND the pending solves of all previous panels;
  locally it keeps every unrolled step a small fixed op set instead of a
  per-step recursive whole-trailing trsm the AOT compile budget could
  not afford.

* ``"twosolve"`` — Hermitianize A, then TWO whole-matrix triangular solves
  (each a fully parallel blocked substitution). ~2x the flops, but two
  perfectly MXU-shaped dense sweeps with no panel round-trips and O(1)
  step count; kept as the fallback/cross-check and as the scan-mode
  route: a masked uniform-shape scan of the blocked form would pay the
  usual ~3x masked-work premium on its n^3 (~3n^3) — MORE than
  twosolve's 2n^3 dense flops — so at step counts where the compile
  hatch matters, twosolve IS the optimal scan-mode HEGST, not a
  placeholder (``dist_step_mode`` auto/scan routes here).

Local + distributed, both uplos (reference parity: local L/U + distributed
L/U, ``call_L``/``call_U``).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import obs
from ..config import get_configuration, register_program_cache
from ..comm import collectives as cc
from ..comm.grid import COL_AXIS, ROW_AXIS
from ..common.asserts import dlaf_assert
from ..matrix import ops as mops
from ..matrix import util_distribution as ud
from ..matrix.distribution import assert_slot_aligned
from ..matrix.matrix import Matrix
from ..matrix.panel import (DistContext, transpose_col_to_rows,
                            transpose_row_to_cols)
from ..matrix.tiling import (storage_tile_grid, tiles_to_global,
                             global_to_tiles_donated,
                             quiet_donation, donate_argnums_kw)
from ..tile_ops import blas as tb
from ..tile_ops import mixed as mx
from ..tile_ops import pallas_panel as ppan
from ..tile_ops import ozaki as oz
from ..types import ceil_div
from .triangular import triangular_solve


def _gen_to_std_twosolve(uplo: str, a: Matrix, b_factor: Matrix,
                         donate: bool = False) -> Matrix:
    """Two-whole-solve formulation (see module docstring). ``ah`` and ``x``
    are owned intermediates — each solve consumes its rhs, so at most two
    full matrices of this chain are live at once; ``donate`` additionally
    consumes ``a`` at the final triangle merge."""
    ah = mops.hermitianize(a, uplo)
    if uplo == "L":
        x = triangular_solve("L", "L", "N", "N", 1.0, b_factor, ah,
                             donate_b=True)
        y = triangular_solve("R", "L", "C", "N", 1.0, b_factor, x,
                             donate_b=True)
    else:
        x = triangular_solve("L", "U", "C", "N", 1.0, b_factor, ah,
                             donate_b=True)
        y = triangular_solve("R", "U", "N", "N", 1.0, b_factor, x,
                             donate_b=True)
    return mops.merge_triangle(y, a, uplo, donate_orig=donate)


# ---------------------------------------------------------------------------
# Local blocked form (reference impl.h:169-266 call_L / call_U local)
# ---------------------------------------------------------------------------

def _hegst_diag(uplo: str, akk, lkk, inv=None, fused=False,
                interpret=False):
    """Transformed diagonal block, full Hermitian form: W = inv(L) herm(Akk)
    inv(L)^H (uplo='L') / inv(U^H) herm(Akk) inv(U) (uplo='U'). The two
    block-size solves follow the f64_trsm knob via trsm_panel — or, under
    ``panel_impl="fused"`` (``fused=True``, docs/pallas_panel.md), the
    fused Pallas panel-solve kernels; ``inv`` is the optional precomputed
    refined inverse of ``lkk``'s triangle, shared with the step's panel
    solve so the mixed route derives it ONCE."""
    ah = tb.hermitian_from(akk, uplo)
    if uplo == "L":
        w = ppan.panel_solve("L", "L", "N", "N", lkk, ah, inv_a=inv,
                             fused=fused, interpret=interpret)
        w = ppan.panel_solve("R", "L", "C", "N", lkk, w, inv_a=inv,
                             fused=fused, interpret=interpret)
    else:
        w = ppan.panel_solve("L", "U", "C", "N", lkk, ah, inv_a=inv,
                             fused=fused, interpret=interpret)
        w = ppan.panel_solve("R", "U", "N", "N", lkk, w, inv_a=inv,
                             fused=fused, interpret=interpret)
    # the algorithm reads W as Hermitian-stored from its uplo triangle (the
    # reference's hemmPanelTile does the same with the written tile)
    return tb.hermitian_from(w, uplo)


def _step_inv(uplo: str, lkk):
    """Refined triangle inverse for one step's solves, or None when the
    config routes trsm_panel natively."""
    if tb.trsm_panel_uses_mixed(lkk.dtype):
        return mx.tri_inv_refined(tb.tri_mask(lkk, uplo),
                                  lower=(uplo == "L"))
    return None


@register_program_cache
# both operands are the entry point's freshly built global-layout copies
# (the caller's matrices are re-read only at the final triangle merge)
@functools.partial(jax.jit, static_argnames=("uplo", "nb", "lookahead",
                                             "panel_fused",
                                             "panel_interpret"),
                   donate_argnums=(0, 1))
def _hegst_local_blocked(a, l, *, uplo: str, nb: int, lookahead: bool = False,
                         panel_fused: bool = False,
                         panel_interpret: bool = False):
    """Unrolled blocked two-sided transform on the global 2D array.

    Per step (uplo='L', LAPACK xHEGST itype=1 structure, which the
    reference's tile loop realizes — ``impl.h:207-264``):
    deferred-solve update of all PREVIOUS panel columns (row k solved
    with Lkk, one gemm fans it into the rows below — the same
    incremental realization of the trailing inv(L22) solve as the
    distributed builder, so each step is a small fixed op set instead
    of a per-step recursive whole-trailing trsm whose unrolled program
    would dwarf the AOT compile budget); diag hegst; P <- P inv(Lkk)^H;
    P -= 1/2 L21 W; A22 -= P L21^H + L21 P^H (her2k, one gemm +
    transpose here); P -= 1/2 L21 W. uplo='U' is the mirrored row-panel
    sweep. Exact slice shapes per step; the opposite triangle of ``a``
    passes through untouched (merged by the caller).
    """
    n = a.shape[0]
    nt = ceil_div(n, nb)
    # lookahead carry (next diag block, next panel source) — the same
    # next-panel-column-first her2k split as the pipelined Cholesky
    # (docs/lookahead.md): step k+1's hegst-diag solves and panel trsm
    # consume step k's strip values directly instead of reading `a` after
    # the bulk her2k scatter
    la = None
    for k in range(nt):
        k0, k1 = k * nb, min((k + 1) * nb, n)
        lkk = l[k0:k1, k0:k1]
        lkk_inv = _step_inv(uplo, lkk)
        if uplo == "L":
            if k0 > 0:
                # deferred trailing-solve: row k of every previous panel
                # column, then one gemm into the rows below
                rowk = tb.trsm_panel("L", "L", "N", "N", lkk,
                                     a[k0:k1, :k0], inv_a=lkk_inv)
                a = a.at[k0:k1, :k0].set(rowk)
                if k1 < n:
                    a = a.at[k1:, :k0].add(-tb.gemm(l[k1:, k0:k1], rowk))
            w = _hegst_diag(uplo, a[k0:k1, k0:k1] if la is None else la[0],
                            lkk, inv=lkk_inv, fused=panel_fused,
                            interpret=panel_interpret)
            a = a.at[k0:k1, k0:k1].set(w)
            if k1 == n:
                continue
            p = a[k1:, k0:k1] if la is None else la[1]
            l21 = l[k1:, k0:k1]
            p = ppan.panel_solve("R", "L", "C", "N", lkk, p, inv_a=lkk_inv,
                                 fused=panel_fused,
                                 interpret=panel_interpret)
            p = p - 0.5 * tb.gemm(l21, w)
            la = None
            if lookahead:
                # next block column of the her2k first (carried), rest as
                # a row-trimmed her2k of the remaining trailing block
                wn = min(nb, n - k1)
                mt = n - k1
                strip = tb.gemm(p, l21[:wn], op_b="C") \
                    + tb.gemm(l21, p[:wn], op_b="C")
                smask = jnp.arange(mt)[:, None] >= jnp.arange(wn)[None, :]
                new_col = a[k1:, k1:k1 + wn] - jnp.where(smask, strip, 0)
                a = a.at[k1:, k1:k1 + wn].set(new_col)
                la = (new_col[:wn], new_col[wn:])
                if mt > wn:
                    a = a.at[k1 + wn:, k1 + wn:].set(
                        tb.her2k("L", "N", p[wn:], l21[wn:],
                                 a[k1 + wn:, k1 + wn:], alpha=-1.0))
            else:
                a = a.at[k1:, k1:].set(
                    tb.her2k("L", "N", p, l21, a[k1:, k1:], alpha=-1.0))
            p = p - 0.5 * tb.gemm(l21, w)
            a = a.at[k1:, k0:k1].set(p)
        else:
            if k0 > 0:
                colk = tb.trsm_panel("R", "U", "N", "N", lkk,
                                     a[:k0, k0:k1], inv_a=lkk_inv)
                a = a.at[:k0, k0:k1].set(colk)
                if k1 < n:
                    a = a.at[:k0, k1:].add(-tb.gemm(colk, l[k0:k1, k1:]))
            w = _hegst_diag(uplo, a[k0:k1, k0:k1] if la is None else la[0],
                            lkk, inv=lkk_inv, fused=panel_fused,
                            interpret=panel_interpret)
            a = a.at[k0:k1, k0:k1].set(w)
            if k1 == n:
                continue
            p = a[k0:k1, k1:] if la is None else la[1]
            u12 = l[k0:k1, k1:]
            p = ppan.panel_solve("L", "U", "C", "N", lkk, p, inv_a=lkk_inv,
                                 fused=panel_fused,
                                 interpret=panel_interpret)
            p = p - 0.5 * tb.gemm(w, u12)
            la = None
            if lookahead:
                # mirrored: next block row of the her2k first (carried)
                wn = min(nb, n - k1)
                mt = n - k1
                strip = tb.gemm(p[:, :wn], u12, op_a="C") \
                    + tb.gemm(u12[:, :wn], p, op_a="C")
                smask = jnp.arange(wn)[:, None] <= jnp.arange(mt)[None, :]
                new_row = a[k1:k1 + wn, k1:] - jnp.where(smask, strip, 0)
                a = a.at[k1:k1 + wn, k1:].set(new_row)
                la = (new_row[:, :wn], new_row[:, wn:])
                if mt > wn:
                    a = a.at[k1 + wn:, k1 + wn:].set(
                        tb.her2k("U", "C", p[:, wn:], u12[:, wn:],
                                 a[k1 + wn:, k1 + wn:], alpha=-1.0))
            else:
                a = a.at[k1:, k1:].set(
                    tb.her2k("U", "C", p, u12, a[k1:, k1:], alpha=-1.0))
            p = p - 0.5 * tb.gemm(w, u12)
            a = a.at[k0:k1, k1:].set(p)
    return a


# ---------------------------------------------------------------------------
# Distributed blocked form (reference impl.h:268-740 call_L / call_U)
# ---------------------------------------------------------------------------

def _pair_product(x_tiles, y_tiles, cplx: bool, use_mxu: bool):
    """All-pairs tile product ``out[r, c] = x[r] @ conj(y[c])^T`` over two
    tile batches (the distributed gemm fan-out of one her2k term /
    deferred-solve sweep), optionally flattened through the int8/bf16 MXU
    path (``f64_gemm="mxu"``)."""
    if use_mxu:
        nr, mb = x_tiles.shape[0], x_tiles.shape[-2]
        nc = y_tiles.shape[0]
        mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
        full = mmfn(x_tiles.reshape(nr * mb, -1),
                    jnp.conj(y_tiles).reshape(nc * mb, -1).T,
                    slices=tb._oz_slices())
        return full.reshape(nr, mb, nc, mb).transpose(0, 2, 1, 3)
    return jnp.einsum("rab,cdb->rcad", x_tiles, jnp.conj(y_tiles),
                      preferred_element_type=x_tiles.dtype)


def _col_strip_product(x_tiles, y_tile, cplx: bool, use_mxu: bool):
    """``out[r] = x_tiles[r] @ conj(y_tile)^T`` — one tile COLUMN of the
    all-pairs product (the lookahead split's next-column strip), same
    route as :func:`_pair_product`."""
    if use_mxu:
        nr, mb = x_tiles.shape[0], x_tiles.shape[-2]
        mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
        return mmfn(x_tiles.reshape(nr * mb, -1), jnp.conj(y_tile).T,
                    slices=tb._oz_slices()).reshape(nr, mb, mb)
    return jnp.einsum("rab,db->rad", x_tiles, jnp.conj(y_tile),
                      preferred_element_type=x_tiles.dtype)


def _row_strip_product(x_tile, y_tiles, cplx: bool, use_mxu: bool):
    """``out[c] = x_tile @ conj(y_tiles[c])^T`` — one tile ROW of the
    all-pairs product (the mirrored uplo='U' strip)."""
    if use_mxu:
        nc, mb = y_tiles.shape[0], y_tiles.shape[-2]
        mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
        full = mmfn(x_tile, jnp.conj(y_tiles).reshape(nc * mb, mb).T,
                    slices=tb._oz_slices())
        return full.reshape(mb, nc, mb).transpose(1, 0, 2)
    return jnp.einsum("ab,cdb->cad", x_tile, jnp.conj(y_tiles),
                      preferred_element_type=y_tiles.dtype)


def _build_dist_hegst(dist, mesh, uplo: str, use_mxu=False, cplx=False,
                      lookahead=False, comm_la=False, panel_fused=False,
                      panel_interpret=False):
    """shard_map'd blocked HEGST over the 2D mesh, k-loop unrolled.

    Per step k (uplo='L'): broadcast the L diag + col-panel (row-wise and
    transposed — the same panel machinery as the distributed Cholesky);
    FIRST apply the deferred trailing-solve contributions to all previous
    panel columns (row k: A_kj <- inv(L_kk) A_kj, then A_ij -= L_ik A_kj —
    the reference's reshuffled huge-TRSM, ``impl.h:327-372``); then hegst
    the diagonal block (redundantly on every rank, like the dist
    Cholesky's potrf), panel trsm + first half-hemm, broadcast the A
    panel, her2k trailing as two all-pairs tile products, second
    half-hemm. uplo='U' mirrors with row panels / the upper triangle.
    All index bounds are static per k; validity masks are the only traced
    rank-dependent values.

    Phased like the distributed Cholesky (``panel_chain`` / ``step_pre``
    / ``step_bulk``) so ``comm_la`` (``comm_lookahead=1``,
    docs/comm_overlap.md) can emit step k+1's panel chain — the L-panel
    broadcasts (constant operand!), the fused diag ``bcast2d``s, the
    A-panel broadcast and both transposed-panel all_gathers — BEFORE
    step k's bulk her2k product: the chain reads only ``ll`` and the
    carried post-strip values, never ``lt`` after the bulk scatter. The
    deferred-solve broadcast (``akj``/``ajk``) reads ``lt`` rows/cols
    behind the pivot and stays in its serial position — the documented
    exception (docs/comm_overlap.md). Phase order of ``lt`` mutations is
    identical in both modes, so results are bitwise the same with the
    knob on or off.
    """
    nt = dist.nr_tiles.row
    mb = dist.block_size.row
    n = dist.size.row
    Pr, Qc = dist.grid_size.row, dist.grid_size.col
    sr, sc = dist.source_rank.row, dist.source_rank.col
    _, _, ltr, ltc = storage_tile_grid(dist)

    def pad_lkk(lkk, k):
        ts = min(mb, n - k * mb)
        if ts < mb:  # identity pad keeps the edge-tile solves defined
            pad = jnp.arange(mb) >= ts
            lkk = jnp.where(pad[:, None] | pad[None, :], 0, lkk) \
                + jnp.diag(pad.astype(lkk.dtype))
        return lkk

    def _indices(k):
        owner_r = ud.rank_global_tile(k, Pr, sr)
        owner_c = ud.rank_global_tile(k, Qc, sc)
        kr = ud.local_tile_from_global_tile(k, Pr)
        kc = ud.local_tile_from_global_tile(k, Qc)
        lu_r = max(0, -(-(k + 2 - Pr) // Pr))
        lu_c = max(0, -(-(k + 2 - Qc) // Qc))
        return owner_r, owner_c, kr, kc, lu_r, lu_c

    # chain tuples: (lkk, lkk_inv, vpan_l, akk, w, pan, vb_a, vt_a, vt_l)
    # with vpan_l the broadcast L panel, vb_a the broadcast A panel and
    # vt_* the transposed panels; trailing entries None past the static
    # early-exit points (mirroring the serial step's early returns).

    def chain_L(lt, ll, k, la, rr, rc):
        owner_r, owner_c, kr, kc, lu_r, lu_c = _indices(k)
        is_owner_c = cc.this_rank(COL_AXIS) == owner_c

        # -- L diag -> everyone (one fused 2D collective; constant ll) ----
        lkk = pad_lkk(cc.bcast2d(ll[kr, kc], owner_r, owner_c), k)
        # lkk is already triangular: refined inverse computed ONCE per
        # step, shared by the prev-panel solve, diag hegst and panel trsm
        lkk_inv = _step_inv("L", lkk)

        # -- L col-panel (rows > k) row-broadcast (constant ll) -----------
        nrows = ltr - lu_r
        g_rows = (lu_r + jnp.arange(max(nrows, 1))) * Pr + rr
        row_valid = (g_rows > k) & (g_rows < nt)
        vr_l = None
        if nrows > 0:
            vr_l = cc.bcast(jnp.where((is_owner_c & row_valid)[:, None, None],
                                      ll[lu_r:, kc], 0), COL_AXIS, owner_c)
            vr_l = jnp.where(row_valid[:, None, None], vr_l, 0)

        # -- diag hegst (redundant on every rank) -------------------------
        # lookahead carry (next-column strip of step k-1,
        # docs/lookahead.md): the hegst-diag chain consumes it directly —
        # correct on the owner (the only contributor bcast/keep select)
        cand = lt[kr, kc] if la is None else la[0][kr - la[1]]
        akk = cc.bcast2d(cand, owner_r, owner_c)
        w = _hegst_diag("L", akk, lkk, inv=lkk_inv, fused=panel_fused,
                        interpret=panel_interpret)
        if k == nt - 1 or nrows == 0:
            return lkk, lkk_inv, vr_l, akk, w, None, None, None, None

        # -- panel: trsm right with Lkk + first half-hemm -----------------
        pan = ppan.panel_solve("R", "L", "C", "N", lkk,
                               lt[lu_r:, kc] if la is None
                               else la[0][lu_r - la[1]:],
                               inv_a=lkk_inv, fused=panel_fused,
                               interpret=panel_interpret)
        pan = pan - 0.5 * jnp.einsum("rab,bd->rad", vr_l, w)
        pan = jnp.where(row_valid[:, None, None], pan, 0)
        ncols = ltc - lu_c
        if ncols == 0:
            return lkk, lkk_inv, vr_l, akk, w, pan, None, None, None

        # -- A panel broadcast + transposed panels ------------------------
        g_cols = (lu_c + jnp.arange(ncols)) * Qc + rc
        col_valid = (g_cols > k) & (g_cols < nt)
        ctx = DistContext(dist)
        keep = (is_owner_c & row_valid)[:, None, None]
        vr_a = cc.bcast(jnp.where(keep, pan, 0), COL_AXIS, owner_c)
        vc_a = transpose_col_to_rows(ctx, vr_a, lu_r, g_cols)
        vc_l = transpose_col_to_rows(ctx, vr_l, lu_r, g_cols)
        vc_a = jnp.where(col_valid[:, None, None], vc_a, 0)
        vc_l = jnp.where(col_valid[:, None, None], vc_l, 0)
        return lkk, lkk_inv, vr_l, akk, w, pan, vr_a, vc_a, vc_l

    def step_pre_L(lt, k, ch, rr, rc):
        lkk, lkk_inv, vr_l, akk, w, pan, vr_a, vc_a, vc_l = ch
        owner_r, owner_c, kr, kc, lu_r, lu_c = _indices(k)
        is_owner_r = cc.this_rank(ROW_AXIS) == owner_r
        is_owner_c = cc.this_rank(COL_AXIS) == owner_c
        nrows = ltr - lu_r
        g_rows = (lu_r + jnp.arange(max(nrows, 1))) * Pr + rr
        row_valid = (g_rows > k) & (g_rows < nt)

        # -- deferred trailing-solve updates of previous panels -----------
        # (reference impl.h:327-372: only tasks involving the k-th panel
        # of L run at iteration k, so every previous panel updates here).
        # The akj broadcast reads lt rows behind the pivot — the one
        # collective comm_la does NOT hoist (docs/comm_overlap.md).
        lc_ub = ceil_div(k, Qc)   # max local cols with global col < k
        if lc_ub > 0:
            g_pcols = jnp.arange(lc_ub) * Qc + rc
            pcol_valid = g_pcols < k
            rowk = lt[kr, :lc_ub]
            rowk_new = tb.trsm_panel("L", "L", "N", "N", lkk, rowk,
                                     inv_a=lkk_inv)
            keepp = (is_owner_r & pcol_valid)[:, None, None]
            lt = lt.at[kr, :lc_ub].set(jnp.where(keepp, rowk_new, rowk))
            akj = cc.bcast(jnp.where(keepp, rowk_new, 0), ROW_AXIS, owner_r)
            if nrows > 0:
                upd = _pair_product(vr_l, jnp.conj(jnp.swapaxes(
                    akj, -1, -2)), cplx, use_mxu)
                mask4 = (row_valid[:, None] & pcol_valid[None, :]
                         )[:, :, None, None]
                lt = lt.at[lu_r:, :lc_ub].add(-jnp.where(mask4, upd, 0))

        # -- diag write ---------------------------------------------------
        lt = lt.at[kr, kc].set(jnp.where(is_owner_r & is_owner_c,
                                         tb.tri_mask(w, "L")
                                         + tb.tri_mask(akk, "U", k=-1),
                                         lt[kr, kc]))
        if pan is None:
            return lt, None

        keep = (is_owner_c & row_valid)[:, None, None]
        lt = lt.at[lu_r:, kc].set(jnp.where(keep, pan, lt[lu_r:, kc]))
        if vc_l is None:
            # no trailing columns on any rank; finish the second half-hemm
            pan2 = pan - 0.5 * jnp.einsum("rab,bd->rad", vr_l, w)
            lt = lt.at[lu_r:, kc].set(
                jnp.where(keep, pan2, lt[lu_r:, kc]))
            return lt, None
        if not (lookahead and k + 1 < nt):
            return lt, None

        # next panel column of the her2k first (my kc1-slot transposed
        # tiles — the exact tiles the bulk pair product would use),
        # carried to step k+1's hegst-diag/panel chain
        tril_m = jnp.tril(jnp.ones((mb, mb), dtype=bool))
        kc1 = ud.local_tile_from_global_tile(k + 1, Qc)
        owner_c1 = ud.rank_global_tile(k + 1, Qc, sc)
        own_c1 = cc.this_rank(COL_AXIS) == owner_c1
        updc = _col_strip_product(vr_a, vc_l[kc1 - lu_c], cplx, use_mxu) \
            + _col_strip_product(vr_l, vc_a[kc1 - lu_c], cplx, use_mxu)
        below1 = row_valid & (g_rows > k + 1)
        ondiag1 = row_valid & (g_rows == k + 1)
        m3 = (below1[:, None, None] | (ondiag1[:, None, None] & tril_m)) \
            & own_c1
        new_col = lt[lu_r:, kc1] - jnp.where(m3, updc,
                                             jnp.zeros_like(updc))
        lt = lt.at[lu_r:, kc1].set(new_col)
        return lt, (new_col, lu_r)

    def step_bulk_L(lt, k, ch, stripped, rr, rc):
        lkk, lkk_inv, vr_l, akk, w, pan, vr_a, vc_a, vc_l = ch
        if pan is None or vc_l is None:
            return lt
        owner_r, owner_c, kr, kc, lu_r, lu_c = _indices(k)
        is_owner_c = cc.this_rank(COL_AXIS) == owner_c
        nrows, ncols = ltr - lu_r, ltc - lu_c
        g_rows = (lu_r + jnp.arange(nrows)) * Pr + rr
        g_cols = (lu_c + jnp.arange(ncols)) * Qc + rc
        row_valid = (g_rows > k) & (g_rows < nt)
        col_valid = (g_cols > k) & (g_cols < nt)
        keep = (is_owner_c & row_valid)[:, None, None]

        # -- her2k trailing: A_ij -= P_i L_jk^H + L_ik P_j^H --------------
        pair = row_valid[:, None] & col_valid[None, :]
        below = pair & (g_rows[:, None] > g_cols[None, :])
        ondiag = pair & (g_rows[:, None] == g_cols[None, :])
        tril_m = jnp.tril(jnp.ones((mb, mb), dtype=bool))
        if stripped:
            notnext = g_cols != k + 1
            below = below & notnext[None, :]
            ondiag = ondiag & notnext[None, :]
        upd = _pair_product(vr_a, vc_l, cplx, use_mxu) \
            + _pair_product(vr_l, vc_a, cplx, use_mxu)
        mask4 = below[:, :, None, None] | (ondiag[:, :, None, None] & tril_m)
        lt = lt.at[lu_r:, lu_c:].add(-jnp.where(mask4, upd, 0))

        # -- second half-hemm on the panel --------------------------------
        pan2 = pan - 0.5 * jnp.einsum("rab,bd->rad", vr_l, w)
        lt = lt.at[lu_r:, kc].set(jnp.where(keep, pan2, lt[lu_r:, kc]))
        return lt

    def chain_U(lt, ll, k, la, rr, rc):
        owner_r, owner_c, kr, kc, lu_r, lu_c = _indices(k)
        is_owner_r = cc.this_rank(ROW_AXIS) == owner_r

        ukk = pad_lkk(cc.bcast2d(ll[kr, kc], owner_r, owner_c), k)
        ukk_inv = _step_inv("U", ukk)

        # -- U row-panel (cols > k) col-broadcast (constant ll) -----------
        ncols = ltc - lu_c
        g_cols = (lu_c + jnp.arange(max(ncols, 1))) * Qc + rc
        col_valid = (g_cols > k) & (g_cols < nt)
        vc_u = None
        if ncols > 0:
            vc_u = cc.bcast(jnp.where((is_owner_r & col_valid)[:, None, None],
                                      ll[kr, lu_c:], 0), ROW_AXIS, owner_r)
            vc_u = jnp.where(col_valid[:, None, None], vc_u, 0)

        cand = lt[kr, kc] if la is None else la[0][kc - la[1]]
        akk = cc.bcast2d(cand, owner_r, owner_c)
        w = _hegst_diag("U", akk, ukk, inv=ukk_inv, fused=panel_fused,
                        interpret=panel_interpret)
        if k == nt - 1 or ncols == 0:
            return ukk, ukk_inv, vc_u, akk, w, None, None, None, None

        # -- panel: trsm left with Ukk^H + first half-hemm ----------------
        pan = ppan.panel_solve("L", "U", "C", "N", ukk,
                               lt[kr, lu_c:] if la is None
                               else la[0][lu_c - la[1]:],
                               inv_a=ukk_inv, fused=panel_fused,
                               interpret=panel_interpret)
        pan = pan - 0.5 * jnp.einsum("ab,rbd->rad", w, vc_u)
        pan = jnp.where(col_valid[:, None, None], pan, 0)
        nrows = ltr - lu_r
        if nrows == 0:
            return ukk, ukk_inv, vc_u, akk, w, pan, None, None, None

        g_rows = (lu_r + jnp.arange(nrows)) * Pr + rr
        row_valid = (g_rows > k) & (g_rows < nt)
        ctx = DistContext(dist)
        keep = (is_owner_r & col_valid)[:, None, None]
        vc_a = cc.bcast(jnp.where(keep, pan, 0), ROW_AXIS, owner_r)
        vr_a = transpose_row_to_cols(ctx, vc_a, lu_c, g_rows)
        vr_u = transpose_row_to_cols(ctx, vc_u, lu_c, g_rows)
        vr_a = jnp.where(row_valid[:, None, None], vr_a, 0)
        vr_u = jnp.where(row_valid[:, None, None], vr_u, 0)
        return ukk, ukk_inv, vc_u, akk, w, pan, vc_a, vr_a, vr_u

    def step_pre_U(lt, k, ch, rr, rc):
        ukk, ukk_inv, vc_u, akk, w, pan, vc_a, vr_a, vr_u = ch
        owner_r, owner_c, kr, kc, lu_r, lu_c = _indices(k)
        is_owner_r = cc.this_rank(ROW_AXIS) == owner_r
        is_owner_c = cc.this_rank(COL_AXIS) == owner_c
        ncols = ltc - lu_c
        g_cols = (lu_c + jnp.arange(max(ncols, 1))) * Qc + rc
        col_valid = (g_cols > k) & (g_cols < nt)

        # -- deferred right-solve updates of previous panel rows ----------
        # (the ajk broadcast reads lt cols behind the pivot — the one
        # collective comm_la does NOT hoist, docs/comm_overlap.md)
        lr_ub = ceil_div(k, Pr)   # max local rows with global row < k
        if lr_ub > 0:
            g_prows = jnp.arange(lr_ub) * Pr + rr
            prow_valid = g_prows < k
            colk = lt[:lr_ub, kc]
            colk_new = tb.trsm_panel("R", "U", "N", "N", ukk, colk,
                                     inv_a=ukk_inv)
            keepp = (is_owner_c & prow_valid)[:, None, None]
            lt = lt.at[:lr_ub, kc].set(jnp.where(keepp, colk_new, colk))
            ajk = cc.bcast(jnp.where(keepp, colk_new, 0), COL_AXIS, owner_c)
            if ncols > 0:
                # A_ji -= A_jk U_ki: pair product with x = A_jk tiles,
                # y[c] = conj(U_ki)^T so conj(y)^T = U_ki
                upd = _pair_product(ajk, jnp.conj(jnp.swapaxes(
                    vc_u, -1, -2)), cplx, use_mxu)
                mask4 = (prow_valid[:, None] & col_valid[None, :]
                         )[:, :, None, None]
                lt = lt.at[:lr_ub, lu_c:].add(-jnp.where(mask4, upd, 0))

        lt = lt.at[kr, kc].set(jnp.where(is_owner_r & is_owner_c,
                                         tb.tri_mask(w, "U")
                                         + tb.tri_mask(akk, "L", k=-1),
                                         lt[kr, kc]))
        if pan is None:
            return lt, None

        keep = (is_owner_r & col_valid)[:, None, None]
        lt = lt.at[kr, lu_c:].set(jnp.where(keep, pan, lt[kr, lu_c:]))
        if vr_u is None:
            pan2 = pan - 0.5 * jnp.einsum("ab,rbd->rad", w, vc_u)
            lt = lt.at[kr, lu_c:].set(jnp.where(keep, pan2, lt[kr, lu_c:]))
            return lt, None
        if not (lookahead and k + 1 < nt):
            return lt, None

        # mirrored split: next block row of the her2k first (carried)
        triu_m = jnp.triu(jnp.ones((mb, mb), dtype=bool))
        kr1 = ud.local_tile_from_global_tile(k + 1, Pr)
        owner_r1 = ud.rank_global_tile(k + 1, Pr, sr)
        own_r1 = cc.this_rank(ROW_AXIS) == owner_r1
        xa = jnp.conj(jnp.swapaxes(vr_a[kr1 - lu_r], -1, -2))
        xu = jnp.conj(jnp.swapaxes(vr_u[kr1 - lu_r], -1, -2))
        updr = _row_strip_product(
            xa, jnp.conj(jnp.swapaxes(vc_u, -1, -2)), cplx, use_mxu) \
            + _row_strip_product(
                xu, jnp.conj(jnp.swapaxes(vc_a, -1, -2)), cplx, use_mxu)
        above1 = col_valid & (g_cols > k + 1)
        ondiag1 = col_valid & (g_cols == k + 1)
        m3 = (above1[:, None, None] | (ondiag1[:, None, None] & triu_m)) \
            & own_r1
        new_row = lt[kr1, lu_c:] - jnp.where(m3, updr,
                                             jnp.zeros_like(updr))
        lt = lt.at[kr1, lu_c:].set(new_row)
        return lt, (new_row, lu_c)

    def step_bulk_U(lt, k, ch, stripped, rr, rc):
        ukk, ukk_inv, vc_u, akk, w, pan, vc_a, vr_a, vr_u = ch
        if pan is None or vr_u is None:
            return lt
        owner_r, owner_c, kr, kc, lu_r, lu_c = _indices(k)
        is_owner_r = cc.this_rank(ROW_AXIS) == owner_r
        nrows, ncols = ltr - lu_r, ltc - lu_c
        g_rows = (lu_r + jnp.arange(nrows)) * Pr + rr
        g_cols = (lu_c + jnp.arange(ncols)) * Qc + rc
        row_valid = (g_rows > k) & (g_rows < nt)
        col_valid = (g_cols > k) & (g_cols < nt)
        keep = (is_owner_r & col_valid)[:, None, None]

        # -- her2k trailing (upper): A_ij -= P_i^H U_kj + U_ki^H P_j ------
        # tile (i, j), i < j: A_ij -= conj(P_ki)^T U_kj + conj(U_ki)^T P_kj
        pair = row_valid[:, None] & col_valid[None, :]
        above = pair & (g_rows[:, None] < g_cols[None, :])
        ondiag = pair & (g_rows[:, None] == g_cols[None, :])
        triu_m = jnp.triu(jnp.ones((mb, mb), dtype=bool))
        if stripped:
            notnext = g_rows != k + 1
            above = above & notnext[:, None]
            ondiag = ondiag & notnext[:, None]
        upd = _pair_product(jnp.conj(jnp.swapaxes(vr_a, -1, -2)),
                            jnp.conj(jnp.swapaxes(vc_u, -1, -2)),
                            cplx, use_mxu) \
            + _pair_product(jnp.conj(jnp.swapaxes(vr_u, -1, -2)),
                            jnp.conj(jnp.swapaxes(vc_a, -1, -2)),
                            cplx, use_mxu)
        mask4 = above[:, :, None, None] | (ondiag[:, :, None, None] & triu_m)
        lt = lt.at[lu_r:, lu_c:].add(-jnp.where(mask4, upd, 0))

        pan2 = pan - 0.5 * jnp.einsum("ab,rbd->rad", w, vc_u)
        lt = lt.at[kr, lu_c:].set(jnp.where(keep, pan2, lt[kr, lu_c:]))
        return lt

    chain, step_pre, step_bulk = (
        (chain_L, step_pre_L, step_bulk_L) if uplo == "L"
        else (chain_U, step_pre_U, step_bulk_U))

    def chain_comm_counts(k):
        """Collectives ``chain(k)`` emits per mesh axis (trace-time
        statics mirroring the chain's early-exit structure): two fused
        bcast2d (L diag + A diag) on each axis, the factor-panel
        broadcast whenever trailing slots exist, and — on a full chain —
        the A-panel broadcast plus the two transposed-panel
        all_gathers."""
        _, _, _, _, lu_r, lu_c = _indices(k)
        nrows, ncols = ltr - lu_r, ltc - lu_c
        if uplo == "L":
            full = k < nt - 1 and nrows > 0 and ncols > 0
            row = 2 + (2 if full else 0)
            col = 2 + (1 if nrows > 0 else 0) + (1 if full else 0)
        else:
            full = k < nt - 1 and ncols > 0 and nrows > 0
            row = 2 + (1 if ncols > 0 else 0) + (1 if full else 0)
            col = 2 + (2 if full else 0)
        return row, col

    def transform(lt, ll):
        rr = (cc.this_rank(ROW_AXIS) - sr) % Pr
        rc = (cc.this_rank(COL_AXIS) - sc) % Qc
        la = None
        ch_next = None
        # uniform per-step phase scopes (`hegst.step<k>.<phase>`, shared
        # convention with cholesky — docs/observability.md critical-path
        # attribution); the comm_la-hoisted chain is scoped as step k+1's
        # PANEL even though it executes inside step k's window
        for k in range(nt):
            if comm_la:
                # step k+1's panel chain (collectives included) emitted
                # between step k's strip and step k's bulk her2k
                if ch_next is not None:
                    ch = ch_next
                else:
                    with obs.named_span(f"hegst.step{k:03d}.panel"):
                        ch = chain(lt, ll, k, la, rr, rc)
                with obs.named_span(f"hegst.step{k:03d}.strip"):
                    lt, la = step_pre(lt, k, ch, rr, rc)
                ch_next = None
                if k + 1 < nt and la is not None:
                    with obs.named_span(f"hegst.step{k + 1:03d}.panel"):
                        ch_next = chain(None, ll, k + 1, la, rr, rc)
                    n_row, n_col = chain_comm_counts(k + 1)
                    cc.record_overlapped("hegst_dist", ROW_AXIS, n_row)
                    cc.record_overlapped("hegst_dist", COL_AXIS, n_col)
                with obs.named_span(f"hegst.step{k:03d}.bulk"):
                    lt = step_bulk(lt, k, ch, la is not None, rr, rc)
            else:
                with obs.named_span(f"hegst.step{k:03d}.panel"):
                    ch = chain(lt, ll, k, la, rr, rc)
                with obs.named_span(f"hegst.step{k:03d}.strip"):
                    lt, la = step_pre(lt, k, ch, rr, rc)
                with obs.named_span(f"hegst.step{k:03d}.bulk"):
                    lt = step_bulk(lt, k, ch, la is not None, rr, rc)
        return lt

    return shard_map(transform, mesh=mesh,
                     in_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS)),
                     out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False)


@register_program_cache
@functools.lru_cache(maxsize=64)
def _dist_hegst_cached(dist, mesh, dtype, uplo, use_mxu, donate=False,
                       lookahead=False, comm_la=False, panel_fused=False,
                       panel_interpret=False):
    return jax.jit(_build_dist_hegst(dist, mesh, uplo, use_mxu=use_mxu,
                                     cplx=dtype.startswith("complex"),
                                     lookahead=lookahead, comm_la=comm_la,
                                     panel_fused=panel_fused,
                                     panel_interpret=panel_interpret),
                   **donate_argnums_kw(donate, 0))


def gen_to_std(uplo: str, a: Matrix, b_factor: Matrix, *,
               donate: bool = False, with_info: bool = False):
    """Transform ``a`` (Hermitian, stored in ``uplo``) using ``b_factor`` =
    the Cholesky factor of B (same ``uplo``). Returns the transformed A with
    its opposite triangle passing through unchanged.

    ``donate=True`` permits consuming ``a``'s device storage (the
    reference transforms mat_a in place, ``eigensolver/gen_to_std``);
    ``a`` must not be used afterwards. ``b_factor`` is never consumed
    (callers reuse the factor across runs).

    ``with_info=True`` returns ``(out, info)`` — the singular-diagonal
    detection analogous to the triangular solve's: info is an int32 device
    scalar, 0 when ``b_factor``'s diagonal is finite and nonzero, else the
    1-based first singular global column (HEGST solves against that
    diagonal, so a zero/NaN entry poisons the transform silently).
    In-graph, no host sync (health.matrix_diag_info)."""
    dlaf_assert(uplo in ("L", "U"), f"gen_to_std: bad uplo {uplo!r}")
    info = None
    if with_info:
        from ..health import matrix_diag_info

        info = matrix_diag_info(b_factor, singular=True)
    dlaf_assert(a.size == b_factor.size, "gen_to_std: A/B size mismatch")
    dlaf_assert(a.block_size == b_factor.block_size, "gen_to_std: block mismatch")
    from ..config import resolve_step_mode

    from ..config import resolve_platform_auto
    from ..types import total_ops

    cfg = get_configuration()
    hegst_impl = resolve_platform_auto(
        cfg.hegst_impl, knob="hegst_impl", tpu_choice="twosolve",
        other_choice="blocked",
        detail="twosolve measured 385.3 GF/s at 5.2e-11 residual vs "
               "blocked 298.4 at 2.2e-9 on d/8192/256 — dense MXU sweeps "
               "beat latency-bound panel round-trips; session 4d, "
               "2026-08-02 v5e")
    distributed = a.grid is not None and a.grid.num_devices > 1
    # reference HEGST flop model (miniapp_gen_to_std): n^3/2 muls+adds —
    # the model, not the route's actual flops (twosolve spends ~2x)
    n = a.size.row
    # the scan step mode's O(1)-compile guarantee flows through the
    # triangular solver's scan form; BOTH blocked builders (local and
    # distributed) unroll all nt per-k steps inside one jit, so both
    # reroute — at ~19 s/step on the TPU AOT toolchain an unrolled
    # local blocked run would pay the exact O(nt) cold compile the
    # auto step mode exists to avoid (round-3 advisory)
    use_twosolve = hegst_impl == "twosolve" or \
        resolve_step_mode(a.dist.nr_tiles.row) == "scan"
    # fused panel route for the BLOCKED forms' diag hegst + panel trsm
    # chain (docs/pallas_panel.md); twosolve has no per-step panel chain
    # of its own — its pivot solves route inside triangular_solve
    panel_fused = not use_twosolve and ppan.panel_uses_fused(
        np.dtype(a.dtype), a.block_size.row)
    entry_span = obs.entry_span("gen_to_std", lambda: dict(
        flops=total_ops(np.dtype(a.dtype), n**3 / 2, n**3 / 2),
        n=n, nb=a.block_size.row, uplo=uplo,
        dtype=np.dtype(a.dtype).name,
        impl="twosolve" if use_twosolve else hegst_impl,
        panel_impl="fused" if panel_fused else "xla",
        grid=f"{a.dist.grid_size.row}x{a.dist.grid_size.col}"))
    if use_twosolve:
        with entry_span:
            res = _gen_to_std_twosolve(uplo, a, b_factor, donate=donate)
            return (res, info) if with_info else res
    # blocked forms take the same look-ahead split as the pipelined
    # Cholesky (docs/lookahead.md); twosolve inherits it through the
    # triangular solver's own scan-mode gate above. comm_lookahead
    # (docs/comm_overlap.md) hoists the distributed builder's panel
    # collectives ahead of the bulk her2k — it rides the carry, so it
    # requires lookahead too.
    from ..config import (resolved_cholesky_lookahead,
                          resolved_comm_lookahead)

    lookahead = resolved_cholesky_lookahead()
    comm_la = lookahead and resolved_comm_lookahead()
    if not distributed:
        with entry_span, quiet_donation():
            g = tiles_to_global(a.storage, a.dist)
            lg = tiles_to_global(b_factor.storage, b_factor.dist)
            # program telemetry (DLAF_PROGRAM_TELEMETRY): off = passthrough
            out = obs.telemetry.call(
                "gen_to_std.local", _hegst_local_blocked, g, lg, uplo=uplo,
                nb=a.block_size.row, lookahead=lookahead,
                panel_fused=panel_fused,
                panel_interpret=panel_fused
                and jax.default_backend() != "tpu")
            out_m = a.with_storage(global_to_tiles_donated(out, a.dist))
        res = mops.merge_triangle(out_m, a, uplo, donate_orig=donate)
        return (res, info) if with_info else res
    # the blocked builder shares one set of slot indices between A and L
    # (diag/panel reads of ll at A's kr/kc) — both axes must align
    assert_slot_aligned(a.dist, b_factor.dist, rows=True, cols=True,
                        what="gen_to_std(A, B_factor)")
    dt = np.dtype(a.dtype)
    use_mxu = tb.f64_gemm_uses_mxu(dt, a.block_size.row)
    platform = next(iter(a.grid.mesh.devices.flat)).platform
    fn = _dist_hegst_cached(a.dist, a.grid.mesh, dt.name, uplo, use_mxu,
                            donate=donate, lookahead=lookahead,
                            comm_la=comm_la, panel_fused=panel_fused,
                            panel_interpret=panel_fused
                            and platform != "tpu")
    with entry_span, quiet_donation():
        res = a.with_storage(obs.telemetry.call(
            "gen_to_std.dist", fn, a.storage, b_factor.storage))
        return (res, info) if with_info else res
