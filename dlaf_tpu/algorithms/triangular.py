"""Triangular solve and triangular multiply — local and distributed.

TPU-native counterpart of the reference's ``solver/triangular``
(``solver/triangular/api.h:20-51``, ``impl.h``: all 8 Left/Right x Lower/Upper
x NoTrans/Trans combos, local + distributed) and ``multiplication/triangular``
(``multiplication/triangular/api.h:20-43``).

Local variants ARE one XLA op: ``TriangularSolve`` / masked matmul — XLA's
implementation is already the blocked substitution the reference hand-codes,
so the TPU-idiomatic "algorithm" is the direct lowering.

Distributed variants run the blocked substitution/accumulation over tile
rows/columns inside shard_map, using the panel-exchange helpers
(:mod:`dlaf_tpu.matrix.panel`): the diagonal tile travels with two mask+psum
hops, row/column panels with one, transposed selections with an all_gather —
and the per-``k`` trailing update is one batched einsum (dense rectangle, so
unlike Cholesky there is no triangle waste).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import obs
from ..config import register_program_cache
from ..common.asserts import dlaf_assert
from ..comm import collectives as cc
from ..comm.grid import COL_AXIS, ROW_AXIS
from ..matrix.distribution import assert_slot_aligned
from ..matrix.matrix import Matrix
from ..matrix.panel import (DistContext, bcast_diag, bcast_diag_dyn, col_panel,
                            col_panel_dyn, pad_diag_identity,
                            pad_diag_identity_dyn, row_panel, row_panel_dyn,
                            transpose_col_to_rows, transpose_row_to_cols,
                            uniform_slot_start)
from ..matrix.tiling import (tiles_to_global, global_to_tiles_donated,
                             to_global, quiet_donation, donate_argnums_kw)
from ..tile_ops import blas as tb
from ..tile_ops import pallas_panel as ppan
from ..types import telescope_windows, total_ops


def _tile_op(t, op: str):
    if op == "N":
        return t
    x = jnp.swapaxes(t, -1, -2)
    return jnp.conj(x) if op == "C" else x


# ---------------------------------------------------------------------------
# Local: direct XLA lowering
# ---------------------------------------------------------------------------

def _rhs_chunk_width(side: str, b_shape, dtype) -> int:
    """Trace-time: free-axis chunk width for a local whole-matrix solve,
    0 = unchunked (config ``trsm_rhs_chunk``; see the knob docstring).
    rhs free-axis slices are mathematically independent in a triangular
    solve, so mapping over chunks is bitwise-identical — it only bounds
    the live mxu-route workspaces (slices/partials/products) to one
    chunk's width."""
    m, n = b_shape
    free, solve_dim = (n, m) if side == "L" else (m, n)
    # auto chunks only where the measured OOM lives — TPU, mxu-routed
    # emulated dtypes, both dimensions large (session 4g: HEGST d/16384
    # twosolve RESOURCE_EXHAUSTED with donation already applied)
    return tb.resolve_chunk_width("trsm_rhs_chunk", dtype, solve_dim,
                                  free, solve_dim, free)


# the rhs operand (argnum 1) is always the entry point's freshly built
# global-layout array — donating it bounds peak HBM by one full matrix
@register_program_cache
@functools.partial(jax.jit, static_argnames=("side", "uplo", "op", "diag"),
                   donate_argnums=1)
def _solve_local(a, b, alpha, *, side, uplo, op, diag):
    cw = _rhs_chunk_width(side, b.shape, b.dtype)
    if not cw:
        return tb.trsm(side, uplo, op, diag, a, b, alpha=alpha)
    from jax import lax

    m, n = b.shape
    free = n if side == "L" else m
    nc = -(-free // cw)
    pad = nc * cw - free          # zero columns/rows solve to zero
    if side == "L":
        bp = jnp.pad(b, ((0, 0), (0, pad)))
        # slice each column chunk on the fly (a transposed (nc, m, cw)
        # operand stack would be a second full-matrix HBM temp — on the
        # exact path built to avoid one)
        out = lax.map(
            lambda i: tb.trsm(side, uplo, op, diag, a,
                              lax.dynamic_slice(bp, (jnp.zeros((), i.dtype), i),
                                                (m, cw)),
                              alpha=alpha),
            jnp.arange(nc, dtype=jnp.int32) * cw)
        return jnp.moveaxis(out, 0, 1).reshape(m, nc * cw)[:, :free]
    bp = jnp.pad(b, ((0, pad), (0, 0)))
    out = lax.map(
        lambda bc: tb.trsm(side, uplo, op, diag, a, bc, alpha=alpha),
        bp.reshape(nc, cw, n))
    return out.reshape(nc * cw, n)[:free]


@register_program_cache
@functools.partial(jax.jit, static_argnames=("side", "uplo", "op", "diag"),
                   donate_argnums=1)
def _mult_local(a, b, alpha, *, side, uplo, op, diag):
    return tb.trmm(side, uplo, op, diag, a, b, alpha=alpha)


# ---------------------------------------------------------------------------
# Distributed substitution (solve) — reference solver/triangular/impl.h
# ---------------------------------------------------------------------------

def _build_dist_solve(dist_a, dist_b, mesh, side, uplo, op, diag, dtype,
                      panel_fused=False, panel_interpret=False):
    nt = dist_a.nr_tiles.row
    n = dist_a.size.row
    mb = dist_a.block_size.row

    def prog(lta, ltb):
        ctx_a = DistContext(dist_a)
        ctx_b = DistContext(dist_b)
        eff_lower = (uplo == "L") == (op == "N")
        if side == "L":
            forward = eff_lower
        else:
            forward = not eff_lower
        order = range(nt) if forward else range(nt - 1, -1, -1)
        # uniform per-step phase scopes (`trsm.step<k>.<phase>`, shared
        # convention with cholesky — docs/observability.md critical-path
        # attribution). Backward sweeps keep the GLOBAL step index k in
        # the name; the critpath joiner orders steps by time, not index.
        for k in order:
            with obs.named_span(f"trsm.step{k:03d}.panel"):
                akk = bcast_diag(ctx_a, lta, k)
                if k == nt - 1:  # short edge tile: keep the solve nonsingular
                    akk = pad_diag_identity(akk, min(mb, n - k * mb))
            if side == "L":
                with obs.named_span(f"trsm.step{k:03d}.panel"):
                    # solve op(Akk) Xk = Bk for tile row k of B (all
                    # local cols) — pivot-diag solve on the panel_impl
                    # route (fused Pallas strip kernel or the XLA chain;
                    # docs/pallas_panel.md)
                    bk = row_panel(ctx_b, ltb, k, 0)
                    xk = ppan.panel_solve("L", uplo, op, diag, akk, bk,
                                          fused=panel_fused,
                                          interpret=panel_interpret)
                    own = ctx_b.rank_r == ctx_b.owner_r(k)
                    row = ctx_b.kr(k)
                    ltb = ltb.at[row].set(jnp.where(own, xk, ltb[row]))
                # remaining rows i: B[i,:] -= E[i,k] @ Xk
                if forward:
                    lu = ctx_b.row_start(k + 1)
                    sl = slice(lu, ctx_b.ltr)
                else:
                    lu = 0
                    sl = slice(0, min(ctx_b.ltr, (k - 1) // ctx_b.P + 1) if k else 0)
                count = sl.stop - sl.start if sl.stop is not None else 0
                if count <= 0:
                    continue
                with obs.named_span(f"trsm.step{k:03d}.bulk"):
                    g = ctx_b.g_rows(lu, count)
                    rem = (g > k) if forward else (g < k)
                    rem = rem & (g < nt)
                    if op == "N":
                        e = col_panel(ctx_a, lta, k, lu)[:count]  # A[i,k] my rows
                    else:
                        rk = row_panel(ctx_a, lta, k, 0)      # A[k,j] my cols
                        e = _tile_op(transpose_row_to_cols(ctx_a, rk, 0, g), op)
                    e = jnp.where(rem[:, None, None], e, jnp.zeros_like(e))
                    upd = tb.contract("rab,cbd->rcad", e, xk)
                    ltb = ltb.at[sl].add(-upd)
            else:
                with obs.named_span(f"trsm.step{k:03d}.panel"):
                    # solve Xk op(Akk) = Bk for tile col k of B (all
                    # local rows)
                    bk = col_panel(ctx_b, ltb, k, 0)
                    xk = ppan.panel_solve("R", uplo, op, diag, akk, bk,
                                          fused=panel_fused,
                                          interpret=panel_interpret)
                    own = ctx_b.rank_c == ctx_b.owner_c(k)
                    col = ctx_b.kc(k)
                    ltb = ltb.at[:, col].set(jnp.where(own, xk, ltb[:, col]))
                if forward:
                    lu = ctx_b.col_start(k + 1)
                    sl = slice(lu, ctx_b.ltc)
                else:
                    lu = 0
                    sl = slice(0, min(ctx_b.ltc, (k - 1) // ctx_b.Q + 1) if k else 0)
                count = sl.stop - sl.start
                if count <= 0:
                    continue
                with obs.named_span(f"trsm.step{k:03d}.bulk"):
                    g = ctx_b.g_cols(lu, count)
                    rem = (g > k) if forward else (g < k)
                    rem = rem & (g < nt)
                    if op == "N":
                        e = row_panel(ctx_a, lta, k, 0)[lu: lu + count]  # A[k,j]
                    else:
                        ck = col_panel(ctx_a, lta, k, 0)      # A[i,k] my rows
                        e = _tile_op(transpose_col_to_rows(ctx_a, ck, 0, g), op)
                    e = jnp.where(rem[:, None, None], e, jnp.zeros_like(e))
                    upd = tb.contract("rab,cbd->rcad", xk, e)
                    ltb = ltb.at[:, sl].add(-upd)
        return ltb

    def run(lta, ltb, alpha):
        return prog(lta, alpha * ltb)

    return shard_map(run, mesh=mesh,
                     in_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS), P()),
                     out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False)


def _build_dist_solve_scan(dist_a, dist_b, mesh, side, uplo, op, diag, dtype,
                           lookahead=False, comm_la=False,
                           panel_fused=False, panel_interpret=False):
    """``lax.scan`` form of the distributed solve (config
    ``dist_step_mode="scan"``): one compiled step body per telescoped
    segment, looped over the segment's steps — the same O(1)-compile /
    uniform-masked-shapes trade as the scan Cholesky (see
    ``cholesky._build_dist_cholesky_scan`` and docs/DESIGN.md). Per-``k``
    index math is traced arithmetic; pivot row/column access uses dynamic
    slices. The swept axis of B (rows for side='L', cols for 'R') is
    TELESCOPED: forward substitutions slice the live bottom ``[lu0:]``
    of the slot axis per segment, backward substitutions the live top
    ``[:ub]``, so the uniform masked trailing update tracks the shrinking
    live region instead of paying all slots every step; A's panel reads
    and the transpose-exchange windows shrink with it. B's orthogonal
    axis never shrinks (every step solves the full pivot panel)."""
    nt = dist_a.nr_tiles.row
    n = dist_a.size.row
    mb = dist_a.block_size.row

    def prog(lta, ltb):
        ctx_a = DistContext(dist_a)
        ctx_b = DistContext(dist_b)
        eff_lower = (uplo == "L") == (op == "N")
        forward = eff_lower if side == "L" else not eff_lower
        # swept-axis grid/slot extents (B rows for 'L', B cols for 'R')
        # and A's transpose-exchange axis (the opposite one of A)
        p_swept = ctx_b.P if side == "L" else ctx_b.Q
        lt_swept = ctx_b.ltr if side == "L" else ctx_b.ltc
        q_orth = ctx_a.Q if side == "L" else ctx_a.P
        lt_orth = ctx_a.ltc if side == "L" else ctx_a.ltr

        def make_step(lu0, cnt, lq0, cnt_q):
            """Step body over the swept-axis window ``[lu0, lu0+cnt)`` of
            B's slots (``lq0``/``cnt_q``: matching window of A's
            transpose-exchange axis). Every pivot of the segment lies
            inside the window; validity masks do the rest."""

            def step(sub, i):
                k = i if forward else nt - 1 - i
                with obs.named_span("trsm.panel"):
                    akk = bcast_diag_dyn(ctx_a, lta, k)
                    akk = pad_diag_identity_dyn(akk, jnp.minimum(mb, n - k * mb))
                if side == "L":
                    with obs.named_span("trsm.panel"):
                        bk = row_panel_dyn(ctx_b, sub, k, row_off=lu0)
                        xk = ppan.panel_solve("L", uplo, op, diag, akk, bk,
                                              fused=panel_fused,
                                              interpret=panel_interpret)
                        own = ctx_b.rank_r == ctx_b.owner_r(k)
                        row = ctx_b.kr(k) - lu0
                        cur = jax.lax.dynamic_slice(
                            sub, (row, 0, 0, 0), (1,) + sub.shape[1:])[0]
                        sub = jax.lax.dynamic_update_slice(
                            sub, jnp.where(own, xk, cur)[None], (row, 0, 0, 0))
                    with obs.named_span("trsm.bulk"):
                        g = ctx_b.g_rows(lu0, cnt)
                        rem = ((g > k) if forward else (g < k)) & (g < nt)
                        if op == "N":
                            e = col_panel_dyn(ctx_a, lta, k, lu=lu0, count=cnt)
                        else:
                            rk = row_panel_dyn(ctx_a, lta, k, lu=lq0,
                                               count=cnt_q)
                            e = _tile_op(
                                transpose_row_to_cols(ctx_a, rk, lq0, g), op)
                        e = jnp.where(rem[:, None, None], e, jnp.zeros_like(e))
                        upd = tb.contract("rab,cbd->rcad", e, xk)
                        return sub - upd, None
                with obs.named_span("trsm.panel"):
                    bk = col_panel_dyn(ctx_b, sub, k, col_off=lu0)
                    xk = ppan.panel_solve("R", uplo, op, diag, akk, bk,
                                          fused=panel_fused,
                                          interpret=panel_interpret)
                    own = ctx_b.rank_c == ctx_b.owner_c(k)
                    col = ctx_b.kc(k) - lu0
                    cur = jax.lax.dynamic_slice(
                        sub, (0, col, 0, 0),
                        (sub.shape[0], 1) + sub.shape[2:])[:, 0]
                    sub = jax.lax.dynamic_update_slice(
                        sub, jnp.where(own, xk, cur)[:, None], (0, col, 0, 0))
                with obs.named_span("trsm.bulk"):
                    g = ctx_b.g_cols(lu0, cnt)
                    rem = ((g > k) if forward else (g < k)) & (g < nt)
                    if op == "N":
                        e = row_panel_dyn(ctx_a, lta, k, lu=lu0, count=cnt)
                    else:
                        ck = col_panel_dyn(ctx_a, lta, k, lu=lq0, count=cnt_q)
                        e = _tile_op(
                            transpose_col_to_rows(ctx_a, ck, lq0, g), op)
                    e = jnp.where(rem[:, None, None], e, jnp.zeros_like(e))
                    upd = tb.contract("rab,cbd->rcad", xk, e)
                    return sub - upd, None

            return step

        def make_step_la(lu0, cnt, lq0, cnt_q):
            """Software-pipelined step body (``cholesky_lookahead=1`` —
            the same next-pivot-first split as the pipelined Cholesky):
            carry ``(sub, pe, pxk)`` = the previous step's masked panel
            operands, and apply their BULK update inside this body, where
            it is independent of this body's latency-bound trsm — while
            the NEXT pivot row/column's strip is updated eagerly so the
            following body's solve reads current data. Per-slot
            application order matches the serial body (bulk k-1 before
            strip k), so results are bitwise identical on the native
            route.

            ``comm_la`` (``comm_lookahead=1``, docs/comm_overlap.md)
            additionally hoists this step's A-panel read — the
            ``col_panel``/``row_panel`` broadcast, and for op != 'N' the
            transpose-exchange all_gather — AHEAD of the deferred bulk
            product: the panel reads only the constant ``lta``, so the
            collective can run on the ICI while the bulk contraction is
            in flight. The pivot solve's own panel broadcast and the
            fused diag ``bcast2d`` already precede the bulk either way.
            Pure emission reorder of identical values — bitwise-equal
            results with the knob on or off."""

            def step(carry, i):
                sub, pe, pxk = carry
                k = i if forward else nt - 1 - i
                knext = k + 1 if forward else k - 1
                with obs.named_span("trsm.panel"):
                    akk = bcast_diag_dyn(ctx_a, lta, k)
                    akk = pad_diag_identity_dyn(akk, jnp.minimum(mb, n - k * mb))
                if side == "L":
                    with obs.named_span("trsm.panel"):
                        bk = row_panel_dyn(ctx_b, sub, k, row_off=lu0)
                        xk = ppan.panel_solve("L", uplo, op, diag, akk, bk,
                                              fused=panel_fused,
                                              interpret=panel_interpret)
                        own = ctx_b.rank_r == ctx_b.owner_r(k)
                        row = ctx_b.kr(k) - lu0
                        cur = jax.lax.dynamic_slice(
                            sub, (row, 0, 0, 0), (1,) + sub.shape[1:])[0]
                        sub = jax.lax.dynamic_update_slice(
                            sub, jnp.where(own, xk, cur)[None], (row, 0, 0, 0))
                    with obs.named_span("trsm.bulk"):
                        g = ctx_b.g_rows(lu0, cnt)
                        rem = ((g > k) if forward else (g < k)) & (g < nt)

                        def epanel():
                            if op == "N":
                                e = col_panel_dyn(ctx_a, lta, k, lu=lu0,
                                                  count=cnt)
                            else:
                                rk = row_panel_dyn(ctx_a, lta, k, lu=lq0,
                                                   count=cnt_q)
                                e = _tile_op(
                                    transpose_row_to_cols(ctx_a, rk, lq0, g), op)
                            return jnp.where(rem[:, None, None], e,
                                             jnp.zeros_like(e))

                        if comm_la:
                            # A-panel collectives emitted BEFORE the deferred
                            # bulk of step k-1 (pe is pre-masked)
                            e = epanel()
                            sub = sub - tb.contract("rab,cbd->rcad", pe, pxk)
                        else:
                            sub = sub - tb.contract("rab,cbd->rcad", pe, pxk)
                            e = epanel()
                    with obs.named_span("trsm.strip"):
                        # eager next-pivot-row strip (slot holds global row
                        # knext only on its owner; gval-gating keeps every
                        # other rank's slot in the pending set instead)
                        rnext = ctx_b.kr(knext) - lu0
                        gval = jax.lax.dynamic_slice(g, (rnext,), (1,))[0]
                        hit = (gval == knext) & (knext >= 0) & (knext < nt)
                        er = jax.lax.dynamic_slice(e, (rnext, 0, 0),
                                                   (1, mb, mb))[0]
                        updn = tb.contract("ab,cbd->cad", er, xk)
                        rcur = jax.lax.dynamic_slice(
                            sub, (rnext, 0, 0, 0), (1,) + sub.shape[1:])[0]
                        sub = jax.lax.dynamic_update_slice(
                            sub, (rcur - jnp.where(hit, updn, 0))[None],
                            (rnext, 0, 0, 0))
                    with obs.named_span("trsm.bulk"):
                        pe_next = jnp.where((rem & (g != knext))[:, None, None],
                                            e, jnp.zeros_like(e))
                    return (sub, pe_next, xk), None
                with obs.named_span("trsm.panel"):
                    bk = col_panel_dyn(ctx_b, sub, k, col_off=lu0)
                    xk = ppan.panel_solve("R", uplo, op, diag, akk, bk,
                                          fused=panel_fused,
                                          interpret=panel_interpret)
                    own = ctx_b.rank_c == ctx_b.owner_c(k)
                    col = ctx_b.kc(k) - lu0
                    cur = jax.lax.dynamic_slice(
                        sub, (0, col, 0, 0),
                        (sub.shape[0], 1) + sub.shape[2:])[:, 0]
                    sub = jax.lax.dynamic_update_slice(
                        sub, jnp.where(own, xk, cur)[:, None], (0, col, 0, 0))
                with obs.named_span("trsm.bulk"):
                    g = ctx_b.g_cols(lu0, cnt)
                    rem = ((g > k) if forward else (g < k)) & (g < nt)

                    def epanel():
                        if op == "N":
                            e = row_panel_dyn(ctx_a, lta, k, lu=lu0, count=cnt)
                        else:
                            ck = col_panel_dyn(ctx_a, lta, k, lu=lq0,
                                               count=cnt_q)
                            e = _tile_op(
                                transpose_col_to_rows(ctx_a, ck, lq0, g), op)
                        return jnp.where(rem[:, None, None], e,
                                         jnp.zeros_like(e))

                    if comm_la:
                        e = epanel()
                        sub = sub - tb.contract("rab,cbd->rcad", pxk, pe)
                    else:
                        sub = sub - tb.contract("rab,cbd->rcad", pxk, pe)
                        e = epanel()
                with obs.named_span("trsm.strip"):
                    cnext = ctx_b.kc(knext) - lu0
                    gval = jax.lax.dynamic_slice(g, (cnext,), (1,))[0]
                    hit = (gval == knext) & (knext >= 0) & (knext < nt)
                    ec = jax.lax.dynamic_slice(e, (cnext, 0, 0),
                                               (1, mb, mb))[0]
                    updn = tb.contract("rab,bd->rad", xk, ec)
                    ccur = jax.lax.dynamic_slice(
                        sub, (0, cnext, 0, 0),
                        (sub.shape[0], 1) + sub.shape[2:])[:, 0]
                    sub = jax.lax.dynamic_update_slice(
                        sub, (ccur - jnp.where(hit, updn, 0))[:, None],
                        (0, cnext, 0, 0))
                with obs.named_span("trsm.bulk"):
                    pe_next = jnp.where((rem & (g != knext))[:, None, None],
                                        e, jnp.zeros_like(e))
                return (sub, pe_next, xk), None

            return step

        # telescoped segments over the swept axis (see
        # cholesky._build_dist_cholesky_scan); the transpose-exchange
        # window only splits segments when op != "N" actually uses it
        def window(pos, seg_len):
            # slot bounds via uniform_slot_start — the declared single
            # owner (matrix/panel.py); k//p would be identical today
            if forward:
                lo, loq = (uniform_slot_start(pos, p_swept),
                           uniform_slot_start(pos, q_orth))
                win = (lo, lt_swept - lo)
                winq = (loq, lt_orth - loq)
            else:
                k_hi = nt - 1 - pos
                win = (0, min(lt_swept,
                              uniform_slot_start(k_hi, p_swept) + 1))
                winq = (0, min(lt_orth,
                               uniform_slot_start(k_hi, q_orth) + 1))
            return (win, winq if op != "N" else (0, lt_orth))

        # under lookahead the pending operands carry ACROSS segments (the
        # slots a shrinking window drops are zero by the rem mask — the
        # serial windows already prove they hold no live tiles); the last
        # step's pending is identically zero, so nothing is flushed
        pe = pxk = None
        prev_lu0 = 0
        for ((lu0, cnt), (lq0, cnt_q)), i0, seg_len in \
                telescope_windows(nt, window):
            sub = jax.lax.slice_in_dim(ltb, lu0, lu0 + cnt,
                                       axis=0 if side == "L" else 1)
            if lookahead:
                # collectives emitted ahead of the deferred bulk
                # (docs/comm_overlap.md): the diag bcast2d (one per
                # axis) and the pivot panel broadcast (swept axis)
                # precede it in the pipelined body regardless of the
                # comm knob; comm_la additionally hoists the A-panel
                # read — one broadcast on the opposite axis for
                # op='N', else the source-panel broadcast plus the
                # transpose-exchange all_gather
                n_row = 1 + (side == "L")   # bcast2d + pivot panel bcast
                n_col = 1 + (side == "R")
                if comm_la:
                    if op == "N":
                        n_col += side == "L"   # opposite-axis panel bcast
                        n_row += side == "R"
                    else:                      # source bcast + all_gather
                        n_row += 1
                        n_col += 1
                cc.record_overlapped("triangular_solve_scan",
                                     ROW_AXIS, n_row * seg_len)
                cc.record_overlapped("triangular_solve_scan",
                                     COL_AXIS, n_col * seg_len)
                if pe is None:
                    pe = jnp.zeros((cnt, mb, mb), ltb.dtype)
                    orth = ltb.shape[1] if side == "L" else ltb.shape[0]
                    pxk = jnp.zeros((orth, mb, mb), ltb.dtype)
                else:
                    pe = pe[lu0 - prev_lu0: lu0 - prev_lu0 + cnt]
                prev_lu0 = lu0
                # index-free scope: one traced body for all iterations —
                # critpath reconstructs per-step timing by occurrence
                # order (docs/observability.md one-traced-body note)
                (sub, pe, pxk), _ = jax.lax.scan(
                    obs.scoped_step("trsm.scanstep",
                                    make_step_la(lu0, cnt, lq0, cnt_q),
                                    steps=seg_len),
                    (sub, pe, pxk), jnp.arange(i0, i0 + seg_len))
            else:
                sub, _ = jax.lax.scan(
                    obs.scoped_step("trsm.scanstep",
                                    make_step(lu0, cnt, lq0, cnt_q),
                                    steps=seg_len), sub,
                    jnp.arange(i0, i0 + seg_len))
            if side == "L":
                ltb = ltb.at[lu0:lu0 + cnt].set(sub)
            else:
                ltb = ltb.at[:, lu0:lu0 + cnt].set(sub)
        return ltb

    def run(lta, ltb, alpha):
        return prog(lta, alpha * ltb)

    return shard_map(run, mesh=mesh,
                     in_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS), P()),
                     out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False)


# ---------------------------------------------------------------------------
# Distributed accumulation (multiply) — reference multiplication/triangular
# ---------------------------------------------------------------------------

def _mask_tri_panel(e, g, k, nt, strict, uplo, op, diag):
    """Triangle masking of a pivot panel for the multiply builders: the
    diagonal slot gets the (unit-)triangle-masked tile, strict slots the
    full tile, everything else zero. ``strict``: boolean per-slot mask of
    the strictly-included side (direction already resolved by the
    caller's eff_lower/side logic)."""
    ondiag = (g == k)
    dt = tb.tri_mask(e, uplo if op == "N" else ("U" if uplo == "L" else "L"))
    dt = _unit_diag(dt, diag)
    return jnp.where(ondiag[:, None, None], dt,
                     jnp.where(strict[:, None, None] & (g < nt)[:, None, None],
                               e, jnp.zeros_like(e)))


def _build_dist_mult(dist_a, dist_b, mesh, side, uplo, op, diag, dtype):
    nt = dist_a.nr_tiles.row

    def prog(lta, ltb):
        ctx_a = DistContext(dist_a)
        ctx_b = DistContext(dist_b)
        eff_lower = (uplo == "L") == (op == "N")
        # does step k touch output slots g >= k (True) or g <= k (False)?
        ascending = eff_lower if side == "L" else not eff_lower
        out = jnp.zeros_like(ltb)
        for k in range(nt):
            if side == "L":
                # static accumulation window: step k only reaches output
                # rows on the strict-plus-diagonal side of k
                if ascending:
                    lu = ctx_b.row_start(k)
                    sl = slice(lu, ctx_b.ltr)
                else:
                    lu, sl = 0, slice(0, min(ctx_b.ltr, k // ctx_b.P + 1))
                cnt = sl.stop - sl.start
                if cnt <= 0:
                    continue
                with obs.named_span(f"trmm.step{k:03d}.panel"):
                    bk = row_panel(ctx_b, ltb, k, 0)      # B[k,:] my cols
                    g = ctx_b.g_rows(lu, cnt)
                    if op == "N":
                        e = col_panel(ctx_a, lta, k, lu)[:cnt]  # A[i,k]
                    else:
                        # transpose-exchange windowed to the reachable tiles
                        # (g >= k ascending / g <= k descending)
                        if ascending:
                            lq = uniform_slot_start(k, ctx_a.Q)
                            rk = row_panel(ctx_a, lta, k, lq)
                        else:
                            lq = 0
                            rk = row_panel(ctx_a, lta, k, 0)[
                                :min(ctx_a.ltc,
                                     uniform_slot_start(k, ctx_a.Q) + 1)]
                        e = _tile_op(transpose_row_to_cols(ctx_a, rk, lq, g),
                                     op)
                    strict = (g > k) if eff_lower else (g < k)
                    e = _mask_tri_panel(e, g, k, nt, strict, uplo, op, diag)
                with obs.named_span(f"trmm.step{k:03d}.bulk"):
                    upd = tb.contract("rab,cbd->rcad", e, bk)
                    out = out.at[sl].add(upd)
            else:
                if ascending:
                    lu = ctx_b.col_start(k)
                    sl = slice(lu, ctx_b.ltc)
                else:
                    lu, sl = 0, slice(0, min(ctx_b.ltc, k // ctx_b.Q + 1))
                cnt = sl.stop - sl.start
                if cnt <= 0:
                    continue
                with obs.named_span(f"trmm.step{k:03d}.panel"):
                    bk = col_panel(ctx_b, ltb, k, 0)      # B[:,k] my rows
                    g = ctx_b.g_cols(lu, cnt)
                    if op == "N":
                        e = row_panel(ctx_a, lta, k, lu)[:cnt]  # A[k,j]
                    else:
                        if ascending:
                            lq = uniform_slot_start(k, ctx_a.P)
                            ck = col_panel(ctx_a, lta, k, lq)
                        else:
                            lq = 0
                            ck = col_panel(ctx_a, lta, k, 0)[
                                :min(ctx_a.ltr,
                                     uniform_slot_start(k, ctx_a.P) + 1)]
                        e = _tile_op(transpose_col_to_rows(ctx_a, ck, lq, g),
                                     op)
                    strict = (g > k) if not eff_lower else (g < k)
                    e = _mask_tri_panel(e, g, k, nt, strict, uplo, op, diag)
                with obs.named_span(f"trmm.step{k:03d}.bulk"):
                    upd = tb.contract("rab,cbd->rcad", bk, e)
                    out = out.at[:, sl].add(upd)
        return out

    def run(lta, ltb, alpha):
        return alpha * prog(lta, ltb)

    return shard_map(run, mesh=mesh,
                     in_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS), P()),
                     out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False)


def _build_dist_mult_scan(dist_a, dist_b, mesh, side, uplo, op, diag, dtype):
    """``lax.scan`` form of the distributed multiply, TELESCOPED over the
    triangular axis: step ``k`` only touches output slots on one side of
    the diagonal (``g >= k`` or ``g <= k`` depending on side/uplo/op), so
    each telescoped segment accumulates into just the still-reachable
    window of the output — the windows shrink (or start small and grow)
    exactly like the solve's. ``k`` always ascends (accumulation order is
    the unrolled one); the pivot panel of B spans its full orthogonal
    extent every step."""
    nt = dist_a.nr_tiles.row

    def prog(lta, ltb):
        ctx_a = DistContext(dist_a)
        ctx_b = DistContext(dist_b)
        eff_lower = (uplo == "L") == (op == "N")
        # does step k touch output slots g >= k (True) or g <= k (False)?
        ascending = eff_lower if side == "L" else not eff_lower
        p_out = ctx_b.P if side == "L" else ctx_b.Q
        lt_out = ctx_b.ltr if side == "L" else ctx_b.ltc
        q_orth = ctx_a.Q if side == "L" else ctx_a.P
        lt_orth = ctx_a.ltc if side == "L" else ctx_a.ltr

        def make_step(lu0, cnt, lq0, cnt_q):
            def step(sub, k):
                if side == "L":
                    bk = row_panel_dyn(ctx_b, ltb, k)
                    g = ctx_b.g_rows(lu0, cnt)
                    if op == "N":
                        e = col_panel_dyn(ctx_a, lta, k, lu=lu0, count=cnt)
                    else:
                        rk = row_panel_dyn(ctx_a, lta, k, lu=lq0,
                                           count=cnt_q)
                        e = _tile_op(
                            transpose_row_to_cols(ctx_a, rk, lq0, g), op)
                    strict = (g > k) if eff_lower else (g < k)
                    e = _mask_tri_panel(e, g, k, nt, strict, uplo, op, diag)
                    return sub + tb.contract("rab,cbd->rcad", e, bk), None
                bk = col_panel_dyn(ctx_b, ltb, k)
                g = ctx_b.g_cols(lu0, cnt)
                if op == "N":
                    e = row_panel_dyn(ctx_a, lta, k, lu=lu0, count=cnt)
                else:
                    ck = col_panel_dyn(ctx_a, lta, k, lu=lq0, count=cnt_q)
                    e = _tile_op(
                        transpose_col_to_rows(ctx_a, ck, lq0, g), op)
                strict = (g > k) if not eff_lower else (g < k)
                e = _mask_tri_panel(e, g, k, nt, strict, uplo, op, diag)
                return sub + tb.contract("rab,cbd->rcad", bk, e), None

            return step

        def window(pos, seg_len):
            if ascending:
                lo, loq = (uniform_slot_start(pos, p_out),
                           uniform_slot_start(pos, q_orth))
                win = (lo, lt_out - lo)
                winq = (loq, lt_orth - loq)
            else:
                k_hi = pos + seg_len - 1
                win = (0, min(lt_out, uniform_slot_start(k_hi, p_out) + 1))
                winq = (0, min(lt_orth,
                               uniform_slot_start(k_hi, q_orth) + 1))
            return (win, winq if op != "N" else (0, lt_orth))

        out = jnp.zeros_like(ltb)
        for ((lu0, cnt), (lq0, cnt_q)), k0s, seg_len in \
                telescope_windows(nt, window):
            sub = jax.lax.slice_in_dim(out, lu0, lu0 + cnt,
                                       axis=0 if side == "L" else 1)
            sub, _ = jax.lax.scan(
                obs.scoped_step("trmm.scanstep",
                                make_step(lu0, cnt, lq0, cnt_q),
                                steps=seg_len), sub,
                jnp.arange(k0s, k0s + seg_len))
            if side == "L":
                out = out.at[lu0:lu0 + cnt].set(sub)
            else:
                out = out.at[:, lu0:lu0 + cnt].set(sub)
        return out

    def run(lta, ltb, alpha):
        return alpha * prog(lta, ltb)

    return shard_map(run, mesh=mesh,
                     in_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS), P()),
                     out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False)


def _unit_diag(t, diag):
    if diag != "U":
        return t
    n = t.shape[-1]
    d = jnp.diagonal(t, axis1=-2, axis2=-1)
    return t - d[..., None] * jnp.eye(n, dtype=t.dtype) + jnp.eye(n, dtype=t.dtype)


# ---------------------------------------------------------------------------
# Public API (reference solver/triangular.h, multiplication/triangular.h)
# ---------------------------------------------------------------------------

@register_program_cache
@functools.lru_cache(maxsize=128)
def _dist_solve_cached(dist_a, dist_b, mesh, side, uplo, op, diag, dtype,
                       scan=False, donate_b=False, lookahead=False,
                       comm_la=False, panel_fused=False,
                       panel_interpret=False):
    if scan:
        built = _build_dist_solve_scan(dist_a, dist_b, mesh, side, uplo, op,
                                       diag, dtype, lookahead=lookahead,
                                       comm_la=comm_la,
                                       panel_fused=panel_fused,
                                       panel_interpret=panel_interpret)
    else:
        built = _build_dist_solve(dist_a, dist_b, mesh, side, uplo, op,
                                  diag, dtype, panel_fused=panel_fused,
                                  panel_interpret=panel_interpret)
    return jax.jit(built, **donate_argnums_kw(donate_b, 1))


@register_program_cache
@functools.lru_cache(maxsize=128)
def _dist_mult_cached(dist_a, dist_b, mesh, side, uplo, op, diag, dtype,
                      scan=False):
    build = _build_dist_mult_scan if scan else _build_dist_mult
    return jax.jit(build(dist_a, dist_b, mesh, side, uplo, op, diag, dtype))


def _check_args(side, a: Matrix, b: Matrix):
    dlaf_assert(a.size.row == a.size.col, "triangular: A must be square")
    need = b.size.row if side == "L" else b.size.col
    dlaf_assert(a.size.row == need, f"triangular: A size {a.size} vs B {b.size}")
    dlaf_assert(a.block_size.row == a.block_size.col, "A block must be square")
    k = b.block_size.row if side == "L" else b.block_size.col
    dlaf_assert(a.block_size.row == k, "A/B block sizes must agree")


def triangular_solve(side: str, uplo: str, op: str, diag: str, alpha,
                     a: Matrix, b: Matrix, *, donate_b: bool = False,
                     with_info: bool = False):
    """``X: op(A) X = alpha B`` (side='L') or ``X op(A) = alpha B`` ('R');
    all 8 combos, local + distributed (reference ``solver::triangular``).

    ``donate_b=True`` donates ``b``'s device storage (the reference solves
    in place into ``mat_b``, ``solver/triangular/impl.h``); ``b`` must not
    be used afterwards. Internal stage hand-offs are always donated.

    ``with_info=True`` returns ``(X, info)`` — the singular-diagonal
    detection analogous to ``cholesky``'s info: an int32 device scalar, 0
    when every diagonal entry of ``A`` is finite and nonzero, else the
    1-based first singular global column (a zero/non-finite triangular
    diagonal makes the solve blow up silently). Computed in-graph from
    ``A``'s stored diagonal (health.matrix_diag_info) with no host sync;
    ``diag='U'`` (implicit unit diagonal) is never singular, so info is
    the constant 0 there."""
    _check_args(side, a, b)
    info = None
    if with_info:
        from ..health import matrix_diag_info

        info = (jnp.zeros((), jnp.int32) if diag == "U"
                else matrix_diag_info(a, singular=True))
    # reference flop model (miniapp_triangular_solver): m n^2/2 muls+adds
    # on the solve dimension n = A's order, free dimension the other
    sdim = a.size.row
    free = b.size.col if side == "L" else b.size.row
    # fused panel route applies to the DISTRIBUTED pivot-diag chain only
    # (the local solve is one whole-matrix op — no per-step panel chain);
    # resolved once here so the entry span and the builders agree
    dist_run = not (a.grid is None or a.grid.num_devices == 1)
    panel_fused = dist_run and ppan.panel_uses_fused(np.dtype(a.dtype),
                                                     a.block_size.row)
    entry_span = obs.entry_span("triangular_solve", lambda: dict(
        flops=total_ops(np.dtype(b.dtype), free * sdim**2 / 2,
                        free * sdim**2 / 2),
        side=side, uplo=uplo, op=op, diag=diag, m=b.size.row,
        n=b.size.col, nb=b.block_size.row, dtype=np.dtype(b.dtype).name,
        panel_impl="fused" if panel_fused else "xla",
        grid=f"{b.dist.grid_size.row}x{b.dist.grid_size.col}"))
    if not dist_run:
        # host phases as unfenced spans, as the local Cholesky's
        # (stage.cholesky.*): each is the wall of an async dispatch and
        # labels the device's idle gaps on a profiler timeline
        with entry_span, quiet_donation():
            with obs.span("stage.triangular_solve.to_global", fenced=False):
                bm = to_global(b.storage, b.dist, donate_b)
                am = tiles_to_global(a.storage, a.dist)
            with obs.span("stage.triangular_solve.solve", fenced=False):
                out = _solve_local(am, bm, jnp.asarray(alpha, bm.dtype),
                                   side=side, uplo=uplo, op=op, diag=diag)
            with obs.span("stage.triangular_solve.to_tiles", fenced=False):
                res = b.with_storage(global_to_tiles_donated(out, b.dist))
            return (res, info) if with_info else res
    # the distributed builders combine A's per-slot panels with B's slots
    # on the swept axis — misalignment corrupts silently, so contract it
    assert_slot_aligned(a.dist, b.dist, rows=side == "L", cols=side == "R",
                        what="triangular_solve(A, B)")
    from ..config import (resolve_step_mode, resolved_cholesky_lookahead,
                          resolved_comm_lookahead)

    scan_mode = resolve_step_mode(a.dist.nr_tiles.row) == "scan"
    # the pipelined scan body (same knob as the Cholesky look-ahead;
    # docs/lookahead.md); comm_lookahead additionally hoists the A-panel
    # collectives ahead of the deferred bulk (docs/comm_overlap.md)
    la = scan_mode and resolved_cholesky_lookahead()
    # pivot-diag chain on the fused Pallas route when panel_impl says so
    # (docs/pallas_panel.md); panel_fused resolved above, a cache-key arg
    platform = next(iter(a.grid.mesh.devices.flat)).platform
    fn = _dist_solve_cached(a.dist, b.dist, a.grid.mesh, side, uplo, op, diag,
                            np.dtype(a.dtype).name,
                            scan=scan_mode, donate_b=donate_b,
                            lookahead=la,
                            comm_la=la and resolved_comm_lookahead(),
                            panel_fused=panel_fused,
                            panel_interpret=panel_fused
                            and platform != "tpu")
    with entry_span, quiet_donation():
        # the host's wall to enqueue the one program on every device of
        # the grid (unfenced: completion is the caller's fence); program
        # telemetry (DLAF_PROGRAM_TELEMETRY): off = passthrough
        with obs.span("stage.triangular_solve.dispatch", fenced=False):
            res = b.with_storage(obs.telemetry.call(
                "triangular_solve.dist", fn, a.storage, b.storage,
                jnp.asarray(alpha, b.dtype)))
        return (res, info) if with_info else res


def triangular_multiply(side: str, uplo: str, op: str, diag: str, alpha,
                        a: Matrix, b: Matrix) -> Matrix:
    """``B <- alpha op(A) B`` (side='L') or ``alpha B op(A)`` ('R');
    reference ``multiplication::triangular`` (8 local, LLN/LUN/RLN/RUN + the
    transposed forms distributed)."""
    _check_args(side, a, b)
    sdim = a.size.row
    free = b.size.col if side == "L" else b.size.row
    entry_span = obs.entry_span("triangular_multiply", lambda: dict(
        flops=total_ops(np.dtype(b.dtype), free * sdim**2 / 2,
                        free * sdim**2 / 2),
        side=side, uplo=uplo, op=op, diag=diag, m=b.size.row,
        n=b.size.col, nb=b.block_size.row, dtype=np.dtype(b.dtype).name,
        grid=f"{b.dist.grid_size.row}x{b.dist.grid_size.col}"))
    if a.grid is None or a.grid.num_devices == 1:
        with entry_span, quiet_donation():
            am = tiles_to_global(a.storage, a.dist)
            bm = tiles_to_global(b.storage, b.dist)
            out = _mult_local(am, bm, jnp.asarray(alpha, bm.dtype),
                              side=side, uplo=uplo, op=op, diag=diag)
            return b.with_storage(global_to_tiles_donated(out, b.dist))
    assert_slot_aligned(a.dist, b.dist, rows=side == "L", cols=side == "R",
                        what="triangular_multiply(A, B)")
    from ..config import resolve_step_mode

    fn = _dist_mult_cached(a.dist, b.dist, a.grid.mesh, side, uplo, op, diag,
                           np.dtype(a.dtype).name,
                           scan=resolve_step_mode(a.dist.nr_tiles.row)
                           == "scan")
    with entry_span:
        return b.with_storage(obs.telemetry.call(
            "triangular_multiply.dist", fn, a.storage, b.storage,
            jnp.asarray(alpha, b.dtype)))
