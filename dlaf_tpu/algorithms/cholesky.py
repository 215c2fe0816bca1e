"""Cholesky factorization — local and distributed.

TPU-native counterpart of the reference's ``factorization/cholesky``
(``factorization/cholesky/impl.h:134-276``; public API ``cholesky.h:36,62``):
the right-looking tile algorithm — ``potrf`` on the diagonal block, panel
``trsm``, trailing ``herk``/``gemm`` update — re-designed for XLA:

* The per-``k`` loop is unrolled at *trace time* (the tile count is static),
  so every step has static shapes and the whole factorization is ONE compiled
  program. The reference's look-ahead machinery (round-robin panels,
  priorities, ``impl.h:187-189``) is unnecessary: XLA sees the full dependency
  DAG and overlaps panel ``k+1`` with trailing update ``k`` on its own.
* Within a step the trailing update is a single batched einsum over local
  tiles — the MXU-idiomatic form of the reference's per-tile ``herk``/``gemm``
  task fan-out.
* Distributed (``call_L`` analog, ``impl.h:174-276``): SPMD ``shard_map`` over
  the 2D mesh. The diagonal tile is broadcast with two mask+psum hops (the
  reference's diag-tile column broadcast), every rank solves the panel rows it
  owns, the panel is row-broadcast and all-gathered to build the transposed
  panel (the reference's ``broadcast_panel`` + ``panelT``), and rank-local
  masks derived from ``axis_index`` keep the update inside the trailing lower
  triangle.

Only the lower/upper triangle of the input (per ``uplo``) is read; the other
triangle passes through, matching LAPACK/reference semantics.
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
from ..comm import collectives as cc
from ..comm.grid import COL_AXIS, ROW_AXIS
from ..common.asserts import dlaf_assert
from ..health import info as hinfo
from ..matrix import util_distribution as ud
from ..matrix.matrix import Matrix
from ..matrix.panel import (DistContext, pad_diag_identity_dyn,
                            transpose_col_to_rows, transpose_row_to_cols,
                            uniform_slot_start)
from ..matrix.tiling import (storage_tile_grid, on_global, quiet_donation,
                             donate_argnums_kw)
from ..tile_ops import blas as tb
from ..tile_ops import lapack as tl
from ..tile_ops import mixed as mx
from ..tile_ops import ozaki as oz
from ..tile_ops import pallas_panel as ppan
from ..tile_ops.pallas_kernels import masked_trailing_update, supports_pallas_update
from ..types import ceil_div, telescope_segments, telescope_windows, total_ops

# back-compat alias (tests import the old private name)
_telescope_segments = telescope_segments


# ---------------------------------------------------------------------------
# Local (single device) — reference impl.h:134-171
# ---------------------------------------------------------------------------

#: Valid cholesky_trailing strategies (see config.Configuration); bench.py
#: sweeps this set on the measured hardware.
#: Trailing-update formulations. "scan" is the lax.scan step mode; unlike
#: "ozaki" (which forces the MXU route for f64/c128) it selects its panel
#: and trailing routes from the f64_trsm/f64_gemm knobs, identically on
#: 1 device and on a grid.
VALID_TRAILING = ("loop", "biggemm", "invgemm", "xla", "ozaki", "scan")

#: The local scan builder's bulk product on the slice-product route: from
#: ``SCAN_BULK_CHUNK_AT`` rows of a segment's block on, it is formed
#: ``SCAN_BULK_CHUNK`` columns at a time (``_cholesky_local_scan``:
#: ``bulk_chunks``). The numbers are those of ``trsm_rhs_chunk`` and
#: ``red2band_trail_chunk`` auto (config.py): the same workspaces, the
#: same chip. Not options: the shape decides.
SCAN_BULK_CHUNK = 4096
SCAN_BULK_CHUNK_AT = 8192



def _oz_product(x, y):
    """``x @ y`` on the error-free int8/bf16 MXU route (complex picks the
    4-real-product composition) — the lookahead split's column strip, on
    the same route as the bulk it was split from."""
    mm = oz.matmul_c128 if jnp.iscomplexobj(x) else oz.matmul_f64
    return mm(x, y, slices=tb._oz_slices())


def _count_step_modes(algo: str, overlapped: int, serialized: int) -> None:
    """Trace-time tile-step accounting for the lookahead pipeline: how many
    steps of the compiled program were emitted in the overlapped (next-
    panel-column-first) order vs the plain serialized order, and in how
    many traced step bodies (``dlaf_cholesky_bodies_total{algo}``: one a
    call — an unrolled builder calls once per step, a scan builder once
    per telescoped segment — so a run says which builder it took and how
    many bodies the program holds)."""
    if obs.metrics_active():
        obs.counter("dlaf_cholesky_bodies_total", algo=algo).inc()
        if overlapped:
            obs.counter("dlaf_cholesky_steps_total", algo=algo,
                        mode="overlapped").inc(overlapped)
        if serialized:
            obs.counter("dlaf_cholesky_steps_total", algo=algo,
                        mode="serialized").inc(serialized)


@register_program_cache
@functools.partial(jax.jit, static_argnames=("uplo", "nb", "trailing",
                                             "lookahead", "with_info",
                                             "panel_fused", "step_fused",
                                             "panel_interpret"),
                   donate_argnums=0)
def _cholesky_local(a, *, uplo: str, nb: int, trailing: str = "loop",
                    lookahead: bool = False, with_info: bool = False,
                    panel_fused: bool = False, step_fused: bool = False,
                    panel_interpret: bool = False):
    n = a.shape[0]
    # "ozaki": route the flops-dominant trailing update through int8 MXU
    # passes (tile_ops.ozaki) — f64 and complex128 (4-real-product form);
    # other dtypes keep the native whole-gemm form (static, trace time)
    use_oz = trailing == "ozaki" and a.dtype in (jnp.float64, jnp.complex128)
    if trailing == "ozaki" and not use_oz:
        trailing = "biggemm"
    if trailing == "xla" and n:
        # whole-matrix XLA cholesky: the compiler's own fused/blocked
        # factorization (a TPU-native option the reference cannot take —
        # its local algorithm must hand-block; ours may delegate blocking
        # to XLA). Triangle pass-through semantics preserved.
        from jax import lax

        if uplo == "L":
            ah = jnp.tril(a) + jnp.conj(jnp.tril(a, -1)).T
            l = lax.linalg.cholesky(ah)
            out = jnp.tril(l) + jnp.triu(a, 1)
        else:
            ah = jnp.triu(a) + jnp.conj(jnp.triu(a, 1)).T
            l = lax.linalg.cholesky(ah)
            out = jnp.triu(jnp.conj(l).T) + jnp.tril(a, -1)
        # in-graph info (health.info): a pure extra output on the final
        # factor — the factor subgraph is untouched either way
        return (out, hinfo.local_factor_info(out)) if with_info else out
    nt = ceil_div(n, nb) if n else 0
    # lookahead carry: the next panel column's (diag block, below-diag
    # block) values as step k's SSA outputs, so step k+1's potrf/trsm
    # chain consumes them directly instead of reading `a` after the bulk
    # trailing scatter — the dependency XLA needs to overlap panel k+1
    # with the bulk herk/gemm of step k (reference look-ahead,
    # ``factorization/cholesky/impl.h:147-156,187-189``)
    la = None
    for k in range(nt):
        _count_step_modes("cholesky", *((1, 0) if lookahead and k < nt - 1
                                        else (0, 1)))
        k0, k1 = k * nb, min((k + 1) * nb, n)
        blk = a[k0:k1, k0:k1] if la is None else la[0]
        if step_fused and k1 < n:
            # step_impl route (docs/pallas_panel.md "Fused step kernel"):
            # ONE pallas_call per blocked step — potrf ladder + whole
            # strip solve + the adjacent trailing column/row strip, with
            # the factor, its inverse, and the solved leading strip
            # block VMEM-resident between the three ops. The remaining
            # trailing update is the row/column-trimmed rest-herk of the
            # lookahead split (same dots, same per-cell application
            # order), so the la on/off contract stays bitwise on this
            # route regardless of the lookahead knob.
            m = n - k1
            w = min(nb, m)
            ppan.count_step_kernel("fused")
            if uplo == "L":
                colsrc = a[k1:, k0:k1] if la is None else la[1]
                diag, panel, new_col = ppan.fused_step(
                    "L", blk, colsrc, a[k1:, k1:k1 + w],
                    interpret=panel_interpret)
                a = a.at[k0:k1, k0:k1].set(diag)
                a = a.at[k1:, k0:k1].set(panel)
                a = a.at[k1:, k1:k1 + w].set(new_col)
                la = ((new_col[:w], new_col[w:] if k1 + w < n else None)
                      if lookahead else None)
                if trailing == "loop":
                    for j in range(k + 2, nt):
                        j0, j1 = j * nb, min((j + 1) * nb, n)
                        pj = panel[j0 - k1: j1 - k1]
                        dj = tb.herk("L", "N", pj, a[j0:j1, j0:j1],
                                     alpha=-1.0)
                        a = a.at[j0:j1, j0:j1].set(dj)
                        if j1 < n:
                            below = tb.gemm(panel[j1 - k1:], pj,
                                            a[j1:, j0:j1], alpha=-1.0,
                                            beta=1.0, op_b="C")
                            a = a.at[j1:, j0:j1].set(below)
                elif m > w:
                    pr = panel[w:]
                    upd = pr @ jnp.conj(pr).T
                    mask = jnp.tril(jnp.ones((m - w, m - w), dtype=bool))
                    a = a.at[k1 + w:, k1 + w:].add(jnp.where(mask, -upd, 0))
            else:
                rowsrc = a[k0:k1, k1:] if la is None else la[1]
                diag, panel, new_row = ppan.fused_step(
                    "U", blk, rowsrc, a[k1:k1 + w, k1:],
                    interpret=panel_interpret)
                a = a.at[k0:k1, k0:k1].set(diag)
                a = a.at[k0:k1, k1:].set(panel)
                a = a.at[k1:k1 + w, k1:].set(new_row)
                la = ((new_row[:, :w], new_row[:, w:]
                       if k1 + w < n else None) if lookahead else None)
                if trailing == "loop":
                    for j in range(k + 2, nt):
                        j0, j1 = j * nb, min((j + 1) * nb, n)
                        pj = panel[:, j0 - k1: j1 - k1]
                        dj = tb.herk("U", "C", pj, a[j0:j1, j0:j1],
                                     alpha=-1.0)
                        a = a.at[j0:j1, j0:j1].set(dj)
                        if j1 < n:
                            right = tb.gemm(pj, panel[:, j1 - k1:],
                                            a[j0:j1, j1:], alpha=-1.0,
                                            beta=1.0, op_a="C")
                            a = a.at[j0:j1, j1:].set(right)
                elif m > w:
                    pr = panel[:, w:]
                    upd = jnp.conj(pr).T @ pr
                    mask = jnp.triu(jnp.ones((m - w, m - w), dtype=bool))
                    a = a.at[k1 + w:, k1 + w:].add(jnp.where(mask, -upd, 0))
            continue
        with obs.named_span("cholesky.panel"):
            if use_oz:
                # latency-bound panel ops in mixed precision (f32 seed + Newton,
                # tile_ops.mixed): emulated-f64 potrf/trsm are the wall-clock
                # bottleneck on TPU, not the trailing flops. The fused form
                # shares the f32 seed solves between factor and inverse — one
                # f32 cholesky + one f32 solve per step instead of two solves.
                # Counted under impl="xla" like every non-fused panel kernel
                # (the mixed form is still an XLA op chain)
                ppan.count_panel_kernel("xla", "potrf")
                fac, fac_inv = mx.potrf_inv_refined(uplo, blk)
                other = "U" if uplo == "L" else "L"
                diag = fac + tb.tri_mask(blk, other, k=-1)
            else:
                # panel_impl route (docs/pallas_panel.md): the fused Pallas
                # potrf collapses XLA's blocked-cholesky thunk chain into one
                # VMEM-resident kernel; "xla" keeps tl.potrf
                fac_inv = None
                diag = ppan.panel_potrf(uplo, blk, fused=panel_fused,
                                      interpret=panel_interpret)
            a = a.at[k0:k1, k0:k1].set(diag)
        if k1 == n:
            break
        m = n - k1
        # strip-bearing step on the composed-op chain (step_impl route
        # accounting — the fused branch above counts impl="fused")
        ppan.count_step_kernel("xla")
        if uplo == "L":
            # panel: A[k1:, k] <- A[k1:, k] Lkk^-H   (tile::trsm, high-prio
            # in the reference impl.h:147-156; here XLA schedules it) —
            # under lookahead the panel source is the carried next-column
            # value from step k-1, not an `a` read
            with obs.named_span("cholesky.panel"):
                colsrc = a[k1:, k0:k1] if la is None else la[1]
                if use_oz:
                    # refined explicit inverse (from the fused step above) ->
                    # the panel solve is one gemm instead of an emulated trsm;
                    # the gemm itself rides the int8 MXU path like the trailing
                    # update (native emulated-f64 gemm is ~3x slower)
                    ppan.count_panel_kernel("xla", "solve")
                    panel = tb.mm_mxu(colsrc, jnp.conj(fac_inv).T)
                elif trailing == "invgemm":
                    ppan.count_panel_kernel("xla", "solve")
                    # explicit small triangular inverse, panel formed on the MXU
                    dinv = tb.trsm("L", "L", "N", "N", diag,
                                   jnp.eye(k1 - k0, dtype=a.dtype))
                    panel = colsrc @ jnp.conj(dinv).T
                elif panel_fused:
                    # one grid-batched Pallas kernel for the whole strip
                    panel = ppan.panel_solve("R", "L", "C", "N", diag, colsrc,
                                           fused=True, interpret=panel_interpret)
                else:
                    ppan.count_panel_kernel("xla", "solve")
                    panel = tb.trsm("R", "L", "C", "N", diag, colsrc)
                a = a.at[k1:, k0:k1].set(panel)
            la = None
            if trailing == "loop":
                with obs.named_span("cholesky.bulk"):
                    # trailing per block column: herk on the diagonal block + one
                    # gemm below it — exact n^3/3 flops (reference impl.h:242-271)
                    for j in range(k + 1, nt):
                        j0, j1 = j * nb, min((j + 1) * nb, n)
                        pj = panel[j0 - k1: j1 - k1]
                        dj = tb.herk("L", "N", pj, a[j0:j1, j0:j1], alpha=-1.0)
                        a = a.at[j0:j1, j0:j1].set(dj)
                        below = None
                        if j1 < n:
                            below = tb.gemm(panel[j1 - k1:], pj, a[j1:, j0:j1],
                                            alpha=-1.0, beta=1.0, op_b="C")
                            a = a.at[j1:, j0:j1].set(below)
                        if lookahead and j == k + 1:
                            # the loop schedule already emits column k+1 first;
                            # carrying its values is what frees step k+1 from
                            # the later columns' scatter chain
                            la = (dj, below)
            elif lookahead:
                with obs.named_span("cholesky.strip"):
                    # next-panel-column strip first (consumed by step k+1 via
                    # the carry), then the remaining trailing as a (m-w)^2
                    # herk of the row-trimmed panel — same dots, same per-cell
                    # application order as the single masked product
                    w = min(nb, m)
                    pj = panel[:w]
                    updc = (_oz_product(panel, jnp.conj(pj).T) if use_oz
                            else panel @ jnp.conj(pj).T)
                    cmask = jnp.arange(m)[:, None] >= jnp.arange(w)[None, :]
                    # x + where(mask, -upd, 0): the exact per-cell application
                    # the serial masked add performs (bitwise, zeros included)
                    new_col = a[k1:, k1:k1 + w] + jnp.where(cmask, -updc, 0)
                    a = a.at[k1:, k1:k1 + w].set(new_col)
                    la = (new_col[:w], new_col[w:] if k1 + w < n else None)
                if m > w:
                    with obs.named_span("cholesky.bulk"):
                        pr = panel[w:]
                        if use_oz:
                            upd = (oz.herk_c128(pr, slices=tb._oz_slices())
                                   if jnp.iscomplexobj(pr)
                                   else oz.syrk_f64(pr, slices=tb._oz_slices()))
                        else:
                            upd = pr @ jnp.conj(pr).T
                        mask = jnp.tril(jnp.ones((m - w, m - w), dtype=bool))
                        a = a.at[k1 + w:, k1 + w:].add(jnp.where(mask, -upd, 0))
            else:
                with obs.named_span("cholesky.bulk"):
                    # ONE full trailing update, masked to the lower triangle;
                    # "ozaki" forms it with int8 MXU passes instead of the
                    # software-emulated f64 gemm
                    if use_oz:
                        upd = (oz.herk_c128(panel, slices=tb._oz_slices())
                               if jnp.iscomplexobj(panel)
                               else oz.syrk_f64(panel, slices=tb._oz_slices()))
                    else:
                        upd = panel @ jnp.conj(panel).T
                    mask = jnp.tril(jnp.ones((m, m), dtype=bool))
                    a = a.at[k1:, k1:].add(jnp.where(mask, -upd, 0))
        else:
            # upper: A = U^H U; panel is a block row
            with obs.named_span("cholesky.panel"):
                rowsrc = a[k0:k1, k1:] if la is None else la[1]
                if use_oz:
                    ppan.count_panel_kernel("xla", "solve")
                    panel = tb.mm_mxu(jnp.conj(fac_inv).T, rowsrc)
                elif trailing == "invgemm":
                    ppan.count_panel_kernel("xla", "solve")
                    dinv = tb.trsm("L", "U", "N", "N", diag,
                                   jnp.eye(k1 - k0, dtype=a.dtype))
                    panel = jnp.conj(dinv).T @ rowsrc
                elif panel_fused:
                    panel = ppan.panel_solve("L", "U", "C", "N", diag, rowsrc,
                                           fused=True, interpret=panel_interpret)
                else:
                    ppan.count_panel_kernel("xla", "solve")
                    panel = tb.trsm("L", "U", "C", "N", diag, rowsrc)
                a = a.at[k0:k1, k1:].set(panel)
            la = None
            if trailing == "loop":
                with obs.named_span("cholesky.bulk"):
                    for j in range(k + 1, nt):
                        j0, j1 = j * nb, min((j + 1) * nb, n)
                        pj = panel[:, j0 - k1: j1 - k1]
                        dj = tb.herk("U", "C", pj, a[j0:j1, j0:j1], alpha=-1.0)
                        a = a.at[j0:j1, j0:j1].set(dj)
                        right = None
                        if j1 < n:
                            right = tb.gemm(pj, panel[:, j1 - k1:], a[j0:j1, j1:],
                                            alpha=-1.0, beta=1.0, op_a="C")
                            a = a.at[j0:j1, j1:].set(right)
                        if lookahead and j == k + 1:
                            la = (dj, right)
            elif lookahead:
                with obs.named_span("cholesky.strip"):
                    # next block-row strip first (carried), rest as the
                    # column-trimmed herk — the mirrored split
                    w = min(nb, m)
                    pt = jnp.conj(jnp.swapaxes(panel, -1, -2))
                    updr = (_oz_product(pt[:w], jnp.conj(pt).T) if use_oz
                            else jnp.conj(panel[:, :w]).T @ panel)
                    rmask = jnp.arange(w)[:, None] <= jnp.arange(m)[None, :]
                    new_row = a[k1:k1 + w, k1:] + jnp.where(rmask, -updr, 0)
                    a = a.at[k1:k1 + w, k1:].set(new_row)
                    la = (new_row[:, :w], new_row[:, w:] if k1 + w < n else None)
                if m > w:
                    with obs.named_span("cholesky.bulk"):
                        ptr = pt[w:]
                        if use_oz:
                            upd = (oz.herk_c128(ptr, slices=tb._oz_slices())
                                   if jnp.iscomplexobj(ptr)
                                   else oz.syrk_f64(ptr, slices=tb._oz_slices()))
                        else:
                            pr = panel[:, w:]
                            upd = jnp.conj(pr).T @ pr
                        mask = jnp.triu(jnp.ones((m - w, m - w), dtype=bool))
                        a = a.at[k1 + w:, k1 + w:].add(jnp.where(mask, -upd, 0))
            else:
                with obs.named_span("cholesky.bulk"):
                    if use_oz:
                        pt = jnp.conj(jnp.swapaxes(panel, -1, -2))
                        upd = (oz.herk_c128(pt, slices=tb._oz_slices())
                               if jnp.iscomplexobj(panel)
                               else oz.syrk_f64(pt, slices=tb._oz_slices()))
                    else:
                        upd = jnp.conj(panel).T @ panel
                    mask = jnp.triu(jnp.ones((m, m), dtype=bool))
                    a = a.at[k1:, k1:].add(jnp.where(mask, -upd, 0))
    return (a, hinfo.local_factor_info(a)) if with_info else a


def _carry_window(acc, start, shape):
    """The ``shape`` window of the scan carry ``acc`` at ``start`` as a
    value of its own, for a step that is about to overwrite it: behind the
    barrier the compiler cannot fold the slice into the fusions that write
    the two f32 planes of an f64 carry, and so never has to copy a plane to
    keep its old window readable (:func:`_cholesky_local_scan`: "What a
    step reads before it writes")."""
    return jax.lax.optimization_barrier(
        jax.lax.dynamic_slice(acc, start, shape))


def _scan_bulk_update(acc, xt, lo, rows, live, *, uplo, chunks, syrk_like):
    """``acc`` minus the stored triangle of ``xt @ xt^H`` (``xt``: the
    (m, nb) masked panel, transposed for ``U``), past column / row
    ``lo`` where the pending panel of the look-ahead form reaches
    further up than its update may (None: the panel's own zeros do
    it). One ``syrk_like`` self-product (``chunks`` None), or the
    trapezoids of ``_cholesky_local_scan``'s ``bulk_chunks``. A chunk's
    new contents are formed as a value of their own, behind a barrier,
    and then written back (:func:`_cholesky_local_scan`: "What a step
    reads before it writes"): the value lives from the chunk's last dot
    to its write. Reading the old window out first (:func:`_carry_window`)
    keeps it alive across the chunk's seven dots instead: 0.4% faster at
    N=16384, nb=512 on a v5e and 0.4 GB more of temporaries there, 0.6 GB
    more at n=8192, nb=1024 (PERF.md section 6, PR 32)."""
    if chunks is None:
        with oz.live_outputs(live[0]):
            upd = syrk_like(xt)
        if uplo == "L":
            mask = rows[:, None] >= rows[None, :]
            if lo is not None:
                mask = mask & (rows[None, :] >= lo)
        else:
            mask = rows[:, None] <= rows[None, :]
            if lo is not None:
                mask = mask & (rows[:, None] >= lo)
        return acc - jnp.where(mask, upd, 0)
    for (c0, c1), kept in zip(chunks, live):
        long, short = xt[c0:], xt[c0:c1]
        rl, rs = rows[c0:], rows[c0:c1]
        if uplo == "L":
            with oz.live_outputs(kept):
                upd = _oz_product(long, jnp.conj(short).T)
            mask = rl[:, None] >= rs[None, :]
            if lo is not None:
                mask = mask & (rs[None, :] >= lo)
            acc = acc.at[c0:, c0:c1].set(jax.lax.optimization_barrier(
                acc[c0:, c0:c1] - jnp.where(mask, upd, 0)))
        else:
            with oz.live_outputs(kept):
                upd = _oz_product(short, jnp.conj(long).T)
            mask = rs[:, None] <= rl[None, :]
            if lo is not None:
                mask = mask & (rs[:, None] >= lo)
            acc = acc.at[c0:c1, c0:].set(jax.lax.optimization_barrier(
                acc[c0:c1, c0:] - jnp.where(mask, upd, 0)))
    return acc


@register_program_cache
@functools.partial(jax.jit, static_argnames=("uplo", "nb", "use_mxu",
                                             "use_mixed", "lookahead",
                                             "with_info", "panel_fused",
                                             "step_fused",
                                             "panel_interpret"),
                   donate_argnums=0)
def _cholesky_local_scan(a, *, uplo: str, nb: int, use_mxu: bool = False,
                         use_mixed: bool = False, lookahead: bool = False,
                         with_info: bool = False, panel_fused: bool = False,
                         step_fused: bool = False,
                         panel_interpret: bool = False):
    """``lax.scan`` formulation of the local factorization: one compiled
    step body per telescoped segment (``types.telescope_segments``: eight
    steps each up to 64), looped with the uniform shapes of the segment's
    block. The local entry takes it from the step count
    (:func:`local_step_form`: on a TPU from 32 block steps on).

    Why it exists: the unrolled trace (:func:`_cholesky_local`) compiles in
    time linear in ``nt`` and its per-step intermediates are all visible to
    the allocator at once. The one size at which both forms ran on the chip
    (N=16384, nb=512, f64, one v5e; PERF.md section 6, PR 31): unrolled,
    779.8 s to the first call and the host's 40 GiB exhausted after it,
    1.338 s a warm call; this form, 149.5 s to the first call (12 s from
    the cache), 1.2284 s a call, a 59.6 MB cache entry. The scanned form
    compiles one body a segment and reuses its carry buffers, at the price
    of uniform-shape work: the panel is the segment's FULL block column
    (rows above the pivot masked) and the trailing update covers the
    segment's whole block every step, as one masked (m, m) self-product or,
    on the slice-product route from ``SCAN_BULK_CHUNK_AT`` rows on, as
    block-column trapezoids (``bulk_chunks``). What the masks throw away is
    counted (``dlaf_ozaki_masked_macs_total``, the benchmark's
    ``masked_mac_share``).

    What a step reads before it writes: between a step's read of a window
    of the carried block and its write to the same window stands a value
    of the window's size, behind an ``optimization_barrier``. The small
    windows (the diagonal block, the panel column, the next column's
    strip) are read out as values (:func:`_carry_window`) and the new
    window is computed from them; a chunk of the bulk update is formed
    whole, old window minus update, and then written
    (:func:`_scan_bulk_update`). On the TPU an f64 block is two f32
    planes, and the compiler updates each plane in place with a fusion of
    its own; the double-f32 arithmetic of either plane reads the old
    window of BOTH, so with read and write in one expression the second
    fusion read plane A after the first had overwritten it, and the
    compiler kept the old plane alive by copying all of it: one
    whole-plane ``copy`` a chunk a step and one ahead of the strip's
    update (N=16384, nb=512, one v5e: 181 ms of a 1212 ms program, PERF.md
    section 5, PR 32). A window-sized value costs what it holds; the
    arithmetic, and so every bit of the result, is the same.

    The panel and trailing routes follow the same knobs as the distributed
    scan builder (:func:`_build_dist_cholesky_scan`): ``use_mixed``
    (``f64_trsm="mixed"``) factors panels via the mixed-precision fused
    factor+inverse, ``use_mxu`` (``f64_gemm="mxu"``) contracts the trailing
    product on the ozaki MXU path. Both default off, so the same dtype and
    ``trailing="scan"`` config resolves identically on 1 device and on a
    grid (round-2 advisory: the previous hardwired f64 route made the scan
    variant pathological off-TPU). Triangle pass-through semantics match
    the unrolled path.
    """
    n = a.shape[0]
    if n == 0:
        return (a, jnp.zeros((), jnp.int32)) if with_info else a
    nt = ceil_div(n, nb)
    npad = nt * nb - n
    if npad:
        # pad to uniform blocks with an identity tail: chol([[A,0],[0,I]])
        # = [[L,0],[0,I]] and the pad rows/cols never touch the result
        a = jnp.pad(a, ((0, npad), (0, npad)))
        a = a.at[jnp.arange(n, nt * nb), jnp.arange(n, nt * nb)].set(1)
    other = "U" if uplo == "L" else "L"

    def syrk_like(x):
        """Masked-panel self-product on the configured trailing route: the
        scan forms' one bulk product (x zeroed above its pivot)."""
        if use_mxu:
            return (oz.herk_c128(x, slices=tb._oz_slices())
                    if jnp.iscomplexobj(x)
                    else oz.syrk_f64(x, slices=tb._oz_slices()))
        return x @ jnp.conj(x).T

    def bulk_chunks(m):
        """Static block-column (``L``; block-row for ``U``) chunks
        ``(c0, c1)`` of a segment's bulk product, or None for the one
        (m, m) self-product. The shape decides, by the rule of the other
        workspace bounds (``trsm_rhs_chunk`` / ``red2band_trail_chunk``
        auto): on the slice-product route, from :data:`SCAN_BULK_CHUNK_AT`
        rows on, the update is formed :data:`SCAN_BULK_CHUNK` columns at a
        time: the (m, m) self-product's f64 accumulator, int32 partials
        and mirror stand beside the carry (N=16384 compiled for a described
        v5e: 8.68 GiB of temporaries with the square, 5.31 with the
        chunks). Each chunk starts at its own diagonal, so the square's
        strict other triangle is never multiplied (1.41x fewer
        multiply-accumulates at N=16384, nb=512, by the counters)."""
        if not use_mxu or m < SCAN_BULK_CHUNK_AT:
            return None
        w = max(nb, SCAN_BULK_CHUNK // nb * nb)
        return [(c0, min(c0 + w, m)) for c0 in range(0, m, w)]

    def live_lower(m, lo, c0, c1):
        """Elements ``(i, j)`` of an (m, m) block with ``c0 <= j < c1``,
        ``j >= lo`` and ``i >= j``: what a stored-triangle update keeps
        of the columns ``[c0, c1)`` once the pivot stands at ``lo``."""
        j0 = max(c0, lo)
        cnt = c1 - j0
        return cnt * m - (j0 + c1 - 1) * cnt // 2 if cnt > 0 else 0

    def live_counts(m, seg_len, first):
        """Output elements a segment's products keep, summed over its
        ``seg_len`` executed steps (``oz.live_outputs``): the panel
        product's rows below the pivot, the strip's stored trapezoid and,
        per chunk, the bulk's stored triangle past the pivot (under
        look-ahead the bulk of body k is step k-1's, past column block k;
        the factorization's very first body has none pending)."""
        chunks = bulk_chunks(m) or [(0, m)]
        out = {"panel": 0, "strip": 0, "bulk": [0] * len(chunks)}
        for k in range(seg_len):
            lo = (k + 1) * nb
            out["panel"] += (m - lo) * nb
            if lookahead:
                out["strip"] += live_lower(m, lo, lo, min(lo + nb, m))
                if first and k == 0:
                    continue
            for i, (c0, c1) in enumerate(chunks):
                out["bulk"][i] += live_lower(m, lo, c0, c1)
        return out

    def bulk_update(acc, xt, lo, rows, live):
        with obs.named_span("cholesky.bulk"):
            return _scan_bulk_update(acc, xt, lo, rows, live, uplo=uplo,
                                     chunks=bulk_chunks(xt.shape[0]),
                                     syrk_like=syrk_like)

    def make_step(m, live):
        rows = jnp.arange(m)

        def step(acc, k):
            k0 = k * nb
            with obs.named_span("cholesky.panel"):
                blk = _carry_window(acc, (k0, k0), (nb, nb))
                ppan.count_step_kernel("fused" if step_fused else "xla")
                if use_mixed:
                    ppan.count_panel_kernel("xla", "potrf")
                    fac, fac_inv = mx.potrf_inv_refined(uplo, blk)
                    diag = fac + tb.tri_mask(blk, other, k=-1)
                elif step_fused:
                    # step_impl route, scan form: the potrf is DEFERRED into
                    # the fused factor+solve kernel below (the trailing
                    # update's traced-index masks keep it outside the
                    # kernel, so the scan forms fuse the 2-op panel chain)
                    fac_inv = diag = None
                else:
                    fac_inv = None
                    diag = ppan.panel_potrf(uplo, blk, fused=panel_fused,
                                          interpret=panel_interpret)
                if diag is not None:
                    acc = jax.lax.dynamic_update_slice(acc, diag, (k0, k0))
                below = rows >= k0 + nb      # (m,) rows/cols past the pivot
            if uplo == "L":
                with obs.named_span("cholesky.panel"):
                    col = _carry_window(acc, (0, k0), (m, nb))
                    if use_mixed:
                        ppan.count_panel_kernel("xla", "solve")
                        inv_t = jnp.conj(fac_inv).T
                        with oz.live_outputs(live["panel"]):
                            pfull = (tb.mm_mxu(col, inv_t) if use_mxu
                                     else col @ inv_t)
                    elif step_fused:
                        # col's pivot rows hold the unfactored blk; the
                        # write-back + explicit diag update below restore
                        # the factored tile
                        diag, pfull = ppan.fused_factor_solve(
                            "L", blk, col, interpret=panel_interpret)
                    elif panel_fused:
                        pfull = ppan.panel_solve("R", "L", "C", "N", diag, col,
                                               fused=True,
                                               interpret=panel_interpret)
                    else:
                        ppan.count_panel_kernel("xla", "solve")
                        pfull = tb.trsm("R", "L", "C", "N", diag, col)
                    panel = jnp.where(below[:, None], pfull, 0)
                    acc = jax.lax.dynamic_update_slice(
                        acc, jnp.where(below[:, None], pfull, col), (0, k0))
                    if step_fused:
                        acc = jax.lax.dynamic_update_slice(acc, diag, (k0, k0))
                # panel is zero at rows <= pivot, so the update lives only
                # in the trailing block; restricted to the stored triangle
                acc = bulk_update(acc, panel, None, rows, live["bulk"])
            else:
                with obs.named_span("cholesky.panel"):
                    row = _carry_window(acc, (k0, 0), (nb, m))
                    if use_mixed:
                        ppan.count_panel_kernel("xla", "solve")
                        inv_t = jnp.conj(fac_inv).T
                        with oz.live_outputs(live["panel"]):
                            pfull = (tb.mm_mxu(inv_t, row) if use_mxu
                                     else inv_t @ row)
                    elif step_fused:
                        diag, pfull = ppan.fused_factor_solve(
                            "U", blk, row, interpret=panel_interpret)
                    elif panel_fused:
                        pfull = ppan.panel_solve("L", "U", "C", "N", diag, row,
                                               fused=True,
                                               interpret=panel_interpret)
                    else:
                        ppan.count_panel_kernel("xla", "solve")
                        pfull = tb.trsm("L", "U", "C", "N", diag, row)
                    panel = jnp.where(below[None, :], pfull, 0)
                    acc = jax.lax.dynamic_update_slice(
                        acc, jnp.where(below[None, :], pfull, row), (k0, 0))
                    if step_fused:
                        acc = jax.lax.dynamic_update_slice(acc, diag, (k0, k0))
                with obs.named_span("cholesky.bulk"):
                    pt = jnp.conj(jnp.swapaxes(panel, -1, -2))
                    acc = bulk_update(acc, pt, None, rows, live["bulk"])
            return acc, None

        return step

    def make_step_la(m, live):
        """Software-pipelined step body (``cholesky_lookahead=1``): the
        bulk trailing product of step k-1 is DEFERRED into body k, where
        it carries no dependency on body k's latency-bound potrf/trsm
        chain — XLA overlaps the two inside one iteration, which a
        sequential ``lax.scan`` body can never do across iterations. The
        next panel column's strip is updated eagerly (it is what frees
        the following body's panel chain), so per-cell application order
        — bulk(k-1) before strip(k) — matches the serial body exactly
        and results stay bitwise identical."""
        rows = jnp.arange(m)

        def step(carry, k):
            acc, pp = carry      # pp: previous step's masked panel
            k0 = k * nb
            with obs.named_span("cholesky.panel"):
                blk = _carry_window(acc, (k0, k0), (nb, nb))
                ppan.count_step_kernel("fused" if step_fused else "xla")
                if use_mixed:
                    ppan.count_panel_kernel("xla", "potrf")
                    fac, fac_inv = mx.potrf_inv_refined(uplo, blk)
                    diag = fac + tb.tri_mask(blk, other, k=-1)
                elif step_fused:
                    # potrf deferred into the fused factor+solve kernel
                    fac_inv = diag = None
                else:
                    fac_inv = None
                    diag = ppan.panel_potrf(uplo, blk, fused=panel_fused,
                                          interpret=panel_interpret)
                if diag is not None:
                    acc = jax.lax.dynamic_update_slice(acc, diag, (k0, k0))
                below = rows >= k0 + nb
                valid1 = k0 + 2 * nb <= m    # next block col/row exists
            if uplo == "L":
                with obs.named_span("cholesky.panel"):
                    col = _carry_window(acc, (0, k0), (m, nb))
                    if use_mixed:
                        ppan.count_panel_kernel("xla", "solve")
                        inv_t = jnp.conj(fac_inv).T
                        with oz.live_outputs(live["panel"]):
                            pfull = (tb.mm_mxu(col, inv_t) if use_mxu
                                     else col @ inv_t)
                    elif step_fused:
                        diag, pfull = ppan.fused_factor_solve(
                            "L", blk, col, interpret=panel_interpret)
                    elif panel_fused:
                        pfull = ppan.panel_solve("R", "L", "C", "N", diag, col,
                                               fused=True,
                                               interpret=panel_interpret)
                    else:
                        ppan.count_panel_kernel("xla", "solve")
                        pfull = tb.trsm("R", "L", "C", "N", diag, col)
                    panel = jnp.where(below[:, None], pfull, 0)
                    acc = jax.lax.dynamic_update_slice(
                        acc, jnp.where(below[:, None], pfull, col), (0, k0))
                    if step_fused:
                        acc = jax.lax.dynamic_update_slice(acc, diag, (k0, k0))
                # deferred bulk of step k-1: its next-col (block col k)
                # was applied in body k-1, the rest lands here
                acc = bulk_update(acc, pp, k0 + nb, rows, live["bulk"])
                with obs.named_span("cholesky.strip"):
                    # eager next-column strip from THIS panel
                    nstrip = jax.lax.dynamic_slice(panel, (k0 + nb, 0),
                                                   (nb, nb))
                    with oz.live_outputs(live["strip"]):
                        updc = (_oz_product(panel, jnp.conj(nstrip).T)
                                if use_mxu else panel @ jnp.conj(nstrip).T)
                    ccur = _carry_window(acc, (0, k0 + nb), (m, nb))
                    cols1 = k0 + nb + jnp.arange(nb)
                    cmask = (rows[:, None] >= cols1[None, :]) & valid1
                    acc = jax.lax.dynamic_update_slice(
                        acc, ccur - jnp.where(cmask, updc, 0), (0, k0 + nb))
            else:
                with obs.named_span("cholesky.panel"):
                    row = _carry_window(acc, (k0, 0), (nb, m))
                    if use_mixed:
                        ppan.count_panel_kernel("xla", "solve")
                        inv_t = jnp.conj(fac_inv).T
                        with oz.live_outputs(live["panel"]):
                            pfull = (tb.mm_mxu(inv_t, row) if use_mxu
                                     else inv_t @ row)
                    elif step_fused:
                        diag, pfull = ppan.fused_factor_solve(
                            "U", blk, row, interpret=panel_interpret)
                    elif panel_fused:
                        pfull = ppan.panel_solve("L", "U", "C", "N", diag, row,
                                               fused=True,
                                               interpret=panel_interpret)
                    else:
                        ppan.count_panel_kernel("xla", "solve")
                        pfull = tb.trsm("L", "U", "C", "N", diag, row)
                    panel = jnp.where(below[None, :], pfull, 0)
                    acc = jax.lax.dynamic_update_slice(
                        acc, jnp.where(below[None, :], pfull, row), (k0, 0))
                    if step_fused:
                        acc = jax.lax.dynamic_update_slice(acc, diag, (k0, k0))
                with obs.named_span("cholesky.bulk"):
                    ppt = jnp.conj(jnp.swapaxes(pp, -1, -2))
                    acc = bulk_update(acc, ppt, k0 + nb, rows, live["bulk"])
                with obs.named_span("cholesky.strip"):
                    pt = jnp.conj(jnp.swapaxes(panel, -1, -2))
                    nstrip = jax.lax.dynamic_slice(pt, (k0 + nb, 0), (nb, nb))
                    # nstrip = conj(panel_block)^T, so nstrip @ panel IS the
                    # strip of conj(panel)^T @ panel (same dots as serial)
                    with oz.live_outputs(live["strip"]):
                        updr = (_oz_product(nstrip, jnp.conj(pt).T) if use_mxu
                                else nstrip @ panel)
                    rcur = _carry_window(acc, (k0 + nb, 0), (nb, m))
                    rows1 = k0 + nb + jnp.arange(nb)
                    rmask = (rows1[:, None] <= rows[None, :]) & valid1
                    acc = jax.lax.dynamic_update_slice(
                        acc, rcur - jnp.where(rmask, updr, 0), (k0 + nb, 0))
            return (acc, panel), None

        return step

    # telescoped segments: each segment scans the SHRINKING trailing
    # submatrix (completed panel columns live outside it and are final),
    # so the uniform masked work tracks the live trailing block instead
    # of the original matrix, at one step body a segment (the unrolled
    # form has one a step).
    # Under lookahead the pending panel is carried ACROSS segments (the
    # dropped slots are zero — the panel is masked below its pivot), so
    # no flush products are ever paid; the last step's pending is
    # identically zero and simply dropped.
    off = 0
    pp = None
    for seg_len in telescope_segments(nt):
        m_seg = (nt - off) * nb
        sub = a[off * nb:, off * nb:]
        if lookahead:
            _count_step_modes("cholesky_scan", seg_len, 0)
            if pp is None:
                pp = (jnp.zeros((m_seg, nb), a.dtype) if uplo == "L"
                      else jnp.zeros((nb, m_seg), a.dtype))
            else:
                pp = pp[-m_seg:] if uplo == "L" else pp[:, -m_seg:]
            body = make_step_la(m_seg, live_counts(m_seg, seg_len, off == 0))
        else:
            _count_step_modes("cholesky_scan", 0, seg_len)
            body = make_step(m_seg, live_counts(m_seg, seg_len, off == 0))
        # the index-free scope of the distributed scan builder: ONE traced
        # body serves the segment's iterations, and trace-time counters
        # inside count per executed step (obs.scoped_step)
        body = obs.scoped_step("cholesky.scanstep", body, steps=seg_len)
        if lookahead:
            (sub, pp), _ = jax.lax.scan(body, (sub, pp),
                                        jnp.arange(seg_len))
        else:
            sub, _ = jax.lax.scan(body, sub, jnp.arange(seg_len))
        a = a.at[off * nb:, off * nb:].set(sub)
        off += seg_len
    out = a[:n, :n]
    return (out, hinfo.local_factor_info(out)) if with_info else out





# ---------------------------------------------------------------------------
# Distributed — reference impl.h:174-276
# ---------------------------------------------------------------------------

def _build_dist_cholesky(dist, mesh, uplo, use_pallas, pallas_interpret,
                         use_mxu=False, use_mixed=False, cplx=False,
                         lookahead=False, comm_la=False, with_info=False,
                         panel_fused=False, step_fused=False):
    """Build the shard_map'd factorization program for one (dist, mesh, uplo).

    ``use_mxu`` routes the trailing tile-pair contraction through the
    error-free int8 MXU path (tile_ops.ozaki; ``cplx`` picks the complex128
    composition), following the ``f64_gemm="mxu"`` knob; ``use_mixed`` (f64
    AND complex128, following ``f64_trsm="mixed"``) factors/solves the panel
    with the half-precision-seed-plus-Newton helpers (tile_ops.mixed,
    Hermitian-correct) instead of emulated potrf/trsm.

    The returned function maps tile storage -> tile storage. All index
    arithmetic below is trace-time (static per k); only data and the
    rank-dependent validity masks are traced values.

    uplo='U' is the mirrored sweep (reference ``call_U``): the panel is the
    block *row* ``k`` (``trsm('L','U','C','N')`` per tile), broadcast along
    the column axis, all-gathered along the row axis to index the transposed
    panel by local trailing rows, and the trailing update
    ``A[i,j] -= U[k,i]^H U[k,j]`` touches the upper-triangle tile pairs.

    Each step is three phases — ``panel_chain`` (fused diag ``bcast2d`` +
    potrf + panel trsm + panel broadcast + transposed-panel all_gather),
    ``step_pre`` (diag/panel writes + the lookahead next-column strip) and
    ``step_bulk`` (the bulk trailing product) — so ``comm_la``
    (``comm_lookahead=1``, docs/comm_overlap.md) can emit step k+1's
    ENTIRE panel chain, collectives included, BEFORE step k's bulk
    product: the chain reads only the carried post-strip column values,
    never ``lt`` after the bulk scatter, which is exactly the dependency
    shape that lets XLA run the ICI transfer concurrently with the bulk
    MXU gemms (the reference hides the same transfer behind the trailing
    update, ``broadcast_panel.h`` + ``impl.h:147-156``). Phase order of
    ``lt`` mutations is identical in both modes, so results are bitwise
    the same with the knob on or off.
    """
    nt = dist.nr_tiles.row
    mb = dist.block_size.row
    n = dist.size.row
    Pr, Qc = dist.grid_size.row, dist.grid_size.col
    sr, sc = dist.source_rank.row, dist.source_rank.col
    _, _, ltr, ltc = storage_tile_grid(dist)

    def local_rows_global(lu, rr, count):
        """Global tile rows of local row slots lu..lu+count-1 (traced rr)."""
        return (lu + jnp.arange(count)) * Pr + rr

    def local_cols_global(lu, rc, count):
        return (lu + jnp.arange(count)) * Qc + rc

    def _indices(k):
        """Trace-time per-step index bundle (owners, pivot slots, uniform
        trailing slot starts)."""
        owner_r = ud.rank_global_tile(k, Pr, sr)
        owner_c = ud.rank_global_tile(k, Qc, sc)
        kr = ud.local_tile_from_global_tile(k, Pr)
        kc = ud.local_tile_from_global_tile(k, Qc)
        lu_r = max(0, -(-(k + 2 - Pr) // Pr))
        lu_c = max(0, -(-(k + 2 - Qc) // Qc))
        return owner_r, owner_c, kr, kc, lu_r, lu_c

    def panel_chain(lt, k, la):
        """Panel chain of step k: fused diag broadcast (one collective,
        :func:`cc.bcast2d`) + potrf + panel trsm + panel broadcast +
        transposed-panel all_gather (reference impl.h:215-231 +
        broadcast_panel.h:101-193). With the lookahead carry
        ``la = (tiles, lu)`` (step k-1's post-strip column/row values) the
        chain reads NO ``lt`` value at all — it is independent of step
        k-1's bulk trailing product, which is what allows ``comm_la`` to
        emit it (collectives included) ahead of that product. The carried
        tiles are trusted only under the owner masks, exactly like the
        PR-2 carry. Returns ``(lkk, pan, vbcast, vtrans)``; ``pan`` is
        None past the last trailing step, ``vtrans`` None when no rank
        has trailing columns (rows for uplo='U')."""
        rr = (cc.this_rank(ROW_AXIS) - sr) % Pr
        rc = (cc.this_rank(COL_AXIS) - sc) % Qc
        owner_r, owner_c, kr, kc, lu_r, lu_c = _indices(k)

        # -- diag tile -> everyone (reference: col bcast impl.h:215-219);
        # uplo='U' carries a block ROW, indexed by column slots
        # the chain's collectives, with the selects and gathers around
        # them, are the phase ``cholesky.comm`` (innermost phase wins,
        # obs/scopes.py): ``panel`` keeps the potrf and the panel solve, so
        # the chain's device time splits into arithmetic and communication
        with obs.named_span("cholesky.comm"):
            cand = lt[kr, kc] if la is None \
                else la[0][(kr if uplo == "L" else kc) - la[1]]
            diag = cc.bcast2d(cand, owner_r, owner_c)
        ts = min(mb, n - k * mb)
        if ts < mb:  # pad short edge tile with identity to keep potrf defined
            pad = (jnp.arange(mb) >= ts)
            diag = jnp.where(pad[:, None] | pad[None, :], 0, diag) \
                + jnp.diag(pad.astype(diag.dtype))
        # redundant tiny compute on every rank; mixed mode swaps the
        # latency-bound emulated-f64 potrf for the f32-seed + Newton form
        # (fused with the explicit inverse the panel solve consumes, so
        # each step pays one f32 cholesky + ONE f32 solve, not two)
        lkk_inv = None
        # step_impl route, distributed form: potrf + whole-strip solve as
        # ONE fused pallas_call (the trailing slab stays outside — it
        # needs the POST-collective transposed panel, so only the 2-op
        # chain can fuse here). Deferred past the early-outs: the final
        # step and strip-less shards keep the plain potrf.
        fuse_step = step_fused and not use_mixed and k < nt - 1 and (
            (ltr - lu_r) if uplo == "L" else (ltc - lu_c)) > 0
        if use_mixed:
            ppan.count_panel_kernel("xla", "potrf")
            other = "U" if uplo == "L" else "L"
            fac, lkk_inv = mx.potrf_inv_refined(uplo, diag)
            lkk = fac + tb.tri_mask(diag, other, k=-1)
        elif fuse_step:
            lkk = None   # factored inside the fused kernel below
        else:
            # panel_impl route (docs/pallas_panel.md): fused VMEM potrf
            # kernel or XLA's blocked-cholesky thunk chain
            lkk = ppan.panel_potrf(uplo, diag, fused=panel_fused,
                                 interpret=pallas_interpret)
        if k == nt - 1:
            return lkk, None, None, None

        if uplo == "L":
            nrows = ltr - lu_r
            if nrows == 0:
                return lkk, None, None, None
            g_rows = local_rows_global(lu_r, rr, nrows)
            row_valid = (g_rows > k) & (g_rows < nt)
            ppan.count_step_kernel("fused" if fuse_step else "xla")
            # trsm_panel: native batched solve, or (f64_trsm="mixed")
            # refined inverse + matmul that follows the f64_gemm routing
            # (inverse precomputed by the fused potrf step); the panel
            # source is the carried next-column when pipelined (non-owner
            # ranks' carried tiles are stale pre-bulk values, but every
            # use of `pan` is gated by the owner-column keep/bcast masks)
            colsrc = lt[lu_r:, kc] if la is None else la[0][lu_r - la[1]:]
            if fuse_step:
                lkk, pan = ppan.fused_factor_solve(
                    "L", diag, colsrc, interpret=pallas_interpret)
            else:
                pan = ppan.panel_solve("R", "L", "C", "N", lkk, colsrc,
                                     fused=panel_fused,
                                     interpret=pallas_interpret,
                                     inv_a=lkk_inv)
            with obs.named_span("cholesky.comm"):
                pan = jnp.where(row_valid[:, None, None], pan,
                                jnp.zeros_like(pan))
                # -- panel broadcast (reference broadcast_panel.h:101-193)
                # row-wise: every rank gets the panel tiles for its local
                # rows
                vr = cc.bcast(pan, COL_AXIS, owner_c)
            ncols = ltc - lu_c
            if ncols == 0:
                return lkk, pan, vr, None
            g_cols = local_cols_global(lu_c, rc, ncols)
            col_valid = (g_cols > k) & (g_cols < nt)
            # transposed panel: all_gather along 'row' -> all panel tiles,
            # then gather the tiles matching my local trailing columns
            with obs.named_span("cholesky.comm"):
                vc = transpose_col_to_rows(DistContext(dist), vr, lu_r,
                                           g_cols)
                vc = jnp.where(col_valid[:, None, None], vc,
                               jnp.zeros_like(vc))
            return lkk, pan, vr, vc

        # uplo='U': panel is the block row k (reference ``call_U``)
        ncols = ltc - lu_c
        if ncols == 0:
            return lkk, None, None, None
        g_cols = local_cols_global(lu_c, rc, ncols)
        col_valid = (g_cols > k) & (g_cols < nt)
        ppan.count_step_kernel("fused" if fuse_step else "xla")
        rowsrc = lt[kr, lu_c:] if la is None else la[0][lu_c - la[1]:]
        if fuse_step:
            lkk, pan = ppan.fused_factor_solve(
                "U", diag, rowsrc, interpret=pallas_interpret)
        else:
            pan = ppan.panel_solve("L", "U", "C", "N", lkk, rowsrc,
                                 fused=panel_fused,
                                 interpret=pallas_interpret,
                                 inv_a=lkk_inv)
        # col-wise down the mesh, then all_gather along the column axis
        # to index the transposed panel by local rows
        with obs.named_span("cholesky.comm"):
            pan = jnp.where(col_valid[:, None, None], pan,
                            jnp.zeros_like(pan))
            vcp = cc.bcast(pan, ROW_AXIS, owner_r)
        nrows = ltr - lu_r
        if nrows == 0:
            return lkk, pan, vcp, None
        g_rows = local_rows_global(lu_r, rr, nrows)
        row_valid = (g_rows > k) & (g_rows < nt)
        with obs.named_span("cholesky.comm"):
            vrp = transpose_row_to_cols(DistContext(dist), vcp, lu_c, g_rows)
            vrp = jnp.where(row_valid[:, None, None], vrp,
                            jnp.zeros_like(vrp))
        return lkk, pan, vcp, vrp

    def step_pre(lt, k, ch):
        """Write step k's factored diag + panel and apply the lookahead
        next-column (next-row for 'U') strip; returns ``(lt, la_next)``
        with ``la_next = (post-strip tiles, lu)`` — the SSA carry feeding
        both step k+1's panel chain and its strip indexing."""
        lkk, pan, vb, vt = ch
        rr = (cc.this_rank(ROW_AXIS) - sr) % Pr
        rc = (cc.this_rank(COL_AXIS) - sc) % Qc
        owner_r, owner_c, kr, kc, lu_r, lu_c = _indices(k)
        is_owner_r = cc.this_rank(ROW_AXIS) == owner_r
        is_owner_c = cc.this_rank(COL_AXIS) == owner_c

        # owner writes the factored diagonal back
        upd_tile = jnp.where(is_owner_r & is_owner_c, lkk, lt[kr, kc])
        lt = lt.at[kr, kc].set(upd_tile)
        if pan is None:
            return lt, None

        if uplo == "L":
            nrows = ltr - lu_r
            g_rows = local_rows_global(lu_r, rr, nrows)
            row_valid = (g_rows > k) & (g_rows < nt)
            # owner column keeps the factored panel (others their tiles)
            keep = (is_owner_c & row_valid)[:, None, None]
            lt = lt.at[lu_r:, kc].set(jnp.where(keep, pan, lt[lu_r:, kc]))
            if vt is None or not (lookahead and k + 1 < nt):
                return lt, None
            # -- next panel column first (reference's high-priority
            # first-column herk, impl.h:147-156): one tile-column einsum
            # against MY kc1-slot transposed-panel tile (exactly the tile
            # the bulk product would have used — bitwise-identical dots),
            # emitted before the bulk and carried to step k+1
            vr, vc = vb, vt
            kc1 = ud.local_tile_from_global_tile(k + 1, Qc)
            owner_c1 = ud.rank_global_tile(k + 1, Qc, sc)
            pk1 = vc[kc1 - lu_c]
            own_c1 = cc.this_rank(COL_AXIS) == owner_c1
            below1 = row_valid & (g_rows > k + 1)
            ondiag1 = row_valid & (g_rows == k + 1)
            if use_mxu:
                mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
                updc = mmfn(vr.reshape(nrows * mb, mb), jnp.conj(pk1).T,
                            slices=tb._oz_slices()).reshape(nrows, mb, mb)
            else:
                updc = jnp.einsum("rab,db->rad", vr, jnp.conj(pk1),
                                  preferred_element_type=vr.dtype)
            tril1 = jnp.tril(jnp.ones((mb, mb), dtype=bool))
            m3 = (below1[:, None, None] | (ondiag1[:, None, None] & tril1)) \
                & own_c1
            new_col = lt[lu_r:, kc1] - jnp.where(m3, updc,
                                                 jnp.zeros_like(updc))
            lt = lt.at[lu_r:, kc1].set(new_col)
            return lt, (new_col, lu_r)

        # uplo='U'
        ncols = ltc - lu_c
        g_cols = local_cols_global(lu_c, rc, ncols)
        col_valid = (g_cols > k) & (g_cols < nt)
        keep = (is_owner_r & col_valid)[:, None, None]
        lt = lt.at[kr, lu_c:].set(jnp.where(keep, pan, lt[kr, lu_c:]))
        if vt is None or not (lookahead and k + 1 < nt):
            return lt, None
        # next block row first (mirrored split): my kr1-slot
        # transposed-panel tile, carried to step k+1
        vc, vr = vb, vt
        kr1 = ud.local_tile_from_global_tile(k + 1, Pr)
        owner_r1 = ud.rank_global_tile(k + 1, Pr, sr)
        pk1 = vr[kr1 - lu_r]
        own_r1 = cc.this_rank(ROW_AXIS) == owner_r1
        above1 = col_valid & (g_cols > k + 1)
        ondiag1 = col_valid & (g_cols == k + 1)
        if use_mxu:
            mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
            updr = mmfn(jnp.swapaxes(jnp.conj(pk1), -1, -2),
                        jnp.swapaxes(vc, -1, -2).reshape(
                            ncols * mb, mb).T,
                        slices=tb._oz_slices()).reshape(
                            mb, ncols, mb).transpose(1, 0, 2)
        else:
            updr = jnp.einsum("ba,cbd->cad", jnp.conj(pk1), vc,
                              preferred_element_type=vc.dtype)
        triu1 = jnp.triu(jnp.ones((mb, mb), dtype=bool))
        m3 = (above1[:, None, None] | (ondiag1[:, None, None] & triu1)) \
            & own_r1
        new_row = lt[kr1, lu_c:] - jnp.where(m3, updr,
                                             jnp.zeros_like(updr))
        lt = lt.at[kr1, lu_c:].set(new_row)
        return lt, (new_row, lu_c)

    def step_bulk(lt, k, ch, stripped):
        """Bulk trailing product of step k (reference impl.h:242-271);
        ``stripped`` excludes the eagerly-updated next column/row."""
        lkk, pan, vb, vt = ch
        if pan is None or vt is None:
            return lt
        rr = (cc.this_rank(ROW_AXIS) - sr) % Pr
        rc = (cc.this_rank(COL_AXIS) - sc) % Qc
        _, _, _, _, lu_r, lu_c = _indices(k)
        nrows, ncols = ltr - lu_r, ltc - lu_c
        g_rows = local_rows_global(lu_r, rr, nrows)
        g_cols = local_cols_global(lu_c, rc, ncols)
        row_valid = (g_rows > k) & (g_rows < nt)
        col_valid = (g_cols > k) & (g_cols < nt)
        pair = row_valid[:, None] & col_valid[None, :]

        if uplo == "L":
            # A[i,j] -= L[i,k] L[j,k]^H for trailing lower-triangle tiles:
            # strictly-lower tiles full update, diagonal tiles lower
            # triangle only (the matrix's upper triangle passes through
            # untouched, like the reference's herk vs gemm split)
            vr, vc = vb, vt
            below = pair & (g_rows[:, None] > g_cols[None, :])
            ondiag = pair & (g_rows[:, None] == g_cols[None, :])
            if stripped:
                # the bulk excludes column k+1 (already applied)
                notnext = g_cols != k + 1
                below = below & notnext[None, :]
                ondiag = ondiag & notnext[None, :]
            if use_pallas:
                # predicated Pallas kernel: masked-out tile pairs skip the
                # MXU work entirely (exact flops, not rectangle-then-mask)
                mode = below.astype(jnp.int32) + 2 * ondiag.astype(jnp.int32)
                new_block = masked_trailing_update(lt[lu_r:, lu_c:], vr, vc,
                                                   mode,
                                                   interpret=pallas_interpret)
                return lt.at[lu_r:, lu_c:].set(new_block)
            if use_mxu:
                # same contraction through int8 MXU passes: flatten the tile
                # batch into one (nrows*mb) x mb by (ncols*mb) x mb product
                mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
                full = mmfn(vr.reshape(nrows * mb, mb),
                            jnp.conj(vc).reshape(ncols * mb, mb).T,
                            slices=tb._oz_slices())
                upd = full.reshape(nrows, mb, ncols, mb).transpose(0, 2, 1, 3)
            else:
                upd = jnp.einsum("rab,cdb->rcad", vr, jnp.conj(vc),
                                 preferred_element_type=vr.dtype)
            tril_m = jnp.tril(jnp.ones((mb, mb), dtype=bool))
            mask4 = below[:, :, None, None] \
                | (ondiag[:, :, None, None] & tril_m)
            upd = jnp.where(mask4, upd, jnp.zeros_like(upd))
            return lt.at[lu_r:, lu_c:].add(-upd)

        # uplo='U': A[i,j] -= U[k,i]^H U[k,j], upper triangle
        vc, vr = vb, vt
        above = pair & (g_rows[:, None] < g_cols[None, :])
        ondiag = pair & (g_rows[:, None] == g_cols[None, :])
        if stripped:
            notnext = g_rows != k + 1
            above = above & notnext[:, None]
            ondiag = ondiag & notnext[:, None]
        if use_pallas:
            # transposed tiles keep the kernel's vr @ vc^T contraction;
            # mode 3 = within-tile upper triangle on diagonal tiles
            mode = above.astype(jnp.int32) + 3 * ondiag.astype(jnp.int32)
            new_block = masked_trailing_update(
                lt[lu_r:, lu_c:], jnp.swapaxes(vr, -1, -2),
                jnp.swapaxes(vc, -1, -2), mode, interpret=pallas_interpret)
            return lt.at[lu_r:, lu_c:].set(new_block)
        if use_mxu:
            mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
            ar = jnp.swapaxes(jnp.conj(vr), -1, -2).reshape(nrows * mb, mb)
            bc = jnp.swapaxes(vc, -1, -2).reshape(ncols * mb, mb)
            full = mmfn(ar, bc.T, slices=tb._oz_slices())
            upd = full.reshape(nrows, mb, ncols, mb).transpose(0, 2, 1, 3)
        else:
            upd = jnp.einsum("rba,cbd->rcad", jnp.conj(vr), vc,
                             preferred_element_type=vr.dtype)
        triu_m = jnp.triu(jnp.ones((mb, mb), dtype=bool))
        mask4 = above[:, :, None, None] | (ondiag[:, :, None, None] & triu_m)
        upd = jnp.where(mask4, upd, jnp.zeros_like(upd))
        return lt.at[lu_r:, lu_c:].add(-upd)

    def chain_comm_counts(k):
        """Collectives ``panel_chain(k)`` emits per mesh axis (trace-time
        statics mirroring the chain's early-exit structure): the fused
        diag bcast2d counts once on each axis; a full chain adds the
        panel broadcast on one axis and the transposed-panel all_gather
        on the other."""
        _, _, _, _, lu_r, lu_c = _indices(k)
        nrows, ncols = ltr - lu_r, ltc - lu_c
        row = col = 1
        if k < nt - 1:
            if uplo == "L" and nrows > 0:
                col += 1                      # panel bcast along 'col'
                if ncols > 0:
                    row += 1                  # transpose all_gather
            elif uplo == "U" and ncols > 0:
                row += 1
                if nrows > 0:
                    col += 1
        return row, col

    def factorize(lt):
        la = None
        ch_next = None
        for k in range(nt):
            # uniform per-step phase scopes (`cholesky.step<k>.<phase>`,
            # docs/observability.md critical-path attribution): the names
            # land on the compiled program's op metadata, so the critpath
            # joiner can put every device interval on its (step, phase).
            # Names carry no repeat index — identical across runs, so
            # histograms never fork. Counters are all trace-time.
            with obs.named_span(f"cholesky.step{k:03d}"):
                _count_step_modes(
                    "cholesky_dist",
                    *((1, 0) if lookahead and k + 1 < nt else (0, 1)))
                if comm_la:
                    # comm look-ahead (docs/comm_overlap.md): step k+1's
                    # panel chain — its bcast2d/bcast/all_gather included
                    # — is emitted between step k's strip and step k's
                    # bulk product, reading only the carried strip values.
                    # The hoisted chain is scoped as step k+1's PANEL even
                    # though it executes inside step k's window — that is
                    # the overlap the critpath report must see.
                    if ch_next is not None:
                        ch = ch_next
                    else:
                        with obs.named_span(f"cholesky.step{k:03d}.panel"):
                            ch = panel_chain(lt, k, la)
                    with obs.named_span(f"cholesky.step{k:03d}.strip"):
                        lt, la = step_pre(lt, k, ch)
                    ch_next = None
                    if k + 1 < nt and la is not None:
                        with obs.named_span(
                                f"cholesky.step{k + 1:03d}.panel"):
                            ch_next = panel_chain(None, k + 1, la)
                        n_row, n_col = chain_comm_counts(k + 1)
                        cc.record_overlapped("cholesky_dist", ROW_AXIS,
                                             n_row)
                        cc.record_overlapped("cholesky_dist", COL_AXIS,
                                             n_col)
                    with obs.named_span(f"cholesky.step{k:03d}.bulk"):
                        lt = step_bulk(lt, k, ch, la is not None)
                else:
                    with obs.named_span(f"cholesky.step{k:03d}.panel"):
                        ch = panel_chain(lt, k, la)
                    with obs.named_span(f"cholesky.step{k:03d}.strip"):
                        lt, la = step_pre(lt, k, ch)
                    with obs.named_span(f"cholesky.step{k:03d}.bulk"):
                        lt = step_bulk(lt, k, ch, la is not None)
        if with_info:
            return lt, _dist_factor_info(lt, dist)
        return lt

    return shard_map(factorize, mesh=mesh, in_specs=P(ROW_AXIS, COL_AXIS),
                     out_specs=(P(ROW_AXIS, COL_AXIS), P()) if with_info
                     else P(ROW_AXIS, COL_AXIS), check_vma=False)


def _dist_factor_info(lt, dist):
    """In-graph distributed info (called INSIDE the factorization's
    shard_map, after the last step): each rank scans the diagonals of the
    diagonal tiles it OWNS (health.info owner masks) and the per-rank
    bad-column vectors merge via an all-reduce max over both mesh axes —
    disjoint owner masks make max an OR. Pure extra outputs; the factor
    subgraph is untouched, and nothing here syncs with the host."""
    Pr, Qc = dist.grid_size.row, dist.grid_size.col
    sr, sc = dist.source_rank.row, dist.source_rank.col
    n = dist.size.row
    if n == 0:
        return jnp.zeros((), jnp.int32)
    rr = (cc.this_rank(ROW_AXIS) - sr) % Pr
    rc = (cc.this_rank(COL_AXIS) - sc) % Qc
    vec = hinfo.dist_diag_bad(lt, rr, rc, Pr=Pr, Qc=Qc,
                              nt=dist.nr_tiles.row,
                              mb=dist.block_size.row, n=n)
    vec = cc.all_reduce(vec, ROW_AXIS, "max")
    vec = cc.all_reduce(vec, COL_AXIS, "max")
    return hinfo.first_bad_info(vec > 0)


def _build_dist_cholesky_scan(dist, mesh, uplo, use_mxu=False,
                              use_mixed=False, cplx=False,
                              pallas_interpret=False,
                              lookahead=False, with_info=False,
                              panel_fused=False, step_fused=False):
    """``lax.scan`` form of the distributed factorization: ONE compiled
    step body looped ``nt`` times inside the ``shard_map``.

    Same motivation as :func:`_cholesky_local_scan` (the hardware
    toolchain's ~19 s/step unrolled-compile constant — docs/DESIGN.md —
    puts north-star tile counts at tens of minutes cold), same uniform-
    shape price: every step solves the panel over ALL local row slots and
    updates the ALL-pairs trailing grid under traced validity masks
    (~2x panel work, ~3x trailing flops vs the unrolled exact schedule).
    All per-``k`` index math — owner ranks, local slot of the pivot,
    global tile indices, edge-tile extents — is traced arithmetic on the
    scan counter; tile reads/writes at the pivot use dynamic slices.
    """
    nt = dist.nr_tiles.row
    mb = dist.block_size.row
    n = dist.size.row
    Pr, Qc = dist.grid_size.row, dist.grid_size.col
    _, _, ltr, ltc = storage_tile_grid(dist)

    def make_step(lu_r0, lu_c0, ltr_s, ltc_s):
        """Step body over the sliced local grid ``lt[lu_r0:, lu_c0:]`` — the
        telescoped segment's trailing view. For every k in the segment the
        pivot's local slot satisfies ``kr >= lu_r0`` (kr = k // P and the
        segment starts at ``k_start`` with ``lu_r0 = k_start // P``), so
        slot indices shift by the static offsets and validity masks do the
        rest."""

        def step(lt, k):
            # block-cyclic index math through DistContext (shared with
            # the scan solve in triangular.py — single owner)
            ctx = DistContext(dist)
            owner_r, owner_c = ctx.owner_r(k), ctx.owner_c(k)
            kr = ctx.kr(k) - lu_r0
            kc = ctx.kc(k) - lu_c0
            is_owner_r = ctx.rank_r == owner_r
            is_owner_c = ctx.rank_c == owner_c

            # -- diag tile -> everyone (one fused 2D collective) --------
            with obs.named_span("cholesky.comm"):
                cand = jax.lax.dynamic_slice(lt, (kr, kc, 0, 0),
                                             (1, 1, mb, mb))[0, 0]
                diag = cc.bcast2d(cand, owner_r, owner_c)
            ts = jnp.minimum(mb, n - k * mb)
            pad = jnp.arange(mb) >= ts   # short-edge mask
            diag = pad_diag_identity_dyn(diag, ts)
            # step_impl route, scan form: potrf deferred into the fused
            # factor+solve kernel at the panel-solve site (the diag
            # write-back then trails the column/row write)
            fuse_step = step_fused and not use_mixed
            ppan.count_step_kernel("fused" if fuse_step else "xla")
            if use_mixed:
                ppan.count_panel_kernel("xla", "potrf")
                other = "U" if uplo == "L" else "L"
                fac, lkk_inv = mx.potrf_inv_refined(uplo, diag)
                lkk = fac + tb.tri_mask(diag, other, k=-1)
            elif fuse_step:
                lkk_inv = lkk = None
            else:
                lkk_inv = None
                lkk = ppan.panel_potrf(uplo, diag, fused=panel_fused,
                                     interpret=pallas_interpret)

            def write_diag(lt, lkk, fallback=None):
                # un-pad: the written diagonal tile keeps stored edge
                # zeros. ``fallback`` is the non-owner tile value —
                # ``cand`` before the column/row write, the CURRENT tile
                # after it (the write-back may have put a solved panel
                # tile into the pivot slot on owner-column ranks that
                # are not the pivot-row owner)
                lkk_w = jnp.where(pad[:, None] | pad[None, :], cand, lkk)
                upd_tile = jnp.where(is_owner_r & is_owner_c, lkk_w,
                                     cand if fallback is None else fallback)
                return jax.lax.dynamic_update_slice(
                    lt, upd_tile[None, None], (kr, kc, 0, 0))

            def pivot_tile(lt):
                return jax.lax.dynamic_slice(
                    lt, (kr, kc, 0, 0), (1, 1, mb, mb))[0, 0]

            if lkk is not None:
                lt = write_diag(lt, lkk)

            g_rows = ctx.g_rows(lu_r0, ltr_s)
            g_cols = ctx.g_cols(lu_c0, ltc_s)
            row_valid = (g_rows > k) & (g_rows < nt)
            col_valid = (g_cols > k) & (g_cols < nt)

            if uplo == "L":
                # -- panel trsm over the segment's local row slots -------
                colk = jax.lax.dynamic_slice(
                    lt, (0, kc, 0, 0), (ltr_s, 1, mb, mb))[:, 0]
                if fuse_step:
                    lkk, pan = ppan.fused_factor_solve(
                        "L", diag, colk, interpret=pallas_interpret)
                else:
                    pan = ppan.panel_solve("R", "L", "C", "N", lkk, colk,
                                         fused=panel_fused,
                                         interpret=pallas_interpret,
                                         inv_a=lkk_inv)
                pan = jnp.where(row_valid[:, None, None], pan, 0)
                keep = (is_owner_c & row_valid)[:, None, None]
                lt = jax.lax.dynamic_update_slice(
                    lt, jnp.where(keep, pan, colk)[:, None], (0, kc, 0, 0))
                if fuse_step:
                    # colk predates the factor; fix the pivot tile now
                    lt = write_diag(lt, lkk, fallback=pivot_tile(lt))

                # -- panel broadcast + transposed panel ------------------
                with obs.named_span("cholesky.comm"):
                    vr = cc.bcast(pan, COL_AXIS, owner_c)
                    vc = transpose_col_to_rows(DistContext(dist), vr, lu_r0,
                                               g_cols)
                    vc = jnp.where(col_valid[:, None, None], vc, 0)

                # -- trailing update over the segment's pair grid --------
                pair = row_valid[:, None] & col_valid[None, :]
                below = pair & (g_rows[:, None] > g_cols[None, :])
                ondiag = pair & (g_rows[:, None] == g_cols[None, :])
                if use_mxu:
                    mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
                    full = mmfn(vr.reshape(ltr_s * mb, mb),
                                jnp.conj(vc).reshape(ltc_s * mb, mb).T,
                                slices=tb._oz_slices())
                    upd = full.reshape(ltr_s, mb, ltc_s,
                                       mb).transpose(0, 2, 1, 3)
                else:
                    upd = jnp.einsum("rab,cdb->rcad", vr, jnp.conj(vc),
                                     preferred_element_type=vr.dtype)
                tri_m = jnp.tril(jnp.ones((mb, mb), dtype=bool))
            else:
                # -- mirrored sweep: panel is block row kr ---------------
                rowk = jax.lax.dynamic_slice(
                    lt, (kr, 0, 0, 0), (1, ltc_s, mb, mb))[0]
                if fuse_step:
                    lkk, pan = ppan.fused_factor_solve(
                        "U", diag, rowk, interpret=pallas_interpret)
                else:
                    pan = ppan.panel_solve("L", "U", "C", "N", lkk, rowk,
                                         fused=panel_fused,
                                         interpret=pallas_interpret,
                                         inv_a=lkk_inv)
                pan = jnp.where(col_valid[:, None, None], pan, 0)
                keep = (is_owner_r & col_valid)[:, None, None]
                lt = jax.lax.dynamic_update_slice(
                    lt, jnp.where(keep, pan, rowk)[None], (kr, 0, 0, 0))
                if fuse_step:
                    lt = write_diag(lt, lkk, fallback=pivot_tile(lt))

                with obs.named_span("cholesky.comm"):
                    vcp = cc.bcast(pan, ROW_AXIS, owner_r)
                    vrp = transpose_row_to_cols(DistContext(dist), vcp,
                                                lu_c0, g_rows)
                    vrp = jnp.where(row_valid[:, None, None], vrp, 0)

                pair = row_valid[:, None] & col_valid[None, :]
                below = pair & (g_rows[:, None] < g_cols[None, :])
                ondiag = pair & (g_rows[:, None] == g_cols[None, :])
                if use_mxu:
                    mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
                    ar = jnp.swapaxes(jnp.conj(vrp),
                                      -1, -2).reshape(ltr_s * mb, mb)
                    bc2 = jnp.swapaxes(vcp, -1, -2).reshape(ltc_s * mb, mb)
                    full = mmfn(ar, bc2.T, slices=tb._oz_slices())
                    upd = full.reshape(ltr_s, mb, ltc_s,
                                       mb).transpose(0, 2, 1, 3)
                else:
                    upd = jnp.einsum("rba,cbd->rcad", jnp.conj(vrp), vcp,
                                     preferred_element_type=vrp.dtype)
                tri_m = jnp.triu(jnp.ones((mb, mb), dtype=bool))

            mask4 = below[:, :, None, None] \
                | (ondiag[:, :, None, None] & tri_m)
            lt = lt - jnp.where(mask4, upd, 0)
            return lt, None

        return step

    def _pair_upd(xr, xc):
        """All-pairs tile product over (row tiles, transposed-col tiles) on
        the configured trailing route — shared by the serial body's eager
        update and the pipelined body's deferred one."""
        ltr_s, ltc_s = xr.shape[0], xc.shape[0]
        if use_mxu:
            mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
            full = mmfn(xr.reshape(ltr_s * mb, mb),
                        jnp.conj(xc).reshape(ltc_s * mb, mb).T,
                        slices=tb._oz_slices())
            return full.reshape(ltr_s, mb, ltc_s, mb).transpose(0, 2, 1, 3)
        return jnp.einsum("rab,cdb->rcad", xr, jnp.conj(xc),
                          preferred_element_type=xr.dtype)

    def make_step_la(lu_r0, lu_c0, ltr_s, ltc_s):
        """Software-pipelined step body (``cholesky_lookahead=1``): carry
        ``(lt, prev_vr, prev_vc)`` — step k-1's masked panel broadcast +
        transposed panel — and apply its BULK trailing product inside body
        k, where it is independent of body k's latency-bound potrf/trsm
        chain (a sequential scan body can only overlap work within one
        iteration). The next panel column's tile strip is updated eagerly
        so body k+1's pivot column is current; per-cell application order
        matches the serial body (bulk k-1 before strip k), keeping
        results bitwise identical on the native routes."""

        def step(carry, k):
            lt, pvr, pvc = carry
            ctx = DistContext(dist)
            owner_r, owner_c = ctx.owner_r(k), ctx.owner_c(k)
            kr = ctx.kr(k) - lu_r0
            kc = ctx.kc(k) - lu_c0
            is_owner_r = ctx.rank_r == owner_r
            is_owner_c = ctx.rank_c == owner_c

            # -- diag tile -> everyone (one fused 2D collective; pivot
            # column is current: it took the k-1 strip eagerly and the
            # k-2 bulk in body k-1). Emitted — like this body's panel
            # bcast/all_gather below — BEFORE the deferred bulk of step
            # k-1, so the scan form's collectives overlap the bulk MXU
            # product by construction (docs/comm_overlap.md).
            with obs.named_span("cholesky.comm"):
                cand = jax.lax.dynamic_slice(lt, (kr, kc, 0, 0),
                                             (1, 1, mb, mb))[0, 0]
                diag = cc.bcast2d(cand, owner_r, owner_c)
            ts = jnp.minimum(mb, n - k * mb)
            pad = jnp.arange(mb) >= ts
            diag = pad_diag_identity_dyn(diag, ts)
            # step_impl route: potrf fused with the strip solve below
            fuse_step = step_fused and not use_mixed
            ppan.count_step_kernel("fused" if fuse_step else "xla")
            if use_mixed:
                ppan.count_panel_kernel("xla", "potrf")
                other = "U" if uplo == "L" else "L"
                fac, lkk_inv = mx.potrf_inv_refined(uplo, diag)
                lkk = fac + tb.tri_mask(diag, other, k=-1)
            elif fuse_step:
                lkk_inv = lkk = None
            else:
                lkk_inv = None
                lkk = ppan.panel_potrf(uplo, diag, fused=panel_fused,
                                     interpret=pallas_interpret)

            def write_diag(lt, lkk, fallback=None):
                lkk_w = jnp.where(pad[:, None] | pad[None, :], cand, lkk)
                upd_tile = jnp.where(is_owner_r & is_owner_c, lkk_w,
                                     cand if fallback is None else fallback)
                return jax.lax.dynamic_update_slice(
                    lt, upd_tile[None, None], (kr, kc, 0, 0))

            def pivot_tile(lt):
                return jax.lax.dynamic_slice(
                    lt, (kr, kc, 0, 0), (1, 1, mb, mb))[0, 0]

            if lkk is not None:
                lt = write_diag(lt, lkk)

            g_rows = ctx.g_rows(lu_r0, ltr_s)
            g_cols = ctx.g_cols(lu_c0, ltc_s)
            row_valid = (g_rows > k) & (g_rows < nt)
            col_valid = (g_cols > k) & (g_cols < nt)
            valid1 = k + 1 < nt

            if uplo == "L":
                colk = jax.lax.dynamic_slice(
                    lt, (0, kc, 0, 0), (ltr_s, 1, mb, mb))[:, 0]
                if fuse_step:
                    lkk, pan = ppan.fused_factor_solve(
                        "L", diag, colk, interpret=pallas_interpret)
                else:
                    pan = ppan.panel_solve("R", "L", "C", "N", lkk, colk,
                                         fused=panel_fused,
                                         interpret=pallas_interpret,
                                         inv_a=lkk_inv)
                pan = jnp.where(row_valid[:, None, None], pan, 0)
                keep = (is_owner_c & row_valid)[:, None, None]
                lt = jax.lax.dynamic_update_slice(
                    lt, jnp.where(keep, pan, colk)[:, None], (0, kc, 0, 0))
                if fuse_step:
                    lt = write_diag(lt, lkk, fallback=pivot_tile(lt))
                with obs.named_span("cholesky.comm"):
                    vr = cc.bcast(pan, COL_AXIS, owner_c)
                    vc = transpose_col_to_rows(DistContext(dist), vr, lu_r0,
                                               g_cols)
                    vc = jnp.where(col_valid[:, None, None], vc, 0)

                # -- deferred bulk of step k-1 (its column-k strip was
                # applied eagerly in body k-1, so exclude column k) ------
                rv_p = (g_rows > k - 1) & (g_rows < nt)
                cv_p = (g_cols > k - 1) & (g_cols < nt) & (g_cols != k)
                pairp = rv_p[:, None] & cv_p[None, :]
                belowp = pairp & (g_rows[:, None] > g_cols[None, :])
                ondiagp = pairp & (g_rows[:, None] == g_cols[None, :])
                updp = _pair_upd(pvr, pvc)
                tri_m = jnp.tril(jnp.ones((mb, mb), dtype=bool))
                mask4p = belowp[:, :, None, None] \
                    | (ondiagp[:, :, None, None] & tri_m)
                lt = lt - jnp.where(mask4p, updp, 0)

                # -- eager next-column strip from THIS panel -------------
                kc1 = ctx.kc(k + 1) - lu_c0
                own_c1 = ctx.rank_c == ctx.owner_c(k + 1)
                pk1 = jax.lax.dynamic_slice(vc, (kc1, 0, 0),
                                            (1, mb, mb))[0]
                below1 = (g_rows > k + 1) & (g_rows < nt)
                ondiag1 = g_rows == k + 1
                if use_mxu:
                    mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
                    updc = mmfn(vr.reshape(ltr_s * mb, mb),
                                jnp.conj(pk1).T,
                                slices=tb._oz_slices()).reshape(
                                    ltr_s, mb, mb)
                else:
                    updc = jnp.einsum("rab,db->rad", vr, jnp.conj(pk1),
                                      preferred_element_type=vr.dtype)
                m3 = (below1[:, None, None]
                      | (ondiag1[:, None, None] & tri_m)) \
                    & (own_c1 & valid1)
                colcur = jax.lax.dynamic_slice(
                    lt, (0, kc1, 0, 0), (ltr_s, 1, mb, mb))
                lt = jax.lax.dynamic_update_slice(
                    lt, colcur - jnp.where(m3, updc, 0)[:, None],
                    (0, kc1, 0, 0))
                return (lt, vr, vc), None

            # -- mirrored sweep (uplo='U') ------------------------------
            rowk = jax.lax.dynamic_slice(
                lt, (kr, 0, 0, 0), (1, ltc_s, mb, mb))[0]
            if fuse_step:
                lkk, pan = ppan.fused_factor_solve(
                    "U", diag, rowk, interpret=pallas_interpret)
            else:
                pan = ppan.panel_solve("L", "U", "C", "N", lkk, rowk,
                                     fused=panel_fused,
                                     interpret=pallas_interpret,
                                     inv_a=lkk_inv)
            pan = jnp.where(col_valid[:, None, None], pan, 0)
            keep = (is_owner_r & col_valid)[:, None, None]
            lt = jax.lax.dynamic_update_slice(
                lt, jnp.where(keep, pan, rowk)[None], (kr, 0, 0, 0))
            if fuse_step:
                lt = write_diag(lt, lkk, fallback=pivot_tile(lt))
            with obs.named_span("cholesky.comm"):
                vcp = cc.bcast(pan, ROW_AXIS, owner_r)
                vrp = transpose_row_to_cols(DistContext(dist), vcp, lu_c0,
                                            g_rows)
                vrp = jnp.where(row_valid[:, None, None], vrp, 0)

            # deferred bulk of step k-1 (row-k strip applied in body k-1)
            rv_p = (g_rows > k - 1) & (g_rows < nt) & (g_rows != k)
            cv_p = (g_cols > k - 1) & (g_cols < nt)
            pairp = rv_p[:, None] & cv_p[None, :]
            abovep = pairp & (g_rows[:, None] < g_cols[None, :])
            ondiagp = pairp & (g_rows[:, None] == g_cols[None, :])
            if use_mxu:
                mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
                ar = jnp.swapaxes(jnp.conj(pvr),
                                  -1, -2).reshape(ltr_s * mb, mb)
                bc2 = jnp.swapaxes(pvc, -1, -2).reshape(ltc_s * mb, mb)
                full = mmfn(ar, bc2.T, slices=tb._oz_slices())
                updp = full.reshape(ltr_s, mb, ltc_s,
                                    mb).transpose(0, 2, 1, 3)
            else:
                updp = jnp.einsum("rba,cbd->rcad", jnp.conj(pvr), pvc,
                                  preferred_element_type=pvc.dtype)
            tri_m = jnp.triu(jnp.ones((mb, mb), dtype=bool))
            mask4p = abovep[:, :, None, None] \
                | (ondiagp[:, :, None, None] & tri_m)
            lt = lt - jnp.where(mask4p, updp, 0)

            # eager next-row strip from THIS panel
            kr1 = ctx.kr(k + 1) - lu_r0
            own_r1 = ctx.rank_r == ctx.owner_r(k + 1)
            pk1 = jax.lax.dynamic_slice(vrp, (kr1, 0, 0), (1, mb, mb))[0]
            above1 = (g_cols > k + 1) & (g_cols < nt)
            ondiag1 = g_cols == k + 1
            if use_mxu:
                mmfn = oz.matmul_c128 if cplx else oz.matmul_f64
                updr = mmfn(jnp.swapaxes(jnp.conj(pk1), -1, -2),
                            jnp.swapaxes(vcp, -1, -2).reshape(
                                ltc_s * mb, mb).T,
                            slices=tb._oz_slices()).reshape(
                                mb, ltc_s, mb).transpose(1, 0, 2)
            else:
                updr = jnp.einsum("ba,cbd->cad", jnp.conj(pk1), vcp,
                                  preferred_element_type=vcp.dtype)
            m3 = (above1[:, None, None]
                  | (ondiag1[:, None, None] & tri_m)) \
                & (own_r1 & valid1)
            rowcur = jax.lax.dynamic_slice(
                lt, (kr1, 0, 0, 0), (1, ltc_s, mb, mb))
            lt = jax.lax.dynamic_update_slice(
                lt, rowcur - jnp.where(m3, updr, 0)[None],
                (kr1, 0, 0, 0))
            return (lt, vrp, vcp), None

        return step

    def factorize(lt):
        # telescoped segments (see _cholesky_local_scan): each segment
        # scans only the remaining trailing slice of the local grid, so
        # the uniform masked work tracks the live trailing block.
        # Adjacent segments whose slice offsets coincide (large grids:
        # the local grid can't shrink every halving) coalesce into one
        # scan — no duplicate identically-shaped step programs
        # (types.telescope_windows, shared by all telescoped builders).
        # Under lookahead the pending panel pair is carried ACROSS
        # segments (dropped slots hold rows/cols behind the window and
        # are zero by the panel masks); the final step's pending is
        # identically zero, so nothing is ever flushed.
        pvr = pvc = None
        for (lu_r0, lu_c0), k0_seg, seg_len in telescope_windows(
                nt, lambda k_start, _len: (uniform_slot_start(k_start, Pr),
                                           uniform_slot_start(k_start, Qc))):
            ltr_s, ltc_s = ltr - lu_r0, ltc - lu_c0
            sub = lt[lu_r0:, lu_c0:]
            if lookahead:
                _count_step_modes("cholesky_dist_scan", seg_len, 0)
                # the pipelined body emits its diag bcast2d + panel bcast
                # + transposed-panel all_gather ahead of the deferred
                # bulk product of step k-1 — per step: 2 collectives per
                # mesh axis run while the bulk MXU product is in flight
                cc.record_overlapped("cholesky_dist_scan", ROW_AXIS,
                                     2 * seg_len)
                cc.record_overlapped("cholesky_dist_scan", COL_AXIS,
                                     2 * seg_len)
                if pvr is None:
                    pvr = jnp.zeros((ltr_s, mb, mb), lt.dtype)
                    pvc = jnp.zeros((ltc_s, mb, mb), lt.dtype)
                else:
                    pvr, pvc = pvr[-ltr_s:], pvc[-ltc_s:]
                # scan bodies carry the index-free `cholesky.scanstep`
                # scope: ONE traced body serves every iteration, so
                # per-step critpath reconstruction uses occurrence order
                # (docs/observability.md, one-traced-body limitation)
                (sub, pvr, pvc), _ = jax.lax.scan(
                    obs.scoped_step(
                        "cholesky.scanstep",
                        make_step_la(lu_r0, lu_c0, ltr_s, ltc_s),
                        steps=seg_len),
                    (sub, pvr, pvc), jnp.arange(k0_seg, k0_seg + seg_len))
            else:
                _count_step_modes("cholesky_dist_scan", 0, seg_len)
                sub, _ = jax.lax.scan(
                    obs.scoped_step(
                        "cholesky.scanstep",
                        make_step(lu_r0, lu_c0, ltr_s, ltc_s),
                        steps=seg_len), sub,
                    jnp.arange(k0_seg, k0_seg + seg_len))
            lt = lt.at[lu_r0:, lu_c0:].set(sub)
        if with_info:
            return lt, _dist_factor_info(lt, dist)
        return lt

    return shard_map(factorize, mesh=mesh, in_specs=P(ROW_AXIS, COL_AXIS),
                     out_specs=(P(ROW_AXIS, COL_AXIS), P()) if with_info
                     else P(ROW_AXIS, COL_AXIS), check_vma=False)


@register_program_cache
@functools.lru_cache(maxsize=64)
def _dist_cholesky_cached(dist, mesh, dtype, uplo, use_pallas,
                          pallas_interpret, use_mxu, use_mixed,
                          scan=False, donate=False,
                          lookahead=False, comm_la=False, with_info=False,
                          panel_fused=False, step_fused=False):
    # dtype stays in the cache key: storage dtype changes retrace the jit
    # anyway, but distinct keys keep program caches per element type.
    donate_kw = donate_argnums_kw(donate, 0)
    if scan:
        # comm_la is not a scan cache key: the pipelined scan body already
        # emits its collectives ahead of the deferred bulk (callers
        # normalize it to False — see cholesky())
        return jax.jit(_build_dist_cholesky_scan(
            dist, mesh, uplo, use_mxu=use_mxu, use_mixed=use_mixed,
            cplx=dtype.startswith("complex"),
            pallas_interpret=pallas_interpret,
            lookahead=lookahead, with_info=with_info,
            panel_fused=panel_fused, step_fused=step_fused), **donate_kw)
    return jax.jit(_build_dist_cholesky(dist, mesh, uplo, use_pallas,
                                        pallas_interpret, use_mxu=use_mxu,
                                        use_mixed=use_mixed,
                                        cplx=dtype.startswith("complex"),
                                        lookahead=lookahead,
                                        comm_la=comm_la,
                                        with_info=with_info,
                                        panel_fused=panel_fused,
                                        step_fused=step_fused),
                   **donate_kw)




@register_program_cache
@functools.lru_cache(maxsize=64)
def _local_cholesky_cached(local, dist, donate, statics):
    """The local branch's ONE program: tile storage in, the factor's tile
    storage (and ``info``) out, the layout moves inside it
    (``matrix/tiling.py:on_global``), so the matrix crosses no program
    boundary between them. ``local`` is :func:`_cholesky_local` or
    :func:`_cholesky_local_scan`, which inlines here, ``statics`` its
    sorted static keyword arguments; ``donate`` is the caller's opt-in,
    the hand-offs inside are the compiler's."""
    kw = dict(statics)

    def cholesky_local(a):
        return local(a, **kw)

    return jax.jit(on_global(cholesky_local, dist),
                   **donate_argnums_kw(donate, 0))


# ---------------------------------------------------------------------------
# Public API (reference factorization/cholesky.h:36,62)
# ---------------------------------------------------------------------------

def local_step_form(steps: int) -> str:
    """``"scan"`` or ``"unrolled"``: the builder the local branch of
    :func:`cholesky` takes for a matrix of ``steps`` block steps under the
    active configuration. A ``cholesky_trailing`` that names a form keeps
    it ("scan" the scan builder, every other the unrolled one); "auto"
    asks the resolver of every other builder, ``config.resolve_step_mode``
    (``dist_step_mode``; auto: the scan form from
    ``STEP_MODE_AUTO_SCAN_AT`` steps on, 32 on a TPU). What a caller, or
    the benchmark's op file, can ask before it pays for a compile."""
    from ..config import get_configuration, resolve_step_mode

    trailing = get_configuration().cholesky_trailing
    if trailing != "auto":
        return "scan" if trailing == "scan" else "unrolled"
    return resolve_step_mode(steps)


def cholesky(uplo: str, mat: Matrix, *, donate: bool = False,
             with_info: bool = False):
    """Factorize the Hermitian positive-definite ``mat`` in the ``uplo``
    triangle: L L^H (uplo='L') or U^H U (uplo='U').

    Local (1x1 grid) or distributed over ``mat.grid``'s mesh, like the
    reference's two overloads. Returns a new Matrix whose ``uplo`` triangle
    holds the factor; the other triangle passes through.

    ``with_info=True`` returns ``(factor, info)`` instead — the reference's
    ``potrfInfo`` contract lifted to the blocked algorithm: ``info`` is an
    int32 DEVICE scalar, 0 on success or the 1-based first failing global
    column, computed in-graph inside the same compiled program (no host
    sync; fetching it — ``int(info)`` — is the caller's explicit decision,
    e.g. :func:`dlaf_tpu.health.robust_cholesky`'s recovery point). The
    factor is bitwise identical with the flag on or off: detection is a
    pure extra output on the final factor's diagonal (distributed: combined
    across ranks via max over the owner masks). Precision of the column
    locator follows the backend's NaN semantics — see
    ``tile_ops/lapack.py:potrf_info`` and docs/robustness.md.

    ``donate=True`` donates ``mat``'s device storage to the factorization
    (the reference's in-place semantics, ``factorization/cholesky.h:36``:
    its ``mat_a`` IS overwritten): ``mat`` must not be used afterwards.
    This removes one full-matrix HBM buffer from the peak live set — the
    difference between fitting and OOM near the single-chip ceiling
    (N=16384 asked ~14-16 GB of 15.75 with all step forms pre-donation).
    On one device the call is ONE program either way (layout transform ->
    factorization -> layout transform inside it); ``donate`` only decides
    whether that program may reuse ``mat``'s buffer.
    """
    dlaf_assert(uplo in ("L", "U"), f"cholesky: uplo must be 'L' or 'U', got {uplo!r}")
    from ..config import get_configuration, resolve_platform_auto

    cfg = get_configuration()
    trailing = resolve_platform_auto(
        cfg.cholesky_trailing, knob="cholesky_trailing",
        tpu_choice="ozaki", other_choice="loop",
        detail="the route of the chol_d_n4096_1x1 cell: call_s 0.0464 s "
               "(PERF_LEDGER.jsonl, PR 28); the other forms are not "
               "measured on the chip through benchmark/run.py")
    dlaf_assert(trailing in VALID_TRAILING,
                f"cholesky_trailing must be one of {VALID_TRAILING}, got {trailing!r}")
    dlaf_assert(mat.size.row == mat.size.col, "cholesky: matrix must be square")
    dlaf_assert(mat.block_size.row == mat.block_size.col,
                "cholesky: block must be square")
    dt = np.dtype(mat.dtype)
    n = mat.size.row
    grid_shape = (mat.dist.grid_size.row, mat.dist.grid_size.col)
    local = mat.grid is None or mat.grid.num_devices == 1
    # the local step form comes from the step count (local_step_form: on a
    # TPU the scan builder from 32 block steps on) unless cholesky_trailing
    # names a form itself. The products keep the platform's route: where
    # "auto" is the slice-product form ("ozaki"), the scan bodies run it too
    scan_form = local and local_step_form(mat.dist.nr_tiles.row) == "scan"
    oz_scan = scan_form and trailing == "ozaki" \
        and dt in (np.dtype(np.float64), np.dtype(np.complex128))
    if scan_form:
        trailing = "scan"
    # look-ahead step order (docs/lookahead.md): pipelined when the knob
    # resolves 1; the whole-matrix "xla" delegation has no step structure
    # to pipeline. comm_lookahead (docs/comm_overlap.md) extends the
    # carry across the collectives of the unrolled distributed builder —
    # it rides the SSA carry, so it requires lookahead too.
    from ..config import (resolved_cholesky_lookahead,
                          resolved_comm_lookahead)

    lookahead = resolved_cholesky_lookahead() and trailing != "xla"
    comm_la = lookahead and resolved_comm_lookahead()
    # fused Pallas panel route (panel_impl knob, docs/pallas_panel.md):
    # resolved ONCE per entry (single owner pallas_panel.panel_uses_fused
    # — dtype/block policy + injection gate + fallback accounting) and
    # threaded into every builder as a static/cache-key argument; the
    # whole-matrix "xla" trailing delegation has no panel chain to route
    panel_fused = trailing != "xla" and ppan.panel_uses_fused(
        dt, mat.block_size.row)
    # fused STEP route (step_impl knob, docs/pallas_panel.md "Fused step
    # kernel"): one pallas_call per blocked step — resolved once here
    # (single owner pallas_panel.step_uses_fused: dtype/block/VMEM
    # policy + injection gate + site="step" fallback accounting) and
    # threaded into every builder as a static/cache-key argument
    step_fused = trailing != "xla" and ppan.step_uses_fused(
        dt, mat.block_size.row)
    # entry span: host wall around trace+dispatch, unfenced (device
    # completion is the caller's fence — the miniapp span carries the
    # honest GFlop/s); attrs and the reference flop model build lazily
    entry_span = obs.entry_span("cholesky", lambda: dict(
        flops=total_ops(dt, n**3 / 6, n**3 / 6),
        n=n, nb=mat.block_size.row, uplo=uplo, dtype=dt.name,
        trailing=trailing, lookahead=int(lookahead),
        comm_lookahead=int(comm_la),
        panel_impl="fused" if panel_fused else "xla",
        step_impl="fused" if step_fused else "xla",
        grid=f"{grid_shape[0]}x{grid_shape[1]}"))
    # the scan formulations follow the f64_gemm/f64_trsm knobs (identical
    # resolution local and distributed, single owner in tile_ops.blas);
    # the unrolled local path selects its route via cholesky_trailing
    use_mxu = oz_scan or tb.f64_gemm_uses_mxu(dt, mat.block_size.row)
    use_mixed = oz_scan or tb.trsm_panel_uses_mixed(dt)
    if local:
        # off-TPU the fused panel kernels run in interpret mode (same
        # convention as the pallas trailing kernels)
        statics = dict(
            uplo=uplo, nb=mat.block_size.row, lookahead=lookahead,
            with_info=with_info, panel_fused=panel_fused,
            step_fused=step_fused,
            panel_interpret=(panel_fused or step_fused)
            and jax.default_backend() != "tpu")
        if trailing == "scan":
            site, local = "cholesky.local_scan", _cholesky_local_scan
            statics.update(use_mxu=use_mxu, use_mixed=use_mixed)
        else:
            site, local = "cholesky.local", _cholesky_local
            statics.update(trailing=trailing)
        fn = _local_cholesky_cached(local, mat.dist, donate,
                                    tuple(sorted(statics.items())))
        # ONE program a call, tile storage to tile storage; the host phase
        # (``stage.*``, unfenced: it brackets an async dispatch) labels the
        # device's idle gap before it on a profiler timeline; program
        # telemetry (DLAF_PROGRAM_TELEMETRY): compile wall / retraces / HBM
        # footprint per site, off = the same jitted callable
        # (docs/observability.md)
        with entry_span, quiet_donation():
            with obs.span("stage.cholesky.factor", fenced=False):
                if obs.metrics_active():
                    obs.counter("dlaf_entry_programs_total",
                                entry="cholesky").inc()
                out = obs.telemetry.call(site, fn, mat.storage)
            if with_info:
                return mat.with_storage(out[0]), out[1]
            return mat.with_storage(out)
    platform = next(iter(mat.grid.mesh.devices.flat)).platform
    scan_mode = trailing == "scan"
    fn = _dist_cholesky_cached(mat.dist, mat.grid.mesh, dt.name, uplo,
                               # the f32/bf16 pallas trailing kernel is
                               # unrolled-only; normalize it out of scan
                               # cache keys
                               (not scan_mode)
                               and supports_pallas_update(mat.dtype, platform)
                               and not use_mxu,
                               platform != "tpu",
                               use_mxu, use_mixed,
                               scan=scan_mode, donate=donate,
                               lookahead=lookahead,
                               # scan bodies overlap by construction; the
                               # hoist (and cache key) is unrolled-only
                               comm_la=comm_la and not scan_mode,
                               with_info=with_info,
                               panel_fused=panel_fused,
                               step_fused=step_fused)
    with entry_span, quiet_donation():
        # ONE program a call on every device of the grid; the host phase
        # (unfenced: the wall of the enqueue, completion is the caller's
        # fence) names the device's idle gap before it, and the program is
        # counted as the entry's, as on the local branch
        with obs.span("stage.cholesky.dispatch", fenced=False):
            if obs.metrics_active():
                obs.counter("dlaf_entry_programs_total",
                            entry="cholesky").inc()
            out = obs.telemetry.call("cholesky.dist", fn, mat.storage)
        if with_info:
            return mat.with_storage(out[0]), out[1]
        return mat.with_storage(out)
