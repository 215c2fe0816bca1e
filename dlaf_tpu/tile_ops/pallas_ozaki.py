"""Fused Pallas kernel for the Ozaki slice products (opt-in).

The jnp path of :mod:`.ozaki` materializes every per-shift int32 group
(``s`` arrays of the full output shape) before the f64 combine — for a
3840x3840 trailing update that is ~0.5 GB of intermediate HBM traffic per
product. This kernel keeps the whole reduction in VMEM: for each output
tile it runs all ``s(s+1)/2`` int8 MXU dots, accumulates each shift group
exactly in int32, and folds the groups into a double-f32 accumulator
(Knuth two-sum), writing ONE (hi, lo) pair to HBM.

Accuracy: the int8 dots and int32 group sums are exact (same argument as
ozaki.py); the double-f32 fold carries ~48 mantissa bits vs the jnp path's
full f64 combine (~53) — a few bits under native f64, far inside the
``60 n eps`` algorithm budgets, and documented at the knob
(``Configuration.ozaki_impl``, default "jnp" = full accuracy).

VMEM budget: ``s*(BM + BN)*K`` int8 + ``BM*BN`` int32 + 2 f32 — with the
default 256-blocks and s=8 that is 4 MiB of slices + ~0.75 MiB accumulators
at K=1024 (~4.75 MiB total); the wrapper falls back to the jnp path beyond
``K_MAX``.

:func:`fused_slice_syrk` is the symmetric variant: a square tile grid
whose strictly-upper cells are predicated off (``pl.when`` on the program
ids) so only lower-triangle output tiles run their MXU dots — halving the
MXU work of the general kernel for the Cholesky trailing update; the
caller mirrors the strict lower triangle. (An earlier triangular-grid
form drove the block index maps through scalar-prefetched (i, j) lookup
tables; Mosaic could not legalize SMEM loads inside index-map
functions when compiling for the v5e — observed 2026-07-31 — so the
predicated square grid, whose index maps are pure program-id arithmetic,
is the portable design. Dead cells still pay their block fetch, not
their dots.)

Status: all three kernels compile for the v5e
(tests/test_chip_compile.py) and are validated in interpret mode (CPU CI);
they have not run on a chip, and ``ozaki_impl`` defaults to "jnp" —
this is the designated next perf lever for the trailing update (the int8
dots run at ~4.5 TF/s standalone while the jnp ozaki syrk lands at ~650
GF/s effective; the gap is intermediate traffic this kernel removes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ozaki import SLICE_BITS
from .pallas_kernels import I0

#: Largest contraction depth the fused kernel accepts (VMEM bound).
K_MAX = 1024


def _two_sum(a, b):
    """Knuth two-sum: s + err == a + b exactly (f32)."""
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def _fold_body(s: int, ia_ref, ib_ref, hi_ref, lo_ref, rhs_contract: int,
               dot: str = "int8"):
    """Shared numerical body: per-shift int32 group accumulation, exact
    int32 -> double-f32 split (|p| <= s*k*2^12 < 2^27, so the residual
    after the f32 round fits f32 exactly), and the two-sum fold.
    ``rhs_contract`` picks the rhs contraction axis (0: (K, BN) blocks;
    1: (BN, K) blocks as in the syrk form, contracting K against K).
    ``dot``: "int8" (s8 MXU dot) or "bf16" — cast the slices in VMEM and
    contract on the native bf16 path with f32 accumulation, exact for
    the K <= K_MAX <= 2^12 depths this kernel accepts (same bound
    argument as ozaki._dot_bf16); bit-identical outputs."""
    bm = hi_ref.shape[0]
    bn = hi_ref.shape[1]
    hi = jnp.zeros((bm, bn), jnp.float32)
    lo = jnp.zeros((bm, bn), jnp.float32)
    for d in range(s):
        p = jnp.zeros((bm, bn), jnp.int32)
        for t in range(d + 1):
            if dot == "bf16":
                g = jax.lax.dot_general(
                    ia_ref[t].astype(jnp.bfloat16),
                    ib_ref[d - t].astype(jnp.bfloat16),
                    dimension_numbers=(((1,), (rhs_contract,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(jnp.int32)
            else:
                g = jax.lax.dot_general(
                    ia_ref[t], ib_ref[d - t],
                    dimension_numbers=(((1,), (rhs_contract,)), ((), ())),
                    preferred_element_type=jnp.int32)
            p = p + g
        phi = p.astype(jnp.float32)
        plo = (p - phi.astype(jnp.int32)).astype(jnp.float32)
        scale = float(2.0 ** (-SLICE_BITS * (d + 2)))  # exact pow2 mult
        hi, err = _two_sum(hi, phi * scale)
        lo = lo + (err + plo * scale)
    hi_ref[:] = hi
    lo_ref[:] = lo


def _make_kernel(s: int, dot: str):
    def kernel(ia_ref, ib_ref, hi_ref, lo_ref):
        _fold_body(s, ia_ref, ib_ref, hi_ref, lo_ref, rhs_contract=0,
                   dot=dot)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "interpret", "dot"))
def fused_slice_product(ia, ib, *, block_m: int = 256, block_n: int = 256,
                        interpret: bool = False, dot: str = "int8"):
    """All-shift Ozaki reduction of stacked int8 slices, fused per tile.

    ``ia``: (s, M, K) int8 slices of the normalized A; ``ib``: (s, K, N) of
    B. Returns ``(hi, lo)`` float32 arrays with
    ``hi + lo ~= sum_{t+u=d<s} 2^(-q(d+2)) IA_t @ IB_u``
    (the caller applies ``*4*sa*sb`` in f64, as :func:`ozaki._apply_scales`).
    M/N are padded to block multiples internally.
    """
    s, m, k = ia.shape
    n = ib.shape[-1]
    assert k <= K_MAX, f"fused kernel contraction depth {k} > {K_MAX}"
    pm = (-m) % block_m
    pn = (-n) % block_n
    if pm:
        ia = jnp.pad(ia, ((0, 0), (0, pm), (0, 0)))
    if pn:
        ib = jnp.pad(ib, ((0, 0), (0, 0), (0, pn)))
    mp, np_ = m + pm, n + pn
    grid = (mp // block_m, np_ // block_n)
    hi, lo = pl.pallas_call(
        _make_kernel(s, dot),
        out_shape=(jax.ShapeDtypeStruct((mp, np_), jnp.float32),
                   jax.ShapeDtypeStruct((mp, np_), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((s, block_m, k), lambda i, j: (I0, i, I0)),
            pl.BlockSpec((s, k, block_n), lambda i, j: (I0, I0, j)),
        ],
        out_specs=(pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
                   pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))),
        interpret=interpret,
    )(ia, ib)
    return hi[:m, :n], lo[:m, :n]


#: Largest tile edge the predicated per-tile-pair kernel accepts: its
#: per-cell VMEM is ~2*s*mb^2 int8 slice blocks + int32/f32 accumulators
#: + two mb^2 f32 outputs — ~1.8 MiB at mb=256 (safe with pipelining),
#: ~14 MiB at mb=512 (over budget with double buffering). Distinct from
#: K_MAX, which budgets the fixed-256-block matmul/syrk kernels' depth.
MASKED_MB_MAX = 256


def _make_masked_kernel(s: int, dot: str):
    def kernel(mode_ref, ia_ref, ib_ref, hi_ref, lo_ref):
        # whole (R, C) mode table in SMEM, indexed by the grid step in the
        # kernel BODY: TPU lowering rejects sub-(8, 128) SMEM blocks (the
        # earlier (1, 1)-block form — r4 session finding), and loads
        # inside the INDEX MAP failed Mosaic AOT legalization (r2 session
        # finding). A program_id-indexed body load is the form the Pallas
        # docs sanction for per-cell predication, and it compiles for the
        # v5e (tests/test_chip_compile.py)
        mode = mode_ref[pl.program_id(0), pl.program_id(1)]

        @pl.when(mode == 0)
        def _():
            hi_ref[...] = jnp.zeros_like(hi_ref)
            lo_ref[...] = jnp.zeros_like(lo_ref)

        @pl.when(mode > 0)
        def _():
            # both operands are row blocks contracting k against k — the
            # syrk rhs layout, so the shared fold applies unchanged
            _fold_body(s, ia_ref, ib_ref, hi_ref, lo_ref, rhs_contract=1,
                       dot=dot)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret", "dot"))
def masked_slice_product(ia, ib, mode, *, interpret: bool = False,
                         dot: str = "int8"):
    """Per-tile-pair Ozaki slice reduction, PREDICATED on ``mode``: pairs
    with ``mode[r, c] == 0`` skip the MXU work entirely (outputs zero).

    The exact-flop form of the distributed Cholesky trailing update
    (reference hot loop ``factorization/cholesky/impl.h:242-271``): only
    trailing lower-triangle tile pairs run their ``s(s+1)/2`` int8 dots,
    instead of computing the full rectangle and masking (~2x the flops).

    ``ia``: (s, R, bm, k) int8 slices of the row-side tiles; ``ib``:
    (s, C, bn, k) of the column-side tiles (both contract their LAST axis);
    ``mode``: (R, C) int32. Returns ``(hi, lo)`` float32 (R, C, bm, bn)
    with ``hi + lo ~= sum_d 2^(-q(d+2)) IA_t @ IB_u^T``; the caller applies
    ``*4*sa*sb`` in f64 and its element masks, as :func:`ozaki._apply_scales`.
    """
    s, R, bm, k = ia.shape
    C, bn = ib.shape[1], ib.shape[2]
    assert max(bm, bn, k) <= MASKED_MB_MAX, \
        f"masked kernel tile edge {max(bm, bn, k)} > {MASKED_MB_MAX}"
    # None block dims squeeze the R/C axes away, so the kernel sees the
    # same (s, b, k)/(b, b) refs as the matmul/syrk kernels and shares
    # their _fold_body
    hi, lo = pl.pallas_call(
        _make_masked_kernel(s, dot),
        grid=(R, C),
        in_specs=[
            pl.BlockSpec((R, C), lambda r, c: (I0, I0),
                         memory_space=pltpu.SMEM),                   # mode
            pl.BlockSpec((s, None, bm, k), lambda r, c: (I0, r, I0, I0)),
            pl.BlockSpec((s, None, bn, k), lambda r, c: (I0, c, I0, I0)),
        ],
        out_specs=(
            pl.BlockSpec((None, None, bm, bn), lambda r, c: (r, c, I0, I0)),
            pl.BlockSpec((None, None, bm, bn), lambda r, c: (r, c, I0, I0))),
        out_shape=(jax.ShapeDtypeStruct((R, C, bm, bn), jnp.float32),
                   jax.ShapeDtypeStruct((R, C, bm, bn), jnp.float32)),
        interpret=interpret,
    )(mode, ia, ib)
    return hi, lo


def _make_syrk_kernel(s: int, dot: str):
    def kernel(ia_ref, ja_ref, hi_ref, lo_ref):
        r = pl.program_id(0)
        c = pl.program_id(1)

        @pl.when(c > r)
        def _():
            # strictly-upper tile: mirrored by the caller, never computed
            hi_ref[...] = jnp.zeros_like(hi_ref)
            lo_ref[...] = jnp.zeros_like(lo_ref)

        @pl.when(c <= r)
        def _():
            # rhs blocks are (BN, K) row blocks of the SAME operand:
            # contract the K axes directly (no transposed copy)
            _fold_body(s, ia_ref, ja_ref, hi_ref, lo_ref, rhs_contract=1,
                       dot=dot)

    return kernel


@functools.partial(jax.jit, static_argnames=("block", "interpret", "dot"))
def fused_slice_syrk(ia, *, block: int = 256, interpret: bool = False,
                     dot: str = "int8"):
    """Symmetric fused reduction: lower-triangle tiles of the gram product
    of the stacked slices ``ia`` (s, M, K) with themselves.

    Returns ``(hi, lo)`` float32 (M, M) pairs whose LOWER triangle (block
    diagonal included, full blocks) is valid; tiles strictly above the
    block diagonal skip their MXU dots (``pl.when`` predication on the
    program ids) — the caller mirrors: ``C = tril(H) + tril(H, -1).T``.
    Halves the MXU work of :func:`fused_slice_product` for syrk-shaped
    uses; see the module docstring for why the grid is a predicated
    square rather than a scalar-prefetched triangle.
    """
    s, m, k = ia.shape
    assert k <= K_MAX, f"fused kernel contraction depth {k} > {K_MAX}"
    pm = (-m) % block
    if pm:
        ia = jnp.pad(ia, ((0, 0), (0, pm), (0, 0)))
    mp = m + pm
    nt = mp // block
    hi, lo = pl.pallas_call(
        _make_syrk_kernel(s, dot),
        out_shape=(jax.ShapeDtypeStruct((mp, mp), jnp.float32),
                   jax.ShapeDtypeStruct((mp, mp), jnp.float32)),
        grid=(nt, nt),
        in_specs=[
            pl.BlockSpec((s, block, k), lambda i, j: (I0, i, I0)),
            pl.BlockSpec((s, block, k), lambda i, j: (I0, j, I0)),
        ],
        out_specs=(pl.BlockSpec((block, block), lambda i, j: (i, j)),
                   pl.BlockSpec((block, block), lambda i, j: (i, j))),
        interpret=interpret,
    )(ia, ia)
    return hi[:m, :m], lo[:m, :m]
