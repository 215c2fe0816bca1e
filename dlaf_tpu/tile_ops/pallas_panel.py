"""Fused Pallas panel factorization — the ``tpu_lapack`` panel shim.

The blocked factorizations' critical path is the per-step PANEL chain:
potrf on the diagonal tile, then the panel TRSM against it. On the XLA
route both lower to chains of small latency-bound thunks (XLA's generic
blocked Cholesky emits a while loop of tiny solves; the panel trsm is a
separate TriangularSolve op), so every step pays dispatch latency that no
amount of MXU throughput can hide — the 1.9-7.3% MFU signature of the
2026-08 v5e record, where neither the compute nor the HBM roofline binds. The
reference dispatches exactly this path to hand-tuned ``cusolver`` tile
kernels; this module is the TPU analog (BASELINE north star "tpu_lapack
shim"): Pallas kernels that factor/solve the whole panel without leaving
VMEM, one ``pallas_call`` per panel step instead of one XLA op (or op
chain) per tile.

Kernels
-------

:func:`fused_potrf`
    Right-looking Cholesky of ONE nb x nb tile, entirely in VMEM: the
    kernel body is statically unrolled over a micro-block ladder (width
    :data:`MICRO`) — within a micro-block, ``rsqrt``-scaled column
    updates (VPU rank-1s on the narrow micro-panel); between
    micro-blocks, ONE MXU ``dot_general`` applies the rank-``MICRO``
    trailing update. Exact right-looking flops, no HBM round trips
    between columns. Failure semantics match ``tile_ops.lapack
    .potrf_info``'s contract: a non-positive pivot turns into
    ``rsqrt(d) = NaN/inf`` which propagates into every later column, so
    the factor's diagonal is non-finite from the first failing column on
    (the info scan reads exactly that prefix).

:func:`fused_panel_solve`
    The panel TRSM applied to the stacked strip of below-diagonal tiles
    with the factored diagonal held in VMEM: the kernel grids over the
    strip's tile axis; grid step 0 builds the triangular inverse of the
    diagonal factor into VMEM scratch (micro-blocked substitution,
    statically unrolled), and every step then applies it as ONE MXU gemm
    — the TPU grid is sequential, so the scratch inverse persists across
    steps and is derived once per ``pallas_call``, not once per tile.

Numerics contract: the fused route is NOT bitwise-equal to the XLA route
(different factorization order within the tile; explicit-inverse solve
application) — parity is pinned at documented ulp-level bounds instead
(tests/test_pallas_panel.py, docs/pallas_panel.md). WITHIN the fused
route all the bitwise knob contracts hold unchanged (``cholesky_lookahead``
/ ``comm_lookahead`` on/off, ``with_info`` on/off): the kernels are pure
deterministic functions and those knobs only reorder emission.

Supported dtypes: float32 / bfloat16 (MXU-native; compute in f32, cast
back). float64/complex stay on the XLA (or mixed) route — on TPU their
panel latency problem is already attacked by ``tile_ops.mixed``'s
f32-seed-plus-Newton path, whose *seed* is exactly the shape this kernel
accelerates next.

Status: compiles for the v5e (tests/test_chip_compile.py keeps every
kernel here compiling at nb=128/256, f32/bf16, "L"/"U") and validated in
interpret mode on the CPU; chip_smoke.py runs the f32 route on the chip.

Routing (``panel_impl`` knob — "fused" / "xla" / "auto"): single owner
:func:`panel_uses_fused`; the builders call :func:`panel_potrf` /
:func:`panel_solve`, which also maintain the trace-time
``dlaf_panel_kernel_total{impl,op}`` counters. ``auto`` = fused on TPU
for f32/bf16 inputs, xla elsewhere. An EXPLICIT ``panel_impl="fused"``
with an unsupported dtype registers through
``health.registry.report_fallback(site="panel")`` (counted, strict-mode
raise); ``health.inject.disable_pallas`` covers the route like every
pallas kernel.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from .pallas_kernels import I0

#: Micro-block width of the potrf ladder and the in-kernel triangular
#: inverse: 8 = the f32 sublane, so every micro-panel/row op is at least
#: one full VPU sublane wide.
MICRO = 8

#: Largest diagonal-tile edge the fused panel route accepts (route
#: policy): the potrf ladder and the
#: solve's scratch inverse hold O(nb^2) f32 working values in VMEM —
#: ~0.75 MiB at nb=256 plus the strip tile being solved; 512 would put
#: the solve step's live set past comfortable double-buffering.
PANEL_MB_MAX = 256

_SUPPORTED = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def _pad_size(m: int, interpret: bool) -> int:
    """Padded square edge: micro-block multiple always; full (8, 128)
    lane alignment when headed for the Mosaic compiler (interpret mode
    keeps the pad minimal so tiny-tile tests stay cheap)."""
    s = -(-m // MICRO) * MICRO
    if not interpret:
        s = -(-s // 128) * 128
    return s


def _identity_pad(a, s: int):
    """Embed the (m, m) block top-left in an (s, s) identity-padded
    block: ``chol(blkdiag(A, I)) = blkdiag(chol(A), I)`` and a
    triangular ``blkdiag(T, I)`` inverts blockwise, so the pad region
    never contaminates the sliced-back result."""
    m = a.shape[-1]
    if s == m:
        return a
    pad = jnp.arange(s) >= m
    out = jnp.zeros((s, s), a.dtype).at[:m, :m].set(a)
    return out + jnp.diag(pad.astype(a.dtype))


# ---------------------------------------------------------------------------
# fused_potrf
# ---------------------------------------------------------------------------

def _iota(shape, dim: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _potrf_rows(x_ref, s: int) -> None:
    """In-place right-looking Cholesky of the SYMMETRIC (s, s) f32 block
    in ``x_ref``, statically unrolled over MICRO-row panels; on exit the
    ref holds the UPPER factor ``U`` (``U^H U = A``, zeros below the
    diagonal). ``rsqrt``-scaled rows: a non-positive pivot yields
    NaN/inf that propagates to every later row — the ``potrf_info``
    failure contract.

    Written for the Mosaic TPU lowering: every load/store is a full-lane
    sublane-aligned (MICRO, s) row panel of the ref, and single rows,
    columns and pivots are taken out of a panel by masked reductions
    (keepdims), never by unaligned slices, scalar extraction or
    ``dynamic_update_slice`` (which the TPU lowering does not have)."""
    lane = _iota((MICRO, s), 1)
    sub = _iota((MICRO, s), 0)
    sub1 = _iota((MICRO, 1), 0)
    for j0 in range(0, s, MICRO):
        p = jnp.where(lane >= j0, x_ref[j0:j0 + MICRO, :], 0.0)
        for jj in range(MICRO):
            j = j0 + jj
            # column j of the panel (== row j by symmetry of the block's
            # Schur complement), its pivot, and row jj — as (8, 1),
            # (1, 1) and (1, s) keepdims reductions
            colj = jnp.sum(jnp.where(lane == j, p, 0.0), axis=1,
                           keepdims=True)
            d = jnp.sum(jnp.where(sub1 == jj, colj, 0.0), axis=0,
                        keepdims=True)
            r = jax.lax.rsqrt(d)
            rowj = jnp.sum(jnp.where(sub == jj, p, 0.0), axis=0,
                           keepdims=True)
            u = jnp.where(lane[:1] >= j, rowj * r, 0.0)
            # rank-1 update of the panel's LATER rows only
            p = p - jnp.where(sub1 > jj, colj * r, 0.0) * u
            p = jnp.where(sub == jj, u, p)
        x_ref[j0:j0 + MICRO, :] = p
        j1 = j0 + MICRO
        if j1 < s:
            # ONE rank-MICRO MXU update of the trailing block; the lane
            # mask keeps the finished rows (< j1) untouched
            pm = jnp.where(lane >= j1, p, 0.0)
            x_ref[...] -= jax.lax.dot_general(
                pm, pm, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def _make_potrf_kernel(uplo: str, s: int):
    def kernel(a_ref, out_ref, x_ref):
        a = a_ref[...].astype(jnp.float32)
        rows = _iota((s, s), 0)
        cols = _iota((s, s), 1)
        # symmetrize from the stored triangle, factor to U in scratch
        stored = rows >= cols if uplo == "L" else rows <= cols
        x_ref[...] = (jnp.where(stored, a, 0.0)
                      + jnp.where(stored & (rows != cols), a, 0.0).T)
        _potrf_rows(x_ref, s)
        # factor in the stored triangle, the other passes through
        f = x_ref[...].T if uplo == "L" else x_ref[...]
        out_ref[...] = jnp.where(stored, f, a).astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("uplo", "interpret"))
def _fused_potrf(a, *, uplo: str, interpret: bool = False):
    m = a.shape[-1]
    s = _pad_size(m, interpret)
    ap = _identity_pad(a, s)
    out = pl.pallas_call(
        _make_potrf_kernel(uplo, s),
        out_shape=jax.ShapeDtypeStruct((s, s), a.dtype),
        scratch_shapes=[pltpu.VMEM((s, s), jnp.float32)],
        interpret=interpret,
    )(ap)
    return out[:m, :m]


def fused_potrf(uplo: str, a, *, interpret: bool = False):
    """Cholesky factor of one SPD block stored in ``uplo``, as ONE fused
    Pallas kernel (micro-blocked right-looking ladder in VMEM). Same
    LAPACK storage semantics as ``tile_ops.lapack.potrf``: the factor
    lands in the ``uplo`` triangle, the opposite triangle of ``a``
    passes through. f32/bf16 only (computed in f32)."""
    assert a.ndim == 2 and a.shape[-1] == a.shape[-2], a.shape
    assert jnp.dtype(a.dtype) in _SUPPORTED, a.dtype
    fn = _fused_potrf
    if not _tracing(a):
        return obs.telemetry.call("pallas_panel.potrf", fn, a, uplo=uplo,
                                  interpret=interpret)
    return fn(a, uplo=uplo, interpret=interpret)


# ---------------------------------------------------------------------------
# fused_panel_solve
# ---------------------------------------------------------------------------

def _tri_inv_rows(t_ref, inv_ref, s: int) -> None:
    """``inv_ref <- inv(T)`` for the lower-triangular (s, s) f32 block in
    ``t_ref``, one MICRO-row panel at a time (statically unrolled): the
    panel's right-hand side ``E - T[panel, :j0] X[:j0]`` is ONE MXU gemm
    against the already-inverted prefix, then the panel's MICRO x MICRO
    diagonal block is forward-substituted on the VPU. Same Mosaic
    discipline as :func:`_potrf_rows` (aligned row-panel loads/stores,
    masked keepdims reductions)."""
    lane = _iota((MICRO, s), 1)
    sub = _iota((MICRO, s), 0)
    sub1 = _iota((MICRO, 1), 0)
    inv_ref[...] = jnp.zeros((s, s), jnp.float32)
    for j0 in range(0, s, MICRO):
        tp = t_ref[j0:j0 + MICRO, :]
        b = (lane == sub + j0).astype(jnp.float32)
        if j0:
            b = b - jax.lax.dot_general(
                jnp.where(lane < j0, tp, 0.0), inv_ref[...],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        for k in range(MICRO):
            dcol = jnp.sum(jnp.where(lane == j0 + k, tp, 0.0), axis=1,
                           keepdims=True)
            dkk = jnp.sum(jnp.where(sub1 == k, dcol, 0.0), axis=0,
                          keepdims=True)
            yk = jnp.sum(jnp.where(sub == k, b, 0.0), axis=0,
                         keepdims=True) / dkk
            b = b - jnp.where(sub1 > k, dcol, 0.0) * yk
            b = jnp.where(sub == k, yk, b)
        inv_ref[j0:j0 + MICRO, :] = b


def _make_solve_kernel(uplo: str, op: str, diag: str, s: int):
    """Right-side canonical solve kernel: each grid step computes
    ``out = b_block @ op(inv(T))`` with ``T`` the stored (identity-
    padded) triangle. The scratch inverse is built ONCE at grid step 0
    (the TPU grid is sequential, so it persists across steps)."""

    def kernel(a_ref, b_ref, out_ref, inv_ref, t_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            t = a_ref[...].astype(jnp.float32)
            rows = _iota((s, s), 0)
            cols = _iota((s, s), 1)
            tri = rows >= cols if uplo == "L" else rows <= cols
            t = jnp.where(tri, t, 0.0)
            if diag == "U":
                t = jnp.where(rows == cols, 1.0, t)
            t_ref[...] = t if uplo == "L" else t.T
            _tri_inv_rows(t_ref, inv_ref, s)
            if uplo == "U":
                inv_ref[...] = inv_ref[...].T

        b = b_ref[...].astype(jnp.float32)
        inv = inv_ref[...]
        # contract b's columns against op(inv): "N" uses inv's rows,
        # "T"/"C" (real dtypes only) its columns
        rhs_dim = 0 if op == "N" else 1
        out = jax.lax.dot_general(
            b, inv, dimension_numbers=(((1,), (rhs_dim,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[...] = out.astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("uplo", "op", "diag",
                                             "interpret"))
def _fused_solve_rows(a, b, *, uplo: str, op: str, diag: str,
                      interpret: bool = False):
    """Canonical right-side solve ``X op(T) = B`` over the rows of the
    2D ``b`` (free axis first): rows are independent, so the kernel
    grids over row blocks of the padded triangle's edge."""
    na = a.shape[-1]
    f = b.shape[0]
    s = _pad_size(na, interpret)
    ap = _identity_pad(a, s)
    rb = s
    fp = -(-max(f, 1) // rb) * rb
    bp = jnp.zeros((fp, s), b.dtype).at[:f, :na].set(b)
    out = pl.pallas_call(
        _make_solve_kernel(uplo, op, diag, s),
        grid=(fp // rb,),
        in_specs=[
            pl.BlockSpec((s, s), lambda i: (I0, I0)),
            pl.BlockSpec((rb, s), lambda i: (i, I0)),
        ],
        out_specs=pl.BlockSpec((rb, s), lambda i: (i, I0)),
        out_shape=jax.ShapeDtypeStruct((fp, s), b.dtype),
        scratch_shapes=[pltpu.VMEM((s, s), jnp.float32),
                        pltpu.VMEM((s, s), jnp.float32)],
        interpret=interpret,
    )(ap, bp)
    return out[:f, :na]


def fused_panel_solve(side: str, uplo: str, op: str, diag: str, a, b, *,
                      alpha=1.0, interpret: bool = False):
    """Panel TRSM against ONE triangular block ``a``, fused: one
    ``pallas_call`` for the WHOLE (possibly batched) strip ``b``,
    batched over the strip's tile axis via the Pallas grid, with the
    factored diagonal (its in-kernel triangular inverse) held in VMEM
    scratch across grid steps.

    Same call convention as ``tile_ops.blas.trsm_panel`` (solve
    ``op(A) X = alpha B`` for side='L' / ``X op(A) = alpha B`` for 'R';
    ``b`` 2D or a stacked (R, nb, nb) tile batch). Left-side solves are
    mapped to the right-side canonical kernel through the transpose
    identity ``op(A) X = B  <=>  X^T op'(A) = B^T`` (real dtypes: 'C'
    == 'T'); the transposes are cheap XLA relayouts outside the single
    kernel. f32/bf16 only."""
    assert a.ndim == 2 and jnp.dtype(a.dtype) in _SUPPORTED, (a.shape,
                                                              a.dtype)
    out_dtype = b.dtype
    if alpha != 1.0:
        b = (alpha * b).astype(out_dtype)
    flip = {"N": "T", "T": "N", "C": "N"}
    if side == "L":
        bt = jnp.swapaxes(b, -1, -2)
        out = fused_panel_solve("R", uplo, flip[op], diag, a, bt,
                                interpret=interpret)
        return jnp.swapaxes(out, -1, -2)
    shape = b.shape
    b2 = b.reshape(-1, shape[-1])
    kw = dict(uplo=uplo, op="T" if op == "C" else op, diag=diag,
              interpret=interpret)
    if not _tracing(a, b2):
        out = obs.telemetry.call("pallas_panel.solve", _fused_solve_rows,
                                 a, b2, **kw)
    else:
        out = _fused_solve_rows(a, b2, **kw)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# Fused STEP kernels (step_impl route, docs/pallas_panel.md)
# ---------------------------------------------------------------------------

def _factor_into(a_ref, fac_ref, inv_ref, x_ref, s: int):
    """Grid-step-0 shared prologue of the fused step kernels: run the
    row-panel potrf on the identity-padded diagonal tile in the ``x_ref``
    scratch, write the factor out with the LAPACK pass-through triangle,
    and build the factor's triangular inverse into VMEM scratch for the
    strip solve (the sequential TPU grid keeps both resident across grid
    steps)."""
    a = a_ref[...].astype(jnp.float32)
    rows = _iota((s, s), 0)
    cols = _iota((s, s), 1)
    tril = rows >= cols
    x_ref[...] = jnp.where(tril, a, 0.0) + jnp.where(rows > cols, a, 0.0).T
    _potrf_rows(x_ref, s)
    f = x_ref[...].T                    # lower factor, zeros above
    fac_ref[...] = jnp.where(tril, f, a).astype(fac_ref.dtype)
    x_ref[...] = f
    _tri_inv_rows(x_ref, inv_ref, s)


def _make_factor_solve_kernel(s: int):
    """2-op step kernel (canonical lower/right form): grid step 0
    factors the diagonal tile and derives its inverse into scratch;
    every grid step then applies the inverse to its strip block as ONE
    MXU gemm — potrf + whole-strip solve in a single ``pallas_call``,
    the factor never round-tripping to HBM between the two ops."""

    def kernel(a_ref, b_ref, fac_ref, out_ref, inv_ref, x_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            _factor_into(a_ref, fac_ref, inv_ref, x_ref, s)

        b = b_ref[...].astype(jnp.float32)
        out = jax.lax.dot_general(
            b, inv_ref[...], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[...] = out.astype(out_ref.dtype)

    return kernel


def _make_step_kernel(s: int, w: int):
    """3-op step kernel (canonical lower form): the factor+solve
    prologue of :func:`_make_factor_solve_kernel` plus the ADJACENT
    trailing-update slab consumed in the same kernel. Block 0's solved
    strip rows (the rows aligned with the slab's columns) persist in a
    second VMEM scratch square across the sequential grid, and every
    grid step subtracts its ``p_i p_0^H`` outer product from its slab
    block under the trailing lower-triangle mask (``w`` = the slab's
    true column count). The solved strip never leaves VMEM between the
    solve and the slab gemm that consumes it."""

    def kernel(a_ref, b_ref, c_ref, fac_ref, p_ref, nc_ref, inv_ref,
               p0_ref, x_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            _factor_into(a_ref, fac_ref, inv_ref, x_ref, s)

        b = b_ref[...].astype(jnp.float32)
        p = jax.lax.dot_general(
            b, inv_ref[...], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p_ref[...] = p.astype(p_ref.dtype)

        @pl.when(i == 0)
        def _():
            p0_ref[...] = p

        upd = jax.lax.dot_general(
            p, p0_ref[...], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # strip row (global) vs slab column mask: strictly-below rows
        # take the full update, the leading block its lower triangle;
        # pad columns (>= w) pass the slab through untouched
        grow = _iota((s, s), 0) + i * s
        col = _iota((s, s), 1)
        mask = (grow >= col) & (col < w)
        c = c_ref[...].astype(jnp.float32)
        nc_ref[...] = (c + jnp.where(mask, -upd, 0.0)).astype(nc_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_factor_solve_rows(diag, b, *, interpret: bool = False):
    """Canonical lower 2-op step over the rows of the 2D strip ``b``."""
    d = diag.shape[-1]
    f = b.shape[0]
    s = _pad_size(d, interpret)
    ap = _identity_pad(diag, s)
    fp = -(-max(f, 1) // s) * s
    bp = jnp.zeros((fp, s), b.dtype).at[:f, :d].set(b)
    fac, out = pl.pallas_call(
        _make_factor_solve_kernel(s),
        grid=(fp // s,),
        in_specs=[
            pl.BlockSpec((s, s), lambda i: (I0, I0)),
            pl.BlockSpec((s, s), lambda i: (i, I0)),
        ],
        out_specs=[
            pl.BlockSpec((s, s), lambda i: (I0, I0)),
            pl.BlockSpec((s, s), lambda i: (i, I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, s), diag.dtype),
            jax.ShapeDtypeStruct((fp, s), b.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((s, s), jnp.float32),
                        pltpu.VMEM((s, s), jnp.float32)],
        interpret=interpret,
    )(ap, bp)
    return fac[:d, :d], out[:f, :d]


def fused_factor_solve(uplo: str, diag, strip, *, interpret: bool = False):
    """Fused panel CHAIN: potrf of the diagonal tile + the whole panel
    strip solve in ONE ``pallas_call`` (the 2-op step kernel — the
    scan/distributed builders' step form, where the trailing slab is
    separated from the panel chain by collectives or traced-index
    masking and cannot join the kernel).

    uplo='L': ``fac = chol(tril(diag))`` (upper triangle passes
    through) and each strip row block solves ``X fac^H = strip`` — the
    ``("R", "L", "C", "N")`` panel convention. uplo='U' is the mirrored
    sweep (``fac^H X = strip``), mapped onto the canonical lower kernel
    through cheap transposes outside the single kernel. ``strip`` is 2D
    (rows, d) or a stacked (R, d, d) tile batch. f32/bf16 only
    (computed in f32)."""
    assert diag.ndim == 2 and jnp.dtype(diag.dtype) in _SUPPORTED, (
        diag.shape, diag.dtype)
    if uplo == "U":
        st = jnp.swapaxes(strip, -1, -2)
        fac, pan = fused_factor_solve("L", diag.T, st, interpret=interpret)
        return fac.T, jnp.swapaxes(pan, -1, -2)
    shape = strip.shape
    b2 = strip.reshape(-1, shape[-1])
    kw = dict(interpret=interpret)
    if not _tracing(diag, b2):
        fac, out = obs.telemetry.call("pallas_panel.factor_solve",
                                      _fused_factor_solve_rows, diag, b2,
                                      **kw)
    else:
        fac, out = _fused_factor_solve_rows(diag, b2, **kw)
    return fac, out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("w", "interpret"))
def _fused_step_lower(diag, strip, slab, *, w: int,
                      interpret: bool = False):
    """Canonical lower 3-op step: pad, grid over the strip's row blocks,
    slice the three outputs back."""
    d = diag.shape[-1]
    m = strip.shape[0]
    s = _pad_size(d, interpret)
    ap = _identity_pad(diag, s)
    r = -(-max(m, 1) // s)
    mp = r * s
    bp = jnp.zeros((mp, s), strip.dtype).at[:m, :d].set(strip)
    cp = jnp.zeros((mp, s), slab.dtype).at[:m, :w].set(slab)
    fac, pan, nc = pl.pallas_call(
        _make_step_kernel(s, w),
        grid=(r,),
        in_specs=[
            pl.BlockSpec((s, s), lambda i: (I0, I0)),
            pl.BlockSpec((s, s), lambda i: (i, I0)),
            pl.BlockSpec((s, s), lambda i: (i, I0)),
        ],
        out_specs=[
            pl.BlockSpec((s, s), lambda i: (I0, I0)),
            pl.BlockSpec((s, s), lambda i: (i, I0)),
            pl.BlockSpec((s, s), lambda i: (i, I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, s), diag.dtype),
            jax.ShapeDtypeStruct((mp, s), strip.dtype),
            jax.ShapeDtypeStruct((mp, s), slab.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((s, s), jnp.float32)] * 3,
        interpret=interpret,
    )(ap, bp, cp)
    return fac[:d, :d], pan[:m, :d], nc[:m, :w]


def fused_step(uplo: str, diag, strip, slab, *, interpret: bool = False):
    """One fused Cholesky STEP — panel potrf + panel strip solve + the
    ADJACENT trailing-update slab — as ONE ``pallas_call``: the factor,
    its triangular inverse, and block 0 of the solved strip all stay
    resident in VMEM between the three ops (the ROADMAP item-4 kernel;
    docs/pallas_panel.md "Fused step kernel").

    uplo='L': ``diag`` (d, d) lower-stored, ``strip`` (m, d) the rows
    below the diagonal, ``slab`` (m, w) the first ``w = min(d, m)``
    trailing columns. Returns ``(fac, panel, new_slab)`` where ``fac``
    is the factored tile (opposite triangle passed through), ``panel``
    the solved strip, and ``new_slab = slab - mask(panel panel[:w]^H)``
    with the trailing lower-triangle mask — exactly the builders'
    lookahead-split column strip, so the SSA carry can consume it
    directly. uplo='U' is the mirrored sweep (``strip`` (d, m), ``slab``
    (w, m)), mapped onto the canonical lower kernel through transposes
    outside the single kernel. f32/bf16 only (computed in f32); the
    NaN-prefix ``potrf_info`` failure contract propagates through the
    solve and slab like the composed ops."""
    assert diag.ndim == 2 and jnp.dtype(diag.dtype) in _SUPPORTED, (
        diag.shape, diag.dtype)
    if uplo == "U":
        fac, pan, ns = fused_step("L", diag.T, strip.T, slab.T,
                                  interpret=interpret)
        return fac.T, pan.T, ns.T
    w = slab.shape[-1]
    kw = dict(w=w, interpret=interpret)
    if not _tracing(diag, strip, slab):
        return obs.telemetry.call("pallas_panel.step", _fused_step_lower,
                                  diag, strip, slab, **kw)
    return _fused_step_lower(diag, strip, slab, **kw)


# ---------------------------------------------------------------------------
# Routing — the panel_impl knob's single owner
# ---------------------------------------------------------------------------

def _tracing(*arrs) -> bool:
    """Are we inside a jax trace? (telemetry.call AOT-compiles on
    concrete args only — inside a builder's jit the kernels inline.)"""
    return any(isinstance(x, jax.core.Tracer) for x in arrs)


def panel_uses_fused(dtype, nb: int, platform=None) -> bool:
    """Will the panel chain route through the fused Pallas kernels under
    the current config? Single owner of the ``panel_impl`` route
    decision (mirrors ``blas.f64_gemm_uses_mxu`` /
    ``trsm_panel_uses_mixed``): callers resolve it ONCE per entry and
    thread it into the builders as a static/cache-key argument.

    * ``"xla"`` — never.
    * ``"auto"`` — fused on TPU for f32/bf16 tiles within
      :data:`PANEL_MB_MAX`; everything else is route POLICY (uncounted).
    * ``"fused"`` (explicit) — fused wherever supported (off-TPU the
      call sites run the kernels in interpret mode); an unsupported
      dtype/block registers through ``health.registry.report_fallback``
      (``dlaf_fallback_total{site="panel"}``, strict-mode raise).

    ``health.inject.disable_pallas`` forces the gate closed; when that
    flips a would-be-True answer the degradation is counted at
    ``site="panel"`` like every pallas route.
    """
    from ..config import get_configuration, resolved_panel_impl
    from ..health.registry import report_fallback, route_available

    impl = resolved_panel_impl()
    if impl != "fused":
        return False
    supported = jnp.dtype(dtype) in _SUPPORTED and nb <= PANEL_MB_MAX
    if not supported:
        if get_configuration().panel_impl == "fused":
            # the user explicitly asked for the fused route: landing on
            # XLA is a degradation, not policy — counted, strict raises
            report_fallback(
                "panel", "unsupported_dtype"
                if jnp.dtype(dtype) not in _SUPPORTED else "block_too_large",
                detail=f"dtype={np.dtype(dtype).name} nb={nb} (fused panel "
                       f"needs f32/bf16, nb<={PANEL_MB_MAX})")
        return False
    return route_available("pallas", "panel")


def step_vmem_bytes(nb: int, dtype, interpret: bool = False) -> int:
    """Modeled VMEM live set of the fused 3-op STEP kernel at block edge
    ``nb``: the resident diagonal tile + factor output (single-buffered
    by their constant index maps), double-buffered strip/slab/panel/
    new-slab grid blocks, and the three f32 scratch squares (potrf work
    square, triangular inverse, leading solved strip block). docs/pallas_panel.md walks
    the arithmetic."""
    s = _pad_size(nb, interpret)
    db = jnp.dtype(dtype).itemsize
    return s * s * (2 * db + 8 * db + 3 * 4)


def step_uses_fused(dtype, nb: int) -> bool:
    """Will the blocked-Cholesky STEP route through the fused step
    kernels under the current config? Single owner of the ``step_impl``
    route decision (mirrors :func:`panel_uses_fused`); callers resolve
    it ONCE per entry and thread it into the builders as a static
    cache-key argument.

    * ``"xla"`` — never (the panel chain stays composed ops; the
      ``panel_impl`` route still decides potrf/solve individually).
    * ``"auto"`` — fused on TPU for f32/bf16 within
      :data:`PANEL_MB_MAX` and the ``step_vmem_limit`` budget;
      everything else is route POLICY (uncounted).
    * ``"fused"`` (explicit) — wherever supported (off-TPU the call
      sites run the kernel in interpret mode); an unsupported
      dtype/block or a VMEM-budget overflow registers through
      ``report_fallback(site="step")`` (counted, strict raises).

    ``health.inject.disable_route("pallas")`` forces the gate closed;
    when that flips a would-be-True answer the degradation is counted
    at ``site="step"`` like every pallas route.
    """
    from ..config import get_configuration, resolved_step_impl
    from ..health.registry import report_fallback, route_available

    impl = resolved_step_impl()
    if impl != "fused":
        return False
    cfg = get_configuration()
    explicit = cfg.step_impl == "fused"
    supported = jnp.dtype(dtype) in _SUPPORTED and nb <= PANEL_MB_MAX
    need = step_vmem_bytes(nb, dtype)
    if not supported or need > cfg.step_vmem_limit:
        if explicit:
            # the user explicitly asked for the fused step: landing on
            # XLA is a degradation, not policy — counted, strict raises
            if not supported:
                reason = ("unsupported_dtype"
                          if jnp.dtype(dtype) not in _SUPPORTED
                          else "block_too_large")
                detail = (f"dtype={np.dtype(dtype).name} nb={nb} (fused "
                          f"step needs f32/bf16, nb<={PANEL_MB_MAX})")
            else:
                reason = "vmem_budget"
                detail = (f"nb={nb}: fused step kernel models ~{need} B "
                          f"VMEM > step_vmem_limit={cfg.step_vmem_limit}")
            report_fallback("step", reason, detail=detail)
        return False
    return route_available("pallas", "step")


def count_step_kernel(impl: str) -> None:
    """Trace-time step-route accounting (once per emitted strip-bearing
    step in the compiled program): how many blocked-factorization steps
    run their panel chain through a fused step kernel vs the composed
    XLA/op chain — ``dlaf_step_kernel_total{impl}``."""
    if obs.metrics_active():
        obs.counter("dlaf_step_kernel_total", impl=impl).inc()


def count_panel_kernel(impl: str, op: str) -> None:
    """Trace-time panel-kernel accounting (once per emitted kernel in
    the compiled program): how many panel potrf/solve steps route
    through the fused kernels vs the XLA op chain."""
    if obs.metrics_active():
        obs.counter("dlaf_panel_kernel_total", impl=impl, op=op).inc()


def panel_potrf(uplo: str, a, *, fused: bool, interpret: bool = False):
    """Route one diagonal-tile potrf: the fused Pallas kernel or the
    XLA route (``tile_ops.lapack.potrf``), counted either way under
    ``dlaf_panel_kernel_total{impl, op="potrf"}``."""
    if fused:
        count_panel_kernel("fused", "potrf")
        return fused_potrf(uplo, a, interpret=interpret)
    from . import lapack as tl

    count_panel_kernel("xla", "potrf")
    return tl.potrf(uplo, a)


def panel_solve(side: str, uplo: str, op: str, diag: str, a, b, *,
                fused: bool, interpret: bool = False, inv_a=None,
                alpha=1.0):
    """Route one panel strip solve: the fused grid-batched kernel or
    the XLA route (``tile_ops.blas.trsm_panel``, which itself honors
    the ``f64_trsm`` mixed path and consumes ``inv_a``), counted under
    ``dlaf_panel_kernel_total{impl, op="solve"}``."""
    if fused:
        count_panel_kernel("fused", "solve")
        return fused_panel_solve(side, uplo, op, diag, a, b, alpha=alpha,
                                 interpret=interpret)
    from . import blas as tb

    count_panel_kernel("xla", "solve")
    return tb.trsm_panel(side, uplo, op, diag, a, b, alpha=alpha,
                         inv_a=inv_a)
