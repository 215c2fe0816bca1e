"""Emulated float64 matmul on the MXU via error-free slicing (Ozaki scheme).

TPU hardware has no native f64 multiply: XLA emulates f64 dots in software at
~1 TFlop/s on a v5e while the MXU runs int8/bf16 contractions two to three
orders of magnitude faster. The Ozaki splitting (Ozaki et al., "Error-free
transformations of matrix multiplication", 2012; int8-tensor-core variants in
recent GPU literature) recovers f64-accurate GEMM from fast low-precision
hardware:

1. normalize each row of ``A`` (column of ``B``) to ``[-1/2, 1/2]`` by its
   max (halving folded back in at recombine, so nothing overflows even at
   ``max ~ DBL_MAX``),
2. peel ``s`` slices of ``q=7`` mantissa bits each: every slice is a small
   integer in ``[-64, 64]`` — exactly representable in int8 (a deep
   product's wide operand in one pass over it, :func:`_peel_stack`),
3. contract slice pairs on the MXU with **exact** int32 accumulation
   (``|sum| <= k * 2^12 * s < 2^31`` for any practical ``k``),
4. recombine partial products grouped by total shift ``d = t+u`` (at most
   ``2s-1`` int32->f64 conversions, not ``s^2``), applying the row/col
   scales back. The syrk computes only the pair half ``t < u`` of each
   group and mirrors ONCE, after the group loop: the mirror is linear and
   the group scales are scalars, so ``sum_d s_d (g_d + g_d^T + D_d) = C +
   C^T`` with ``C = sum_d s_d (g_d + D_d/2)`` — one (m, m) transpose of
   the f64 accumulator per call instead of one of the int32 partial per
   shift group (:func:`_mirror`).

Cross terms with ``t+u >= s`` fall below the kept mantissa (relative to the
row/column scale) and are dropped, leaving ``s(s+1)/2`` int8 gemms: 36 for the
default ``s=8`` (56 mantissa bits — slightly tighter than f64's 53, so the
result matches a native f64 gemm to its own rounding error on well-scaled
data). The error bound is relative to ``rowmax(A) * colmax(B)``, like the
classical f64 bound ``k * eps * |A||B|``.

This is a *capability the reference cannot express*: its f64 GEMM rides
cuBLAS; the TPU-native framework routes f64 tile contractions through the
int8 systolic array. Used by the Cholesky trailing update (the flops-dominant
stage of BASELINE config #1) behind ``cholesky_trailing = "ozaki"`` and
available as ``tile_ops.ozaki.{matmul_f64,syrk_f64}``.

Scope/caveats (documented, asserted where cheap): finite inputs only (no
inf/nan propagation guarantees); real f64 directly, complex128 via the
3-real-product composition (:func:`matmul_c128`/:func:`herk_c128`);
accumulation exactness needs
``k * 2^12 * min(s, d+1) < 2^31`` per grouped sum — beyond that the group sum
switches to f64. On TPU, XLA's X64 rewrite emulates f64 with f32 pairs, so
*every* f64 op there (this module included) is limited to f32's exponent
range: magnitudes beyond ~1e38 overflow the emulation. That is a platform
property, not an algorithm one — the CPU path handles the full f64 range
(covered by the pathological-scale tests).
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["matmul_f64", "syrk_f64", "matmul_c128", "herk_c128",
           "DEFAULT_SLICES", "SLICE_BITS"]

SLICE_BITS = 7          # q: mantissa bits per slice; int8 holds +-64 exactly
DEFAULT_SLICES = 8      # s: 8 * 7 = 56 bits >= f64's 53-bit mantissa


def _scale(x, axis):
    """Per-row/col max ``M = max|x|`` (zero rows map to 1). The normalized
    block is ``(x / M) * 0.5`` — in ``[-1/2, 1/2]`` — and :func:`_fold_group`/:func:`_apply_scales`
    folds the two implicit factors of 2 back in as an exact constant, so no
    intermediate (like ``2*M``) can overflow even at ``M ~ DBL_MAX``.

    The scale need not be a power of two: slices stay integer-exact either
    way, and the one rounding of the normalize/rescale pair is a ~1-ulp
    relative error — the same order as native f64 gemm rounding. (A
    power-of-two scale would need ``frexp``/``ldexp``, whose 64-bit
    bit-twiddling the TPU X64-emulation pipeline does not implement.)"""
    m = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    return jnp.where(m > 0, m, 1.0)


def _normalize(x, scale):
    """``(x / scale) * 0.5`` — in ``[-1/2, 1/2]``; the *0.5 is exact."""
    return (x / scale) * 0.5


def _peel_slices(xn, s: int):
    """``s`` int8 slices of the normalized block: ``xn ~= sum_t I_t 2^-q(t+1)``
    with every ``|I_t| <= 2^(q-1)`` (round-to-nearest residual peeling).

    Two hardening rules, both REQUIRED on TPU's 2xf32 f64 emulation
    (root-caused on the v5e 2026-08-02 — the source of red2band's 2e-5 eigenvalue
    residual and the dominant term of cholesky's 6.1e-9):

    * The integer is extracted by a NATIVE f32 round — ``r*sc`` is cast
      to f32 first, then rounded — never by the emulated-f64 ``round``.
      The emulated round mis-rounds exact round-to-nearest ties plus an
      epsilon (measured: ``xn*128 = 17.5000005`` rounded to 19, not 18),
      and the one-unit overshoot pushes the next residual*scale to ~192:
      OUTSIDE int8, where the f32->s8 conversion saturates at +-127 and
      every later slice stays pinned at the rail — the decomposition is
      permanently off by ``~2^-q(t+1)``. The f32 cast loses at most
      2^-24-relative of ``r*sc`` (|values| <= ~64), which moves the
      integer choice by at most one unit off a tie — exactly what the
      next slice absorbs (|I| <= 65, well inside int8).
    * The residual subtracts the STORED slice value (int8 cast back
      through f32 — exact for |I| <= 127), so slice and residual cannot
      disagree whatever the rounding path did; any quantization surprise
      flows into the next slice instead of corrupting the sum.

    On platforms with true f64 the f32 round differs from an f64 round
    only by tie-vs-cast-noise unit choices that the residual re-absorbs:
    accuracy is unchanged (property-tested), though slice values may
    differ from a pure-f64 peel."""
    out = []
    r = xn
    for t in range(s):
        sc = float(2.0 ** (SLICE_BITS * (t + 1)))
        # f32 bridge both ways: native f32 round (see above), and small
        # integers cast exactly; f64->s8 directly could also route
        # through s64 ops the TPU emulation pipeline lacks
        it8 = jnp.round((r * sc).astype(jnp.float32)).astype(jnp.int8)
        out.append(it8)
        r = r - it8.astype(jnp.float32).astype(xn.dtype) * (1.0 / sc)
    return out


def _count_peel(form: str, elements: int) -> None:
    """Trace-time count of the elements a product's peels slice,
    ``dlaf_ozaki_peeled_total{form}``, per traced 2D operand and EXECUTED
    step (``obs.traced_step_count()``, as :func:`_count_macs`): ``chain``
    where a product calls :func:`_peel_slices`, ``one_pass`` for
    :func:`_peel_stack` (whose host branch, the chain itself, is not
    counted again)."""
    from .. import obs

    if obs.metrics_active():
        obs.counter("dlaf_ozaki_peeled_total", form=form).inc(
            elements * obs.traced_step_count())


def _fast_two_sum(a, b):
    """``(s, e)``, ``s = fl(a + b)`` and ``e = a + b - s`` exactly, for
    ``|a| >= |b|`` (or ``a`` zero)."""
    s = a + b
    return s, b - (s - a)


def _planes(xn):
    """``(hi, lo)``: ``hi = f32(xn)`` and ``lo`` the f32 of what is left.
    Where f64 is the TPU's double-f32 emulation this is its own
    representation, and the split moves no bit."""
    hi = xn.astype(jnp.float32)
    return hi, (xn - hi.astype(xn.dtype)).astype(jnp.float32)


def _peel_planes(planes, s: int, store) -> None:
    """:func:`_peel_slices` on a double-f32 pair ``planes = (h, l)``, in
    f32 alone: ``store(t, I_t)`` receives each int8 slice once. ``h`` is
    kept the f32 rounding of the whole residual ``h + l`` by
    ``fast_two_sum(h, l)``, the renormalization the emulated f64 multiply
    and subtraction apply before :func:`_peel_slices` casts ``r
    2^q(t+1)``; once more before the first step, since the pair a program
    is handed need not be normalized (the chip's own transfer of an f64
    array leaves some exact f32 ties the other way, PERF.md). So
    ``round(h 2^q(t+1))`` is the native f32 round of ``f32(r 2^q(t+1))``
    that :func:`_peel_slices` takes (its first hardening rule), and the
    residual subtracts the stored int8 value (its second) from ``h``,
    which is exact (``|h 2^q(t+1) - I_t| <= 1/2`` on a 24-bit grid at or
    below ``h``'s). Where IEEE binary64 holds the pair's sum exactly (48
    bits), the slices are :func:`_peel_slices`' in binary64, whose
    residuals are exact there too (the oracle of tests/test_ozaki.py)."""
    h, l = _fast_two_sum(*planes)
    for t in range(s):
        sc = float(2.0 ** (SLICE_BITS * (t + 1)))
        it8 = jnp.round(h * sc).astype(jnp.int8)
        store(t, it8)
        h, l = _fast_two_sum(h - it8.astype(jnp.float32) * (1.0 / sc), l)


#: the one-pass peel's block (rows, columns) and the column strip its body
#: works on at a time: a block's planes and slices, double-buffered, take
#: 2 x 15 B x 256 x 1024 = 7.5 MiB of VMEM; a strip's planes are 32 vregs
_PEEL_BLOCK = (256, 1024)
_PEEL_STRIP = 128


def _peel_kernel(hi_ref, lo_ref, out_ref, *, s: int, width: int):
    """One block: the planes read once, the residual kept in registers and
    VMEM across the ``s`` steps, each slice written once into its place
    in the stack, a column strip of ``width`` at a time."""
    import numpy as np
    from jax.experimental import pallas as pl

    def peel(cols):
        def store(t, it8):
            out_ref[t, :, cols] = it8

        _peel_planes((hi_ref[:, cols], lo_ref[:, cols]), s, store)

    strips = hi_ref.shape[-1] // width
    if strips == 1:
        peel(slice(None))
        return

    def strip(j):
        peel(pl.ds(pl.multiple_of(j * width, width), width))
        return j + 1

    # a while, not a fori_loop: a static trip count would make it a scan,
    # and a product's scans are its schedule (tests/test_ozaki.py)
    lax.while_loop(lambda j: j < strips, strip, np.int32(0))


def _peel_stack_tpu(hi, lo, s: int, *, interpret: bool = False):
    """:func:`_peel_stack` on a TPU, from the double-f32 planes ``hi``,
    ``lo`` (:func:`_planes`): one Pallas kernel over (256, 1024) blocks (a
    dimension below the block's takes its full extent, as the chase's 255
    rows do). ``interpret`` runs the kernel off the chip, for the tests."""
    import numpy as np
    from jax.experimental import pallas as pl

    rows, cols = hi.shape
    br, bc = min(rows, _PEEL_BLOCK[0]), min(cols, _PEEL_BLOCK[1])
    width = _PEEL_STRIP if bc % _PEEL_STRIP == 0 else bc
    plane = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_peel_kernel, s=s, width=width),
        grid=(pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[plane, plane],
        out_specs=pl.BlockSpec((s, br, bc), lambda i, j: (np.int32(0), i, j)),
        out_shape=jax.ShapeDtypeStruct((s, rows, cols), jnp.int8),
        interpret=interpret,
    )(hi, lo)


def _peel_stack(xn, s: int):
    """``jnp.stack(_peel_slices(xn, s))``, bit for bit, in one pass over
    the normalized block: read once, the residual kept on chip across the
    ``s`` steps, each slice written once into the ``s8[s, rows, cols]``
    stack (:func:`_peel_planes`). :func:`_peel_slices` compiles to ``s``
    passes on a TPU, each reading and writing the residual's two f32
    planes beside its slice: 17 B an element a slice where the work needs
    15 B an element. The backend the program is lowered for picks the
    form (``lax.platform_dependent``: the chip's compiler never sees the
    other): on a TPU one Pallas kernel on the two f32 planes its f64
    carries; elsewhere the chain itself (two f32 planes hold 48 of IEEE
    binary64's 53 bits)."""
    _count_peel("one_pass", xn.size)
    return lax.platform_dependent(
        xn, tpu=lambda x: _peel_stack_tpu(*_planes(x), s),
        default=lambda x: jnp.stack(_peel_slices(x, s)))


# int32 accumulation of int8 x int8 products (each |p| <= 2^12) is provably
# exact while k * 2^12 < 2^31, i.e. k < 2^19; deeper contractions are chunked
_K_I32_EXACT = 1 << 19
_K_CHUNK = 1 << 18
# f32 accumulation of the same products is integer-exact while
# k * 2^12 <= 2^24, i.e. k <= 2^12 — the bound of the bf16-dot route
_K_F32_EXACT = 1 << 12


def _slice_dot_impl() -> str:
    """"int8" (s8 x s8 -> s32 dot) or "bf16": cast the slices to bf16 —
    every value is a small integer in [-2^6, 2^6], exactly representable —
    and contract on the MXU's native bf16 path with f32 accumulation,
    which is integer-exact while ``k * 2^12 <= 2^24`` (deeper
    contractions are chunked). Same bits out either way. On the v5e the
    two routes time alike where it was measured: the 2x2 solve's cell
    0.1359 s a call on "int8" against 0.1374 on "bf16", its group dots
    46.9 against 48.1 ms (PERF.md section 7, PR 28), so the int8 peak of
    twice the bf16 rate does not show through XLA's s8 dot. The "auto"
    default resolves bf16 on TPU, int8 elsewhere, keyed on the PROCESS
    default backend like blas._oz_slices (config ``ozaki_dot``)."""
    from ..config import get_configuration, resolve_platform_auto

    return resolve_platform_auto(
        get_configuration().ozaki_dot, knob="ozaki_dot",
        tpu_choice="bf16", other_choice="int8",
        detail="routes bit-identical ON DEVICE and at performance parity "
               "at the pipeline level — dot_ab, one v5e chip, 2026-08-01")


def _group_scale(d: int, half: bool = False) -> float:
    """Fold scale ``2^-q(d+2)`` of shift group ``d`` (``half``: half of it,
    for the syrk's un-mirrored groups, :func:`_mirror`); a power of two, so
    multiplying by it is exact."""
    return 2.0 ** (-SLICE_BITS * (d + 2) - int(half))


def _group_scales(s, half: bool = False):
    """(s,) f64 :func:`_group_scale` of every shift group (the padded
    scan's operand)."""
    import numpy as np

    return jnp.asarray([_group_scale(d, half) for d in range(s)],
                       dtype=np.float64)


def _pad_k(x, k_pad, axis):
    """Zero-pad int8 slice operand ``x`` to ``k_pad`` along ``axis`` —
    exact on both dot routes (0 * anything accumulates to 0)."""
    pad = k_pad - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _exact_i32(s: int, k: int) -> bool:
    """Do the int32 shift-group sums of ``s`` slices a side at depth ``k``
    stay exact: ``(d + 1) k 2^12 < 2^31`` for every group ``d < s``."""
    return (s * k) << (2 * SLICE_BITS - 2) < (1 << 31)


def _sequenced_form(m: int, n: int, k: int, s: int) -> str:
    """Which form the slice product takes for an (m, k) x (k, n) product
    of ``s`` slices a side: one rule on the shape, no knob. Every form
    keeps one int32 group partial plus the f64 accumulator live, whatever
    the slice count (the bound the N=16384 local Cholesky needs), folds
    the groups in the order ``d = 0..s-1`` with the same scales, and
    gives the same bits (zero int8 pad blocks contribute exactly nothing
    on either dot route).

    * ``"ragged"``, ``min(m, n) > k``: bulk products, both output
      dimensions wider than the contraction. Ragged groups multiply no
      padding but are ``s`` distinct dot kernels where a scan has one
      body, and a program's code is resident in HBM: ~0.4 MiB a kernel,
      once per product instance (PERF.md section 6, PR 28).
    * ``"groups"``, ``k == min(m, n)``: panel products one block wide and
      one block deep (the Cholesky's panel and strip products, the
      solve's pivot products). ``lax.scan`` over the ``s`` shift groups,
      each zero-padded to the widest depth ``s k``: one body,
      ``s (s - 1) / 2`` of its ``s^2`` depth slots zeros on BOTH
      operands, both stacked ``s`` times.
    * ``"slices"``, ``k > min(m, n)``: deep products, the contraction
      deeper than the narrower output side (the reduction to band's ``W =
      A (V T)``, (m, m) x (m, band), whose wide operand is the whole
      trailing matrix and whose MACs are 46% of that program's; ``V^H
      W``; ``bt_reduction_to_band``'s ``V^H C``). Stacking the wide
      operand's padded groups writes and reads ``s^2`` times its size a
      product; this form scans the wide operand's ``s`` slices as they
      were peeled (in one pass, :func:`_peel_stack`) against the narrow
      operand's slices shifted into ``s`` blocks, so the zero slots sit on
      the narrow side only, and the ``s`` groups add up in one int32
      carry. It needs every group sum
      exact in int32 (``s k 2^12 < 2^31``); deeper than that the product
      keeps ``"groups"``, whose dots chunk into f64."""
    if min(m, n) > k:
        return "ragged"
    return "slices" if k > min(m, n) and _exact_i32(s, k) else "groups"


def _scan_slices(wide, narrow, a_wide: bool, s: int):
    """``[G_0 .. G_{s-1}]``, the int32 shift-group sums ``G_d = sum_t I_t
    J_{d-t}`` of a deep product (:func:`_sequenced_form` ``"slices"``),
    by one ``lax.scan`` over the WIDE operand's slices ``wide``, stacked
    as :func:`_peel_stack` wrote them. With A (m, k) the wide one
    (``a_wide``, m > n), step ``t`` multiplies ``I_t`` by ``[0 x t | J_0
    | ... | J_{s-1-t}]`` (k, s n), the narrow operand's slices
    ``narrow``: block ``d`` of the (m, s n) product is ``I_t J_{d-t}``
    for ``d >= t`` and exactly zero before it, and the steps add into one
    int32 carry. With B the wide one the roles are exchanged: step ``u``
    multiplies ``[0 x u; I_0; ...; I_{s-1-u}]`` (s m, k) by ``J_u``.
    Nothing of the wide operand is concatenated or padded."""
    m, n = (wide.shape[-2], narrow[0].shape[-1]) if a_wide \
        else (narrow[0].shape[-2], wide.shape[-1])
    axis = -1 if a_wide else -2
    zero = jnp.zeros_like(narrow[0])
    shifted = jnp.stack([jnp.concatenate([zero] * t + narrow[:s - t],
                                         axis=axis) for t in range(s)])

    def body(g, xs):
        wide_t, shifted_t = xs
        return g + (_dot_i8(wide_t, shifted_t) if a_wide
                    else _dot_i8(shifted_t, wide_t)), None

    g, _ = lax.scan(body,
                    jnp.zeros((m, s * n) if a_wide else (s * m, n),
                              jnp.int32),
                    (wide, shifted))
    return [g[:, d * n:(d + 1) * n] if a_wide else g[d * m:(d + 1) * m]
            for d in range(s)]


def _dot_bf16(ia, ib):
    """Exact slice contraction over the native bf16 MXU path: bf16
    operands (exact for 7-bit slices), f32 accumulation (exact while
    ``k * 2^12 <= 2^24``), int32 result (each f32 partial is an integer
    below 2^24, so the cast is exact)."""
    k = ia.shape[-1]
    # single chunk for k <= 2^12; int32 chunk sums stay exact up to
    # 2^31 / 2^24 = 128 chunks, i.e. k < 2^19 — callers route deeper
    # contractions to the int8 path
    acc = None
    for s0 in range(0, k, _K_F32_EXACT):
        p = jnp.matmul(ia[..., s0:s0 + _K_F32_EXACT].astype(jnp.bfloat16),
                       ib[..., s0:s0 + _K_F32_EXACT, :].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        acc = p.astype(jnp.int32) if acc is None else acc + p.astype(jnp.int32)
    return acc


def _dot_i8(ia, ib):
    """Batched exact slice contraction (last axis of ``ia`` with
    second-to-last of ``ib``); route per ``config.ozaki_dot``.

    int8 route: s8 x s8 -> s32. For contraction depth ``k >= 2^19`` a
    single int32 accumulation could wrap (``k * 2^12 >= 2^31`` —
    reachable through ``blas.contract``, which flattens multiple
    contracted dims into one k), so the axis is chunked into exact int32
    partials summed in f64 (the caller's group-sum path is already f64 in
    that regime, since ``s*k*2^12 >= 2^31`` too)."""
    k = ia.shape[-1]
    if _slice_dot_impl() == "bf16" and k < _K_I32_EXACT:
        return _dot_bf16(ia, ib)
    if k < _K_I32_EXACT:
        return jnp.matmul(ia, ib, preferred_element_type=jnp.int32)
    acc = None
    for s0 in range(0, k, _K_CHUNK):
        p = jnp.matmul(ia[..., s0:s0 + _K_CHUNK],
                       ib[..., s0:s0 + _K_CHUNK, :],
                       preferred_element_type=jnp.int32).astype(jnp.float64)
        acc = p if acc is None else acc + p
    return acc


def _fold_group(acc, d, p):
    """Fold one per-shift group into the running f64 accumulator:
    ``acc + P_d 2^-q(d+2)`` (:func:`_group_scale`). The power-of-two
    constant multiply is exact and avoids ldexp (s64 ops). Folding each
    group as soon as it is complete — instead of collecting all ``s``
    (m, n) groups and combining at the end — keeps at most one group plus
    the accumulator live, which is what lets the unrolled N=16384
    factorization fit HBM (the collect-then-combine form compiled to a
    22.7 GB peak on a 16 GB v5e)."""
    term = p.astype(jnp.float64) * _group_scale(d)
    return term if acc is None else acc + term


def _fold_groups(s, group, cats):
    """``sum_d group(d, *cats) 2^-q(d+2)`` folded in the order ``d =
    0..s-1`` (:func:`_fold_group`). ``group(d, *cats)`` builds shift
    group ``d``'s integer partial from static slices of the operands'
    concatenations ``cats``, at the group's real depth. A barrier between
    groups makes group ``d``'s operands available only with the
    accumulator group ``d - 1`` folded into, so the compiler cannot run
    the dots ahead of the folds and hold their (m, n) partials: the live
    set of the padded scan's carry, without the scan (on the TPU
    compiler; XLA:CPU ignores the hint, tests/test_chip_compile.py)."""
    acc = None
    for d in range(s):
        if d:
            acc, cats = lax.optimization_barrier((acc, cats))
        acc = _fold_group(acc, d, group(d, *cats))
    return acc


_live = threading.local()


@contextlib.contextmanager
def live_outputs(live: int):
    """Around a product whose output a uniform-shape step body masks:
    ``live`` is how many of its output elements the body keeps, summed
    over the EXECUTED steps of the scan body being traced (a scan step's
    shapes cover the segment's whole block, the stored triangle of the
    live trailing block is what the mathematics needs). The products
    traced inside count the rest of their multiply-accumulates, real and
    padding alike, under ``dlaf_ozaki_masked_macs_total{route}``
    (:func:`_count_macs`); outside any such scope nothing is masked."""
    prev = getattr(_live, "n", None)
    _live.n = live
    try:
        yield
    finally:
        _live.n = prev


def _count_macs(route: str, mn: int, real: int, emitted: int) -> None:
    """Trace-time accounting of the slice dots' multiply-accumulates,
    ``dlaf_ozaki_macs_total{route, kind}``, per traced 2D product and
    EXECUTED step (a product in a step builder's scan body counts the
    scan's trip count, ``obs.traced_step_count()``, like the collectives'
    counters): ``real`` the ``mn * real`` the slice pairs need, ``zero``
    what the emitted depth holds beyond that (padding). Inside a
    :func:`live_outputs` scope the output elements beyond the live ones
    count their whole emitted depth under
    ``dlaf_ozaki_masked_macs_total{route}`` too: a share of the first
    counter's sum, not a third kind of it."""
    from .. import obs

    if obs.metrics_active():
        mn *= obs.traced_step_count()
        obs.counter("dlaf_ozaki_macs_total", route=route,
                    kind="real").inc(mn * real)
        obs.counter("dlaf_ozaki_macs_total", route=route,
                    kind="zero").inc(mn * (emitted - real))
        live = getattr(_live, "n", None)
        if live is not None and mn:
            obs.counter("dlaf_ozaki_masked_macs_total",
                        route=route).inc((mn - live) * emitted)


def _group_dot(ga, gb, pairs: int, k: int):
    """``ga @ gb``: ``pairs`` slice-pair products of depth ``k`` summed on
    the MXU accumulator by one exact dot that runs once per call, counted
    (:func:`_count_macs`) at the depth of the operands it was handed."""
    _count_macs("scan", ga.shape[-2] * gb.shape[-1], pairs * k, ga.shape[-1])
    return _dot_i8(ga, gb)


def _mirror(acc):
    """``acc + acc^T``: the syrk's one (m, m) transpose. The slice-pair
    half-products ``g_d = sum_{t<u, t+u=d} I_t I_u^T`` and the symmetric
    diagonal pairs ``D_d`` make the product ``sum_d s_d (g_d + g_d^T +
    D_d)``; the mirror is linear and the group scales are scalars, so that
    is ``C + C^T`` with ``C = sum_d s_d (g_d + D_d / 2)``:
    :func:`_syrk_f64_2d` folds the integers ``2 g_d + D_d`` at ``s_d / 2``
    (exact: a power of two) and mirrors the f64 accumulator here once,
    instead of each group's int32 partial. Counted at trace time, once
    per emitted mirror: ``dlaf_ozaki_mirror_total{route="scan"}``."""
    from .. import obs

    if obs.metrics_active():
        obs.counter("dlaf_ozaki_mirror_total", route="scan").inc()
    return acc + jnp.swapaxes(acc, -1, -2)


def _apply_scales(acc, sa, sb):
    """``((acc * 4) * sa) * sb`` — *4 = the two deferred halvings of
    :func:`_normalize`; the scales multiply in last so nothing overflows
    unless the true result does."""
    return ((acc * 4.0) * sa) * sb


@functools.partial(jnp.vectorize, signature="(m,k),(k,n)->(m,n)",
                   excluded=frozenset({"slices"}))
def _matmul_f64_2d(a, b, *, slices=DEFAULT_SLICES):
    s = int(slices)
    k = a.shape[-1]
    sa = _scale(a, axis=-1)           # (m, 1)
    sb = _scale(b, axis=-2)           # (1, n)
    m, n = a.shape[-2], b.shape[-1]
    form = _sequenced_form(m, n, k, s)
    if form == "slices":
        # deep product: the wide operand peeled in one pass, the s^2 slots
        # of the padded scan (zeros among them, on the narrow operand's
        # side) emitted as s dots
        # B on a tie: the reduction's M = V^H W would hand the kernel V^H,
        # a column block of the trailing matrix transposed, and the
        # compiler then laid the whole trailing matrix out column-major
        # and copied it twice a step (PERF.md section 6)
        a_wide = m > n
        wide, narrow = (a, sa), (b, sb)
        if not a_wide:
            wide, narrow = narrow, wide
        _count_macs("scan_slices", m * n, s * (s + 1) // 2 * k, s * s * k)
        _count_peel("chain", narrow[0].size)
        acc = None
        for d, g_d in enumerate(_scan_slices(
                _peel_stack(_normalize(*wide), s),
                _peel_slices(_normalize(*narrow), s), a_wide, s)):
            acc = _fold_group(acc, d, g_d)
        return _apply_scales(acc, sa, sb)
    _count_peel("chain", a.size + b.size)
    ia = _peel_slices(_normalize(a, sa), s)
    ib = _peel_slices(_normalize(b, sb), s)
    if form == "groups":
        # panel product: uniform zero-padded groups scanned with an f64
        # carry (one body; s (s - 1) / 2 of its s^2 slots are zero columns)
        k_pad = s * k
        ga = jnp.stack([_pad_k(jnp.concatenate(
            [ia[t] for t in range(d + 1)], axis=-1), k_pad, -1)
            for d in range(s)])
        gb = jnp.stack([_pad_k(jnp.concatenate(
            [ib[d - t] for t in range(d + 1)], axis=-2), k_pad, -2)
            for d in range(s)])
        _count_macs("scan", m * n, s * (s + 1) // 2 * k, s * ga.shape[-1])

        def body(carry, xs):
            a_d, b_d, scale = xs
            p = _dot_i8(a_d, b_d)
            return carry + p.astype(jnp.float64) * scale, None

        acc, _ = lax.scan(body, jnp.zeros((m, n), jnp.float64),
                          (ga, gb, _group_scales(s)))
        return _apply_scales(acc, sa, sb)
    # ragged groups: one dot per shift group over k-concatenated operands,
    # the d + 1 pair sums riding the MXU accumulator (the concatenated
    # contraction is exactly the sum of the per-pair ones, so the
    # exactness bounds of _dot_i8/_dot_bf16 apply to (d + 1) k unchanged).
    # Group d's operands [I_0 | ... | I_d] and [J_d; ...; J_0] are
    # contiguous slices of ONE concatenation per operand, at their real
    # depth (d + 1) k
    a_cat = jnp.concatenate(ia, axis=-1)          # [I_0 | ... | I_{s-1}]
    b_rev = jnp.concatenate(ib[::-1], axis=-2)    # [J_{s-1}; ...; J_0]

    def group(d, a_cat, b_rev):
        return _group_dot(a_cat[..., :(d + 1) * k],
                          b_rev[..., (s - 1 - d) * k:, :], d + 1, k)

    acc = _fold_groups(s, group, (a_cat, b_rev))
    return _apply_scales(acc, sa, sb)


def matmul_f64(a, b, *, slices: int = DEFAULT_SLICES):
    """``a @ b`` for real float64 inputs through int8 MXU passes.

    Batch dims broadcast like ``jnp.matmul``. ``slices`` trades speed for
    mantissa coverage: gemm count is ``slices*(slices+1)/2``; accuracy is
    ``~2^(-7*slices)`` relative to ``rowmax(a)*colmax(b)`` (8 -> f64-grade,
    6 -> ~f64 with 3 fewer mantissa digits at half the gemms).

    One schedule: the shift groups fold into an f64 accumulator in the
    order ``d = 0..s-1``, one int32 group partial live at a time, in the
    form the operands' shape picks (:func:`_sequenced_form`: ragged
    groups, a scan over padded groups, or a scan over the wide operand's
    slices); every form gives the same bits.
    """
    return _matmul_f64_2d(a, b, slices=slices)


@functools.partial(jnp.vectorize, signature="(m,k)->(m,m)",
                   excluded=frozenset({"slices"}))
def _syrk_f64_2d(a, *, slices=DEFAULT_SLICES):
    s = int(slices)
    k = a.shape[-1]
    sa = _scale(a, axis=-1)           # (m, 1)
    _count_peel("chain", a.size)
    ia = _peel_slices(_normalize(a, sa), s)
    cast = (lambda x: x) if _exact_i32(s, k) \
        else (lambda x: x.astype(jnp.float64))
    # one dot for the strict-upper pair half of each shift group (mirrored
    # once), plus the even-shift diagonal pair separately: the syrk MAC
    # halving with the pair sums on the MXU accumulator. A padded scan:
    # ragged groups would be s + s // 2 kernels a product where the scan
    # has one body, and its only caller of size is the unrolled local
    # Cholesky, where they are resident code, +95 MiB at N=4096 (PERF.md
    # section 6, PR 28). Half-pair concats zero-padded to the widest
    # group, the diagonal pair as a zeroed operand on odd shifts (its dot
    # is then exactly zero)
    m = a.shape[-2]
    halves = [[t for t in range(d // 2 + 1) if t != d - t] for d in range(s)]
    h_pad = max(max((len(h) for h in halves), default=0), 1) * k
    zero = jnp.zeros_like(ia[0])

    def half_cat(idx):
        return _pad_k(jnp.concatenate([ia[t] for t in idx], axis=-1)
                      if idx else zero, h_pad, -1)

    ga = jnp.stack([half_cat(halves[d]) for d in range(s)])
    gb = jnp.stack([half_cat([d - t for t in halves[d]]) for d in range(s)])
    gd = jnp.stack([ia[d // 2] if d % 2 == 0 else zero for d in range(s)])
    _count_macs("scan", m * m, (sum(map(len, halves)) + (s + 1) // 2) * k,
                s * (ga.shape[-1] + gd.shape[-1]))

    def body(carry, xs):
        a_d, b_d, d_d, scale = xs
        # cast BEFORE the elementwise pair sum when the group magnitude
        # bound exceeds int32: 2 g + diag can wrap in the window where
        # s*k*2^12 >= 2^31 but the half-concat depth is still below
        # _dot_i8's own f64-chunking threshold
        p = 2 * cast(_dot_i8(a_d, jnp.swapaxes(b_d, -1, -2))) \
            + cast(_dot_i8(d_d, jnp.swapaxes(d_d, -1, -2)))
        return carry + p.astype(jnp.float64) * scale, None

    acc, _ = lax.scan(body, jnp.zeros((m, m), jnp.float64),
                      (ga, gb, gd, _group_scales(s, half=True)))
    return _apply_scales(_mirror(acc), sa, jnp.swapaxes(sa, -1, -2))


def syrk_f64(a, *, slices: int = DEFAULT_SLICES):
    """``a @ a.T`` (symmetric rank-k update) for real float64 ``a`` through
    int8 MXU passes; slices of ``a`` are peeled once and pair symmetry halves
    the gemm count vs :func:`matmul_f64`."""
    return _syrk_f64_2d(a, slices=slices)


# ---------------------------------------------------------------------------
# complex128: composed from real products (3-multiplication Karatsuba form)
# ---------------------------------------------------------------------------

def matmul_c128(a, b, *, slices: int = DEFAULT_SLICES):
    """``a @ b`` for complex128 inputs via four real :func:`matmul_f64`
    products, each on the int8 MXU path.

    The 3-product Karatsuba form (``(ar+ai)(br+bi) - p1 - p2``) is NOT used:
    its operand sums overflow for component magnitudes above ``DBL_MAX/2``
    and its intermediates grow ~2x beyond what a native complex product
    forms — the 4-product form has exactly the native overflow and error
    profile, and ozaki gemms are cheap enough that the extra product is the
    right trade."""
    ar, ai = jnp.real(a), jnp.imag(a)
    br, bi = jnp.real(b), jnp.imag(b)
    re = matmul_f64(ar, br, slices=slices) - matmul_f64(ai, bi, slices=slices)
    im = matmul_f64(ar, bi, slices=slices) + matmul_f64(ai, br, slices=slices)
    return lax.complex(re, im)


def herk_c128(a, *, slices: int = DEFAULT_SLICES):
    """``a @ a^H`` (Hermitian gram block) for complex128 ``a``: two real
    syrks for the real part, one real matmul (plus its transpose, free) for
    the imaginary part — 2 peels + ~1.5x one real product's gemm count."""
    ar, ai = jnp.real(a), jnp.imag(a)
    re = syrk_f64(ar, slices=slices) + syrk_f64(ai, slices=slices)
    m = matmul_f64(ai, jnp.swapaxes(ar, -1, -2), slices=slices)
    return lax.complex(re, m - jnp.swapaxes(m, -1, -2))
