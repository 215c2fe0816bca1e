"""Pallas TPU kernels for the hot tile ops.

The reference needed custom CUDA kernels where vendor libraries fell short
(SURVEY §2/L5). On TPU most of those collapse into trivial XLA ops; the one
place a custom kernel genuinely pays is the Cholesky trailing update in SPMD
form: the batched einsum over local tile pairs computes the FULL (rows x
cols) rectangle and then masks, spending ~2x the required MXU flops (only
trailing lower-triangle tile pairs matter). This kernel predicates per tile
pair with ``@pl.when``, so masked-out pairs skip the matmul entirely —
exact-flop trailing updates with the masking fused into the epilogue.

``mode`` per tile pair: 0 = untouched, 1 = full update, 2 = update only the
within-tile lower triangle (diagonal tiles of the uplo='L' sweep), 3 = only
the within-tile upper triangle (diagonal tiles of the uplo='U' sweep; the
caller passes transposed panel tiles so the contraction stays vr @ vc^T).

Supported dtypes: float32 / bfloat16 (MXU-native). float64 and complex fall
back to the einsum path at the call site (TPU f64 is emulated anyway; complex
matmul is not a single MXU op).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: Block index 0 of every Pallas index map in this package, as int32: the
#: library always runs under ``jax_enable_x64``, where a Python ``0`` becomes
#: an i64 constant, which Mosaic refuses in an index map's return.
I0 = np.int32(0)


def _update_kernel(mode_ref, vr_ref, vc_ref, a_ref, out_ref):
    # whole (R, C) mode table in SMEM, indexed by the grid step in the
    # kernel body (TPU lowering rejects sub-(8, 128) SMEM blocks and
    # loads inside the index map)
    mode = mode_ref[pl.program_id(0), pl.program_id(1)]

    @pl.when(mode == 0)
    def _():
        out_ref[...] = a_ref[...]

    @pl.when(mode > 0)
    def _():
        acc = jax.lax.dot_general(
            vr_ref[0], vc_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        a = a_ref[0, 0].astype(jnp.float32)
        nb = a.shape[-1]
        # signed distance below the diagonal, flipped for the upper
        # sweep and flattened to 0 for full tiles: ONE integer compare
        # gives the keep mask (Mosaic cannot select between two boolean
        # vectors, so the mode is folded in before the compare)
        below = (jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
                 - jax.lax.broadcasted_iota(jnp.int32, (nb, nb), 1))
        below = jnp.where(mode == 3, -below, below)
        below = jnp.where(mode == 1, jnp.zeros_like(below), below)
        out_ref[0, 0] = jnp.where(below >= 0, a - acc, a).astype(
            out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_trailing_update(a, vr, vc, mode, *, interpret: bool = False):
    """``a[r,c] -= vr[r] @ vc[c]^T`` where ``mode[r,c]`` directs the update
    (0 skip / 1 full / 2 tile lower triangle / 3 tile upper triangle).
    Shapes: a (R, C, nb, nb),
    vr (R, nb, nb), vc (C, nb, nb), mode (R, C) int32."""
    R, C, nb, _ = a.shape
    return pl.pallas_call(
        _update_kernel,
        grid=(R, C),
        in_specs=[
            pl.BlockSpec((R, C), lambda r, c: (I0, I0),
                         memory_space=pltpu.SMEM),                 # mode
            pl.BlockSpec((1, nb, nb), lambda r, c: (r, I0, I0)),  # vr
            pl.BlockSpec((1, nb, nb), lambda r, c: (c, I0, I0)),  # vc
            pl.BlockSpec((1, 1, nb, nb), lambda r, c: (r, c, I0, I0)),
        ],
        out_specs=pl.BlockSpec((1, 1, nb, nb),
                               lambda r, c: (r, c, I0, I0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        # the trailing block is updated in place (the reference's
        # semantics). It also keeps the XLA TPU fusion pass alive: without
        # the alias it aborts on a partitioned program that holds this
        # kernel AND the fused panel kernels
        # (tests/test_chip_compile.py::test_dist_f32_cholesky_steps_compile)
        input_output_aliases={3: 0},
        interpret=interpret,
    )(mode, vr, vc, a)


def supports_pallas_update(dtype, platform: str) -> bool:
    """Gate: MXU-native real dtypes on real TPU hardware.

    ``DLAF_FORCE_PALLAS_UPDATE=1`` drops the platform requirement so tests can
    exercise the Pallas integration path off-TPU (the call site then runs the
    kernel in interpret mode).

    Fault injection (``health.inject.disable_pallas``) forces the gate
    closed; when that flips a would-be-True answer the pallas -> XLA
    degradation is registered (dlaf_fallback_total{site="pallas_update"},
    strict mode raises) — the platform/dtype gate itself is route policy,
    not degradation, and stays uncounted.
    """
    import os

    dtype_ok = jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                    jnp.dtype(jnp.bfloat16))
    supported = dtype_ok if os.environ.get(
        "DLAF_FORCE_PALLAS_UPDATE"  # dlaf: disable=lint-unregistered-knob(CI/test hook forcing the pallas route on CPU interpret mode; not a user-facing runtime knob)
    ) == "1" \
        else (platform == "tpu" and dtype_ok)
    if supported:
        from ..health.registry import route_available

        return route_available("pallas", "pallas_update")
    return supported
