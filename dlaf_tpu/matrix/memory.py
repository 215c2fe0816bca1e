"""Device-memory placement.

TPU-native counterpart of the reference's ``memory/`` layer
(``MemoryChunk``/``MemoryView`` over umpire host/device pools,
``memory/memory_chunk.h:38-165``): PJRT owns allocation, pooling, pinning,
and non-owning host wraps (numpy views), so the one placement decision left
to the framework is host→HBM transfer with a sharding — :func:`place`, the
H2D path of every :class:`~dlaf_tpu.matrix.matrix.Matrix` construction and
checkpoint restore. In-place reuse (the reference's tile writes into pooled
chunks) is expressed per jit boundary via buffer donation where an
algorithm needs it, not as a pool API.

complex128 transfer fallback: some PJRT transfer paths reject complex128
buffers even though complex128 *compute* works through the X64 rewrite
(suspected on a v5e, 2026-07-31: config #3's ``device_put`` of the c128
input died first thing; the root cause is still open). :func:`place`/:func:`fetch` try the direct
transfer first and, on failure, retry with the real and imaginary parts as
two f64 transfers combined by ``lax.complex`` on the destination side; the
mode latches process-wide (with a warning) only when the pair retry
actually succeeds, so transient backend failures — which fail both ways —
never flip it.

Scope limits of the fallback (round-2 advisory): only the transfer-error
types in :data:`_TRANSFER_ERRORS` trigger the retry — and RESOURCE_EXHAUSTED
(device OOM) is re-raised without one, since the pair path needs MORE
transient memory, not less. PJRT transfers can also fail ASYNCHRONOUSLY:
``device_put`` may return a future-backed array whose failure only
surfaces at consumption (``block_until_ready``/compute). Such deferred
failures bypass this guard entirely — a wedge observed at
``block_until_ready`` will NOT auto-latch pair mode; set it explicitly by
calling :func:`_latch_pair_mode` or retry at the operator level.
"""

from __future__ import annotations

import warnings

import numpy as np

import jax
import jax.numpy as jnp

#: Tri-state per-process cache: None = direct complex transfers untested,
#: False/None treated as direct-first, True = pair fallback required.
_complex_pair_mode = None

# the PJRT runtime-error type (transfer rejections, backend faults)
from jax.errors import JaxRuntimeError as _JaxRuntimeError

#: Exception types that plausibly mean "this transfer path rejected the
#: buffer" and are worth a pair retry. Bare ``Exception`` used to be
#: caught here; that routed unrelated failures (OOM, interpreter
#: teardown) into a doomed second transfer attempt.
_TRANSFER_ERRORS = (_JaxRuntimeError, ValueError, TypeError)


def _retryable_transfer_error(e: Exception) -> bool:
    """A pair retry is sensible: a recognized transfer-error type that is
    NOT device OOM (RESOURCE_EXHAUSTED needs less memory, and the pair
    path transiently needs more)."""
    return (isinstance(e, _TRANSFER_ERRORS)
            and "RESOURCE_EXHAUSTED" not in str(e))

_combine = jax.jit(jax.lax.complex)


def _is_device_array(x) -> bool:
    return hasattr(x, "devices")


def _place_pair(array, sharding):
    if _is_device_array(array):
        # device-resident complex input (e.g. the distributed reshard in
        # Matrix._shard): split on device — no host round trip, and no
        # direct complex transfer
        re = jax.device_put(jnp.real(array), sharding)
        im = jax.device_put(jnp.imag(array), sharding)
    else:
        a = np.asarray(array)
        re = jax.device_put(np.ascontiguousarray(a.real), sharding)
        im = jax.device_put(np.ascontiguousarray(a.imag), sharding)
    return _combine(re, im)


#: Direct-complex failures whose health probe passed anyway (a
#: sharding/size-specific transfer bug the tiny probe cannot see); after
#: a few of these the pair mode latches regardless.
_probe_passed_failures = 0
_PROBE_PASS_LATCH_AFTER = 3


def _latch_pair_mode(op: str):
    """Latch when a TINY direct complex transfer also fails right now
    (clear-cut backend rejection), or after several CONSECUTIVE direct
    failures whose probe passed (a transfer bug specific to the real
    shapes/shardings that the 1-element probe cannot reproduce; the
    counter resets on any direct success). One-off transient failures
    latch nothing."""
    global _complex_pair_mode, _probe_passed_failures
    if _complex_pair_mode is True:
        return
    reason = f"direct complex128 {op} failed; the 1-element probe failed too"
    try:
        jax.device_get(jax.device_put(np.zeros((1,), np.complex128)))
        _probe_passed_failures += 1
        if _probe_passed_failures < _PROBE_PASS_LATCH_AFTER:
            return   # probably transient; keep trying direct first
        reason = (f"direct complex128 {op} failed "
                  f"{_probe_passed_failures} consecutive times while the "
                  "1-element probe kept passing (shape/sharding-specific "
                  "transfer bug)")
    except Exception:
        pass
    warnings.warn(
        f"{reason}; the real/imag pair transfer succeeded — enabling pair "
        "mode for all further complex transfers in this process "
        "(matrix/memory.py)")
    _complex_pair_mode = True


def place(array, sharding=None):
    """Move a host array into device memory (reference: MemoryChunk alloc +
    H2D); with a NamedSharding this is the distributed placement. Also the
    device-to-device reshard path for device-array inputs."""
    global _probe_passed_failures
    if np.iscomplexobj(array) and _complex_pair_mode:
        return _place_pair(array, sharding)
    try:
        out = jax.device_put(array, sharding)
        if np.iscomplexobj(array):
            _probe_passed_failures = 0   # direct works; reset the streak
        return out
    except Exception as e:
        if not np.iscomplexobj(array) or not _retryable_transfer_error(e):
            raise
        out = _place_pair(array, sharding)   # raises too if truly broken
        _latch_pair_mode("device_put")
        return out


def as_device(x):
    """``jnp.asarray`` for possibly-host inputs, routed through
    :func:`place` so complex host arrays get the pair-transfer fallback;
    device arrays pass through untouched."""
    if _is_device_array(x):
        return x
    return place(np.asarray(x))


def fetch(x) -> np.ndarray:
    """Device array -> host numpy (reference: D2H copy), with the symmetric
    complex-pair fallback: real/imag computed on device, transferred as two
    real arrays, combined on host."""
    global _probe_passed_failures
    if np.iscomplexobj(x) and _complex_pair_mode:
        return _fetch_pair(x)
    try:
        out = np.asarray(jax.device_get(x))
        if np.iscomplexobj(x):
            _probe_passed_failures = 0   # direct works; reset the streak
        return out
    except Exception as e:
        if not np.iscomplexobj(x) or not _retryable_transfer_error(e):
            raise
        out = _fetch_pair(x)
        _latch_pair_mode("device_get")
        return out


def _fetch_pair(x) -> np.ndarray:
    re = np.asarray(jax.device_get(jnp.real(x)))
    im = np.asarray(jax.device_get(jnp.imag(x)))
    return re + 1j * im
