"""Metric arithmetic of the benchmark: percentiles, residual digits and the
tolerance that decides ``correct``. Pure Python; no JAX, no numpy."""

from __future__ import annotations

import math

#: -log10 of the smallest residual that is told apart from zero (a residual
#: of exactly 0 would otherwise read as infinitely many digits).
MAX_DIGITS = 18.0


def percentile(values, q: float) -> float:
    """``q``-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default method), of a non-empty sequence."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def residual_digits(worst_residual: float) -> float:
    """Correct decimal digits of the worst relative residual: ``-log10``.
    NaN (a failed solve) reads as 0 digits."""
    if not worst_residual == worst_residual:
        return 0.0
    if worst_residual <= 0.0:
        return MAX_DIGITS
    return min(-math.log10(worst_residual), MAX_DIGITS)


def tolerance(guarantee: dict, n: int, platform: str) -> float:
    """``c * n * eps`` with the effective epsilon of the platform: f64 on a
    TPU is double-f32 emulation (``eps_tpu``, 2^-47), native elsewhere."""
    eps = guarantee["eps_tpu"] if platform == "tpu" else guarantee["eps_native"]
    return float(guarantee["c"]) * n * float(eps)
