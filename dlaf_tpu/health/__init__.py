"""dlaf_tpu.health — failure detection, recovery, injection, degradation.

The robustness layer (docs/robustness.md), four surfaces:

* **Info plumbing** — ``cholesky(..., with_info=True)`` returns
  ``(L, info)`` with info = 1-based first failing global column computed
  in-graph (:mod:`.info`); analogous singular-diagonal detection for the
  triangular solve and HEGST (``matrix_diag_info``).
* **Recovery** — :func:`robust_cholesky` retries a failed factorization
  under an exponentially growing diagonal shift, raising a structured
  :class:`FactorizationError` when exhausted; the ``DLAF_CHECK`` knob
  adds opt-in finite guards on inputs/outputs (:mod:`.recovery`).
* **Fault injection** — :mod:`.inject`: deterministic, seedable faults
  (NaN a tile, corrupt one collective, force the native-library load to
  fail, disable a pallas/ozaki route) so every degradation path is
  testable end-to-end.
* **Degradation registry** — :mod:`.registry`: the four ad-hoc fallback
  chains (secular, band chase, pallas, ozaki) share one policy with
  ``dlaf_fallback_total{site,reason}`` counters and a strict mode
  (``DLAF_STRICT``) that raises instead of degrading.
"""

from __future__ import annotations

from . import circuit, info, inject, policy, registry  # noqa: F401
from .circuit import CIRCUIT_GAUGE, CircuitBreaker, breaker  # noqa: F401
from .errors import (CheckError, CircuitOpenError,  # noqa: F401
                     DeadlineExceededError,
                     DegradationError, FactorizationError, HealthError,
                     OverloadError, PreemptionError, ResumeError)
from .info import matrix_diag_info  # noqa: F401
from .policy import (DEADLINE_COUNTER, RETRY_COUNTER, RetryPolicy,  # noqa: F401
                     with_policy)
from .registry import (FALLBACK_COUNTER, report_fallback, route_available,  # noqa: F401
                       run_with_fallback, strict_mode)

__all__ = [
    "CheckError", "CircuitBreaker", "CircuitOpenError",
    "DeadlineExceededError", "DegradationError", "FactorizationError",
    "HealthError", "OverloadError", "PreemptionError", "ResumeError",
    "CIRCUIT_GAUGE", "DEADLINE_COUNTER", "FALLBACK_COUNTER",
    "RETRY_COUNTER", "BatchRecoveryResult", "RecoveryResult", "RetryPolicy",
    "breaker", "check_finite", "circuit", "inject", "info",
    "matrix_diag_info", "policy", "registry", "report_fallback", "resume",
    "robust_cholesky", "robust_cholesky_batched", "route_available",
    "run_with_fallback", "shift_diagonal", "strict_mode", "with_policy",
]

#: Symbols served lazily from .recovery / .resume (they import the matrix
#: layer; keeping them out of package-import time lets low-level modules —
#: comm, tile_ops — consult .inject/.registry/.policy without an import
#: cycle).
_LAZY = ("robust_cholesky", "robust_cholesky_batched", "RecoveryResult",
         "BatchRecoveryResult",
         "check_finite", "shift_diagonal", "recovery")


def __getattr__(name: str):
    if name in _LAZY:
        import importlib

        recovery = importlib.import_module(".recovery", __name__)
        globals()["recovery"] = recovery
        return recovery if name == "recovery" else getattr(recovery, name)
    if name == "resume":
        import importlib

        resume = importlib.import_module(".resume", __name__)
        globals()["resume"] = resume
        return resume
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
