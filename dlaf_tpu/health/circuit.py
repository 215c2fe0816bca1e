"""Circuit breakers: stop hammering a failing site (docs/robustness.md §3).

A retry policy protects ONE call; a breaker protects the SITE across
calls. Under sustained failure (a lost device, a native
library that segfault-loops, a bucket program that OOMs every dispatch)
retrying every submit multiplies the damage — the breaker converts the
N-th consecutive failure into fast, cheap rejections until a cooldown
lets one probe through.

State machine (the classic three states):

    closed ──(threshold consecutive failures)──> open
    open ──(cooldown elapsed; ONE probe admitted)──> half_open
    half_open ──probe success──> closed
    half_open ──probe failure──> open  (cooldown restarts)

``allow()`` raises :class:`~dlaf_tpu.health.errors.CircuitOpenError`
when the breaker rejects; ``record_success``/``record_failure`` feed
outcomes back. Any success fully closes the breaker (consecutive-failure
count resets). Thread-safe: one lock per breaker; in ``half_open``
exactly one in-flight probe is admitted — concurrent callers are
rejected until it resolves, so a recovering dependency is never
thundering-herded.

Every transition sets the ``dlaf_circuit_state{site}`` gauge
(0 = closed, 1 = half_open, 2 = open) and lands as a ``resilience``
JSONL record (events ``circuit_open`` / ``circuit_half_open`` /
``circuit_close``), so an artifact shows exactly when a site tripped and
recovered — and ``--require-resilience`` REJECTS an artifact whose final
snapshot leaves any breaker open (a run that ended in a tripped state
must not pass CI silently).

Defaults come from the config knobs ``DLAF_CIRCUIT_THRESHOLD`` /
``DLAF_CIRCUIT_COOLDOWN_S``; per-breaker overrides (and an injectable
``clock`` for deterministic tests) are constructor arguments. The
process-wide registry (:func:`breaker`) keys breakers by site — the
serving queue uses one per bucket program, ``run_with_fallback`` one per
degradation site.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

from .. import obs
from .errors import CircuitOpenError

#: Gauge holding each breaker's state (labels: site).
CIRCUIT_GAUGE = "dlaf_circuit_state"

#: Gauge values (also the ``state()`` -> value mapping).
STATE_VALUES = {"closed": 0, "half_open": 1, "open": 2}

_EVENTS = {"closed": "circuit_close", "half_open": "circuit_half_open",
           "open": "circuit_open"}


class CircuitBreaker:
    """One site's breaker (module docstring). ``threshold``/``cooldown_s``
    default to the config knobs at construction; ``clock`` is injectable
    so cooldown behavior is deterministic under test."""

    def __init__(self, site: str, *, threshold: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        from ..config import get_configuration

        cfg = get_configuration()
        self.site = str(site)
        self.threshold = int(threshold if threshold is not None
                             else cfg.circuit_threshold)
        self.cooldown_s = float(cooldown_s if cooldown_s is not None
                                else cfg.circuit_cooldown_s)
        self.clock = clock
        self._lock = threading.Lock()
        self._state = "closed"
        self._consecutive = 0
        self._opened_at = 0.0
        self._probe_live = False

    # -- state -----------------------------------------------------------

    def state(self) -> str:
        """"closed" | "half_open" | "open" (point-in-time; an elapsed
        cooldown still reports "open" until a caller's allow() admits
        the probe — transitions happen on calls, not on a timer)."""
        with self._lock:
            return self._state

    def _set(self, state: str) -> None:
        """Transition (lock held): gauge + resilience record. The
        flight-recorder dump a transition TO open owes
        (docs/observability.md trigger catalog) is fired by the caller
        AFTER the lock is released — the dump is file I/O (write +
        fsync + replace), and holding the breaker lock through it would
        stall every dispatch thread and the /healthz scrape at exactly
        the moment of an incident storm."""
        if state == self._state:
            return
        self._state = state
        obs.gauge(CIRCUIT_GAUGE, site=self.site).set(
            float(STATE_VALUES[state]))
        obs.emit_event("resilience", site=self.site, event=_EVENTS[state],
                       attrs={"consecutive": self._consecutive})

    # -- the breaker protocol --------------------------------------------

    def allow(self) -> None:
        """Admit or reject one call. Raises :class:`CircuitOpenError`
        when open (cooldown pending) or when a half-open probe is already
        in flight; admits exactly one probe once the cooldown elapses."""
        with self._lock:
            if self._state == "closed":
                return
            now = self.clock()
            if self._state == "open":
                remaining = self.cooldown_s - (now - self._opened_at)
                if remaining > 0:
                    raise CircuitOpenError(self.site, retry_in_s=remaining)
                self._set("half_open")
                self._probe_live = True
                return          # this caller IS the probe
            # half_open: one probe at a time
            if self._probe_live:
                raise CircuitOpenError(self.site, retry_in_s=0.0)
            self._probe_live = True

    def record_success(self) -> None:
        """A call succeeded: any state fully closes (consecutive count
        resets — the site is healthy again)."""
        with self._lock:
            self._consecutive = 0
            self._probe_live = False
            self._set("closed")

    def record_failure(self) -> None:
        """A call failed: a half-open probe failure re-opens (cooldown
        restarts); the threshold-th consecutive closed-state failure
        opens. An opening trips the flight recorder (reason
        ``breaker_open``) — AFTER the lock is released (see
        :meth:`_set`) and after the transition record landed in the
        ring, so the dump includes the opening itself."""
        opened = False
        with self._lock:
            self._consecutive += 1
            if self._state == "half_open":
                self._probe_live = False
                self._opened_at = self.clock()
                self._set("open")
                opened = True
            elif self._state == "closed" \
                    and self._consecutive >= self.threshold:
                self._opened_at = self.clock()
                self._set("open")
                opened = True
            consecutive = self._consecutive
        if opened:
            from ..obs import flight

            flight.trigger("breaker_open", site=self.site,
                           consecutive=consecutive)

    def reset(self) -> None:
        """Force-close (tests / injection reset-safety)."""
        with self._lock:
            self._consecutive = 0
            self._probe_live = False
            self._set("closed")


# ---------------------------------------------------------------------------
# Process registry
# ---------------------------------------------------------------------------

_BREAKERS: Dict[str, CircuitBreaker] = {}
_REG_LOCK = threading.Lock()


def breaker(site: str, **kwargs) -> CircuitBreaker:
    """The process breaker for ``site``, created on first use. On later
    calls ``threshold``/``cooldown_s`` are ignored (first creation wins;
    use :func:`reset` + recreate to change them), but an explicitly
    passed ``clock`` REBINDS — the active caller drives time, so a
    breaker created under one queue's injected test clock can never
    wedge a later caller's cooldown (its ``now - opened_at`` would
    otherwise never elapse)."""
    with _REG_LOCK:
        br = _BREAKERS.get(site)
        if br is None:
            br = _BREAKERS[site] = CircuitBreaker(site, **kwargs)
        elif "clock" in kwargs:
            br.clock = kwargs["clock"]
        return br


def peek(site: str) -> Optional[str]:
    """``site``'s state without creating a breaker (None = never used)."""
    with _REG_LOCK:
        br = _BREAKERS.get(site)
    return br.state() if br is not None else None


def states() -> dict:
    """``{site: state_name}`` for every registered breaker — the live
    ``/healthz`` endpoint's breaker table (dlaf_tpu/obs/exporter.py),
    sorted by site so the JSON is deterministic."""
    with _REG_LOCK:
        live = sorted(_BREAKERS.items())
    return {site: br.state() for site, br in live}


def reset(prefix: Optional[str] = None) -> int:
    """Close and drop registered breakers (all, or those whose site
    starts with ``prefix``); returns how many were dropped. The
    injection contexts call this on exit so an injected failure storm
    never leaves a breaker open into unrelated code (reset-safety)."""
    with _REG_LOCK:
        sites = [s for s in _BREAKERS
                 if prefix is None or s.startswith(prefix)]
        dropped = [_BREAKERS.pop(s) for s in sites]
    for br in dropped:
        br.reset()          # gauge back to closed before the drop
    return len(dropped)
