"""qrack_tpu.resilience — watchdogged dispatch, circuit breaker,
fault injection, and TPU→CPU failover.

The whole layer is OFF by default: every guarded site costs one
module-attribute read plus a truth test until :data:`_ACTIVE` flips
(the telemetry `_ENABLED` discipline).  Activation:

* env — ``QRACK_TPU_RESILIENCE=1``, or any nonempty
  ``QRACK_TPU_FAULTS`` (injecting faults implies you want the layer
  that catches them);
* runtime — :func:`enable` / :func:`disable` (tests).

Layout (import order matters — no cycles, no jax at import time):

* errors.py    — exception hierarchy (FAILOVER_ERRORS is the contract)
* faults.py    — deterministic injection (QRACK_TPU_FAULTS grammar)
* breaker.py   — process-wide circuit breaker
* dispatch.py  — call_guarded / instrument_dispatch (watchdog+retry)
* probe.py     — stdlib-only SIGTERM-first subprocess probe
* failover.py  — ResilientEngine + fail_over_engine (imports engines;
  loaded lazily by consumers, NOT here)
* integrity.py — silent-corruption detection, window replay, device
  quarantine (imports errors + telemetry only; loaded lazily by the
  flush path — see docs/INTEGRITY.md)

See docs/RESILIENCE.md.
"""

from __future__ import annotations

import os as _os

from .errors import (BreakerOpen, CorruptionDetected, DeviceLost,
                     DispatchFailure, DispatchGiveUp, DispatchTimeout,
                     FAILOVER_ERRORS, InjectedFault, NaNPoisoned,
                     ResilienceError)
from . import faults
from .breaker import CircuitBreaker, get_breaker, reset_breaker
from .dispatch import (DispatchParams, call_guarded, configure,
                       guard_callable, guarded, instrument_dispatch, params)
from .probe import ProbeResult, ensure_backend, run_probe

__all__ = [
    "ResilienceError", "DispatchFailure", "DispatchTimeout", "DeviceLost",
    "NaNPoisoned", "InjectedFault", "CorruptionDetected",
    "DispatchGiveUp", "BreakerOpen",
    "FAILOVER_ERRORS",
    "faults",
    "CircuitBreaker", "get_breaker", "reset_breaker",
    "DispatchParams", "params", "configure",
    "call_guarded", "guarded", "guard_callable", "instrument_dispatch",
    "run_probe", "ProbeResult", "ensure_backend",
    "active", "enable", "disable",
]

_ACTIVE: bool = (
    _os.environ.get("QRACK_TPU_RESILIENCE", "") not in ("", "0")
    or bool(_os.environ.get("QRACK_TPU_FAULTS", "").strip())
)


def active() -> bool:
    return _ACTIVE


def enable() -> None:
    global _ACTIVE
    _ACTIVE = True


def disable() -> None:
    global _ACTIVE
    _ACTIVE = False
