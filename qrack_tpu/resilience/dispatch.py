"""Watchdogged dispatch: timeout / retry / exponential backoff around
the hang-prone sites.

Env knobs (read into :func:`params`, overridable via :func:`configure`):

* ``QRACK_TPU_DISPATCH_TIMEOUT`` — seconds one dispatch may take
  before the watchdog declares it timed out (0, the default, disables
  the watchdog: dispatch runs inline with no extra thread).
* ``QRACK_TPU_MAX_RETRIES`` — retries after the first failed attempt
  (default 2 → up to 3 attempts).
* ``QRACK_TPU_BACKOFF`` — base backoff seconds; attempt k sleeps
  ``backoff * 2**k`` (default 0.05).
* ``QRACK_TPU_VALIDATE`` — 1 = finite-check every guarded output
  (forces completion of that output; an opt-in debugging net).

The watchdog runs the dispatch on a daemon thread and abandons it on
timeout — a wedged XLA call cannot be cancelled from Python, but the
CALLER gets control back (:class:`~.errors.DispatchTimeout`), which is
the property the ad-hoc shell watchdogs had and the library never did.
Abandoned threads are counted (`resilience.abandoned_threads`); a
process that accumulates them is talking to a hung backend and should
let the breaker take over.

Retry is only safe because every injected fault fires at site entry
(faults.py) and real XLA runtime errors surface before results are
committed; donated operands of a genuinely-completed-then-failed
dispatch cannot be replayed, which is why retries exhausting escalates
to :class:`~.errors.DispatchGiveUp` and engine-level failover
(resilience/failover.py) rather than looping forever.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from typing import Optional

from .. import telemetry as _tele
from . import breaker as _breaker
from . import faults as _faults
from .errors import DispatchFailure, DispatchGiveUp, DispatchTimeout

_ABANDONED = 0  # threads left behind by watchdog timeouts (diagnostic)


@dataclass
class DispatchParams:
    timeout_s: float = 0.0
    max_retries: int = 2
    backoff_s: float = 0.05
    validate: bool = False

    @classmethod
    def from_env(cls) -> "DispatchParams":
        return cls(
            timeout_s=float(os.environ.get("QRACK_TPU_DISPATCH_TIMEOUT", "0")),
            max_retries=int(os.environ.get("QRACK_TPU_MAX_RETRIES", "2")),
            backoff_s=float(os.environ.get("QRACK_TPU_BACKOFF", "0.05")),
            validate=os.environ.get("QRACK_TPU_VALIDATE", "") not in ("", "0"),
        )


_PARAMS: Optional[DispatchParams] = None


def params() -> DispatchParams:
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = DispatchParams.from_env()
    return _PARAMS


def configure(**kw) -> DispatchParams:
    """Override dispatch params at runtime (tests); unknown keys fail.
    Call with no arguments to re-read the environment."""
    global _PARAMS
    if not kw:
        _PARAMS = DispatchParams.from_env()
        return _PARAMS
    p = params()
    for k, v in kw.items():
        if not hasattr(p, k):
            raise AttributeError(f"unknown dispatch param {k!r}")
        setattr(p, k, v)
    return p


def _is_xla_runtime_error(exc: BaseException) -> bool:
    """True for the backend's runtime error class (link loss, OOM,
    deleted-buffer replay...) without importing jaxlib eagerly."""
    for cls in type(exc).__mro__:
        if cls.__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
            return True
    return False


def _hang_stub(timeout_s: float):
    """Stand-in body for the injected `hang` kind: sleeps long enough
    that only the watchdog can end the dispatch, but bounded so a
    watchdog-less run does not wedge forever."""
    nap = min(max(4.0 * timeout_s, 0.5), 30.0)

    def stub():
        time.sleep(nap)
        raise DispatchTimeout("<hang>", timeout_s or nap,
                              "injected hang outlived the dispatch")

    return stub


def _run_with_watchdog(site: str, fn, args, kwargs, timeout_s: float):
    box = {}

    def worker():
        try:
            box["out"] = fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 — re-raised in caller
            box["err"] = e

    t = threading.Thread(target=worker, daemon=True,
                         name=f"qrack-dispatch-{site}")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        global _ABANDONED
        _ABANDONED += 1
        if _tele._ENABLED:
            _tele.event(f"resilience.timeout.{site}", timeout_s=timeout_s,
                        abandoned_threads=_ABANDONED)
            _tele.inc("resilience.abandoned_threads")
        raise DispatchTimeout(site, timeout_s)
    if "err" in box:
        raise box["err"]
    return box["out"]


def call_guarded(site: str, fn, args=(), kwargs=None):
    """Run `fn(*args, **kwargs)` as one guarded dispatch at `site`:
    breaker gate, fault injection, watchdog timeout, finite validation,
    then retry with exponential backoff.  Raises BreakerOpen (breaker
    refused) or DispatchGiveUp (retries exhausted) — the FAILOVER_ERRORS
    the engine wrappers recover from."""
    kwargs = kwargs or {}
    if _faults.is_suspended():
        # recovery path (failover snapshot): raw call — an open breaker
        # must not refuse the read that gets state OFF the failing engine
        return fn(*args, **kwargs)
    p = params()
    br = _breaker.get_breaker()
    last: Optional[DispatchFailure] = None
    attempts = max(1, p.max_retries + 1)
    for attempt in range(attempts):
        br.allow(site)  # raises BreakerOpen: stop hammering the accelerator
        try:
            directive = _faults.check(site)  # may raise a DispatchFailure
            if directive == "hang":
                out = _run_with_watchdog(site, _hang_stub(p.timeout_s), (), {},
                                         p.timeout_s if p.timeout_s > 0 else 35.0)
            elif p.timeout_s > 0:
                out = _run_with_watchdog(site, fn, args, kwargs, p.timeout_s)
            else:
                out = fn(*args, **kwargs)
            if _faults._HAS_CORRUPT:
                # amp-corrupt fires at site EXIT: the dispatch SUCCEEDS
                # and hands back a silently-wrong result (faults.py)
                out = _faults.corrupt_output(site, out)
            if p.validate:
                _faults.validate_finite(site, out)
            br.record_success()
            return out
        except DispatchFailure as e:
            last = e
            br.record_failure(site)
            if _tele._ENABLED:
                _tele.inc(f"resilience.failure.{site}")
            if not e.retryable:
                break
        except Exception as e:  # noqa: BLE001 — only XLA errors handled
            if not _is_xla_runtime_error(e):
                raise
            last = DispatchFailure(site, f"{type(e).__name__}: {e}")
            br.record_failure(site)
            if _tele._ENABLED:
                _tele.inc(f"resilience.failure.{site}")
        if attempt + 1 < attempts:
            if _tele._ENABLED:
                _tele.event(f"resilience.retry.{site}", attempt=attempt + 1,
                            cause=getattr(last, "kind", "failure"))
            if p.backoff_s > 0:
                time.sleep(p.backoff_s * (2 ** attempt))
    raise DispatchGiveUp(site, last)


def guarded(site: str, fn, *args, **kwargs):
    """Sugar: positional-args form of :func:`call_guarded`."""
    return call_guarded(site, fn, args, kwargs)


def guard_callable(site: str, fn):
    """Closure form for program objects fetched per dispatch (the pager
    `_program` path): returns a callable routing through call_guarded."""
    def run(*args, **kwargs):
        return call_guarded(site, fn, args, kwargs)

    run._guarded_site = site
    run._guarded_fn = fn
    return run


_RES_PKG = None  # the qrack_tpu.resilience module, bound after its init


def _res_pkg():
    global _RES_PKG
    if _RES_PKG is None:
        import importlib

        _RES_PKG = importlib.import_module(__package__)
    return _RES_PKG


class _GuardedProgram:
    """Persistent wrapper over a module-level jitted program (the
    QEngineTPU `_jit` path).  Disabled cost is one module-attribute read
    and a truth test — the telemetry `_JitProgram` discipline."""

    __slots__ = ("_fn", "_site")

    def __init__(self, site: str, fn):
        self._fn = fn
        self._site = site

    def __call__(self, *args, **kwargs):
        pkg = _RES_PKG or _res_pkg()  # late: runtime enable() must be seen
        if not pkg._ACTIVE:
            return self._fn(*args, **kwargs)
        return call_guarded(self._site, self._fn, args, kwargs)

    def __getattr__(self, attr):  # _cache_size/lower/etc. pass through
        return getattr(self._fn, attr)


def instrument_dispatch(site: str, fn):
    return _GuardedProgram(site, fn)
