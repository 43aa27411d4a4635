"""qrack_tpu.serve — multi-tenant serving over a single dispatch owner.

The library above this package is single-caller: every user owns an
engine and dispatches at will.  Serving inverts that: sessions are
tenants, ALL device traffic is serialized through one executor thread
(the one-jax-client-per-chip discipline, codified), same-shape circuit
jobs from different tenants are vmapped into one compiled program over
stacked amplitude planes, and admission control sheds load while the
resilience breaker says the backend is hung.

Layout:

* errors.py    — typed admission / lifecycle errors
* session.py   — Session + SessionManager (per-tenant rng, idle evict)
* scheduler.py — priority queue, admission control, batch windowing
* batcher.py   — shape-keyed vmapped batch programs (PR-1 ProgramCache)
* executor.py  — the dispatch-owner thread (call_guarded + failover)
* service.py   — QrackService, the in-process front API

Deliberately NOT imported from the qrack_tpu package root: a library
user who never serves pays zero import or dispatch cost.  See
docs/SERVING.md.
"""

from .errors import (AdmissionRejected, LoadShed, Overloaded,
                     QueueBudgetExceeded, QueueFull, ServeError,
                     ServiceStopped, SessionNotFound)
from .scheduler import JobHandle
from .service import QrackService

__all__ = [
    "QrackService", "JobHandle",
    "ServeError", "AdmissionRejected", "QueueFull", "LoadShed",
    "Overloaded", "QueueBudgetExceeded", "ServiceStopped",
    "SessionNotFound",
]
