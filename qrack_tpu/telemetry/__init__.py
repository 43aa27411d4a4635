"""Process-wide telemetry: counters, host spans, and exporters.

The whole engine stack is instrumented through this module (see
docs/OBSERVABILITY.md for the metric namespace).  Everything is gated
on ``QRACK_TPU_TELEMETRY=1`` (or :func:`enable`): when disabled, every
entry point returns after one module-global boolean read and records
NOTHING — hot gate paths guard with ``if telemetry._ENABLED:`` so even
the counter-name f-string is never built.

Three surfaces:

* **counters** — :func:`inc` monotonic named counters (gate dispatches
  by kind/width/engine, compile-cache hits/misses/evictions, pager
  exchange events + bytes, layer escalations).  :func:`observe` feeds a
  named duration into both the span aggregate and a merge-able
  log-bucket :class:`~qrack_tpu.telemetry.histogram.Histogram`, so
  :func:`percentile` can answer p50/p95/p99 SLO questions per process
  and — after the supervisor merges heartbeat-flushed snapshots —
  fleet-wide (docs/OBSERVABILITY.md "Fleet observability plane").
* **spans** — ``with telemetry.span("fuse.flush"):`` nestable timers
  of HOST time: what the calling thread spent inside, waiting behind
  the device included.  Device time is not a span's to give; it comes
  from a profiler trace.  While enabled a span also opens a
  ``jax.profiler.TraceAnnotation`` named ``"qrack." + name``, so that
  under an open ``jax.profiler`` trace it lands on the ``/host:`` plane
  of the same ``.xplane.pb`` as the device's operations, on their
  clock.  Each recorded span has a process-wide ``id`` and the
  ``parent`` id of the span that encloses it on its thread
  (:func:`self_seconds` is duration minus what children cover); the
  annotation carries the ``id`` as a statistic of its event, so a
  reader of the ``.xplane.pb`` finds a span's ring entry and, from the
  pairs, the offset between this module's clock and the trace's.  Spans
  and events carry the thread's current distributed-trace id
  (:func:`set_trace` / :func:`current_trace`) so per-process traces can
  be correlated across a fleet; ring timestamps are relative to the
  import epoch, whose wall-clock anchor (``epoch_unix_s``) rides in
  every snapshot so exporters can merge processes onto one timeline.
* **export** — :func:`snapshot` (plain dict), :func:`write_jsonl`
  (atexit-armed via ``QRACK_TPU_TELEMETRY_OUT=path``),
  and :func:`chrome_trace` (Perfetto-loadable trace-event JSON).

The hardware-truth profiling plane lives in two sibling modules:
:mod:`~qrack_tpu.telemetry.roofline` (per-dispatch planned-bytes ledger,
device-class fingerprints, the implied-bandwidth honesty clamp) and
:mod:`~qrack_tpu.telemetry.sentinel` (stdlib-only shared formula and
peak table) —
import them explicitly (``from qrack_tpu.telemetry import roofline``);
they are deliberately not re-exported here so this module stays
importable without touching them.

Compile-cache accounting comes from two helpers:
:class:`ProgramCache`, the bounded-LRU replacement for the module-level
``_PROGRAMS`` dicts (parallel/pager.py, engines/turboquant.py), and
:func:`instrument_jit`, a thin wrapper over module-level ``jax.jit``
programs (engines/tpu.py) that classifies each call as hit or miss via
the jitted function's ``_cache_size()``.  What a miss costs is JAX's to
say: once telemetry is on, JAX's own ``jax.monitoring`` events of its
compile pipeline are recorded as the spans ``compile.trace``,
``compile.lower``, ``compile.backend`` and ``compile.cache_load``,
children of whatever span is open on the thread that compiles.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Deque, Dict, List, Optional

from .histogram import Histogram

__all__ = [
    "enabled", "enable", "disable", "inc", "event", "span", "record_span",
    "observe",
    "gauge", "percentile", "set_trace", "current_trace", "snapshot",
    "self_seconds", "merge_snapshots",
    "reset", "write_jsonl", "chrome_trace", "write_chrome_trace",
    "merged_chrome_trace", "write_merged_chrome_trace",
    "local_trace_source", "instrument_jit",
    "ProgramCache", "Histogram", "FlightRecorder", "read_blackbox",
]

# single hot-path gate: instrumentation sites read this module attribute
# directly (`if telemetry._ENABLED:`) so the disabled cost is one dict
# lookup + truth test, with no call and no string formatting
_ENABLED: bool = os.environ.get("QRACK_TPU_TELEMETRY", "") not in ("", "0")

_LOCK = threading.Lock()
# trace timestamps are relative to import; the wall clock sampled at the
# same instant anchors them to an absolute timeline (epoch_unix_s in
# every snapshot / black box) so N processes' traces can be merged
_EPOCH = time.perf_counter()
_EPOCH_WALL = time.time()

_TRACE_CAP = int(os.environ.get("QRACK_TPU_TELEMETRY_TRACE_CAP", "65536"))
_EVENT_CAP = int(os.environ.get("QRACK_TPU_TELEMETRY_EVENT_CAP", "4096"))
_HIST_CAP = int(os.environ.get("QRACK_TPU_TELEMETRY_HIST_CAP", "1024"))

_COUNTERS: Dict[str, float] = {}
_GAUGES: Dict[str, float] = {}        # name -> last observed value
_SPANS: Dict[str, List[float]] = {}   # name -> [count, total_s, min_s, max_s]
_HISTS: Dict[str, Histogram] = {}     # name -> log-bucket distribution
# both rings drop OLDEST on overflow (drops counted): the tail is what a
# postmortem needs — the black box must hold what the worker was doing
# when it died, not what it did at boot
_TRACE: Deque[dict] = deque(maxlen=_TRACE_CAP)  # chrome-trace "X" events
_EVENTS: Deque[dict] = deque(maxlen=_EVENT_CAP)  # discrete annotated events

_TLS = threading.local()  # per-thread span stack (nesting depth) + trace id


def enabled() -> bool:
    return _ENABLED


def enable() -> None:
    """Turn telemetry on at runtime (tests; equivalent of the env gate).
    Arms the atexit JSONL dump if QRACK_TPU_TELEMETRY_OUT is set."""
    global _ENABLED
    _ENABLED = True
    _listen()
    from . import export

    export.arm_atexit()


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def reset() -> None:
    """Drop all recorded data (counters, spans, hists, traces, events).
    The rings are rebuilt from the CURRENT cap globals, so tests may
    shrink ``_EVENT_CAP``/``_TRACE_CAP`` and reset to apply them."""
    global _TRACE, _EVENTS
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
        _SPANS.clear()
        _HISTS.clear()
        _TRACE = deque(maxlen=_TRACE_CAP)
        _EVENTS = deque(maxlen=_EVENT_CAP)


# ---------------------------------------------------------------------------
# counters + events
# ---------------------------------------------------------------------------

def inc(name: str, n: float = 1) -> None:
    """Add `n` to the named monotonic counter (no-op when disabled)."""
    if not _ENABLED:
        return
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def gauge(name: str, value: float) -> None:
    """Record the latest value of a named gauge (last-write-wins; the
    serving layer uses these for queue depth / p50-p99 latencies)."""
    if not _ENABLED:
        return
    with _LOCK:
        _GAUGES[name] = float(value)


def observe(name: str, seconds: float) -> None:
    """Feed one measured duration into the named span aggregate AND the
    named log-bucket histogram, without a context manager — for
    durations measured externally (queue waits, per-job latencies)
    where enter/exit bracketing does not fit.  The histogram is what
    :func:`percentile` and the fleet SLO gauges read; the name space is
    bounded (`QRACK_TPU_TELEMETRY_HIST_CAP`) against label cardinality
    blowups — overflow names keep their span aggregate but drop the
    distribution (counted in ``telemetry.hists.dropped``)."""
    if not _ENABLED:
        return
    with _LOCK:
        agg = _SPANS.get(name)
        if agg is None:
            _SPANS[name] = [1, seconds, seconds, seconds]
        else:
            agg[0] += 1
            agg[1] += seconds
            agg[2] = min(agg[2], seconds)
            agg[3] = max(agg[3], seconds)
        h = _HISTS.get(name)
        if h is None:
            if len(_HISTS) >= _HIST_CAP:
                _COUNTERS["telemetry.hists.dropped"] = \
                    _COUNTERS.get("telemetry.hists.dropped", 0) + 1
                return
            h = _HISTS[name] = Histogram()
        h.record(seconds)


def percentile(name: str, q: float) -> Optional[float]:
    """p`q` of the named observed distribution (None when unrecorded)."""
    with _LOCK:
        h = _HISTS.get(name)
        return h.percentile(q) if h is not None else None


def event(name: str, **fields) -> None:
    """Record a discrete annotated event AND bump its counter.  The
    event ring holds the most recent QRACK_TPU_TELEMETRY_EVENT_CAP
    events (drop-OLDEST; evictions are counted) — postmortems need the
    tail, not the boot transcript.  The thread's current trace id, if
    any, is attached."""
    if not _ENABLED:
        return
    tid = getattr(_TLS, "trace", None)
    if tid is not None and "trace" not in fields:
        fields["trace"] = tid
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + 1
        if len(_EVENTS) == _EVENTS.maxlen:
            _COUNTERS["telemetry.events.dropped"] = \
                _COUNTERS.get("telemetry.events.dropped", 0) + 1
        _EVENTS.append({"name": name,
                        "t_s": time.perf_counter() - _EPOCH, **fields})


# ---------------------------------------------------------------------------
# distributed trace context
# ---------------------------------------------------------------------------

def set_trace(trace_id: Optional[str]) -> Optional[str]:
    """Set (or clear, with None) the calling thread's distributed-trace
    id; returns the previous value so callers can restore it.  Spans and
    events recorded while set carry ``trace: <id>``, which is how one
    submit's work is correlated across the front door and its worker."""
    prev = getattr(_TLS, "trace", None)
    _TLS.trace = trace_id
    return prev


def current_trace() -> Optional[str]:
    return getattr(_TLS, "trace", None)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class _NullSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


# process-wide span sequence: next() on a count is atomic under the GIL
_SPAN_IDS = itertools.count(1)
# jax.profiler.TraceAnnotation, looked up by the first enabled span: the
# module itself imports no jax, and the disabled path never gets here
_ANNOTATION = None


def _annotation(name: str, span_id: int, arg=None):
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
        _listen()  # the environment gate, before jax was imported
    # the id is a statistic of the host-plane event: what ties a ring
    # entry (perf_counter) to the trace's clock
    if arg is not None:
        return _ANNOTATION("qrack." + name, id=span_id, arg=arg)
    return _ANNOTATION("qrack." + name, id=span_id)


def _record(entry: dict) -> None:
    """One finished interval into the span aggregates and the ring."""
    name, wall = entry["name"], entry["dur_s"]
    with _LOCK:
        agg = _SPANS.get(name)
        if agg is None:
            _SPANS[name] = [1, wall, wall, wall]
        else:
            agg[0] += 1
            agg[1] += wall
            agg[2] = min(agg[2], wall)
            agg[3] = max(agg[3], wall)
        if len(_TRACE) == _TRACE.maxlen:
            # drop-OLDEST ring, same rationale as the event ring
            _COUNTERS["telemetry.trace.dropped"] = \
                _COUNTERS.get("telemetry.trace.dropped", 0) + 1
        _TRACE.append(entry)


class _Span:
    __slots__ = ("name", "t0", "depth", "trace", "id", "parent", "_ann",
                 "arg")

    def __init__(self, name: str, trace=None, annotate: bool = True,
                 arg=None):
        self.name = name
        self.trace = trace
        self.arg = arg
        self._ann = None if annotate else _NULL_SPAN

    def __enter__(self):
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        self.depth = len(stack)
        self.parent = stack[-1] if stack else None
        self.id = next(_SPAN_IDS)
        stack.append(self.id)
        # under an open jax.profiler trace the span is an event of the
        # host plane too, on the device trace's clock; without one the
        # annotation costs a flag test
        if self._ann is None:
            self._ann = _annotation(self.name, self.id, self.arg)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        _TLS.stack.pop()
        trace = self.trace if self.trace is not None \
            else getattr(_TLS, "trace", None)
        entry = {
            "name": self.name,
            "ts_s": self.t0 - _EPOCH,
            "dur_s": wall,
            "tid": threading.get_ident(),
            "depth": self.depth,
            "id": self.id,
            "parent": self.parent,
        }
        if trace is not None:
            entry["trace"] = trace
        if self.arg is not None:
            entry["arg"] = self.arg
        _record(entry)
        return False


def span(name: str, trace=None, arg=None):
    """Nestable timer of host time (see the module docstring: device
    time comes from a profiler trace, where this span is an event
    named ``"qrack." + name``).  `trace` pins a distributed-trace id on
    the recorded span (defaults to the thread's :func:`current_trace` —
    pass it explicitly when the span runs on a different thread than
    the one that minted the id, e.g. the executor's dispatch owner).
    `arg` is one word the span carries into its ring entry and its trace
    event (``engine.alu``: the ALU call's name)."""
    if not _ENABLED:
        return _NULL_SPAN
    return _Span(name, trace, arg=arg)


def self_seconds(entries) -> Dict[int, float]:
    """``{span id: duration minus what its children cover}`` over
    recorded span entries (``local_trace_source()["spans"]``).  Children
    of one span run one after another on its thread, so what they cover
    is the sum of their durations."""
    own = {e["id"]: e["dur_s"] for e in entries}
    for e in entries:
        if e.get("parent") in own:
            own[e["parent"]] -= e["dur_s"]
    return own


def record_span(name: str, start_s: float, dur_s: float,
                trace=None, parent: Optional[int] = None,
                depth: int = 0) -> None:
    """Append an already-measured interval to the trace ring and span
    aggregates — for callers that own their own stopwatch (e.g. the
    executor re-emitting a job's t_submit->t_done serve latency so the
    merged fleet timeline carries one bar per job and the raw durations
    can cross-check the bucketed histogram gauges).  `start_s` is a
    ``time.perf_counter()`` reading from THIS process; `parent` and
    `depth` place the interval under a span that encloses it."""
    if not _ENABLED:
        return
    if trace is None:
        trace = getattr(_TLS, "trace", None)
    entry = {
        "name": name,
        "ts_s": start_s - _EPOCH,
        "dur_s": dur_s,
        "tid": threading.get_ident(),
        "depth": depth,
        "id": next(_SPAN_IDS),
        "parent": parent,
    }
    if trace is not None:
        entry["trace"] = trace
    _record(entry)


# ---------------------------------------------------------------------------
# JAX's compile pipeline as spans
# ---------------------------------------------------------------------------

# jax/_src/dispatch.py brackets each stage with a context manager that
# fires the event twice through jax.monitoring: as a scalar (its start)
# on entry and as a duration on exit.  Begin and end are a span's enter
# and exit, so a stage nests under the program span that is open on the
# thread and holds whatever compiles inside it.
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
# fired on exit alone, inside the backend stage
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_LISTENING = False


def _listen() -> None:
    """Register the two compile listeners, once a process and only once
    jax is there: this module imports none (a first enabled span does)."""
    global _LISTENING
    if _LISTENING or "jax" not in sys.modules:
        return
    _LISTENING = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_compile_begin)
    monitoring.register_event_duration_secs_listener(_on_compile_end)


def _on_compile_begin(event: str, _start, **_kw) -> None:
    # the thread's open stages, innermost last; None stands for a trace
    # inside a trace (a jit that calls a jit), which counts once: in the
    # outer one.  While a stage is open its inner ones are followed
    # whatever the gate says, so that every begin meets its end.
    open_stages = getattr(_TLS, "compile", None)
    if not (_ENABLED or open_stages):
        return
    name = _COMPILE_STAGES.get(event)
    if name is None:
        return
    if open_stages is None:
        open_stages = _TLS.compile = []
    nested = name == "compile.trace" and any(
        s is not None and s.name == name for s in open_stages)
    # ring and aggregates only: a profiler trace keeps its own account
    # of what JAX does
    open_stages.append(
        None if nested else _Span(name, annotate=False).__enter__())


def _on_compile_end(event: str, duration: float, **_kw) -> None:
    open_stages = getattr(_TLS, "compile", None)
    if not (_ENABLED or open_stages):
        return
    name = _COMPILE_STAGES.get(event)
    if name is not None and open_stages:
        stage = open_stages.pop()
        if stage is not None:
            stage.__exit__(None, None, None)
        return
    if name is None:
        if event != _CACHE_LOAD_EVENT:
            return
        name = "compile.cache_load"
    # no begin was seen (the cache's load has none; telemetry came on
    # inside a stage): an interval that ended now and lasted `duration`
    stack = getattr(_TLS, "stack", None) or ()
    record_span(name, time.perf_counter() - duration, duration,
                parent=stack[-1] if stack else None, depth=len(stack))


# ---------------------------------------------------------------------------
# compile-cache accounting
# ---------------------------------------------------------------------------

class _JitProgram:
    """Transparent wrapper over a module-level jitted program that
    counts `compile.<name>.miss` (a call that grew the jit cache — XLA
    compiled) vs `.hit` (dispatch straight from cache).  Disabled path:
    one boolean test, then the raw call."""

    __slots__ = ("_fn", "_name")

    def __init__(self, name: str, fn):
        self._fn = fn
        self._name = name

    def __call__(self, *args, **kwargs):
        if not _ENABLED:
            return self._fn(*args, **kwargs)
        try:
            before = self._fn._cache_size()
        except Exception:
            before = None
        out = self._fn(*args, **kwargs)
        if before is None:
            inc(f"compile.{self._name}.call")
        elif self._fn._cache_size() > before:
            inc(f"compile.{self._name}.miss")
        else:
            inc(f"compile.{self._name}.hit")
        return out

    def __getattr__(self, attr):  # lower/_cache_size/etc. pass through
        return getattr(self._fn, attr)


def instrument_jit(name: str, fn):
    """Wrap a jitted callable for per-call compile hit/miss counting."""
    return _JitProgram(name, fn)


class ProgramCache:
    """Bounded LRU of compiled programs with hit/miss/eviction stats.

    Replacement for the module-global ``_PROGRAMS: dict`` pattern: a
    long-lived process no longer accumulates one compiled program (and
    its closed-over mesh) per key forever.  Keys are tuples; a key part
    produced by :meth:`mesh_token` is weakly tied to its mesh — when the
    mesh is garbage-collected every entry keyed to it is dropped, so
    dead meshes cannot pin compiled programs until LRU pressure.

    Stats are kept unconditionally (they are O(1) ints); the telemetry
    counters mirror them only while telemetry is enabled.
    """

    def __init__(self, name: str, cap: Optional[int] = None,
                 cap_env: str = "QRACK_TPU_PROGRAM_CACHE_CAP",
                 default_cap: int = 256):
        if cap is None:
            cap = int(os.environ.get(cap_env, str(default_cap)))
        self.name = name
        self.cap = max(1, cap)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._od: "OrderedDict" = OrderedDict()
        self._lock = threading.Lock()

    def get_or_build(self, key, builder):
        with self._lock:
            fn = self._od.get(key)
            if fn is not None:
                self._od.move_to_end(key)
                self.hits += 1
                if _ENABLED:
                    inc(f"compile.{self.name}.hit")
                return fn
        fn = builder()  # build outside the lock: builders trace/compile
        with self._lock:
            self._od[key] = fn
            self._od.move_to_end(key)
            self.misses += 1
            if _ENABLED:
                inc(f"compile.{self.name}.miss")
            while len(self._od) > self.cap:
                self._od.popitem(last=False)
                self.evictions += 1
                if _ENABLED:
                    inc(f"compile.{self.name}.eviction")
        return fn

    def mesh_token(self, mesh) -> int:
        """A cache-key part for `mesh` that is weakly tied to it: a
        finalizer drops every entry containing the token once the mesh
        is collected (id() alone would let dead meshes pin programs)."""
        import weakref

        token = id(mesh)
        try:
            weakref.finalize(mesh, self._drop_token, token)
        except TypeError:
            pass  # non-weakref-able key source: LRU cap still bounds us
        return token

    def _drop_token(self, token: int) -> None:
        def has(part) -> bool:
            if part == token and isinstance(part, int):
                return True
            if isinstance(part, tuple):
                return any(has(p) for p in part)
            return False

        with self._lock:
            dead = [k for k in self._od if has(k)]
            for k in dead:
                del self._od[k]
                self.evictions += 1
            if dead and _ENABLED:
                inc(f"compile.{self.name}.eviction", len(dead))

    def stats(self) -> dict:
        return {"size": len(self._od), "cap": self.cap, "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions}

    def __len__(self) -> int:
        return len(self._od)

    def __contains__(self, key) -> bool:
        return key in self._od

    def clear(self) -> None:
        with self._lock:
            self._od.clear()


# ---------------------------------------------------------------------------
# snapshot
# ---------------------------------------------------------------------------

def snapshot(include_events: bool = True) -> dict:
    """Plain-dict view of everything recorded so far (JSON-safe).

    Besides the raw stores, the snapshot *publishes* SLO gauges: every
    observed distribution contributes ``<name>.p50/.p95/.p99`` to the
    returned ``gauges`` (computed at snapshot time, never stored — a
    stale percentile gauge would outlive its histogram).  The
    ``epoch_unix_s`` wall anchor converts this process's relative span
    timestamps to absolute time for cross-process merging."""
    with _LOCK:
        gauges = dict(_GAUGES)
        hists = {name: h.to_dict() for name, h in _HISTS.items()}
        for name, h in _HISTS.items():
            for pname, v in h.percentiles().items():
                if v is not None:
                    gauges[f"{name}.{pname}"] = v
        out = {
            "enabled": _ENABLED,
            "pid": os.getpid(),
            "epoch_unix_s": _EPOCH_WALL,
            "counters": dict(_COUNTERS),
            "gauges": gauges,
            "hists": hists,
            "spans": {
                name: {"count": int(agg[0]), "total_s": agg[1],
                       "min_s": agg[2], "max_s": agg[3]}
                for name, agg in _SPANS.items()
            },
        }
        if include_events:
            out["events"] = list(_EVENTS)
    return out


def merge_snapshots(snaps) -> dict:
    """Fold N snapshot dicts (one per process/incarnation) into one:
    counters sum, span aggregates combine, histograms merge cell-wise,
    gauges last-write-wins in input order — EXCEPT the SLO percentile
    gauges, which are recomputed from the merged distributions (a
    fleet p99 is not any worker's p99)."""
    counters: Dict[str, float] = {}
    gauges: Dict[str, float] = {}
    spans: Dict[str, dict] = {}
    hists: Dict[str, Histogram] = {}
    for s in snaps:
        for k, v in (s.get("counters") or {}).items():
            counters[k] = counters.get(k, 0) + v
        gauges.update(s.get("gauges") or {})
        for k, d in (s.get("spans") or {}).items():
            agg = spans.get(k)
            if agg is None:
                spans[k] = dict(d)
            else:
                agg["count"] += d["count"]
                agg["total_s"] += d["total_s"]
                agg["min_s"] = min(agg["min_s"], d["min_s"])
                agg["max_s"] = max(agg["max_s"], d["max_s"])
        for k, d in (s.get("hists") or {}).items():
            h = hists.get(k)
            if h is None:
                hists[k] = Histogram.from_dict(d)
            else:
                h.merge(d)
    for name, h in hists.items():
        for pname, v in h.percentiles().items():
            if v is not None:
                gauges[f"{name}.{pname}"] = v
    return {"counters": counters, "gauges": gauges,
            "hists": {k: h.to_dict() for k, h in hists.items()},
            "spans": spans}


# exporters live in export.py; re-export the public surface
from .export import (  # noqa: E402  (cycle-safe: export imports nothing above lazily)
    chrome_trace, local_trace_source, merged_chrome_trace,
    write_chrome_trace, write_jsonl, write_merged_chrome_trace,
)
from .blackbox import FlightRecorder, read_blackbox  # noqa: E402

if _ENABLED:
    _listen()
# arm the atexit JSONL dump when the env gate + out path are both set
if _ENABLED and os.environ.get("QRACK_TPU_TELEMETRY_OUT"):
    from .export import arm_atexit as _arm

    _arm()
