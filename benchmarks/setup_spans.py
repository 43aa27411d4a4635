"""A run's set-up by the program's own spans: what the ``setup.*_s``
readers share.

The program keeps every span of the process in a ring
(``qrack_tpu.telemetry``: ``local_trace_source()["spans"]``, on
``perf_counter``, each entry with ``id``, ``parent``, ``tid``), the
set-up's among them; the profiler's trace holds only the window.  A
span hands its ``id`` to its ``TraceAnnotation`` as a statistic of the
host-plane event, so the ``qrack.*`` events of the window's
``.xplane.pb`` pair with ring entries by ``id``, and the pairs give the
offset between the two clocks (their median; the spread is printed).
``bench.window``'s begin, moved onto the ring's clock by that offset,
says which ring entries began before the window opened: the set-up.
(What precedes the program's first span is the process's start, the
imports and the runtime's start; ``first_span_to_window_seconds`` minus
the program's seconds is the caller between the program's calls.)

Of those the caller's thread's are kept.  The program's part of the
set-up is the union of its top-level spans (no parent, and not a
``compile.*`` stage: a stage under no span is a jit of the benchmark's
own); seconds by name are self seconds, ``telemetry.self_seconds``'
rule, so that they add up to that union.  A compile stage counts where
it is outermost: a trace, a lowering or a backend compile that runs
inside another stage (an eager operation inside a trace) is that
stage's time.

``load(ctx)`` gives a ``SetupSpans`` for the run's trace, or None where
there is no trace or no host event of it carries an ``id`` that the
ring holds (a rehearsal, a program older than the ``id`` statistic): a
reader then returns None too.  The whole table is printed once, on an
earlier line of the run (``setup_seconds_by_span``).
"""

import os
import statistics
import threading

import harness
import program_spans
import tracing

ROOT = program_spans.ROOT
PROGRAM, BENCH = program_spans.PROGRAM, program_spans.BENCH
STAGE = "compile."

_CACHE = {}  # path of an .xplane.pb -> SetupSpans or None: seven readers ask


def host_events(path):
    """``(events, window_start_ns)`` of an ``.xplane.pb``: the
    ``qrack.*`` host events that carry the statistic ``id``, as
    ``(name, id, start_ns)``, and the begin of the last ``bench.window``
    (None where the trace holds none)."""
    from jax.profiler import ProfileData

    events, window = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == BENCH + "window":
                    window = int(ev.start_ns)
                elif ev.name.startswith(PROGRAM):
                    span_id = dict(ev.stats).get("id")
                    if span_id is not None:
                        events.append((ev.name, int(span_id),
                                       int(ev.start_ns)))
    return events, window


class SetupSpans:
    """``ring``: the program's span entries; ``events``: the traced
    host events ``(name, id, start_ns)``; ``window_start_ns``: the
    window's begin on the trace's clock; ``tid``: the caller's thread."""

    def __init__(self, ring, events, window_start_ns, tid):
        by_id = {e["id"]: e for e in ring}
        pairs = [(by_id[i], start) for name, i, start in events
                 if i in by_id and PROGRAM + by_id[i]["name"] == name]
        self.host_events, self.matched = len(events), len(pairs)
        self.ring_entries = len(ring)
        self.entries = self.program = self._outermost = []
        self.program_s = self.outside_s = self.since_first_span_s = 0.0
        if not pairs:
            return
        # the trace's clock minus the ring's, in seconds
        offsets = [start / 1e9 - e["ts_s"] for e, start in pairs]
        self.offset_s = statistics.median(offsets)
        self.offset_spread_s = max(offsets) - min(offsets)
        self.window_opened_s = window_start_ns / 1e9 - self.offset_s
        self.entries = [e for e in ring if e["tid"] == tid
                        and e["ts_s"] < self.window_opened_s]
        kept = {e["id"]: e for e in self.entries}

        def top(e):
            while e["parent"] in kept:
                e = kept[e["parent"]]
            return e

        def is_stage(e):
            return e is not None and e["name"].startswith(STAGE)

        tops = {e["id"]: top(e) for e in self.entries}
        # the program's own: everything under a top-level span of its
        self.program = [e for e in self.entries
                        if not is_stage(tops[e["id"]])]
        self.outside_s = sum(e["dur_s"] for e in self.entries
                             if tops[e["id"]] is e and is_stage(e))
        spans = [(e["ts_s"], e["ts_s"] + e["dur_s"]) for e in self.program
                 if tops[e["id"]] is e]
        self.program_s = tracing._union_ns(spans)
        # from the program's first span to the window: what is not
        # inside a span there is the caller between the program's calls
        self.since_first_span_s = self.window_opened_s - min(
            [s for s, _ in spans], default=self.window_opened_s)
        self._outermost = [e for e in self.program if is_stage(e)
                           and not is_stage(kept.get(e["parent"]))]

    def named_s(self, name):
        """Summed duration of the program's spans called ``name``."""
        return sum(e["dur_s"] for e in self.program if e["name"] == name)

    def stage_s(self, name):
        """Summed duration of the compile stage ``name`` where it is
        outermost under a span of the program."""
        return sum(e["dur_s"] for e in self._outermost if e["name"] == name)

    def self_seconds_by_name(self):
        """Self seconds of the program's spans before the window, by
        name: they add up to ``program_s``."""
        from qrack_tpu import telemetry

        own = telemetry.self_seconds(self.program)
        totals = {}
        for e in self.program:
            totals[e["name"]] = totals.get(e["name"], 0.0) + own[e["id"]]
        return totals


def load(ctx):
    """The ``SetupSpans`` of this run, or None.  A test hands one in as
    ``ctx["setup_spans"]``."""
    if "setup_spans" in ctx:
        return ctx["setup_spans"]
    if ctx.get("trace") is None or "cell" not in ctx:
        return None
    try:
        path = tracing.newest_xplane(
            os.path.join(ROOT, "bench_out", "trace", ctx["cell"].name))
    except FileNotFoundError:
        return None
    if path not in _CACHE:
        _CACHE.clear()  # one trace a process
        _CACHE[path] = _read(path, ctx)
    return _CACHE[path]


def _read(path, ctx):
    from qrack_tpu import telemetry

    events, window = host_events(path)
    if not events or window is None:
        return None
    ring = telemetry.local_trace_source()["spans"]
    found = SetupSpans(ring, events, window, threading.get_ident())
    if not found.matched:
        return None
    table = found.self_seconds_by_name()
    counters = telemetry.snapshot(include_events=False)["counters"]
    harness.say(
        setup_seconds_by_span=dict(
            sorted(table.items(), key=lambda kv: -kv[1])),
        accounted_seconds=sum(table.values()),
        program_seconds=found.program_s,
        setup_seconds=ctx.get("setup_seconds"),
        first_span_to_window_seconds=found.since_first_span_s,
        compile_seconds_under_no_span=found.outside_s,
        clock_offset_seconds=found.offset_s,
        clock_offset_spread_seconds=found.offset_spread_s,
        host_events_with_id=found.host_events,
        ring_entries_matched=found.matched,
        ring_entries=found.ring_entries,
        ring_entries_before_window=len(found.entries),
        telemetry_trace_dropped=counters.get("telemetry.trace.dropped", 0))
    return found
