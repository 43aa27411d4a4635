"""The program's own spans and names, read from the trace a traced
window wrote: what the per-layer readers of PR 28 share.

The program writes each ``telemetry.span(name)`` into the profiler's
trace as a ``TraceAnnotation`` named ``qrack.<name>``, beside the
benchmark's ``bench.*`` spans on the ``/host:`` planes.  What it runs on
the device it names in two ways that survive the benchmark's
``jax_include_full_tracebacks_in_locations=False`` (which drops the name
stack, and with it every ``jax.named_scope`` and kernel ``name=``, from
an operation's ``op_name``: PERF.md, PR 28): a kernel's ``metadata=``
rides the custom call's frontend attributes, which are part of the
device event's name (``kernels/*.json`` match there), and a jitted
function's name is its compiled module's (``jit_qrack_xla_window``).
The trace keeps the module of an operation as the statistic
``program_id`` of the event's *metadata*, and the modules' names on the
line ``XLA Modules``.  ``jax.profiler.ProfileData`` gives an event's own
statistics only, so this module reads the few fields it needs from the
``.xplane.pb`` itself (``XSpace`` of tsl/profiler/protobuf/xplane.proto).

``load(ctx)`` gives a ``ProgramSpans`` for the cell's newest trace, or
None where there is no trace or it holds no ``qrack.*`` span (a parent
of PR 28, a rehearsal): a reader then returns None too.  All times are
on the trace's one clock, in nanoseconds, kept to the ``bench.window``
span.
"""

import os
import re
import statistics
import struct

import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROGRAM, BENCH = "qrack.", "bench."
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
KET_ELEMENTS = 1 << 16  # the window kernel's tile: no ket is smaller

_CACHE = {}  # path of an .xplane.pb -> ProgramSpans: several readers ask


# -- the protobuf wire format, as far as an XSpace needs it ----------------

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, bytes
    for a length-delimited field, the raw 8 or 4 bytes of a fixed one."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield number, value


def _signed(value):
    return value - (1 << 64) if value >> 63 else value


def _stat(buf, stat_names):
    """(name, value) of one XStat; a reference resolves to its text."""
    name = value = None
    for number, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v)
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number in (3, 4):
            value = v
        elif number in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane_name(buf):
    return next((bytes(v).decode() for n, v in _fields(buf) if n == 2), "")


def _plane(buf, wanted_stats):
    """The lines of one XPlane: ``(line name, events)``, an event being
    ``(event name, start_ns, duration_ns, {stat: value})`` with the
    metadata's statistics named in ``wanted_stats``."""
    lines, metadata, stat_names = [], {}, {}
    for number, v in _fields(buf):
        if number == 3:
            lines.append(v)
        elif number in (4, 5):  # map entries: key = 1, value = 2
            entry = dict(_fields(v))
            if number == 4:
                metadata[entry.get(1, 0)] = entry.get(2, b"")
            else:  # an XStatMetadata: its name is field 2
                fields = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = bytes(fields.get(2, b"")).decode()
    resolved = {}

    def describe(metadata_id):
        if metadata_id not in resolved:
            ev_name, stats = "", {}
            for n, x in _fields(metadata.get(metadata_id, b"")):
                if n == 2:
                    ev_name = bytes(x).decode("utf-8", "replace")
                elif n == 5 and wanted_stats:
                    k, val = _stat(x, stat_names)
                    if k in wanted_stats:
                        stats[k] = val
            resolved[metadata_id] = (ev_name, stats)
        return resolved[metadata_id]

    out = []
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for number, v in _fields(line):
            if number == 2:
                line_name = bytes(v).decode()
            elif number == 3:
                t0_ns = _signed(v)
            elif number == 4:
                events.append(v)
        decoded = []
        for ev in events:
            metadata_id = offset_ps = duration_ps = 0
            for number, v in _fields(ev):
                if number == 1:
                    metadata_id = v
                elif number == 2:
                    offset_ps = _signed(v)
                elif number == 3:
                    duration_ps = _signed(v)
            ev_name, stats = describe(metadata_id)
            decoded.append((ev_name, t0_ns + offset_ps // 1000,
                            duration_ps // 1000, stats))
        out.append((line_name, decoded))
    return out


def read_xplane(path):
    """``(device, spans)`` of an ``.xplane.pb``: per device plane the
    leaf operations ``(name, start_ns, dur_ns, module)``, ``module``
    being the name of the compiled module the operation belongs to
    (``jit_qrack_xla_window``), and the ``qrack.*`` and ``bench.*`` host
    events ``(name, start_ns, dur_ns, thread)``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    device, spans = {}, []
    for number, plane in _fields(space):
        if number != 1:
            continue
        name = _plane_name(plane)
        if name.startswith("/device:TPU:"):
            lines = dict(_plane(plane, {"program_id"}))
            # a module's events are named "<module>(<program id>)"
            modules = {}
            for n, _, _, _ in lines.get(MODULES_LINE, ()):
                module, _, program = n.rpartition("(")
                modules[program.rstrip(")")] = module
            device[name] = [
                (n, s, d, modules.get(str(st.get("program_id")), ""))
                for n, s, d, st in lines.get(OPS_LINE, ())]
        elif name.startswith("/host:"):
            for line_name, events in _plane(plane, ()):
                spans += [(n, s, d, line_name) for n, s, d, _ in events
                          if n.startswith((PROGRAM, BENCH))]
    return device, spans


# -- what the readers ask ------------------------------------------------------

class ProgramSpans:
    def __init__(self, device, spans):
        window = [s for s in spans if s[0] == BENCH + "window"]
        if not window:
            raise ValueError("the trace holds no bench.window span")
        self.start, self.end = window[-1][1], window[-1][1] + window[-1][2]
        inside = lambda e: e[1] >= self.start and e[1] + e[2] <= self.end  # noqa: E731
        self.spans = sorted((s for s in spans if inside(s) and s is not window[-1]),
                            key=lambda s: (s[1], -s[2]))
        self.device = {k: sorted(filter(inside, v), key=lambda e: e[1])
                       for k, v in device.items() if v}

    @classmethod
    def from_events(cls, recorded):
        return cls({k: [tuple(e) for e in v]
                    for k, v in recorded["devices"].items()},
                   [tuple(s) for s in recorded["spans"]])

    def named(self, name):
        return [s for s in self.spans if s[0] == name]

    def has_program_spans(self):
        return any(s[0].startswith(PROGRAM) for s in self.spans)

    @staticmethod
    def _within(inner, outer):
        return (inner[3] == outer[3] and inner[1] >= outer[1]
                and inner[1] + inner[2] <= outer[1] + outer[2])

    def per_application(self, name, inside=BENCH + "application"):
        """For every ``bench.application`` span, the summed duration
        (ns) of the spans called ``name`` on its thread inside it."""
        found = self.named(name)
        return [sum(s[2] for s in found if self._within(s, app))
                for app in self.named(inside)]

    def self_ns(self, span):
        """A span's duration minus what its children cover: the same
        rule as ``qrack_tpu.telemetry.self_seconds``.  Children are the
        spans within it that no other span within it contains."""
        within = [s for s in self.spans
                  if s is not span and self._within(s, span)]
        children = [s for s in within
                    if not any(o is not s and self._within(s, o) for o in within)]
        return span[2] - sum(s[2] for s in children)

    def idle_by_span(self):
        """A chip's idle time inside the window, in ns, by the innermost
        ``qrack.*`` span that covers the middle of each gap; where none
        does, by the innermost ``bench.*`` span, and ``between`` where
        none does either.  Every device plane's gaps, averaged over the
        planes (``planes`` names them)."""
        totals = {}
        for events in self.device.values():
            cursor = self.start
            for _, s, d, _ in events + [("", self.end, 0, "")]:
                if s > cursor:
                    mid = (cursor + s) // 2
                    covering = [sp for sp in self.spans
                                if sp[1] <= mid < sp[1] + sp[2]]
                    label = "between"
                    for prefix in (PROGRAM, BENCH):
                        mine = [sp for sp in covering
                                if sp[0].startswith(prefix)]
                        if mine:
                            label = min(mine, key=lambda sp: sp[2])[0]
                            break
                    totals[label] = totals.get(label, 0) + (s - cursor)
                cursor = max(cursor, s + d)
        return {k: v / len(self.device) for k, v in totals.items()}

    @property
    def planes(self):
        return sorted(self.device)

    def device_classes(self, launches):
        """A chip's device time (ns: every plane's, averaged over the
        planes) in every operation that is no kernel launch
        (``launches``: compiled expressions of ``kernels/*.json``), as
        ``{"<module>:<what>": ns}``.  The module is the program's own
        (``jit_qrack_xla_window``, ...) or ``eager`` for any other (an
        eager operation compiles once per primitive and has no name of
        the program's); ``what`` is ``small`` for an operation that
        touches no ket-sized array (building operands), else the
        operation (``copy``, ``fusion``, ``collective-permute``)."""
        totals = {}
        for events in self.device.values():
            for name, _, dur, module in events:
                if any(p.search(name) for p in launches):
                    continue
                if not module.startswith("jit_qrack_"):
                    module = "eager"
                what = "small"
                if _largest_shape(name) >= KET_ELEMENTS:
                    what = re.sub(r"[.\d]+$", "", tracing.short_name(name)
                                  .split(" ")[0].lstrip("%"))
                label = module + ":" + what
                totals[label] = totals.get(label, 0) + dur
        return {k: v / len(self.device) for k, v in totals.items()}


def _largest_shape(hlo_text):
    """Elements of the largest array an HLO instruction's text names."""
    largest = 1
    for dims in re.findall(r"\b(?:pred|bf16|[sfu]\d+)\[([\d,]+)\]", hlo_text):
        size = 1
        for d in dims.split(","):
            size *= int(d)
        largest = max(largest, size)
    return largest


def load(ctx):
    """The ``ProgramSpans`` of this run's trace, or None.  A test hands
    one in as ``ctx["program_spans"]``."""
    if "program_spans" in ctx:
        return ctx["program_spans"]
    if ctx.get("trace") is None or "cell" not in ctx:
        return None
    try:
        path = tracing.newest_xplane(
            os.path.join(ROOT, "bench_out", "trace", ctx["cell"].name))
    except FileNotFoundError:
        return None
    if path not in _CACHE:
        _CACHE.clear()  # one trace a process
        found = ProgramSpans(*read_xplane(path))
        _CACHE[path] = found if found.has_program_spans() else None
    return _CACHE[path]


def median_ms(values_ns):
    return statistics.median(values_ns) / 1e6 if values_ns else None


def span_ms_per_application(ctx, name):
    """Median over the traced applications of the summed duration (ms)
    of the program's spans called ``name``: what a ``fuser.*_ms`` reads."""
    spans = load(ctx)
    if spans is None:
        return None
    return median_ms(spans.per_application(name))
