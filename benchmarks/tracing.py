"""From a profiler trace to numbers: the reduction every per-layer time
comes from.

``capture`` runs a window under ``jax.profiler`` and returns the path
of the ``.xplane.pb`` it wrote.  ``load`` reads it with nothing but JAX
into a ``Trace``: the device's leaf operations (the line that holds the
XLA ops of each device plane), and the benchmark's own host spans
(``bench.*`` ``TraceAnnotation`` events), all on the trace's one clock.
``Trace.from_events`` builds the same object from recorded events, for
the tests.

A device event is ``(name, start_ns, duration_ns)``.  Which events are
launches of a named kernel is decided by ``kernels/<kernel>.json``:
regular expressions on the event's name, one file per kernel.
"""

import contextlib
import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@contextlib.contextmanager
def capture(jax, directory):
    """Profile what runs inside; host Python frames are left out, they
    are most of a trace's bytes and none of its use here."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "window"):
            yield
    finally:
        jax.profiler.stop_trace()


def newest_xplane(directory):
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def short_name(name, limit=120):
    """A device event's HLO text cut to what tells it apart: the
    instruction, its operation and its operands' shapes."""
    m = re.match(r"%?([\w.\-]+) = \S+ ([\w\-]+)\((.*)", name)
    if not m:
        return name[:limit]
    shapes = re.findall(r"\b(?:pred|bf16|[sfu]\d+)\[[\d,]*\]", m.group(3).split("), ")[0])
    target = re.search(r'custom_call_target="([^"]+)"', name)
    op = target.group(1) if target else m.group(2)
    return f"{m.group(1)} {op}({','.join(shapes)})"[:limit]


def result_bytes(name):
    """Bytes of the first array in the result of an instruction's HLO
    text: ``%x = (f32[2,8]{...}, ...) op(...)`` gives 64."""
    m = re.search(r"=\s*\(?\s*(pred|bf16|[sfu]\d+)\[([\d,]*)\]", name)
    if not m:
        return 0
    size = {"pred": 1, "bf16": 2}.get(m.group(1)) or int(m.group(1)[1:]) // 8
    for d in filter(None, m.group(2).split(",")):
        size *= int(d)
    return size


def _union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


class Trace:
    def __init__(self, devices, spans, kernels=None):
        # devices: {plane name: [(name, start_ns, dur_ns), ...]} leaf ops
        # spans: [(name without prefix, start_ns, dur_ns), ...] host spans
        self.devices = {k: sorted(v, key=lambda e: e[1])
                        for k, v in devices.items() if v}
        self.spans = sorted(spans, key=lambda e: e[1])
        if kernels is None:
            kernels = {}
            for path in sorted(glob.glob(os.path.join(HERE, "kernels", "*.json"))):
                with open(path) as f:
                    kernels[os.path.basename(path)[:-len(".json")]] = json.load(f)
        self.kernels = {k: [re.compile(p) for p in v["event_name_matches"]]
                        for k, v in kernels.items()}

    @classmethod
    def from_events(cls, recorded, kernels=None):
        return cls({k: [tuple(e) for e in v]
                    for k, v in recorded["devices"].items()},
                   [tuple(e) for e in recorded["spans"]], kernels)

    # -- the traced window ------------------------------------------------

    def window(self):
        """(start_ns, end_ns) of the ``bench.window`` span."""
        found = [s for s in self.spans if s[0] == "window"]
        if not found:
            raise ValueError("the trace holds no bench.window span")
        _, start, dur = found[-1]
        return start, start + dur

    def window_s(self):
        start, end = self.window()
        return (end - start) / 1e9

    def in_window(self, events):
        start, end = self.window()
        return [e for e in events if e[1] >= start and e[1] + e[2] <= end]

    def device_events(self):
        """Leaf device operations inside the window, per device plane."""
        return {k: self.in_window(v) for k, v in self.devices.items()}

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        per_chip = [_union_ns([(s, s + d) for _, s, d in events]) / 1e9
                    for events in self.device_events().values()]
        return sum(per_chip) / len(per_chip) if per_chip else 0.0

    # -- one chip's share ----------------------------------------------------
    # Every time a reader reports is what *one chip* spends: taken over
    # every device plane and averaged over them, as ``busy_s`` is.  With
    # one plane that is the plane's own sum.

    @property
    def chips(self):
        """Device planes that hold an operation: the chips that worked."""
        return len(self.devices)

    def chip_ns(self, events):
        """Summed duration of ``events`` (of all planes), a chip."""
        return sum(d for _, _, d in events) / self.chips

    def chip_count(self, events):
        """How many of ``events`` (of all planes) one chip ran."""
        n, rest = divmod(len(events), self.chips)
        return n if not rest else len(events) / self.chips

    # -- kernels -----------------------------------------------------------

    def is_kernel(self, kernel, name):
        return any(p.search(name) for p in self.kernels[kernel])

    def kernel_events(self, kernel):
        return [e for events in self.device_events().values() for e in events
                if self.is_kernel(kernel, e[0])]

    def other_events(self):
        """Device operations that are no named kernel's launches."""
        return [e for events in self.device_events().values() for e in events
                if not any(self.is_kernel(k, e[0]) for k in self.kernels)]

    # -- transfers between chips ----------------------------------------------

    def transfers(self, kernel):
        """Per device plane, the transfers of the collective ``kernel``
        inside the window: ``(begin_ns, end_ns, sent_bytes)``.  An
        operation split into ``-start`` and ``-done`` is one transfer,
        from the start's begin to the done's end, whatever ran between
        them; the bytes are the first array of the start's (or the whole
        operation's) result: what this chip sends."""
        out = {}
        for plane, events in self.device_events().items():
            found, opened = [], []
            for name, start, dur in events:
                if not self.is_kernel(kernel, name):
                    continue
                if re.search(r"-done\(", name):
                    begin, sent = opened.pop() if opened else (start, 0)
                    found.append((begin, start + dur, sent))
                elif re.search(r"-start\(", name):
                    opened.append((start, result_bytes(name)))
                else:
                    found.append((start, start + dur, result_bytes(name)))
            out[plane] = found
        return out

    def exposed_ns(self, kernel):
        """Per device plane ``(in flight, exposed)``: the time in which a
        transfer of ``kernel`` was under way, and the part of it in which
        no other operation ran on that chip."""
        out, events = {}, self.device_events()
        for plane, found in self.transfers(kernel).items():
            flight = [(b, e) for b, e, _ in found]
            others = [(s, s + d) for n, s, d in events[plane]
                      if not self.is_kernel(kernel, n)]
            covered = _union_ns(flight)
            both = covered + _union_ns(others) - _union_ns(flight + others)
            out[plane] = (covered, covered - both)
        return out

    # -- the breakdown the ledger keeps -----------------------------------------

    def top_ops(self, limit=10):
        totals = {}
        for events in self.device_events().values():
            for name, _, dur in events:
                name = short_name(name)
                totals[name] = totals.get(name, 0) + dur
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, limit=10, labels=("set_permutation", "gate_calls",
                                         "completion_read")):
        """A chip's idle time inside the window, by the benchmark's own
        host span that covers the middle of each gap (``between`` where
        none does): every device plane's gaps, averaged over the planes."""
        start, end = self.window()
        spans = [s for s in self.spans if s[0] in labels]
        totals = {}
        for events in self.device_events().values():
            cursor = start
            for _, s, d in events + [("", end, 0)]:
                if s > cursor:
                    mid = (cursor + s) // 2
                    label = next((n for n, ss, sd in spans
                                  if ss <= mid < ss + sd), "between")
                    totals[label] = totals.get(label, 0) + (s - cursor)
                cursor = max(cursor, s + d)
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, ns / self.chips / 1e9] for name, ns in top]


def load(path, kernels=None):
    """Read an ``.xplane.pb`` (or a directory that holds one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = newest_xplane(path)
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      int(ev.start_ns), int(ev.duration_ns)))
    return Trace(devices, spans, kernels)


def describe(path, limit=40):
    """What a trace holds, for a look by hand: planes, lines, and the
    commonest event names of each line with their counts and seconds."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = newest_xplane(path)
    data = ProfileData.from_file(path)
    out = {"file": path, "bytes": os.path.getsize(path), "planes": []}
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            names, first, stats = {}, None, None
            for ev in line.events:
                c = names.setdefault(ev.name, [0, 0])
                c[0] += 1
                c[1] += ev.duration_ns
                if first is None:
                    first = int(ev.start_ns)
                    try:
                        stats = {str(k): str(v)[:200] for k, v in ev.stats}
                    except Exception as e:  # a stat that does not decode
                        stats = {"error": repr(e)}
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:limit]
            lines.append({"line": line.name, "events": sum(c[0] for c in names.values()),
                          "first_start_ns": first, "first_event_stats": stats,
                          "names": [[n, c[0], c[1] / 1e9] for n, c in top]})
        out["planes"].append({"plane": plane.name, "lines": lines})
    return out
