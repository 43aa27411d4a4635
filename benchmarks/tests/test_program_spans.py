"""The readers PR 28 added, on recorded events: the first application of
a traced run of ``tfim_w28.library`` on a TPU v5e (PR 28), cut by
``tools/cut_program_spans.py``.  It holds what PR 27's recordings cannot:
the program's ``qrack.*`` spans, the named launches, and each device
operation's module."""

import json
import os

import pytest

import harness
import program_spans
import tracing
from conftest import ROOT, TESTS

W = 28
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    _PER_LAYER = json.load(_f)["per_layer"]
CELL = "tfim_w28.library"
NEW = [m["name"] for m in _PER_LAYER if CELL in m.get("workloads", ())]
PARTS = ("qrack.fuse.lower", "qrack.fuse.operands", "qrack.fuse.dispatch")
# one Trotter step at w28 (tests/test_structure.py): 7 windows, 6 of them
# through the kernel in 28 sweeps, 24 of those cross-tile; 109 gates
WINDOWS, LAUNCHES, CROSS, KERNEL_OPS, PROGRAMS = 7, 28, 24, 96, 224


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(TESTS, "data", "program_spans_tfim_w28.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spans(recorded):
    return program_spans.ProgramSpans.from_events(recorded)


@pytest.fixture(scope="module")
def ctx(recorded, spans):
    n = recorded["applications"]
    trace = tracing.Trace.from_events({
        "devices": {k: [e[:3] for e in v]
                    for k, v in recorded["devices"].items()},
        "spans": [[s[0][len("bench."):]] + s[1:3] for s in recorded["spans"]
                  if s[0].startswith("bench.")]})
    return {
        "trace": trace, "program_spans": spans, "attempted": n, "width": W,
        "pages": 1,
        "peaks": harness.load_json("peaks.json")["TPU v5 lite"],
        "window_counters": {"fuse.kernel.sweeps": LAUNCHES * n,
                            "fuse.kernel.sweeps.cross": CROSS * n,
                            "fuse.kernel.ops": KERNEL_OPS * n,
                            "fuse.tpu.programs": PROGRAMS * n},
        "host_spans": {}, "compiles_before_window": (19, 0.4),
        "window_compiles": 0,
    }


def _read(metric, ctx):
    return harness.load_module("per_layer", metric).read(ctx)


def test_one_flush_a_window_and_the_last_inside_the_read(spans):
    flushes = spans.named("qrack.fuse.flush")
    assert len(flushes) == WINDOWS
    in_calls = spans.per_application("qrack.fuse.flush",
                                     inside="bench.gate_calls")
    in_read = spans.per_application("qrack.fuse.flush",
                                    inside="bench.completion_read")
    whole = spans.per_application("qrack.fuse.flush")
    # the last window flushes inside the completion read, before
    # engine.read is entered: counted once, in neither or both never
    assert whole == [a + b for a, b in zip(in_calls, in_read)]
    assert all(b > 0 for b in in_read)
    read = spans.named("qrack.engine.read")
    last = max(flushes, key=lambda s: s[1])
    assert len(read) == 1 and last[1] + last[2] <= read[0][1]


def test_per_application_sums(spans):
    for name in PARTS:
        found = spans.named(name)
        assert len(found) == WINDOWS
        assert spans.per_application(name) == [sum(s[2] for s in found)]


def test_the_three_parts_cover_every_flush(spans):
    """The first rule of addition: what the flushes keep to themselves
    (their counters, the call into the engine) is under 1 % of them;
    a single short flush keeps a little more (0.1-0.2 ms of 7-17 ms)."""
    flushes = spans.named("qrack.fuse.flush")
    for flush in flushes:
        assert 0 <= spans.self_ns(flush) < 0.03 * flush[2]
    total = sum(s[2] for s in flushes)
    assert sum(spans.self_ns(f) for f in flushes) < 0.01 * total
    parts = sum(s[2] for name in PARTS for s in spans.named(name))
    assert parts == pytest.approx(total, rel=0.01)


def test_in_tile_and_cross_tile_add_up_to_the_kernel(ctx):
    """The second rule of addition, within 0.1 %, and the launches."""
    intile = _read("kernel.intile_ms_per_circuit", ctx)
    cross = _read("kernel.cross_ms_per_circuit", ctx)
    whole = _read("kernel.ms_per_circuit", ctx)
    assert intile + cross == pytest.approx(whole, rel=1e-3)
    trace = ctx["trace"]
    assert len(trace.kernel_events("window_cross")) == CROSS
    assert len(trace.kernel_events("window_intile")) == LAUNCHES - CROSS
    assert len(trace.kernel_events("window_kernel")) == LAUNCHES
    assert _read("kernel.ms_per_op", ctx) == pytest.approx(
        whole / KERNEL_OPS, rel=1e-9)


def test_a_named_launch_matches_its_file_and_the_old_one(ctx):
    trace = ctx["trace"]
    for mine, other in (("window_intile", "window_cross"),
                        ("window_cross", "window_intile")):
        name = trace.kernel_events(mine)[0][0]
        assert trace.is_kernel("window_kernel", name)
        assert not trace.is_kernel(other, name)
    # as a compiled program prints it (tests/test_chip_compile.py)
    printed = ('%tpu_custom_call.3 = f32[2,268435456]{1,0:T(2,128)} custom-call('
               '%copy.2, %copy.3, %planes.1), custom_call_target="tpu_custom_call", '
               'frontend_attributes={kernel_metadata={\n"qrack_kernel":'
               '"qrack_window_intile"\n}}')
    assert trace.is_kernel("window_intile", printed)
    assert trace.is_kernel("window_kernel", printed)
    assert not trace.is_kernel("window_cross", printed)


def test_the_chain_and_the_operands(ctx, spans):
    classes = spans.device_classes(ctx["trace"].kernels["window_kernel"])
    chain = _read("xla.chain_ms_per_circuit", ctx)
    operands = _read("xla.operand_ms_per_circuit", ctx)
    other = _read("xla.ms_per_circuit", ctx)
    assert sum(classes.values()) / 1e6 == pytest.approx(other, rel=1e-9)
    assert 0 < operands < 0.01 * chain < chain < other
    assert not [k for k in classes if k.startswith("jit_qrack_kernel_window:")
                and not k.endswith((":small", ":copy"))]


def test_every_new_reader_reads_the_recording(ctx):
    for metric in NEW:
        value = _read(metric, ctx)
        assert value is not None and value >= 0, metric
    assert _read("fuser.programs_per_circuit", ctx) == PROGRAMS
    assert _read("device.idle_unattributed_share", ctx) < 10


def test_a_trace_without_the_programs_names_reads_as_nothing():
    """A parent of PR 28, or PR 27's recordings: no reader raises, none
    invents a value."""
    with open(os.path.join(TESTS, "data", "trace_tfim_w28.json")) as f:
        old = json.load(f)
    ctx = {"trace": tracing.Trace.from_events(old), "attempted": 1,
           "width": W, "window_counters": {"fuse.kernel.sweeps": 28},
           "host_spans": {}}
    for metric in (m["name"] for m in _PER_LAYER if "workloads" in m):
        assert _read(metric, ctx) is None, metric
    spans = program_spans.ProgramSpans(
        {}, [("bench.window", 0, 100, "t"), ("bench.gate_calls", 10, 50, "t")])
    assert not spans.has_program_spans()


# -- gap attribution, on events small enough to read ---------------------------------

def _toy():
    # the device runs 100-200, 400-500 and 900-1000; window 0-1000
    device = {"/device:TPU:0": [("%a = f32[4]{0} add()", 100, 100, ""),
                                ("%b = f32[4]{0} add()", 400, 100, ""),
                                ("%c = f32[4]{0} add()", 900, 100, "")]}
    spans = [("bench.window", 0, 1000, "t"),
             ("bench.application", 0, 650, "t"),
             ("bench.gate_calls", 0, 600, "t"),       # covers gaps 1 and 2
             ("qrack.fuse.flush", 210, 180, "t"),     # covers gap 2 ...
             ("qrack.fuse.operands", 220, 160, "t"),  # ... and so does this
             ("qrack.fuse.lower", 212, 6, "t")]       # not over its middle
    return program_spans.ProgramSpans(device, spans)


def test_a_gap_goes_to_the_innermost_span_over_its_middle():
    idle = _toy().idle_by_span()
    assert idle == {"bench.gate_calls": 100,      # 0-100: no qrack span
                    "qrack.fuse.operands": 200,   # 200-400: innermost wins
                    "between": 400}               # 500-900: no span at all
    assert sum(idle.values()) == 1000 - 300


def test_unattributed_share_is_what_no_program_span_covers():
    value = _read("device.idle_unattributed_share",
                  {"program_spans": _toy()})
    assert value == pytest.approx(100.0 * 500 / 700)


def test_self_time_is_duration_minus_children():
    toy = _toy()
    flush = toy.named("qrack.fuse.flush")[0]
    assert toy.self_ns(flush) == 180 - 160 - 6
    assert toy.per_application("qrack.fuse.flush",
                               inside="bench.gate_calls") == [180]
    calls = toy.per_application("bench.gate_calls")
    assert _read("fuser.queue_ms", {"program_spans": toy}) == pytest.approx(
        (calls[0] - 180) / 1e6)


# -- the reader of the file itself ------------------------------------------------------

def test_read_xplane_agrees_with_profile_data_on_a_cpu_trace(tmp_path):
    """The wire-format reader against ``jax.profiler.ProfileData``: the
    host events of a trace made here (a CPU trace has no device plane;
    the device half was held against ProfileData on the chip's trace,
    PERF.md PR 28)."""
    import jax
    import jax.numpy as jnp

    from qrack_tpu import telemetry

    was = telemetry.enabled()
    telemetry.enable()
    try:
        with tracing.capture(jax, str(tmp_path)):
            with telemetry.span("fuse.flush"):
                with telemetry.span("fuse.dispatch"):
                    jnp.ones(8).block_until_ready()
    finally:
        if not was:
            telemetry.disable()
    path = tracing.newest_xplane(str(tmp_path))
    device, spans = program_spans.read_xplane(path)
    assert device == {}
    from jax.profiler import ProfileData

    want = sorted(
        (ev.name, int(ev.start_ns), int(ev.duration_ns))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith(("qrack.", "bench.")))
    assert sorted(s[:3] for s in spans) == want
    assert [s[0] for s in sorted(spans, key=lambda s: (s[1], -s[2]))] == [
        "bench.window", "qrack.fuse.flush", "qrack.fuse.dispatch"]
    found = program_spans.ProgramSpans(device, spans)
    flush = found.named("qrack.fuse.flush")[0]
    assert found.self_ns(flush) == flush[2] - found.named(
        "qrack.fuse.dispatch")[0][2]
