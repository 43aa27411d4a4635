"""Spans at the layer boundaries of the dense path (docs/OBSERVABILITY.md
"Spans inside the fuser and the engine"): ids and parents, the three
spans beneath every ``fuse.flush``, the counters that ride with them,
the scopes a device trace finds the programs by, and the disabled path;
JAX's compile stages as children of the span that is open, and the
``id`` that ties the ring's clock to a profiler trace's.

The names a compiled kernel carries are held where the chip's compiler
is (tests/test_chip_compile.py)."""

import ast
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from qrack_tpu import telemetry as tele
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.models.algorithms import trotter_qcircuit
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk
from qrack_tpu.parallel.pager import QPager
from qrack_tpu.utils.rng import QrackRandom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 12
PARTS = ("fuse.lower", "fuse.operands", "fuse.dispatch")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    tele.disable()
    tele.reset()
    yield
    tele.disable()
    tele.reset()


def _kernel_tiles(monkeypatch, block_pow):
    """The kernel lowering under the interpreter, with tiles of
    ``2^block_pow``."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setattr(pk, "DEFAULT_BLOCK_POW", block_pow)
    fu.PROGRAMS.clear()
    yield
    fu.PROGRAMS.clear()


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 2^6, so that a w12 ket has cross-tile targets as a w28
    ket has; the kernel body keeps the flat tile."""
    yield from _kernel_tiles(monkeypatch, 6)


@pytest.fixture
def dense_tiles(monkeypatch):
    """Tiles of 2^10, the smallest the kernel body views as (rows, 128):
    a w12 ket, and a 4-page pager's 2^10 pages, compute dense."""
    yield from _kernel_tiles(monkeypatch, 10)


def _recorded():
    return tele.local_trace_source()["spans"]


def _qft(q):
    q.SetPermutation(0b101101110011 & ((1 << q.qubit_count) - 1))
    q.QFT(0, q.qubit_count)
    return q.GetAmplitude(5)


def _trotter(q):
    # two steps: a step is 23 gates since a bond is one (PR 47), and a
    # window holds 32
    trotter_qcircuit(q.qubit_count, steps=2).Run(q)
    return q.GetAmplitude(3)


def _dense():
    return QEngineTPU(W, rng=QrackRandom(7), rand_global_phase=False)


def _pager():
    return QPager(W, rng=QrackRandom(7), rand_global_phase=False, n_pages=4)


# -- the mechanism -------------------------------------------------------------

def test_spans_record_id_and_parent():
    tele.enable()
    with tele.span("outer"):
        with tele.span("first"):
            pass
        with tele.span("second"):
            with tele.span("leaf"):
                pass
    by_name = {e["name"]: e for e in _recorded()}
    ids = [e["id"] for e in _recorded()]
    assert len(set(ids)) == 4 and all(isinstance(i, int) for i in ids)
    assert by_name["outer"]["parent"] is None
    assert by_name["first"]["parent"] == by_name["outer"]["id"]
    assert by_name["second"]["parent"] == by_name["outer"]["id"]
    assert by_name["leaf"]["parent"] == by_name["second"]["id"]
    args = {e["name"]: e["args"] for e in tele.chrome_trace()["traceEvents"]
            if e["ph"] == "X"}
    assert args["leaf"]["parent"] == args["second"]["id"]
    assert "synced" not in args["leaf"]


def test_self_seconds_is_duration_minus_children():
    entries = [
        {"id": 1, "parent": None, "dur_s": 1.0},
        {"id": 2, "parent": 1, "dur_s": 0.25},
        {"id": 3, "parent": 1, "dur_s": 0.5},
        {"id": 4, "parent": 3, "dur_s": 0.125},
        {"id": 5, "parent": 99, "dur_s": 2.0},  # its parent fell off the ring
    ]
    own = tele.self_seconds(entries)
    assert own == {1: 0.25, 2: 0.25, 3: 0.375, 4: 0.125, 5: 2.0}
    tele.enable()
    with tele.span("outer"):
        with tele.span("inner"):
            pass
    rec = {e["name"]: e for e in _recorded()}
    own = tele.self_seconds(_recorded())
    assert own[rec["outer"]["id"]] == pytest.approx(
        rec["outer"]["dur_s"] - rec["inner"]["dur_s"])


def test_span_takes_no_sync_argument():
    tele.enable()
    with pytest.raises(TypeError):
        tele.span("x", sync=jnp.zeros((2, 2)))
    assert not hasattr(tele, "xplane_bracket")


def test_an_enabled_span_is_an_event_of_an_open_profiler_trace(tmp_path):
    """The span rides the profiler's clock: under an open trace it is a
    host event named ``qrack.<name>`` of the same ``.xplane.pb``."""
    from jax.profiler import ProfileData

    tele.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tele.span("fuse.flush"):
            jnp.zeros(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    found = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    names = {ev.name for plane in ProfileData.from_file(found[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "qrack.fuse.flush" in names


# -- JAX's compile stages under the span that is open ------------------------------

STAGES = ("compile.trace", "compile.lower", "compile.backend")
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _nested_jits():
    """A jit that calls a jit, new functions every time: never cached."""
    inner = jax.jit(lambda x: x * 2.0 + 1.0)
    return jax.jit(lambda x: inner(x) + inner(x + 1.0))


def _named(name):
    return [e for e in _recorded() if e["name"] == name]


def _inside(child, parent, slack=1e-3):
    # a stage's end is JAX's own reading of another clock
    return (child["ts_s"] >= parent["ts_s"] - slack
            and child["ts_s"] + child["dur_s"]
            <= parent["ts_s"] + parent["dur_s"] + slack)


def test_a_first_call_leaves_its_stages_under_the_open_span():
    tele.enable()
    fn, x = _nested_jits(), np.ones(8, np.float32)  # numpy: no eager program
    with tele.span("program.first_call"):
        fn(x).block_until_ready()
    with tele.span("program.second_call"):
        fn(x).block_until_ready()
    first, second = _named("program.first_call")[0], \
        _named("program.second_call")[0]
    stages = [e for e in _recorded() if e["name"] in STAGES]
    # the inner jit's trace is part of the outer one's: counted once
    assert sorted(e["name"] for e in stages) == sorted(STAGES)
    for e in stages:
        assert e["parent"] == first["id"] and e["depth"] == first["depth"] + 1
        assert _inside(e, first)
    assert sum(e["dur_s"] for e in stages) <= first["dur_s"]
    assert not [e for e in _recorded() if e["parent"] == second["id"]]
    agg = tele.snapshot()["spans"]
    assert [agg[n]["count"] for n in STAGES] == [1, 1, 1]


def test_an_eager_program_inside_a_trace_is_the_traces_child():
    """What compiles while a function is traced (an operation evaluated
    at trace time) nests under that trace, so outermost stages never sum
    to more than the span around them."""
    tele.enable()

    def fn(x):
        with jax.ensure_compile_time_eval():  # eager, while tracing
            c = jnp.arange(8, dtype=jnp.float32) * 3.0
        return x + c

    with tele.span("program.call"):
        jax.jit(fn)(np.ones(8, np.float32)).block_until_ready()
    call = _named("program.call")[0]
    by_id = {e["id"]: e for e in _recorded()}
    outer = [e for e in _recorded() if e["parent"] == call["id"]]
    assert sorted(e["name"] for e in outer) == sorted(STAGES)
    trace = [e for e in outer if e["name"] == "compile.trace"][0]
    inner = [e for e in _recorded() if e["parent"] == trace["id"]]
    assert inner and {e["name"] for e in inner} <= set(STAGES)
    assert all(_inside(e, trace) for e in inner)
    assert sum(e["dur_s"] for e in outer) <= call["dur_s"]
    own = tele.self_seconds(_recorded())
    assert all(v >= -1e-3 for v in own.values())
    assert all(by_id[e["parent"]]["name"] != e["name"] for e in inner)


def test_a_stage_under_no_span_has_no_parent():
    tele.enable()
    _nested_jits()(np.ones(4, np.float32)).block_until_ready()
    stages = [e for e in _recorded() if e["name"] in STAGES]
    assert sorted(e["name"] for e in stages) == sorted(STAGES)
    assert all(e["parent"] is None and e["depth"] == 0 for e in stages)


def test_the_caches_load_is_a_child_of_the_backend_stage():
    """The persistent cache fires its retrieval's duration inside the
    backend stage, with no begin of its own (jax/_src/compiler.py)."""
    from jax import monitoring

    tele.enable()
    with tele.span("program.call"):
        monitoring.record_scalar(BACKEND_EVENT, 0.0, fun_name="f")
        monitoring.record_event_duration_secs(CACHE_LOAD_EVENT, 1e-4)
        monitoring.record_event_duration_secs(BACKEND_EVENT, 2e-4,
                                              fun_name="f")
    call, backend, load = (_named(n)[0] for n in (
        "program.call", "compile.backend", "compile.cache_load"))
    assert backend["parent"] == call["id"]
    assert load["parent"] == backend["id"] and load["depth"] == 2
    assert load["dur_s"] == 1e-4
    # an end whose begin was never seen: an interval that ended now
    monitoring.record_event_duration_secs(BACKEND_EVENT, 0.5, fun_name="g")
    late = _named("compile.backend")[-1]
    assert late["parent"] is None and late["dur_s"] == 0.5


def test_telemetry_off_the_listeners_record_nothing():
    tele.enable()   # registers the listeners, once a process
    tele.disable()
    _nested_jits()(np.ones(4, np.float32)).block_until_ready()
    assert tele.span("fuse.flush") is tele._NULL_SPAN
    assert _recorded() == [] and tele.snapshot()["spans"] == {}
    assert not getattr(tele._TLS, "compile", None)


def test_a_stage_begun_while_on_meets_its_end_while_off():
    from jax import monitoring

    tele.enable()
    with tele.span("program.call"):
        monitoring.record_scalar(BACKEND_EVENT, 0.0, fun_name="f")
        tele.disable()
        monitoring.record_event_duration_secs(BACKEND_EVENT, 1e-4,
                                              fun_name="f")
    assert tele._TLS.compile == [] and tele._TLS.stack == []
    assert [e["name"] for e in _recorded()] == ["compile.backend",
                                                "program.call"]


def test_record_span_takes_a_parent_and_a_depth():
    import time

    tele.enable()
    with tele.span("outer"):
        tele.record_span("measured", time.perf_counter() - 0.25, 0.25,
                         parent=tele._TLS.stack[-1], depth=1)
    outer, measured = _named("outer")[0], _named("measured")[0]
    assert (measured["parent"], measured["depth"]) == (outer["id"], 1)
    tele.record_span("alone", time.perf_counter(), 0.5)
    assert (_named("alone")[0]["parent"], _named("alone")[0]["depth"]) \
        == (None, 0)


def _stack_tpu_qft():
    from qrack_tpu import create_quantum_interface

    q = create_quantum_interface("tpu", W, rng=QrackRandom(7),
                                 rand_global_phase=False)
    return _qft(q)


def _stack_pager_trotter_step():
    from helpers import issue, trotter_step_gates
    from qrack_tpu import create_quantum_interface

    q = create_quantum_interface("pager", 14, n_pages=4, rng=QrackRandom(3),
                                 rand_global_phase=False)
    q.SetPermutation(0b10110011101011)
    issue(q, trotter_step_gates(14))
    return q.GetAmplitude(0b10110011101011)


@pytest.mark.parametrize("drive", [_stack_tpu_qft, _stack_pager_trotter_step],
                         ids=["tpu-qft-w12", "pager-trotter-w14"])
def test_every_compile_stage_of_a_stack_is_under_a_program_span(drive):
    from qrack_tpu.parallel import pager

    fu.PROGRAMS.clear()
    pager._PROGRAMS.clear()  # the stack's programs compile in this test
    tele.enable()
    drive()
    rec = _recorded()
    by_id = {e["id"]: e for e in rec}
    stages = [e for e in rec if e["name"].startswith("compile.")]
    assert {"compile.trace", "compile.lower", "compile.backend"} \
        <= {e["name"] for e in stages}
    for e in stages:
        while e["name"].startswith("compile."):
            assert e["parent"] is not None, e
            e = by_id[e["parent"]]
    # the engine's names, whatever the engine
    names = {e["name"] for e in rec}
    assert {"engine.read", "engine.set_permutation",
            "factory.create_interface"} <= names
    create = _named("factory.create_interface")
    assert len(create) == 1
    fills = _named("engine.set_permutation")
    assert fills[0]["parent"] == create[0]["id"]      # the constructor's
    assert fills[-1]["parent"] is None                # the caller's
    own = tele.self_seconds(rec)
    assert all(v >= -1e-3 for v in own.values())


# -- the ring on a profiler trace's clock ----------------------------------------------

def _host_events(directory):
    """(name, id statistic, start_ns) of the ``qrack.*`` host events."""
    from jax.profiler import ProfileData

    found = [os.path.join(d, f) for d, _, fs in os.walk(directory)
             for f in fs if f.endswith(".xplane.pb")]
    return [(ev.name, dict(ev.stats).get("id"), ev.start_ns)
            for plane in ProfileData.from_file(found[0]).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("qrack.")]


def test_a_traced_span_carries_its_id_and_ties_the_two_clocks(tmp_path):
    tele.enable()
    with tele.span("engine.set_permutation"):   # before the trace opens
        jnp.zeros(4).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with tele.span("fuse.flush"):
                with tele.span("fuse.dispatch"):
                    jnp.zeros(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    by_id = {e["id"]: e for e in _recorded()}
    events = _host_events(tmp_path)
    assert sorted(n for n, _, _ in events) == sorted(
        ["qrack.fuse.flush", "qrack.fuse.dispatch"] * 3)
    # every traced event has its ring twin, by id and by name
    for name, span_id, _ in events:
        assert name == "qrack." + by_id[span_id]["name"]
    offsets = [start / 1e9 - by_id[i]["ts_s"] for _, i, start in events]
    assert max(offsets) - min(offsets) < 1e-3
    offset = sorted(offsets)[len(offsets) // 2]
    # the ring's entries on the trace's clock: the one recorded before
    # the trace opened lies ahead of the first traced event
    before = _named("engine.set_permutation")[0]
    assert before["id"] not in {i for _, i, _ in events}
    first_traced = min(start for _, _, start in events) / 1e9
    assert before["ts_s"] + before["dur_s"] + offset < first_traced


# -- the disabled path ---------------------------------------------------------

def test_disabled_records_nothing_and_builds_no_span():
    assert tele.span("fuse.flush") is tele._NULL_SPAN
    q = _dense()
    _qft(q)
    snap = tele.snapshot()
    assert snap["counters"] == {} and snap["spans"] == {}
    assert _recorded() == []


def test_telemetry_module_imports_no_jax_at_top_level():
    path = os.path.join(REPO, "qrack_tpu", "telemetry", "__init__.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    top = []
    for node in tree.body:  # statements of the module itself, no bodies
        if isinstance(node, ast.Import):
            top += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            top.append(node.module)
    assert not [m for m in top if m.split(".")[0] in ("jax", "jaxlib")], top


# -- one flush, three parts, and the counters ---------------------------------------

def _flushes_and_their_parts():
    rec = _recorded()
    flushes = [e for e in rec if e["name"] == "fuse.flush"]
    children = {f["id"]: [e["name"] for e in rec if e["parent"] == f["id"]]
                for f in flushes}
    return flushes, children


def _spy_window_programs(monkeypatch):
    """(structure, block_pow) of every window that asks for its kernel
    program: what plan_window is asked about, independently of the
    counter."""
    seen = []
    real = fu.kernel_lowering

    def spy(n, structure, backend=None):
        plan, why = real(n, structure, backend)
        if plan is not None:
            seen.append((structure, plan["block_pow"]))
        return plan, why

    monkeypatch.setattr(fu, "kernel_lowering", spy)
    return seen


def _assert_three_parts(flushes, children):
    for f in flushes:
        assert sorted(n for n in children[f["id"]] if n in PARTS) \
            == sorted(PARTS), children[f["id"]]


@pytest.mark.parametrize("drive", [_qft, _trotter], ids=["qft", "trotter"])
def test_dense_flush_has_its_three_parts(small_tiles, monkeypatch, drive):
    seen = _spy_window_programs(monkeypatch)
    tele.enable()
    q = _dense()
    drive(q)
    c = tele.snapshot()["counters"]
    flushes, children = _flushes_and_their_parts()
    # one span per flush; a flush is a counted window or a one-op flush
    n_flush = sum(v for k, v in c.items() if k.startswith("fuse.tpu.flush."))
    windows = c.get("fuse.kernel.windows", 0) + c.get("fuse.xla.windows", 0)
    assert len(flushes) == n_flush >= 2
    assert windows == len(seen) == c["fuse.kernel.windows"]
    _assert_three_parts(flushes, children)
    # a window issues its two operand columns and its program, a
    # one-op flush one eager program
    assert c["fuse.tpu.programs"] == 3 * windows + (n_flush - windows)
    # cross-tile segments: the counter against plan_window itself
    want = sum(1 for structure, bp in seen
               for seg in pk.plan_window(structure, bp)
               if seg["xgen"] is not None)
    assert 0 < c["fuse.kernel.sweeps.cross"] == want < c["fuse.kernel.sweeps"]
    # the read is a span of the engine, outside every flush
    reads = [e for e in _recorded() if e["name"] == "engine.read"]
    assert reads and all(e["parent"] is None for e in reads)


@pytest.mark.parametrize("drive", [_qft, _trotter], ids=["qft", "trotter"])
def test_dense_sweeps_are_those_of_the_plan(dense_tiles, monkeypatch, drive):
    seen = _spy_window_programs(monkeypatch)
    tele.enable()
    drive(_dense())
    c = tele.snapshot()["counters"]
    assert {bp for _, bp in seen} == {10}
    want = sum(len(pk.plan_window(structure, bp)) for structure, bp in seen)
    assert 0 < c["fuse.kernel.sweeps.dense"] == want == c["fuse.kernel.sweeps"]
    assert c["fuse.kernel.sweeps.cross"] > 0


def test_flat_sweeps_are_not_counted_dense(small_tiles):
    tele.enable()
    _qft(_dense())
    _qft(_pager())
    c = tele.snapshot()["counters"]
    assert c["fuse.kernel.sweeps"] > 0 == c.get("fuse.kernel.sweeps.dense", 0)


def test_pager_dense_sweeps_are_its_kernel_segments(dense_tiles):
    """An exchange is a sweep and no launch: the pager's dense sweeps
    are the planned segments of its local runs.  (The placement fixed:
    the planner leaves no gate on a paged qubit.)"""
    tele.enable()
    q = QPager(W, rng=QrackRandom(7), rand_global_phase=False, n_pages=4,
               remap="off")
    assert q.local_bits == 10
    _trotter(q)
    c = tele.snapshot()["counters"]
    exchanges = c.get("exchange.pager.global_2x2", 0)
    assert exchanges > 0
    assert 0 < c["fuse.kernel.sweeps.dense"] \
        == c["fuse.kernel.sweeps"] - exchanges


def test_dense_set_permutation_and_build_spans(small_tiles):
    tele.enable()
    q = _dense()
    _qft(q)
    rec = _recorded()
    # the constructor's and the test's
    assert sum(e["name"] == "engine.set_permutation" for e in rec) == 2
    builds = [e for e in rec if e["name"] == "fuse.build"]
    lowers = {e["id"] for e in rec if e["name"] == "fuse.lower"}
    assert builds and all(b["parent"] in lowers for b in builds)
    assert len(builds) == tele.snapshot()["counters"]["compile.fuse.miss"]


def test_one_op_flush_counts_one_program():
    tele.enable()
    q = _dense()
    q.H(3)
    q.GetAmplitude(0)
    c = tele.snapshot()["counters"]
    flushes, children = _flushes_and_their_parts()
    assert len(flushes) == 1 and sorted(children[flushes[0]["id"]]) == sorted(PARTS)
    assert c["fuse.tpu.programs"] == 1
    assert "fuse.kernel.windows" not in c and "fuse.xla.windows" not in c


def test_flush_parts_cover_the_flush():
    """lower + operands + dispatch leave the flush only its own few
    lines: its self time, by the rule an operator uses too."""
    tele.enable()
    q = _dense()
    _trotter(q)
    rec = _recorded()
    own = tele.self_seconds(rec)
    flushes = [e for e in rec if e["name"] == "fuse.flush"]
    total = sum(f["dur_s"] for f in flushes)
    assert sum(own[f["id"]] for f in flushes) < 0.2 * total  # CPU, w12: µs


@pytest.mark.parametrize("drive", [_qft, _trotter], ids=["qft", "trotter"])
def test_pager_flush_has_its_three_parts(small_tiles, monkeypatch, drive):
    seen = []
    real = fu.sharded_kernel_lowering

    def spy(L, structure, backend=None):
        plan, why = real(L, structure, backend)
        if plan is not None:
            seen.append((structure, L, plan["block_pow"]))
        return plan, why

    monkeypatch.setattr(fu, "sharded_kernel_lowering", spy)
    tele.enable()
    q = _pager()
    drive(q)
    c = tele.snapshot()["counters"]
    flushes, children = _flushes_and_their_parts()
    n_flush = sum(v for k, v in c.items() if k.startswith("fuse.pager.flush."))
    assert len(flushes) == n_flush >= 2
    _assert_three_parts(flushes, children)
    windows = c.get("fuse.kernel.windows", 0) + c.get("fuse.xla.windows", 0)
    assert windows == len(seen)
    # a window: its two operand columns and its program; else one program
    assert c["fuse.pager.programs"] == 3 * windows + (n_flush - windows)
    want = sum(1 for structure, L, bp in seen
               for seg in fu._sharded_segments(structure, L) if seg[0] == "run"
               for s in pk.plan_window(fu._sharded_run_structure(seg[1], L), bp)
               if s["xgen"] is not None)
    assert c["fuse.kernel.sweeps.cross"] == want


# -- a window of bare cross-tile gen is a kernel window ------------------------

def _noremap_pager():
    """Upstream's placement, as the paged cell runs it: qubits 10 and 11
    stay paged, a gate on them exchanges half pages."""
    return QPager(W, rng=QrackRandom(7), rand_global_phase=False, n_pages=4,
                  remap="off")


# (engine, RX targets, exchanges): at tiles of 2^6 every target from 6
# on is cross-tile; the window the fuser kept on the XLA chain until
# PR 35, as many planned sweeps as ops until two bare leads shared a
# launch (PR 50: a sweep for every two local ones and one for the odd
# one left).  The last is the paged Trotter step's last window in small:
# three local cross-tile gen, then two on paged qubits
_BARE_CROSS_WINDOWS = [(_dense, (7, 11), 0), (_dense, (6, 9, 11), 0),
                       (_dense, tuple(range(6, 12)), 0),
                       (_noremap_pager, (8, 9), 0),
                       (_noremap_pager, (7, 8, 9, 10, 11), 2)]


@pytest.mark.parametrize(
    "make,targets,exchanges", _BARE_CROSS_WINDOWS,
    ids=[f"{m.__name__.strip('_')}-{len(t)}gen"
         for m, t, _ in _BARE_CROSS_WINDOWS])
def test_bare_cross_tile_window_is_a_kernel_window(small_tiles, make, targets,
                                                   exchanges):
    from qrack_tpu import QEngineCPU

    tele.enable()
    q = make()
    o = QEngineCPU(W, rng=QrackRandom(7), rand_global_phase=False)
    for e in (q, o):
        e.SetPermutation(0b101101110011)
        for j, t in enumerate(targets):
            e.RX(0.3 + 0.17 * j, t)
    got = np.asarray(q.GetQuantumState())
    assert np.max(np.abs(got - np.asarray(o.GetQuantumState()))) < 1e-6
    c = tele.snapshot()["counters"]
    k = len(targets)
    assert (c["fuse.kernel.windows"], c["fuse.kernel.ops"]) == (1, k)
    # an exchange is counted a sweep and is no launch; the local leads
    # (the pager hands them to its per-page kernel controlled) pair
    paired = (k - exchanges) // 2
    assert c["fuse.kernel.leads.paired"] == paired
    assert c["fuse.kernel.sweeps"] == k - paired
    assert c["fuse.kernel.sweeps.cross"] == k - exchanges - paired
    assert c.get("exchange.pager.global_2x2", 0) == exchanges
    assert c.get("fuse.xla.windows", 0) == c.get("fuse.xla.sweeps", 0) == 0
    assert not [name for name in c if name.startswith("fuse.kernel.fallback")]
    flushes, children = _flushes_and_their_parts()
    assert len(flushes) == 1
    _assert_three_parts(flushes, children)
    assert c[f"fuse.{q._tele_name}.programs"] == 3


def test_no_code_path_writes_no_sweep_gain():
    """The reason left with the rule: the package's source does not
    spell it, and the fallback counter is written from the reason."""
    pkg = os.path.join(REPO, "qrack_tpu")
    for root, _, files in os.walk(pkg):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    assert "no_sweep_gain" not in f.read(), name


# -- one packed operand put per window -----------------------------------------

class _OperandSpy:
    """What a window program is handed after the planes, and the device
    calls made under the ``fuse.operands`` span of the same flush."""

    DEVICE_CALLS = ((jnp, "asarray"), (jnp, "array"), (jnp, "stack"),
                    (jnp, "concatenate"), (jax, "device_put"))

    def __init__(self, monkeypatch):
        self.windows = []       # (arguments after the planes, device calls)
        self._in_operands = False
        self._device_calls = 0
        real_span = tele.span
        spy = self

        class span:
            def __init__(self, name, *a, **kw):
                self._name = name
                self._real = real_span(name, *a, **kw)

            def __enter__(self):
                if self._name == "fuse.operands":
                    spy._in_operands, spy._device_calls = True, 0
                return self._real.__enter__()

            def __exit__(self, *exc):
                if self._name == "fuse.operands":
                    spy._in_operands = False
                return self._real.__exit__(*exc)

        monkeypatch.setattr(tele, "span", span)
        for mod, name in self.DEVICE_CALLS:
            monkeypatch.setattr(mod, name, self._counted(getattr(mod, name)))
        for name in ("dense_window_program", "kernel_window_program"):
            monkeypatch.setattr(fu, name, self._spied(getattr(fu, name)))
        monkeypatch.setattr(QPager, "_p_fuse_window",
                            self._spied(QPager._p_fuse_window))

    def _counted(self, real):
        def call(*a, **kw):
            self._device_calls += self._in_operands
            return real(*a, **kw)
        return call

    def _spied(self, real_program):
        def program(*a, **kw):
            prog = real_program(*a, **kw)

            def run(planes, *operands):
                self.windows.append((operands, self._device_calls))
                return prog(planes, *operands)
            return run
        return program


@pytest.mark.parametrize("tiles", ["chain", "small_tiles"])
@pytest.mark.parametrize("drive", [_qft, _trotter], ids=["qft", "trotter"])
@pytest.mark.parametrize("make", [_dense, _pager], ids=["dense", "pager"])
def test_a_window_issues_two_operand_arrays(request, monkeypatch, make, drive,
                                            tiles):
    """Whatever a window holds, the flush hands its program two host
    columns after the planes, packed without one call to the device, and
    counts them and the program: 3 a window, 1 a one-op flush."""
    if tiles != "chain":
        request.getfixturevalue(tiles)
    spy = _OperandSpy(monkeypatch)
    tele.enable()
    q = make()
    drive(q)
    c = tele.snapshot()["counters"]
    name = q._tele_name
    n_flush = sum(v for k, v in c.items()
                  if k.startswith(f"fuse.{name}.flush."))
    windows = c.get("fuse.kernel.windows", 0) + c.get("fuse.xla.windows", 0)
    assert windows == len(spy.windows) >= 2
    assert c[f"fuse.{name}.programs"] == 3 * windows + (n_flush - windows)
    for operands, device_calls in spy.windows:
        iv, fv = operands
        assert type(iv) is np.ndarray and type(fv) is np.ndarray
        assert iv.dtype == np.int32 and iv.ndim == 2 and iv.shape[1] == 1
        assert fv.dtype == q.dtype and fv.ndim == 2 and fv.shape[1] == 1
        assert device_calls == 0


def test_pager_and_dense_agree_with_spans_on():
    tele.enable()
    a, b = _dense(), _pager()
    assert _qft(a) == pytest.approx(_qft(b), abs=1e-5)


# -- the scopes a device trace finds the programs by ---------------------------------

def _lowered_text(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _ops(structure):
    return [fu.FusedOp(kind, target, int(c), int(c), np.eye(2))
            for kind, target, c in structure]


STRUCTURE = (("gen", 9, False), ("cphase", 3, True), ("gen", 2, False))


def test_window_fn_is_lowered_under_its_names():
    """The scope is in the operations' locations; the function's name is
    the module's, which a trace keeps whatever the locations carry."""
    operands = fu.pack_operands(_ops(STRUCTURE), jnp.float32)
    text = _lowered_text(fu.window_fn(W, STRUCTURE),
                         jnp.zeros((2, 1 << W), jnp.float32), *operands)
    assert "qrack.fuse.xla_window" in text
    assert "module @jit_qrack_xla_window" in text


def test_kernel_window_fn_is_lowered_under_its_names():
    operands = fu.pack_operands(_ops(STRUCTURE), jnp.float32)
    fn = pk.make_window_fn(W, STRUCTURE, block_pow=6, interpret=True)
    text = _lowered_text(fn, jnp.zeros((2, 1 << W), jnp.float32), *operands)
    assert "qrack.fuse.kernel_window" in text
    assert "module @jit_qrack_kernel_window" in text


def test_pager_window_program_is_lowered_under_its_scopes(small_tiles):
    q = _pager()
    L = q.local_bits
    ops = _ops((("gen", W - 1, False), ("gen", 2, False), ("cphase", 1, True)))
    structure = fu.sharded_structure_of(ops)
    operands = fu.pack_operands(ops, q.dtype, split_at=L)
    plan, _ = fu.sharded_kernel_lowering(L, structure)
    prog = q._p_fuse_window(structure, kernel_plan=plan)
    _, nf, ni = pk._operand_slots(structure, split=True)
    assert [o.shape for o in operands] == [(ni, 1), (nf, 1)]
    text = prog.lower(q._state, *operands).as_text(debug_info=True)
    assert "qrack.pager.exchange" in text
    assert "qrack.fuse.kernel_window" in text
    assert "module @jit_qrack_sharded_kernel_window" in text


def test_served_batch_program_is_lowered_under_its_scope():
    from qrack_tpu.models.qft import qft_qcircuit

    from qrack_tpu.serve import batcher

    fn = qft_qcircuit(4).compile_batched_fn(4)
    text = _lowered_text(fn, jnp.zeros((2, 2, 16), jnp.float32))
    assert "qrack.serve.dispatch" in text
    prog = batcher.batch_program(qft_qcircuit(4), 4, 2)
    text = prog.lower([jnp.zeros((2, 16), jnp.float32)] * 2).as_text()
    assert "module @jit_qrack_serve_dispatch" in text


# -- the program store's spans, and the benchmark's reader of them ---------------------

@pytest.fixture
def benchmark_modules():
    """``load(metric)``: a per-layer reader of the benchmark, and
    ``setup_spans``; the benchmark's modules are imported for the test
    alone."""
    import sys

    bench = os.path.join(REPO, "benchmarks")
    before, path = set(sys.modules), list(sys.path)
    sys.path.insert(0, bench)
    try:
        import harness
        import setup_spans

        yield lambda metric: harness.load_module("per_layer", metric).read, \
            setup_spans
    finally:
        sys.path[:] = path
        for name in set(sys.modules) - before:
            if getattr(sys.modules[name], "__file__", "").startswith(bench):
                del sys.modules[name]


def _setup_of_this_thread(setup_spans):
    """The ring as a traced run's set-up: the window opens now, and one
    host event of the trace carries the id of a ring entry."""
    ring = _recorded()
    offset = 1000.0
    tie = ring[0]
    events = [("qrack." + tie["name"], tie["id"],
               int((tie["ts_s"] + offset) * 1e9))]
    now = time.perf_counter() - tele._EPOCH
    return setup_spans.SetupSpans(ring, events, int((now + offset) * 1e9),
                                  threading.get_ident())


@pytest.mark.parametrize("start", ["cold", "warm"])
def test_programs_loaded_counts_the_programs_read_back(
        small_tiles, program_store, benchmark_modules, start):
    load, setup_spans = benchmark_modules
    read = load("setup.programs_loaded")
    if start == "warm":  # an earlier process stored them
        _qft(_dense())
        fu.PROGRAMS.clear()
    tele.enable()
    _qft(_dense())
    counters = tele.snapshot()["counters"]
    windows = counters["compile.fuse.miss"]
    found = _setup_of_this_thread(setup_spans)
    names = [e["name"] for e in found.program]
    assert read({"setup_spans": found}) == (windows if start == "warm" else 0)
    assert names.count("warmstart.program.export") == (
        0 if start == "warm" else windows)
    assert counters.get("warmstart.program.hit", 0) == (
        windows if start == "warm" else 0)
    assert counters.get("warmstart.program.miss", 0) == (
        0 if start == "warm" else windows)
    # one span a program, inside the dispatch that first called it: the
    # table of self seconds still adds up to the program's seconds
    by_id = {e["id"]: e for e in found.program}
    for e in found.program:
        if e["name"].startswith("warmstart.program."):
            assert by_id[e["parent"]]["name"] == "fuse.dispatch"
    assert sum(found.self_seconds_by_name().values()) == pytest.approx(
        found.program_s)


def test_programs_loaded_reads_nothing_without_a_trace_or_a_store(
        benchmark_modules, monkeypatch):
    load, setup_spans = benchmark_modules
    read = load("setup.programs_loaded")
    assert read({"trace": None, "setup_seconds": 17.0}) is None
    assert read({"setup_seconds": 17.0}) is None
    tele.enable()
    _qft(_dense())
    found = _setup_of_this_thread(setup_spans)
    assert read({"setup_spans": found}) == 0  # no directory: none loaded
    # a program older than the store (the parent the driver lays this
    # reader over) leaves the metric out
    from qrack_tpu.checkpoint import warmstart

    monkeypatch.delattr(warmstart, "stored_program")
    assert read({"setup_spans": found}) is None
