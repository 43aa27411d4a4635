"""Telemetry subsystem: counters, spans, export, and the disabled path.

The contract under test (docs/OBSERVABILITY.md): with
QRACK_TPU_TELEMETRY off the instrumentation adds nothing — no
attributes, no counter writes; with it on, gate/compile/exchange
counters accumulate across every stack layer, spans aggregate host
time, and snapshots round-trip through JSONL and Chrome trace-event
JSON.  Spans inside the fuser and the engine: tests/test_trace_spans.py."""

import json
import os

import numpy as np
import pytest

from qrack_tpu import telemetry as tele
from qrack_tpu.factory import create_quantum_interface


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts disabled with empty stores and leaves no residue."""
    tele.disable()
    tele.reset()
    yield
    tele.disable()
    tele.reset()


def _layers(counters):
    return {k.split(".")[0] for k in counters}


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------

def test_disabled_is_inert():
    assert not tele.enabled()
    tele.inc("gate.cpu.2x2.w4")
    tele.event("stabilizer.to_dense", width=4)
    s = tele.span("anything")
    assert s is tele._NULL_SPAN  # singleton: no per-call allocation
    with s:
        pass
    snap = tele.snapshot()
    assert snap["enabled"] is False
    assert snap["counters"] == {}
    assert snap["spans"] == {}
    assert snap["events"] == []


def test_disabled_engine_run_records_nothing():
    q = create_quantum_interface("cpu", 4)
    q.H(0)
    q.MCMtrxPerm((0,), np.array([[0, 1], [1, 0]], complex), 1, 1)
    assert tele.snapshot()["counters"] == {}


# ---------------------------------------------------------------------------
# counters across the stack sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stack", ["cpu", "optimal", "turboquant"])
def test_gate_counters_per_stack(stack):
    tele.enable()
    n = 12 if stack == "turboquant" else 6
    q = create_quantum_interface(stack, n)
    q.H(0)
    q.MCMtrxPerm((0,), np.array([[0, 1], [1, 0]], complex), 1, 1)
    if stack == "optimal":
        # Clifford circuits never leave the tableau: non-Clifford
        # phases force the dense engines underneath
        q.QFT(0, n)
    q.GetQuantumState()
    snap = tele.snapshot()
    counters = snap["counters"]
    assert any(k.startswith("gate.") for k in counters), counters
    assert snap["spans"]["factory.create_interface"]["count"] == 1


def test_qft20_optimal_counts_three_layers():
    """The ISSUE acceptance shape: 20-qubit QFT on the optimal stack
    yields nonzero gate counters from at least engine, QUnit, and
    factory, and the jit caches record a miss then hits."""
    tele.enable()
    q = create_quantum_interface("optimal", 20)
    q.H(0)
    q.MCMtrxPerm((0,), np.array([[0, 1], [1, 0]], complex), 1, 1)
    q.QFT(0, 20)
    q.Prob(5)  # forces flush through the layers
    q.Prob(5)  # repeat: the second engine read must hit the jit cache
    counters = tele.snapshot()["counters"]
    layers = _layers(counters)
    assert {"gate", "qunit", "factory"} <= layers, layers
    assert counters["qunit.gate.dispatch"] > 0
    assert sum(v for k, v in counters.items() if k.startswith("gate.")) > 0
    misses = [k for k in counters if k.startswith("compile.") and k.endswith(".miss")]
    hits = [k for k in counters if k.startswith("compile.") and k.endswith(".hit")]
    assert misses, counters
    assert hits, counters


def test_exchange_counters_on_pager():
    tele.enable()
    q = create_quantum_interface("pager", 6, n_pages=4)
    q.H(5)  # global qubit: pair exchange, or a remap under the planner
    q.GetQuantumState()
    counters = tele.snapshot()["counters"]
    assert (counters.get("exchange.pager.global_2x2", 0) >= 1
            or counters.get("exchange.pager.remap", 0) >= 1)
    assert counters.get("exchange.pager.bytes", 0) > 0


def test_exchange_counters_remap_off():
    tele.enable()
    q = create_quantum_interface("pager", 6, n_pages=4, remap="off")
    q.H(5)  # planner disabled: the global target pays the pair exchange
    q.GetQuantumState()
    counters = tele.snapshot()["counters"]
    assert counters.get("exchange.pager.global_2x2", 0) >= 1
    assert counters.get("exchange.pager.bytes", 0) > 0


def test_escalation_events():
    tele.enable()
    from qrack_tpu.layers.stabilizerhybrid import QStabilizerHybrid

    q = QStabilizerHybrid(3)
    q.H(0)
    q.SwitchToEngine()
    snap = tele.snapshot()
    assert snap["counters"].get("stabilizer.to_dense") == 1
    names = [e["name"] for e in snap["events"]]
    assert "stabilizer.to_dense" in names


# ---------------------------------------------------------------------------
# program cache (satellite: bounded _PROGRAMS)
# ---------------------------------------------------------------------------

def test_program_cache_hit_miss_eviction():
    tele.enable()
    cache = tele.ProgramCache("t", cap=2)
    built = []

    def builder_for(k):
        def build():
            built.append(k)
            return f"prog-{k}"
        return build

    assert cache.get_or_build("a", builder_for("a")) == "prog-a"
    assert cache.get_or_build("a", builder_for("a")) == "prog-a"  # hit
    cache.get_or_build("b", builder_for("b"))
    cache.get_or_build("c", builder_for("c"))  # evicts "a" (LRU)
    st = cache.stats()
    assert st == {"size": 2, "cap": 2, "hits": 1, "misses": 3, "evictions": 1}
    assert "a" not in cache and "c" in cache
    counters = tele.snapshot()["counters"]
    assert counters["compile.t.miss"] == 3
    assert counters["compile.t.hit"] == 1
    assert counters["compile.t.eviction"] == 1


def test_program_cache_mesh_token_purges_on_gc():
    # a stand-in mesh object: jax may intern real Mesh instances in a
    # global cache, which would keep the finalizer from ever firing in
    # this test (the LRU cap still bounds that case)
    import gc

    class FakeMesh:
        pass

    cache = tele.ProgramCache("m", cap=8)
    mesh = FakeMesh()
    token = cache.mesh_token(mesh)
    cache.get_or_build(("k", token), lambda: "prog")
    cache.get_or_build(("unrelated",), lambda: "keep")
    assert len(cache) == 2
    del mesh
    gc.collect()
    assert len(cache) == 1  # only the mesh-keyed entry was dropped
    assert ("unrelated",) in cache


def test_turboquant_programs_bounded():
    from qrack_tpu.engines import turboquant as tq

    assert isinstance(tq._PROGRAMS, tele.ProgramCache)
    assert tq._PROGRAMS.cap > 0


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_spans_nest_and_aggregate():
    tele.enable()
    with tele.span("outer"):
        with tele.span("inner"):
            pass
        with tele.span("inner"):
            pass
    spans = tele.snapshot()["spans"]
    assert spans["inner"]["count"] == 2
    assert spans["outer"]["count"] == 1
    assert spans["outer"]["total_s"] >= spans["inner"]["total_s"]
    trace = tele.chrome_trace()["traceEvents"]
    depths = {e["name"]: e["args"]["depth"] for e in trace if e["ph"] == "X"}
    assert depths["outer"] == 0 and depths["inner"] == 1


# ---------------------------------------------------------------------------
# export round-trips
# ---------------------------------------------------------------------------

def test_snapshot_jsonl_round_trip(tmp_path):
    tele.enable()
    tele.inc("gate.cpu.2x2.w4", 3)
    tele.event("stabilizer.to_dense", width=4)
    with tele.span("s"):
        pass
    out = tmp_path / "tele.jsonl"
    tele.write_jsonl(str(out))
    tele.write_jsonl(str(out))  # appends, one object per line
    lines = out.read_text().splitlines()
    assert len(lines) == 2
    snap = json.loads(lines[-1])
    assert snap["counters"]["gate.cpu.2x2.w4"] == 3
    assert snap["spans"]["s"]["count"] == 1
    assert snap["events"][0]["name"] == "stabilizer.to_dense"
    assert snap["events"][0]["width"] == 4


def test_chrome_trace_round_trip(tmp_path):
    tele.enable()
    with tele.span("phase.qft"):
        tele.event("marker")
    tele.inc("gate.cpu.2x2.w4")
    out = tmp_path / "trace.json"
    tele.write_chrome_trace(str(out))
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert {"X", "i", "C", "M"} <= phases
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "phase.qft"
    assert x["dur"] >= 0 and isinstance(x["ts"], (int, float))
    c = next(e for e in evs if e["ph"] == "C")
    assert c["args"] == {"value": 1.0}


def test_atexit_env_path(tmp_path, monkeypatch):
    out = tmp_path / "exitdump.jsonl"
    monkeypatch.setenv("QRACK_TPU_TELEMETRY_OUT", str(out))
    tele.enable()
    tele.inc("x")
    from qrack_tpu.telemetry import export

    export._dump()  # what atexit runs
    assert json.loads(out.read_text().splitlines()[-1])["counters"]["x"] == 1


# ---------------------------------------------------------------------------
# reset/enable semantics
# ---------------------------------------------------------------------------

def test_reset_clears_everything():
    tele.enable()
    tele.inc("a")
    with tele.span("b"):
        pass
    tele.event("c")
    tele.reset()
    snap = tele.snapshot()
    assert snap["counters"] == {} and snap["spans"] == {} and snap["events"] == []
    assert tele.enabled()  # reset clears data, not the enable switch


# ---------------------------------------------------------------------------
# scripts/telemetry_report.py smoke (tier-1: no accelerator, <1s)
# ---------------------------------------------------------------------------

def _load_report_module():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "scripts" / "telemetry_report.py"
    spec = importlib.util.spec_from_file_location("telemetry_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_telemetry_report_smoke(tmp_path, capsys):
    tele.enable()
    tele.inc("gate.cpu.2x2.w4", 7)
    tele.inc("gate.cpu.diag.w4", 3)
    tele.inc("compile.tpu.apply_2x2.miss", 1)
    tele.inc("compile.tpu.apply_2x2.hit", 9)
    tele.inc("exchange.pager.global_2x2", 2)
    tele.inc("exchange.pager.bytes", 4096)
    tele.inc("qunit.gate.dispatch", 10)
    with tele.span("qft.w4"):
        pass
    out = tmp_path / "t.jsonl"
    tele.write_jsonl(str(out))
    tele.write_jsonl(str(out))

    mod = _load_report_module()
    rep = mod.report(mod.load(str(out), aggregate=False), top=5)
    assert rep["top_gates"][0] == ("gate.cpu.2x2.w4", 7)
    assert rep["gates_total"] == 10
    assert rep["compile"]["tpu.apply_2x2"] == {
        "hit": 9, "miss": 1, "miss_ratio": 0.1}
    assert rep["exchange"]["exchange.pager.bytes"] == 4096
    assert rep["layer_events"]["qunit.gate.dispatch"] == 10
    assert rep["spans"]["qft.w4"]["count"] == 1

    # --all sums counters across lines
    rep2 = mod.report(mod.load(str(out), aggregate=True), top=5)
    assert rep2["gates_total"] == 20

    # the CLI text path renders every section without raising
    assert mod.main([str(out), "--top", "3"]) == 0
    text = capsys.readouterr().out
    for section in ("top gates", "compile caches", "exchange",
                    "layer events", "spans"):
        assert section in text


def test_telemetry_report_autoscale_section(tmp_path, capsys):
    tele.enable()
    tele.inc("fleet.autoscale.decision.scale_up.backlog", 2)
    tele.inc("fleet.autoscale.decision.brownout.level1")
    tele.inc("fleet.autoscale.scale_up")
    tele.inc("fleet.autoscale.scale_up_failed")
    tele.inc("fleet.adopt.sessions", 3)       # stays in == fleet ==
    tele.inc("serve.brownout.shed", 30)
    tele.inc("serve.brownout.overloaded", 10)
    tele.inc("serve.brownout.quantized", 5)
    tele.inc("serve.jobs.admitted", 160)
    tele.observe("fleet.autoscale.spawn_s", 2.0)
    tele.observe("fleet.autoscale.spawn_s", 6.0)
    tele.gauge("fleet.autoscale.n_workers", 3.0)
    tele.gauge("fleet.autoscale.n_peak", 5.0)
    out = tmp_path / "t.jsonl"
    tele.write_jsonl(str(out))

    mod = _load_report_module()
    rep = mod.report(mod.load(str(out), aggregate=False), top=5)
    asc = rep["autoscale"]
    assert asc["decision.scale_up.backlog"] == 2
    assert asc["decision.brownout.level1"] == 1
    assert asc["scale_up"] == 1 and asc["scale_up_failed"] == 1
    # brownout share counts front-door refusals over everything that
    # asked for admission: (30+10) / (30+10+160)
    assert asc["brownout_share"] == 0.2
    assert asc["brownout.quantized"] == 5
    assert asc["spawn_s"]["count"] == 2
    assert asc["spawn_s"]["p50_s"] <= asc["spawn_s"]["p99_s"]
    assert asc["n_workers"] == 3.0 and asc["n_peak"] == 5.0
    # autoscale names move OUT of == fleet == (no double reporting)
    assert not any(k.startswith("fleet.autoscale.") for k in rep["fleet"])
    assert rep["fleet"]["fleet.adopt.sessions"] == 3

    assert mod.main([str(out)]) == 0
    text = capsys.readouterr().out
    assert "== autoscale ==" in text
    assert "brownout_share" in text
