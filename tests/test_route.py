"""Adaptive circuit router (qrack_tpu.route, docs/ROUTING.md): feature
extraction units, the decision matrix over the algorithm-model IR
builders, routed execution vs the CPU oracle across the fuzz op
vocabulary, one QrackService serving a w100 Clifford tenant next to a
dense w22 QFT tenant, and the mis-route escalation (exactly-once)
regression.  The slow-marked soak at the bottom runs the routed stack
against a dense-forced twin over many random interleavings.
"""

import numpy as np
import pytest

from qrack_tpu import QEngineCPU, create_quantum_interface
from qrack_tpu import matrices as mat
from qrack_tpu import telemetry as tele
from qrack_tpu.layers.qcircuit import QCircuit
from qrack_tpu.models.algorithms import (ghz_qcircuit, qaoa_qcircuit,
                                         quantum_volume_qcircuit,
                                         trotter_qcircuit)
from qrack_tpu.models.qft import qft_qcircuit
from qrack_tpu.route import (INFEASIBLE, MisrouteError, RouteKnobs,
                             choose_stack, decide, extract_features,
                             layers_for, score_stacks)
from qrack_tpu.utils.rng import QrackRandom

from test_fuzz_api import N as FUZZ_N
from test_fuzz_api import _ops


@pytest.fixture
def telemetry():
    tele.enable()
    tele.reset()
    yield tele
    tele.reset()


def _fidelity(a, b) -> float:
    a = np.asarray(a).ravel()
    b = np.asarray(b).ravel()
    return abs(np.vdot(a, b)) ** 2 / (np.vdot(a, a).real
                                      * np.vdot(b, b).real)


# ---------------------------------------------------------------------------
# feature extraction
# ---------------------------------------------------------------------------


def test_features_ghz_fully_clifford():
    n = 12
    f = extract_features(ghz_qcircuit(n), n)
    assert f.is_clifford and f.stabilizer_ok
    assert f.clifford_fraction == 1.0
    assert f.magic_count == 0 and f.general_count == 0
    assert f.entangling_count == n - 1
    assert f.max_component == n          # one chain entangles everything
    assert f.nn_fraction == 1.0          # CNOT ladder is nearest-neighbor
    assert f.distinct_pairs == n - 1


def test_features_qft_controlled_phases_are_general():
    # controlled non-Clifford phases are NOT gadgetable: they must count
    # as general (forcing dense), never as magic
    f = extract_features(qft_qcircuit(8), 8)
    assert f.general_count > 0
    assert not f.stabilizer_ok
    assert not f.is_clifford


def test_features_t_gates_are_magic_not_general():
    c = QCircuit()
    c.append_1q(0, mat.H2)
    c.append_1q(1, mat.T2)
    f = extract_features(c, 4)
    assert f.magic_count == 1
    assert f.general_count == 0
    assert f.stabilizer_ok and not f.is_clifford


def test_features_multi_control_is_general():
    c = QCircuit()
    c.append_ctrl((0, 1), 2, mat.X2, 3)   # Toffoli
    f = extract_features(c, 4)
    assert f.multi_ctrl_count == 1
    assert f.general_count == 1


def test_features_empty_circuit():
    f = extract_features(QCircuit(), 5)
    assert f.gate_count == 0
    assert f.clifford_fraction == 1.0 and f.is_clifford
    assert f.max_component == 1


def test_features_components_track_entangled_blocks():
    # two disjoint CNOT pairs: the largest entangled block is 2, not 4
    c = QCircuit()
    c.append_ctrl((0,), 1, mat.X2, 1)
    c.append_ctrl((2,), 3, mat.X2, 1)
    f = extract_features(c, 6)
    assert f.max_component == 2
    assert f.distinct_pairs == 2


# ---------------------------------------------------------------------------
# cost model / decision matrix
# ---------------------------------------------------------------------------


def _qv(n):
    return quantum_volume_qcircuit(n, rng=QrackRandom(11))


@pytest.mark.parametrize("make,width,stack", [
    (ghz_qcircuit, 100, "stabilizer"),
    (ghz_qcircuit, 20, "stabilizer"),
    (qft_qcircuit, 22, "dense"),
    (_qv, 12, "dense"),
    # shallow QAOA/Trotter at dense-feasible widths: the vectorized
    # dense sweep beats the host-side tree (calibrated bdt_weight)
    (lambda n: qaoa_qcircuit(n, p=1), 12, "dense"),
    (lambda n: trotter_qcircuit(n, steps=2), 16, "dense"),
    # wide + weakly entangled: the tree's bond bound finally pays
    (lambda n: trotter_qcircuit(n, steps=1), 24, "bdt"),
    # wide + general + fully entangled: past the dense cap the
    # compressed dense-equivalent tier wins over the host-side tree
    (qft_qcircuit, 30, "turboquant"),
], ids=["ghz100", "ghz20", "qft22", "qv12", "qaoa12", "trotter16",
        "trotter24", "qft30"])
def test_decide_matrix(make, width, stack, monkeypatch):
    monkeypatch.delenv("QRACK_ROUTE", raising=False)
    d = decide(make(width), width)
    assert d.stack == stack, d.scores
    assert d.layers == layers_for(stack, width, RouteKnobs.from_env())
    assert d.reason == "cost"


def test_clifford_guard_rail_beats_heuristics(monkeypatch):
    # even with stabilizer weighted absurdly high, a fully-Clifford
    # circuit routes to the exact polynomial representation
    monkeypatch.setenv("QRACK_ROUTE_STAB_WEIGHT", "1e9")
    f = extract_features(ghz_qcircuit(10), 10)
    stack, scores = choose_stack(f, RouteKnobs.from_env(), mode="auto")
    assert stack == "stabilizer"
    assert scores["stabilizer"] != INFEASIBLE


def test_scores_wide_general_circuit_falls_to_turboquant():
    # a w30 QFT entangles all 30 qubits with general payloads: dense
    # (width), stabilizer (general), and qunit (block=width) are all
    # infeasible — the compressed tier takes it over the host-side tree
    f = extract_features(qft_qcircuit(30), 30)
    scores = score_stacks(f, RouteKnobs())
    assert scores["dense"] == INFEASIBLE
    assert scores["stabilizer"] == INFEASIBLE
    assert scores["qunit"] == INFEASIBLE
    assert scores["turboquant"] != INFEASIBLE
    assert scores["turboquant"] < scores["bdt"]
    stack, _ = choose_stack(f, RouteKnobs(), mode="auto")
    assert stack == "turboquant"
    # past the compressed cap too (w40), the tree is the only stack left
    f40 = extract_features(qft_qcircuit(8), 40)
    f40.width = 40
    f40.max_component = 40
    f40.max_cone_width = 40  # full-width cone: lightcone rung out too
    scores40 = score_stacks(f40, RouteKnobs())
    assert scores40["turboquant"] == INFEASIBLE
    stack40, _ = choose_stack(f40, RouteKnobs(), mode="auto")
    assert stack40 == "bdt"


def test_route_env_pins_every_decision(monkeypatch):
    monkeypatch.setenv("QRACK_ROUTE", "dense")
    d = decide(ghz_qcircuit(8), 8)
    assert d.stack == "dense" and d.reason == "pinned"
    monkeypatch.setenv("QRACK_ROUTE", "bdt")
    assert decide(ghz_qcircuit(8), 8).stack == "bdt"
    monkeypatch.setenv("QRACK_ROUTE", "not-a-stack")  # falls back to auto
    assert decide(ghz_qcircuit(8), 8).stack == "stabilizer"


def test_knobs_from_env(monkeypatch):
    monkeypatch.setenv("QRACK_ROUTE_DENSE_MAX_QB", "12")
    monkeypatch.setenv("QRACK_ROUTE_MAX_MAGIC", "2")
    monkeypatch.setenv("QRACK_ROUTE_BDT_MAX_NODES", "4096")
    k = RouteKnobs.from_env()
    assert (k.dense_max_qb, k.max_magic, k.bdt_max_nodes) == (12, 2, 4096)
    # width past the (shrunk) dense cap flips dense infeasible
    f = extract_features(qft_qcircuit(4), 14)
    f.width = 14
    assert score_stacks(f, k)["dense"] == INFEASIBLE


# ---------------------------------------------------------------------------
# routed execution vs the CPU oracle (fuzz vocabulary, both fusion windows)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", ["1", "32"])
@pytest.mark.parametrize("trial", range(3))
def test_routed_fuzz_vs_oracle(trial, window, monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", window)
    monkeypatch.delenv("QRACK_ROUTE", raising=False)
    rng = np.random.Generator(np.random.PCG64(7000 + trial))
    o = QEngineCPU(FUZZ_N, rng=QrackRandom(trial), rand_global_phase=False)
    r = create_quantum_interface("route", FUZZ_N, rng=QrackRandom(trial),
                                 rand_global_phase=False)
    assert r.current_stack() is None     # construction builds nothing
    for step in range(30):
        name, args = _ops(rng)
        while name == "SetBit":          # measuring op: rng streams on
            name, args = _ops(rng)       # different stacks may diverge
        getattr(o, name)(*args)
        getattr(r, name)(*args)
        if rng.integers(0, 10) == 0:
            qb = int(rng.integers(0, FUZZ_N))
            assert abs(o.Prob(qb) - r.Prob(qb)) < 5e-4, (trial, step, name)
    f = _fidelity(o.GetQuantumState(), r.GetQuantumState())
    assert f > 1 - 1e-5, (trial, f)
    assert r.current_stack() in ("stabilizer", "dense")


def test_routed_library_circuit_path(telemetry):
    # Run() on the wrapper itself: plan + apply happen implicitly on the
    # caller thread; a Clifford circuit stays tableau-resident
    from qrack_tpu.layers.stabilizerhybrid import QStabilizerHybrid

    r = create_quantum_interface("route", 60, rng=QrackRandom(3),
                                 rand_global_phase=False)
    ghz_qcircuit(60).Run(r)
    assert r.current_stack() == "stabilizer"
    assert isinstance(r._engine, QStabilizerHybrid)
    assert r._engine.engine is None      # still on the tableau
    amp = complex(r.GetAmplitude(0))
    assert abs(amp - 1 / np.sqrt(2)) < 1e-9
    snap = telemetry.snapshot()
    assert snap["counters"]["route.decisions"] == 1
    assert snap["counters"]["route.built.stabilizer"] == 1


# ---------------------------------------------------------------------------
# one service, two representations (the acceptance scenario)
# ---------------------------------------------------------------------------


def test_service_w100_clifford_next_to_dense_w22(telemetry):
    from qrack_tpu.serve import QrackService

    svc = QrackService(engine_layers="route", batch_window_ms=1.0,
                       queue_budget_ms=120_000.0)
    try:
        wide = svc.create_session(100, seed=1)
        dense = svc.create_session(22, seed=2)
        h1 = svc.submit(wide, ghz_qcircuit(100))
        h2 = svc.submit(dense, qft_qcircuit(22))
        h1.result(timeout=300)
        h2.result(timeout=300)
        wide_stack = svc.call(
            wide, lambda eng: eng.current_stack()).result(timeout=60)
        dense_stack = svc.call(
            dense, lambda eng: eng.current_stack()).result(timeout=60)
        assert wide_stack == "stabilizer"
        assert dense_stack == "dense"
        # correctness on both tenants: GHZ amp, uniform QFT marginal
        amp = svc.call(wide, lambda eng: complex(
            eng.GetAmplitude(0))).result(timeout=60)
        assert abs(abs(amp) - 1 / np.sqrt(2)) < 1e-9
        assert abs(svc.prob(dense, 0, timeout=120) - 0.5) < 1e-3
    finally:
        svc.close()
    snap = telemetry.snapshot()
    assert snap["counters"]["route.decision.stabilizer"] == 1
    assert snap["counters"]["route.decision.dense"] == 1
    assert snap["counters"]["route.jobs.stabilizer"] >= 1
    assert snap["counters"]["route.jobs.dense"] >= 1
    assert snap["counters"].get("route.misroutes", 0) == 0
    assert snap["gauges"]["route.residency.stabilizer"] == 1
    assert snap["gauges"]["route.residency.dense"] == 1


def test_service_route_opt_out_pins_dense(telemetry, monkeypatch):
    from qrack_tpu.serve import QrackService

    monkeypatch.setenv("QRACK_ROUTE", "dense")
    svc = QrackService(engine_layers="route", batch_window_ms=1.0)
    try:
        sid = svc.create_session(8, seed=0)
        svc.submit(sid, ghz_qcircuit(8)).result(timeout=60)
        stack = svc.call(
            sid, lambda eng: eng.current_stack()).result(timeout=60)
        assert stack == "dense"     # Clifford circuit, but routing is off
        amp = svc.call(sid, lambda eng: complex(
            eng.GetAmplitude(0))).result(timeout=60)
        assert abs(abs(amp) - 1 / np.sqrt(2)) < 1e-5
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# mis-route escalation: exactly once, state carried, oracle-exact
# ---------------------------------------------------------------------------


def test_misroute_escalates_to_dense_exactly_once(telemetry):
    n = 6
    r = create_quantum_interface("route", n, rng=QrackRandom(7),
                                 rand_global_phase=False)
    o = QEngineCPU(n, rng=QrackRandom(7), rand_global_phase=False)

    ghz = ghz_qcircuit(n)
    ghz.Run(r)
    ghz.Run(o)
    assert r.current_stack() == "stabilizer"

    # a general circuit against the resident stabilizer: planned
    # escalation carries the state to dense BEFORE the circuit runs
    hard = QCircuit()
    hard.append_1q(0, mat.u3_mtrx(0.3, 0.1, 0.2))
    hard.append_ctrl((1,), 2, mat.u3_mtrx(0.7, 0.4, 0.5), 1)
    hard.Run(r)
    hard.Run(o)
    assert r.current_stack() == "dense"
    assert r._escalated

    # a second general circuit must NOT escalate again
    again = QCircuit()
    again.append_1q(3, mat.u3_mtrx(0.9, 0.2, 0.8))
    again.Run(r)
    again.Run(o)

    f = _fidelity(o.GetQuantumState(), r.GetQuantumState())
    assert f > 1 - 1e-5, f
    snap = telemetry.snapshot()
    assert snap["counters"]["route.misroutes"] == 1
    assert snap["counters"]["route.misroute.escalated"] == 1
    assert snap["gauges"]["route.residency.dense"] == 1
    assert snap["gauges"].get("route.residency.stabilizer", 0) == 0


def test_misroute_past_dense_cap_plans_compressed_rung(telemetry):
    # w30 > dense cap (26) but within the compressed tier's cap: the
    # general circuit is no longer refused — the plan records the
    # turboquant rung of the ladder (realized lazily by apply_plan, so
    # the stabilizer state is untouched here)
    n = 30
    r = create_quantum_interface("route", n, rng=QrackRandom(1),
                                 rand_global_phase=False)
    ghz_qcircuit(n).Run(r)
    assert r.current_stack() == "stabilizer"
    hard = QCircuit()
    hard.append_1q(0, mat.u3_mtrx(0.3, 0.1, 0.2))
    d = r.plan(hard)
    assert d.stack == "turboquant"
    assert d.reason == "misroute:planned"
    assert r.current_stack() == "stabilizer"
    amp = complex(r.GetAmplitude(0))
    assert abs(abs(amp) - 1 / np.sqrt(2)) < 1e-9


def test_misroute_past_every_rung_is_refused(telemetry):
    # w40 exceeds the dense cap AND the compressed tier's width cap:
    # refused at plan time with the typed error and the stabilizer
    # state survives untouched
    n = 40
    r = create_quantum_interface("route", n, rng=QrackRandom(1),
                                 rand_global_phase=False)
    ghz_qcircuit(n).Run(r)
    assert r.current_stack() == "stabilizer"
    hard = QCircuit()
    hard.append_1q(0, mat.u3_mtrx(0.3, 0.1, 0.2))
    with pytest.raises(MisrouteError):
        r.plan(hard)
    assert r.current_stack() == "stabilizer"
    amp = complex(r.GetAmplitude(0))
    assert abs(abs(amp) - 1 / np.sqrt(2)) < 1e-9


def test_stabilizer_forced_off_tableau_relabels(telemetry):
    # the ESCALATION path the hybrid handles itself: eager non-Clifford
    # gates materialize its internal dense engine; the read-boundary
    # probe observes and re-labels (no second state carry)
    n = 5
    r = create_quantum_interface("route", n, rng=QrackRandom(2),
                                 rand_global_phase=False)
    o = QEngineCPU(n, rng=QrackRandom(2), rand_global_phase=False)
    for e in (r, o):
        e.H(0)
        e.CNOT(0, 1)
    assert r.current_stack() == "stabilizer"
    for e in (r, o):
        e.RX(0.3, 0)                     # general shard...
        e.CNOT(0, 2)                     # ...on an entangling control:
    f = _fidelity(o.GetQuantumState(), r.GetQuantumState())
    assert f > 1 - 1e-5, f
    assert r.current_stack() == "dense"
    snap = telemetry.snapshot()
    assert snap["counters"]["route.misroutes"] == 1
    assert snap["counters"]["route.misroute.escalated"] == 1


# ---------------------------------------------------------------------------
# checkpoint round-trip through the wrapper
# ---------------------------------------------------------------------------


def test_routed_checkpoint_roundtrip(tmp_path):
    from qrack_tpu.checkpoint import load_state, save_state

    n = 8
    r = create_quantum_interface("route", n, rng=QrackRandom(5),
                                 rand_global_phase=False)
    ghz_qcircuit(n).Run(r)
    before = np.asarray(r.GetQuantumState())
    path = str(tmp_path / "routed.qckpt")
    save_state(r, path)
    back = load_state(path)
    assert back.current_stack() == "stabilizer"
    f = _fidelity(before, back.GetQuantumState())
    assert f > 1 - 1e-9, f


# ---------------------------------------------------------------------------
# telemetry report: routing section + per-stack hit rates
# ---------------------------------------------------------------------------


def test_telemetry_report_routing_section(tmp_path, capsys):
    import importlib.util
    import pathlib

    tele.enable()
    tele.reset()
    tele.inc("route.decisions", 4)
    tele.inc("route.decision.stabilizer", 3)
    tele.inc("route.decision.dense", 1)
    tele.inc("route.jobs.stabilizer", 6)
    tele.inc("route.jobs.dense", 2)
    tele.inc("route.misroutes", 1)
    tele.gauge("route.residency.stabilizer", 3)
    out = tmp_path / "t.jsonl"
    tele.write_jsonl(str(out))
    tele.reset()

    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "telemetry_report.py")
    spec = importlib.util.spec_from_file_location("telemetry_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rep = mod.report(mod.load(str(out), aggregate=False), top=5)
    assert rep["route"]["route.misroutes"] == 1
    assert rep["route"]["hit_rate.stabilizer"] == 0.75
    assert rep["route"]["hit_rate.dense"] == 0.25
    assert mod.main([str(out)]) == 0
    assert "== routing ==" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# slow soak: routed vs dense-forced twin over the fuzz vocabulary
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("trial", range(40))
def test_routed_vs_dense_fuzz_soak(trial, monkeypatch):
    monkeypatch.delenv("QRACK_ROUTE", raising=False)
    rng = np.random.Generator(np.random.PCG64(90_000 + trial))
    r = create_quantum_interface("route", FUZZ_N, rng=QrackRandom(trial),
                                 rand_global_phase=False)
    d = create_quantum_interface("tpu", FUZZ_N, rng=QrackRandom(trial),
                                 rand_global_phase=False)
    for step in range(30):
        name, args = _ops(rng)
        while name == "SetBit":
            name, args = _ops(rng)
        getattr(r, name)(*args)
        getattr(d, name)(*args)
    f = _fidelity(d.GetQuantumState(), r.GetQuantumState())
    assert f > 1 - 1e-5, (trial, f, r.current_stack())
