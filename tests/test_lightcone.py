"""Light-cone circuit engine (qrack_tpu.lightcone, docs/LIGHTCONE.md):
cone extraction/relabeling units, cone-width feature pins on the
algorithm-model IR builders, parity vs the dense CPU oracle across the
observable surface at fusion windows 1 AND 16, mid-circuit-measure
semantics (buffer projector while narrow, projector closure across
entangled reads, materialization past the cap), checkpoint round-trips
(direct and through serve recover), the w50 acceptance scenario
(auto-routed with no pin, analytically exact, forced dense refused),
the lightcone.slice fault site, and the `== lightcone ==` report
section.
"""

import math

import numpy as np
import pytest

from qrack_tpu import QEngineCPU, create_quantum_interface
from qrack_tpu import matrices as mat
from qrack_tpu import telemetry as tele
from qrack_tpu.layers.qcircuit import QCircuit
from qrack_tpu.lightcone.engine import compact_over, sliced_shape_key
from qrack_tpu.models.algorithms import (brickwork_qcircuit,
                                         brickwork_theta, ghz_qcircuit,
                                         qaoa_qcircuit,
                                         quantum_volume_qcircuit,
                                         trotter_qcircuit)
from qrack_tpu.models.qft import qft_qcircuit
from qrack_tpu.resilience import faults
from qrack_tpu.resilience.errors import InjectedFault
from qrack_tpu.route import MisrouteError, decide, extract_features
from qrack_tpu.utils.rng import QrackRandom


@pytest.fixture
def telemetry():
    tele.enable()
    tele.reset()
    yield tele
    tele.reset()


def _fidelity(a, b) -> float:
    a = np.asarray(a).ravel()
    b = np.asarray(b).ravel()
    return float(abs(np.vdot(a, b)) ** 2)


# ---------------------------------------------------------------------------
# cone extraction / relabeling units
# ---------------------------------------------------------------------------


def test_compact_over_brickwork_cone_widths():
    c = brickwork_qcircuit(50)
    compact, order = compact_over(c, (25,))
    # depth-4 brickwork: a bulk qubit's past cone is 6 wide
    assert order == list(range(22, 28))
    assert compact.qubit_count == 6
    # every relabeled gate lives on the compact register
    for g in compact.gates:
        assert all(0 <= q < compact.qubit_count for q in g.qubits())
    # edge qubit: the cone is clipped by the register boundary
    _, order0 = compact_over(c, (0,))
    assert order0 == [0, 1, 2, 3]


def test_compact_over_elides_trailing_gates_and_digest_disambiguates():
    c = QCircuit(4)
    c.append_1q(0, mat.H2)
    c.append_ctrl((0,), 1, mat.X2, 1)
    c.append_1q(1, mat.Y2)
    ca, oa = compact_over(c, (0,))
    cb, ob = compact_over(c, (0, 1))
    # the trailing Y(1) cannot influence Prob(0): elided from its cone
    assert len(ca.gates) == 2
    assert len(cb.gates) == 3
    # ...but both reads share the cone qubit SET — only the structure
    # digest tells the two sliced circuits apart (the cone-cache key)
    assert oa == ob == [0, 1]
    assert ca.structure_digest() != cb.structure_digest()


def test_compact_over_preserves_payloads_and_control_order():
    u = mat.u3_mtrx(0.7, 0.4, 0.5)
    c = QCircuit(9)
    c.append_1q(2, mat.H2)
    c.append_1q(5, mat.H2)
    c.append_ctrl((5, 2), 7, u, 2)
    compact, order = compact_over(c, (7,))
    assert order == [2, 5, 7]
    qmap = {q: i for i, q in enumerate(order)}
    g = compact.gates[-1]
    # control ORDER (not just the set) and the perm key survive the
    # relabeling — perm keys index control positions, not qubit numbers
    assert g.controls == (qmap[5], qmap[2])
    assert g.target == qmap[7]
    assert np.allclose(g.payloads[2], u)


def test_sliced_shape_key_is_offset_invariant():
    a = QCircuit(50)
    a.append_1q(3, mat.H2)
    a.append_ctrl((3,), 4, mat.X2, 1)
    b = QCircuit(50)
    b.append_1q(20, mat.H2)
    b.append_ctrl((20,), 21, mat.X2, 1)
    d = QCircuit(50)
    d.append_1q(20, mat.H2)
    # same local structure at different offsets: one admission bucket
    assert sliced_shape_key(a) == sliced_shape_key(b)
    assert sliced_shape_key(a) != sliced_shape_key(d)
    assert sliced_shape_key(brickwork_qcircuit(50))[0] == 50


# ---------------------------------------------------------------------------
# cone-width features on the algorithm-model IR builders
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("builder,width,max_cone,by_depth", [
    (lambda: brickwork_qcircuit(50), 50, 6, (1, 2, 4, 6)),
    (lambda: ghz_qcircuit(12), 12, 12, tuple(range(1, 13))),
    # a bond (CNOT, RZ, CNOT) is one gate of two controlled phases since
    # PR 47 (QCircuitGate.can_merge): one level a bond, three before
    (lambda: qaoa_qcircuit(8, p=1), 8, 8, (1, 2, 3, 4, 5, 6, 7, 8, 8, 8)),
    (lambda: quantum_volume_qcircuit(6, rng=QrackRandom(17)), 6, 6,
     (1, 2, 2, 4, 4, 6, 6, 6, 6, 6, 6, 6, 6)),
    (lambda: trotter_qcircuit(10, steps=1), 10, 10,
     (2, 3, 4, 5, 6, 7, 8, 9, 10, 10)),
], ids=["brickwork50", "ghz12", "qaoa8", "qv6", "trotter10"])
def test_cone_width_features(builder, width, max_cone, by_depth):
    f = extract_features(builder(), width)
    assert f.max_cone_width == max_cone
    assert f.cone_width_by_depth == by_depth
    d = f.as_dict()
    assert d["max_cone_width"] == max_cone
    assert tuple(d["cone_width_by_depth"]) == by_depth


# ---------------------------------------------------------------------------
# parity vs the dense CPU oracle across the observable surface
# ---------------------------------------------------------------------------


def _random_shallow_qcircuit(n: int, n_gates: int, seed: int) -> QCircuit:
    rng = np.random.Generator(np.random.PCG64(seed))
    c = QCircuit(n)
    for _ in range(n_gates):
        kind = int(rng.integers(0, 4))
        if kind == 0:
            q = int(rng.integers(0, n))
            th, ph, lm = (float(x) for x in rng.uniform(0.0, 2.0, 3))
            c.append_1q(q, mat.u3_mtrx(th, ph, lm))
        else:
            qs = rng.choice(n, size=3, replace=False)
            a, b, t = (int(q) for q in qs)
            if kind == 1:
                c.append_ctrl((a,), b, mat.X2, 1)
            elif kind == 2:
                c.append_ctrl((a,), b, mat.Z2, 1)
            else:
                c.append_ctrl((a, b), t, mat.X2, 3)
    return c


@pytest.mark.parametrize("window", ["1", "32"])
@pytest.mark.parametrize("trial", [0, 1])
def test_observable_surface_parity_vs_dense_oracle(window, trial,
                                                   monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", window)
    monkeypatch.delenv("QRACK_ROUTE", raising=False)
    n = 12
    circ = _random_shallow_qcircuit(n, 30, seed=7100 + trial)
    lc = create_quantum_interface("lightcone", n, rng=QrackRandom(trial),
                                  rand_global_phase=False)
    o = QEngineCPU(n, rng=QrackRandom(trial), rand_global_phase=False)
    circ.Run(lc)
    circ.Run(o)

    for q in range(n):
        assert abs(lc.Prob(q) - o.Prob(q)) < 1e-6
    for mask in (0b1, 0b101, 0b110011, (1 << n) - 1):
        assert abs(lc.ProbParity(mask) - o.ProbParity(mask)) < 1e-6
        assert abs(lc.ProbMask(mask, mask & 0b10101)
                   - o.ProbMask(mask, mask & 0b10101)) < 1e-6
        np.testing.assert_allclose(lc.ProbMaskAll(mask),
                                   o.ProbMaskAll(mask), atol=1e-6)
    bits = [0, 3, 7, 11]
    np.testing.assert_allclose(lc.ProbBitsAll(bits), o.ProbBitsAll(bits),
                               atol=1e-6)
    assert abs(lc.ExpectationBitsAll(bits) - o.ExpectationBitsAll(bits)) \
        < 1e-5
    for perm in (0, 1, 42, (1 << n) - 1):
        # random global phase: compare magnitudes, never raw amplitudes
        assert abs(abs(lc.GetAmplitude(perm))
                   - abs(o.GetAmplitude(perm))) < 1e-6
    np.testing.assert_allclose(np.asarray(lc.GetProbs()),
                               np.asarray(o.GetProbs()), atol=1e-6)
    assert _fidelity(lc.GetQuantumState(), o.GetQuantumState()) > 1 - 1e-6

    # shot keys index q_powers positions; every sampled key must sit in
    # the oracle's support (rng streams legitimately differ per stack)
    powers = [1 << b for b in bits]
    shots = lc.MultiShotMeasureMask(powers, 64)
    assert sum(shots.values()) == 64
    marg = np.asarray(o.ProbBitsAll(bits))
    for key in shots:
        assert marg[key] > 1e-9


# ---------------------------------------------------------------------------
# mid-circuit measurement: buffer projector while the cone is narrow
# ---------------------------------------------------------------------------


def test_m_records_projector_and_closure_reaches_entangled_reads(
        telemetry):
    lc = create_quantum_interface("lightcone", 12, seed=7)
    lc.H(0)
    lc.MCMtrxPerm((0,), mat.X2, 1, 1)
    lc.MCMtrxPerm((1,), mat.X2, 2, 1)
    m = float(lc.M(0))
    # collapse recorded into the buffer — no full-width register
    assert lc.sim is None
    assert len(lc.circuit.gates) == 4
    # the projector on q0 is a TRAILING gate from q1/q2's viewpoint,
    # but non-unitary: the slicer must pull it (and its history) into
    # every entangled read, or GHZ marginals come out 0.5
    assert abs(lc.Prob(0) - m) < 1e-6
    assert abs(lc.Prob(1) - m) < 1e-6
    assert abs(lc.Prob(2) - m) < 1e-6
    clone = lc.Clone()
    assert abs(clone.Prob(2) - m) < 1e-6
    snap = telemetry.snapshot()
    assert snap["counters"]["lightcone.m.projector"] == 1
    assert snap["counters"].get("lightcone.materialize.full", 0) == 0


def test_projector_across_product_cut_stays_elided():
    lc = create_quantum_interface("lightcone", 12, seed=3)
    lc.H(0)
    lc.H(5)
    lc.M(5)
    # q5's collapse is across a product cut: Prob(0)'s cone stays 1 wide
    _, order = lc._slice((0,))
    assert order == [0]
    assert abs(lc.Prob(0) - 0.5) < 1e-6


def test_force_m_zero_probability_raises():
    lc = create_quantum_interface("lightcone", 3, seed=1)
    lc.X(0)
    with pytest.raises(RuntimeError, match="zero probability"):
        lc.ForceM(0, False, do_force=True)


def test_m_past_cap_materializes(telemetry, monkeypatch):
    monkeypatch.setenv("QRACK_LIGHTCONE_M_MAX_QB", "2")
    lc = create_quantum_interface("lightcone", 6, seed=3)
    lc.H(0)
    for q in range(5):
        lc.MCMtrxPerm((q,), mat.X2, q + 1, 1)
    m = float(lc.M(5))   # past cone of q5 is all 6 qubits: > cap
    assert lc.sim is not None
    assert not lc.circuit.gates
    for q in range(6):
        assert abs(lc.Prob(q) - m) < 1e-6
    snap = telemetry.snapshot()
    assert snap["counters"]["lightcone.materialize.full"] == 1
    assert snap["counters"].get("lightcone.m.projector", 0) == 0


def test_force_m_matches_oracle_state():
    n = 8
    lc = create_quantum_interface("lightcone", n, seed=2,
                                  rand_global_phase=False)
    o = QEngineCPU(n, seed=2, rand_global_phase=False)
    for e in (lc, o):
        e.H(0)
        e.MCMtrxPerm((0,), mat.X2, 1, 1)
        e.H(2)
        e.MCMtrxPerm((2,), mat.X2, 3, 1)
    lc.ForceM(1, True)
    o.ForceM(1, True)
    assert _fidelity(lc.GetQuantumState(), o.GetQuantumState()) > 1 - 1e-6


# ---------------------------------------------------------------------------
# checkpoint round-trips: direct, and through serve recover
# ---------------------------------------------------------------------------


def test_lightcone_checkpoint_roundtrip_direct(tmp_path):
    from qrack_tpu.checkpoint import load_state, save_state

    n = 10
    lc = create_quantum_interface("lightcone", n, rng=QrackRandom(5),
                                  rand_global_phase=False)
    brickwork_qcircuit(n).Run(lc)
    _ = lc.Prob(4)          # warm one cone so the snapshot carries it
    lc.M(0)                 # and a recorded projector
    before = np.asarray(lc.GetQuantumState())
    path = str(tmp_path / "lightcone.qckpt")
    save_state(lc, path)
    back = load_state(path)
    assert back.sim is None
    assert len(back.circuit.gates) == len(lc.circuit.gates)
    f = _fidelity(before, back.GetQuantumState())
    assert f > 1 - 1e-6, f


def test_lightcone_session_checkpoint_roundtrip_serve_recover(
        monkeypatch, tmp_path):
    from qrack_tpu.serve import QrackService

    monkeypatch.setenv("QRACK_ROUTE", "lightcone")
    n = 10
    ck = str(tmp_path / "ck")
    a = QrackService(engine_layers="route", checkpoint_dir=ck,
                     batch_window_ms=5.0, tick_s=0.02)
    try:
        sid = a.create_session(n, seed=5, rand_global_phase=False)
        a.apply(sid, brickwork_qcircuit(n), timeout=120)
        out = a.drain()
        assert out == {"drained": [sid], "busy": []}
        with QrackService(engine_layers="route", checkpoint_dir=ck,
                          recover=True, batch_window_ms=5.0,
                          tick_s=0.02) as b:
            assert sid in b.sessions.ids()
            state = b.get_state(sid, timeout=120)
            sess = b.sessions.get(sid)
            assert sess.engine.current_stack() == "lightcone"
    finally:
        a.close()
    oracle = QEngineCPU(n, rng=QrackRandom(5), rand_global_phase=False)
    brickwork_qcircuit(n).Run(oracle)
    assert _fidelity(oracle.GetQuantumState(), state) > 1 - 1e-5


# ---------------------------------------------------------------------------
# w50 acceptance: auto-routed, analytically exact, forced dense refused
# ---------------------------------------------------------------------------


def test_w50_brickwork_auto_routes_lightcone_and_is_exact(telemetry,
                                                          monkeypatch):
    monkeypatch.delenv("QRACK_ROUTE", raising=False)
    d = decide(brickwork_qcircuit(50), 50)
    assert d.stack == "lightcone"
    assert d.reason == "cost"
    r = create_quantum_interface("route", 50, rng=QrackRandom(9))
    brickwork_qcircuit(50).Run(r)
    assert r.current_stack() == "lightcone"
    # CZ bricks are diagonal: Prob(q) = sin^2(theta_q / 2) exactly
    for q in (0, 1, 25, 49):
        want = math.sin(brickwork_theta(q) / 2.0) ** 2
        assert abs(r.Prob(q) - want) < 1e-6
    snap = telemetry.snapshot()
    assert snap["counters"]["lightcone.reads"] >= 4
    assert snap["counters"]["lightcone.cache.miss"] >= 1
    assert snap["counters"]["lightcone.gates.elided"] >= 1


def test_w50_forced_dense_refused(monkeypatch):
    monkeypatch.setenv("QRACK_ROUTE", "dense")
    r = create_quantum_interface("route", 50, rng=QrackRandom(9))
    with pytest.raises(MisrouteError, match="exceeds the dense ladder"):
        brickwork_qcircuit(50).Run(r)


def test_service_w50_shallow_next_to_dense(telemetry, monkeypatch):
    from qrack_tpu.serve import QrackService

    monkeypatch.delenv("QRACK_ROUTE", raising=False)
    svc = QrackService(engine_layers="route", batch_window_ms=1.0,
                       queue_budget_ms=120_000.0)
    try:
        wide = svc.create_session(50, seed=1)
        dense = svc.create_session(16, seed=2)
        h1 = svc.submit(wide, brickwork_qcircuit(50))
        h2 = svc.submit(dense, qft_qcircuit(16))
        h1.result(timeout=300)
        h2.result(timeout=300)
        stacks = {
            sid: svc.call(sid, lambda eng: eng.current_stack(),
                          mutates=False).result(timeout=60)
            for sid in (wide, dense)}
        assert stacks[wide] == "lightcone"
        assert stacks[dense] == "dense"
        for q in (0, 25, 49):
            p = svc.call(wide, lambda eng, q=q: eng.Prob(q),
                         mutates=False).result(timeout=120)
            assert abs(p - math.sin(brickwork_theta(q) / 2.0) ** 2) < 1e-6
        # a pinned-dense deployment refuses the same width AT submit,
        # while the dense tenant keeps serving under the pin
        monkeypatch.setenv("QRACK_ROUTE", "dense")
        pinned = svc.create_session(50, seed=3)
        with pytest.raises(MisrouteError, match="exceeds the dense ladder"):
            svc.submit(pinned, brickwork_qcircuit(50))
        assert abs(svc.prob(dense, 0, timeout=120) - 0.5) < 1e-3
    finally:
        svc.close()
    snap = telemetry.snapshot()
    assert snap["counters"]["route.jobs.lightcone"] >= 1
    assert snap["counters"]["route.jobs.dense"] >= 1


# ---------------------------------------------------------------------------
# lightcone.slice fault site: injected faults surface typed, never silent
# ---------------------------------------------------------------------------


def test_lightcone_slice_fault_surfaces_typed():
    lc = create_quantum_interface("lightcone", 6, seed=1)
    lc.H(0)
    try:
        faults.inject("lightcone.slice", "raise", after_n=0, times=1)
        with pytest.raises(InjectedFault):
            lc.Prob(0)
        # directive kinds the site must act out itself raise in-engine
        faults.inject("lightcone.slice", "hang", after_n=0, times=1)
        with pytest.raises(RuntimeError,
                           match="lightcone.slice injected fault"):
            lc.Prob(0)
    finally:
        faults.clear()
    assert abs(lc.Prob(0) - 0.5) < 1e-6   # state intact after the fault


# ---------------------------------------------------------------------------
# telemetry report: the == lightcone == section
# ---------------------------------------------------------------------------


def test_telemetry_report_lightcone_section(tmp_path, capsys):
    import importlib.util
    import pathlib

    tele.enable()
    tele.reset()
    tele.inc("lightcone.reads", 8)
    tele.inc("lightcone.reads.dense", 6)
    tele.inc("lightcone.reads.stabilizer", 2)
    tele.inc("lightcone.cache.hit", 5)
    tele.inc("lightcone.cache.miss", 3)
    tele.inc("lightcone.gates.cone", 30)
    tele.inc("lightcone.gates.elided", 70)
    tele.inc("lightcone.m.projector", 1)
    for w in (4.0, 6.0, 6.0, 6.0):
        tele.observe("lightcone.cone_width", w)
    out = tmp_path / "t.jsonl"
    tele.write_jsonl(str(out))
    tele.reset()

    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "telemetry_report.py")
    spec = importlib.util.spec_from_file_location("telemetry_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rep = mod.report(mod.load(str(out), aggregate=False), top=5)
    lc = rep["lightcone"]
    assert lc["elided_share"] == 0.7
    assert lc["cache_hit_rate"] == 0.625
    assert lc["rung_share.dense"] == 0.75
    assert lc["rung_share.stabilizer"] == 0.25
    assert lc["cone_width"]["count"] == 4
    assert lc["cone_width"]["max"] == 6.0
    assert mod.main([str(out)]) == 0
    assert "== lightcone ==" in capsys.readouterr().out
