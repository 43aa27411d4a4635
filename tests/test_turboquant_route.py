"""Routed TurboQuant tier: the memory-axis cost model that steers
over-width dense jobs onto the compressed rung, the single-pass fused
window's sweep economics, the chunk-mass fidelity guard, and the
quantized escalation ladder (drift giveup -> dense) — end to end
through the factory "route" pseudo-terminal and the serving plane
(docs/ROUTING.md, docs/PERFORMANCE.md).
"""

import numpy as np
import pytest

from qrack_tpu import QEngineCPU, create_quantum_interface
from qrack_tpu import resilience as res
from qrack_tpu import telemetry as tele
from qrack_tpu.engines.turboquant import QEngineTurboQuant
from qrack_tpu.models.qft import qft_qcircuit
from qrack_tpu.resilience import faults
from qrack_tpu.resilience import integrity as integ
from qrack_tpu.route import cost as rc
from qrack_tpu.serve import QrackService
from qrack_tpu.utils.rng import QrackRandom

N = 6
_TQ = {"bits": 16, "chunk_qb": 3, "block_pow": 2}
_TQ_FLOOR = 1 - 1e-5  # 16-bit codes at w6: comfortably above the
#                       ladder's 1e-3 serving contract


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("QRACK_ROUTE", raising=False)
    monkeypatch.delenv("QRACK_ROUTE_HBM_BYTES", raising=False)
    faults.clear()
    yield
    faults.clear()
    integ.reset()
    integ.set_enabled(False)
    res.disable()
    tele.disable()
    tele.reset()


def _fidelity(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    return abs(np.vdot(a, b)) ** 2 / (np.vdot(a, a).real
                                      * np.vdot(b, b).real)


# ---------------------------------------------------------------------------
# memory-axis cost model
# ---------------------------------------------------------------------------

def test_hbm_bytes_dense_is_sixteen_per_amp():
    f = rc._WidthOnly(20)
    k = rc.RouteKnobs.from_env()
    assert rc.hbm_bytes("dense", f, k) == 16.0 * (1 << 20)


def test_hbm_bytes_turboquant_beats_dense_and_pages_divide():
    k = rc.RouteKnobs.from_env()
    f = rc._WidthOnly(24)
    dense = rc.hbm_bytes("dense", f, k)
    tq = rc.hbm_bytes("turboquant", f, k)
    # int8 codes are 8x under the f32 planes at rest; the model's 2x
    # transient factor (decompressed working chunks) nets out >3x
    assert 0 < tq < dense / 3
    import dataclasses

    k4 = dataclasses.replace(k, tq_pages=4)
    assert rc.hbm_bytes("turboquant", f, k4) == pytest.approx(tq / 4)


def test_ladder_stack_walks_dense_then_turboquant_then_none():
    assert rc.ladder_stack(10) == "dense"
    assert rc.ladder_stack(rc._TQ_BASE_CAP) == "turboquant"
    assert rc.ladder_stack(60) is None


def test_small_hbm_budget_blocks_dense_below_width_cap(monkeypatch):
    # an 8-qubit dense ket is 4 KiB; a 2 KiB budget must block it and
    # hand the job to the compressed rung — the width cap alone would
    # have admitted dense
    monkeypatch.setenv("QRACK_ROUTE_HBM_BYTES", "2048")
    assert rc.ladder_stack(8) == "turboquant"
    tele.enable()
    tele.reset()
    q = create_quantum_interface(("route",), 8, rng=QrackRandom(3),
                                 rand_global_phase=False)
    d = q.plan(qft_qcircuit(8))
    assert d.stack != "dense"
    snap = tele.snapshot()
    assert snap["counters"].get("route.hbm.dense_blocked", 0) >= 1
    assert "route.hbm.budget_bytes" in snap["gauges"]


# ---------------------------------------------------------------------------
# routed fuzz vs the CPU oracle, per-gate AND fused windows
# ---------------------------------------------------------------------------

def _fuzz_ops(rng):
    """The test_fuzz_api vocabulary minus SetBit (a measuring op:
    cross-stack rng streams legitimately diverge on collapse)."""
    from test_fuzz_api import _ops

    while True:
        name, args = _ops(rng)
        if name != "SetBit":
            return name, args


@pytest.mark.parametrize("window", [1, 32])
@pytest.mark.parametrize("trial", range(3))
def test_routed_turboquant_fuzz_matches_oracle(monkeypatch, window, trial):
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(window))
    monkeypatch.setenv("QRACK_ROUTE", "turboquant")
    rng = np.random.Generator(np.random.PCG64(7000 + trial))
    o = QEngineCPU(N, rng=QrackRandom(trial), rand_global_phase=False)
    s = create_quantum_interface(("route",), N, rng=QrackRandom(trial),
                                 rand_global_phase=False, **_TQ)
    for step in range(25):
        op, args = _fuzz_ops(rng)
        getattr(o, op)(*args)
        getattr(s, op)(*args)
        if rng.integers(0, 10) == 0:
            qb = int(rng.integers(0, N))
            assert abs(o.Prob(qb) - s.Prob(qb)) < 5e-4, (trial, step, op)
    assert s.current_stack() == "turboquant"
    f = _fidelity(o.GetQuantumState(), s.GetQuantumState())
    assert f > _TQ_FLOOR, (trial, window, f)


# ---------------------------------------------------------------------------
# single-pass fused windows: counted sweep economics
# ---------------------------------------------------------------------------

def _sweep_count(window: int, monkeypatch) -> int:
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(window))
    tele.enable()
    tele.reset()
    # default (single-chunk) geometry: every target is chunk-local, so
    # the whole stream is window-admissible — the configuration the
    # sweep economics are quoted for (docs/PERFORMANCE.md)
    eng = QEngineTurboQuant(N, rng=QrackRandom(2), rand_global_phase=False,
                            bits=16, block_pow=2)
    rng = np.random.Generator(np.random.PCG64(42))
    for _ in range(3):
        for t in range(N):
            eng.H(t)
            eng.RZ(float(rng.uniform(0, 2 * np.pi)), t)
    _ = eng.GetQuantumState()
    n = tele.snapshot()["counters"].get("tq.sweeps", 0)
    tele.disable()
    tele.reset()
    return int(n)


def test_fused_window_cuts_sweeps_at_least_4x(monkeypatch):
    per_gate = _sweep_count(1, monkeypatch)
    fused = _sweep_count(32, monkeypatch)
    assert per_gate >= 4 * fused, (per_gate, fused)


def test_fused_window_sweeps_saved_counter(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "32")
    tele.enable()
    tele.reset()
    eng = QEngineTurboQuant(N, rng=QrackRandom(2), rand_global_phase=False,
                            **_TQ)
    for t in range(N):
        eng.H(t)
    _ = eng.GetQuantumState()
    c = tele.snapshot()["counters"]
    assert c.get("fuse.tq.windows", 0) >= 1
    ops = c.get("fuse.tq.ops", 0)
    assert ops >= 2
    # one decompress+recompress per WINDOW instead of per op
    assert c.get("fuse.tq.sweeps_saved", 0) == 2 * (ops - c["fuse.tq.windows"])


# ---------------------------------------------------------------------------
# serving plane: over-budget dense request served on the compressed rung
# ---------------------------------------------------------------------------

def test_overbudget_dense_job_routes_to_turboquant_and_serves(monkeypatch):
    monkeypatch.setenv("QRACK_ROUTE_HBM_BYTES", "2048")  # blocks dense w8
    tele.enable()
    tele.reset()
    with QrackService(engine_layers="route", batch_window_ms=5.0,
                      tick_s=0.02) as svc:
        sid = svc.create_session(8, seed=5, rand_global_phase=False, **_TQ)
        svc.apply(sid, qft_qcircuit(8), timeout=120)
        state = svc.get_state(sid, timeout=120)
    snap = tele.snapshot()
    assert snap["counters"].get("route.built.turboquant", 0) >= 1
    oracle = QEngineCPU(8, rng=QrackRandom(5), rand_global_phase=False)
    qft_qcircuit(8).Run(oracle)
    assert _fidelity(oracle.GetQuantumState(), state) > 1 - 1e-3


def test_quantized_session_checkpoint_roundtrip_serve_recover(
        monkeypatch, tmp_path):
    monkeypatch.setenv("QRACK_ROUTE", "turboquant")
    ck = str(tmp_path / "ck")
    a = QrackService(engine_layers="route", checkpoint_dir=ck,
                     batch_window_ms=5.0, tick_s=0.02)
    try:
        sid = a.create_session(N, seed=5, rand_global_phase=False, **_TQ)
        a.apply(sid, qft_qcircuit(N), timeout=120)
        out = a.drain()
        assert out == {"drained": [sid], "busy": []}
        with QrackService(engine_layers="route", checkpoint_dir=ck,
                          recover=True, batch_window_ms=5.0,
                          tick_s=0.02) as b:
            assert sid in b.sessions.ids()
            state = b.get_state(sid, timeout=120)
            sess = b.sessions.get(sid)
            assert sess.engine.current_stack() == "turboquant"
    finally:
        a.close()
    oracle = QEngineCPU(N, rng=QrackRandom(5), rand_global_phase=False)
    qft_qcircuit(N).Run(oracle)
    assert _fidelity(oracle.GetQuantumState(), state) > _TQ_FLOOR


# ---------------------------------------------------------------------------
# fidelity guard: exhausted drift replays escalate up the ladder
# ---------------------------------------------------------------------------

def test_drift_giveup_escalates_routed_session_to_dense(monkeypatch):
    monkeypatch.setenv("QRACK_ROUTE", "turboquant")
    monkeypatch.setenv("QRACK_TPU_INTEGRITY_REPLAYS", "0")
    tele.enable()
    tele.reset()
    q = create_quantum_interface(("route",), 4, rng=QrackRandom(7),
                                 rand_global_phase=False, **_TQ)
    # spread mass into EVERY block row (both planes, all amplitudes)
    # first: a strike on an empty block's scale multiplies zero codes
    # and is legitimately invisible to the chunk-mass fingerprint
    for t in range(4):
        q.H(t)
    q.RZ(1.0, 0)
    _ = q.Prob(0)  # clean flush of the prep
    assert q.current_stack() == "turboquant"
    integ.set_enabled(True)
    res.enable()
    q.H(1)
    q.H(2)
    faults.inject("tpu.fuse.flush", "amp-corrupt", times=1, seed=11)
    state = q.GetQuantumState()
    faults.clear()
    # the poisoned window was re-dispatched on the dense rung, not
    # served from corrupted codes
    assert q.current_stack() == "dense"
    assert q._escalated
    oracle = QEngineCPU(4, rng=QrackRandom(7), rand_global_phase=False)
    for t in range(4):
        oracle.H(t)
    oracle.RZ(1.0, 0)
    oracle.H(1)
    oracle.H(2)
    assert _fidelity(oracle.GetQuantumState(), state) > 1 - 1e-3
    c = tele.snapshot()["counters"]
    assert c.get("integrity.replay.giveup", 0) == 1
    assert c.get("route.misroute.escalated", 0) == 1


def test_drift_giveup_fails_over_wrapped_engine_to_dense(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_INTEGRITY_REPLAYS", "0")
    from qrack_tpu.resilience.failover import ResilientEngine

    integ.set_enabled(True)
    e = ResilientEngine(QEngineTurboQuant(4, rng=QrackRandom(7),
                                          rand_global_phase=False,
                                          bits=8))
    e.H(0)
    e.H(1)
    e.H(2)
    faults.inject("tpu.fuse.flush", "amp-corrupt", times=1, seed=11)
    state = e.GetQuantumState()
    faults.clear()
    assert type(e.engine).__name__ == "QEngineTPU"
    oracle = QEngineCPU(4, rng=QrackRandom(7), rand_global_phase=False)
    oracle.H(0)
    oracle.H(1)
    oracle.H(2)
    # int8 requantization rode along in the carried state: the ladder's
    # serving contract (1e-3) is the right floor here, not exactness
    assert _fidelity(oracle.GetQuantumState(), state) > 1 - 1e-3


def test_clean_quantized_stream_passes_guard(monkeypatch):
    # the guard must not false-positive on legitimate requantization
    # drift: a long clean stream under the armed guard serves at full
    # quantized fidelity with zero violations
    integ.set_enabled(True)
    res.enable()
    tele.enable()
    tele.reset()
    e = QEngineTurboQuant(N, rng=QrackRandom(7), rand_global_phase=False,
                          **_TQ)
    o = QEngineCPU(N, rng=QrackRandom(7), rand_global_phase=False)
    rng = np.random.Generator(np.random.PCG64(9))
    for _ in range(40):
        t = int(rng.integers(N))
        th = float(rng.uniform(0, 2 * np.pi))
        for q in (e, o):
            q.H(t)
            q.RZ(th, t)
    f = _fidelity(o.GetQuantumState(), e.GetQuantumState())
    assert f > _TQ_FLOOR
    c = tele.snapshot()["counters"]
    assert c.get("integrity.replay.giveup", 0) == 0
    assert not any(k.startswith("integrity.violation") for k in c)
