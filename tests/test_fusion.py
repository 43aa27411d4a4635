"""Lazy gate-stream fusion (ops/fusion.py): flush invariants at every
read/boundary, fuzz parity vs the CPU oracle with fusion ON, the
window=1 off-switch, and the parametric (constant-free) compiled-window
contract — same-structure windows with different angles must share ONE
compiled program (compile.fuse telemetry), and a w16 QFT must dispatch
>= 4x fewer programs fused than per-gate.
"""

import numpy as np
import pytest

from qrack_tpu import QEngineCPU, create_quantum_interface
from qrack_tpu import resilience as res
from qrack_tpu import telemetry as tele
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.resilience import faults
from qrack_tpu.utils.rng import QrackRandom

from test_fuzz_api import _ops

N = 6


@pytest.fixture(autouse=True)
def _clean_layers():
    faults.clear()
    res.reset_breaker()
    res.configure(max_retries=2, backoff_s=0.0, timeout_s=0.0)
    yield
    faults.clear()
    res.reset_breaker()
    res.configure()
    res.disable()
    tele.disable()
    tele.reset()


def _fidelity(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return abs(np.vdot(a, b)) ** 2 / (np.vdot(a, a).real * np.vdot(b, b).real)


# ---------------------------------------------------------------------------
# flush invariants: every read/boundary sees the queued gates
# ---------------------------------------------------------------------------

def test_read_flushes_pending_window():
    eng = QEngineTPU(4, rng=QrackRandom(1), rand_global_phase=False)
    assert eng._fuser is not None          # fusion is the default mode
    eng.X(0)
    assert eng._fuser.pending              # queued, not dispatched
    assert abs(eng.Prob(0) - 1.0) < 1e-7   # the read flushed first
    assert not eng._fuser.pending


def test_measurement_sees_queued_gates():
    eng = QEngineTPU(3, rng=QrackRandom(2), rand_global_phase=False)
    eng.X(1)
    assert eng.M(1) == 1                   # deterministic post-X outcome


def test_set_permutation_drops_pending_window():
    eng = QEngineTPU(3, rng=QrackRandom(3), rand_global_phase=False)
    eng.H(0)
    eng.H(2)
    assert eng._fuser.pending
    eng.SetPermutation(5)                  # blind overwrite: gates moot
    assert not eng._fuser.pending
    assert abs(eng.Prob(0) - 1.0) < 1e-7
    assert abs(eng.Prob(1)) < 1e-7
    assert abs(eng.Prob(2) - 1.0) < 1e-7


def test_neighbor_merge_saves_sweeps():
    tele.enable()
    eng = QEngineTPU(3, rng=QrackRandom(4), rand_global_phase=False)
    eng.H(0)
    eng.H(0)                   # H.H = I merges away: nothing to dispatch
    assert not eng._fuser.pending
    assert abs(eng.Prob(0)) < 1e-9
    eng.T(1)
    eng.T(1)                   # same-target phases compose into one sweep
    eng.Prob(1)
    c = tele.snapshot(include_events=False)["counters"]
    assert c.get("fuse.tpu.sweeps_saved", 0) >= 1
    assert c.get("fuse.tpu.queued", 0) == 4


def test_checkpoint_capture_mid_window():
    """capture() reads engine state through the flushing property, so a
    snapshot taken mid-window includes every queued gate."""
    from qrack_tpu.checkpoint import registry as ckpt

    eng = QEngineTPU(5, rng=QrackRandom(5), rand_global_phase=False)
    o = QEngineCPU(5, rng=QrackRandom(5), rand_global_phase=False)
    for e in (eng, o):
        e.H(0)
        e.CNOT(0, 2)
        e.T(1)
    assert eng._fuser.pending
    snap = ckpt.capture(eng)
    assert not eng._fuser.pending          # the capture flushed
    fresh = QEngineTPU(5, rng=QrackRandom(99), rand_global_phase=False)
    ckpt.restore_into(fresh, snap)
    assert _fidelity(fresh.GetQuantumState(), o.GetQuantumState()) > 1 - 1e-10


def test_checkpoint_restore_mid_window_drops_pending():
    from qrack_tpu.checkpoint import registry as ckpt

    eng = QEngineTPU(4, rng=QrackRandom(6), rand_global_phase=False)
    eng.X(0)
    snap = ckpt.capture(eng)               # |0001>
    eng.H(1)                               # pending when the restore lands
    assert eng._fuser.pending
    ckpt.restore_into(eng, snap)           # blind overwrite: H must NOT apply
    assert not eng._fuser.pending
    assert abs(eng.Prob(0) - 1.0) < 1e-7
    assert abs(eng.Prob(1)) < 1e-7


@pytest.mark.parametrize("site", ["tpu.fuse.flush", "flush"])
def test_failover_mid_window_matches_oracle(site):
    """A window whose flush dispatch fails persistently completes on the
    CPU fallback: the failover snapshot (taken under faults.suspended())
    re-runs the flush, so no queued gate is lost or double-applied."""
    res.enable()
    q = create_quantum_interface("tpu", N, rng=QrackRandom(3),
                                 rand_global_phase=False)
    o = QEngineCPU(N, rng=QrackRandom(3), rand_global_phase=False)
    for e in (q, o):
        e.H(0)
        e.CNOT(0, 1)
        e.RZ(0.7, 2)
        e.X(3)
    faults.inject(site, "raise", after_n=0, times=None)
    p = q.Prob(1)                          # read flushes; the fault fires here
    assert type(q.engine).__name__ == "QEngineCPU"
    assert abs(p - o.Prob(1)) < 1e-6
    assert _fidelity(q.GetQuantumState(), o.GetQuantumState()) > 1 - 1e-6


# ---------------------------------------------------------------------------
# the off-switch: QRACK_TPU_FUSE_WINDOW=1 reproduces per-gate behavior
# ---------------------------------------------------------------------------

def test_window_one_reproduces_per_gate(monkeypatch):
    from test_engine_matrix import random_circuit

    o = QEngineCPU(N, rng=QrackRandom(7), rand_global_phase=False)
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "1")
    e_off = QEngineTPU(N, rng=QrackRandom(7), rand_global_phase=False)
    assert e_off._fuser is None            # fusion fully disabled
    monkeypatch.delenv("QRACK_TPU_FUSE_WINDOW")
    e_on = QEngineTPU(N, rng=QrackRandom(7), rand_global_phase=False)
    assert e_on._fuser is not None
    for e in (o, e_off, e_on):
        random_circuit(e, QrackRandom(42), 30, N)
    assert _fidelity(e_off.GetQuantumState(), o.GetQuantumState()) > 1 - 1e-6
    assert _fidelity(e_on.GetQuantumState(), o.GetQuantumState()) > 1 - 1e-6


# ---------------------------------------------------------------------------
# fuzz soak: the whole public op vocabulary with fusion ON, vs the oracle
# ---------------------------------------------------------------------------

def _draw_op(rng):
    # SetBit measures: cross-stack rng streams legitimately diverge on
    # measuring ops (working notes), so the fusion soak skips it — the
    # deterministic measurement path is covered above.
    while True:
        name, args = _ops(rng)
        if name != "SetBit":
            return name, args


_FUZZ_STACKS = [
    ("tpu", {}, 1 - 1e-6, 3e-5),
    ("pager", {"n_pages": 4}, 1 - 1e-6, 3e-5),
    ("turboquant", {"bits": 16, "chunk_qb": 3, "block_pow": 2},
     1 - 1e-5, 5e-4),                      # lossy int16 codes
]


@pytest.mark.parametrize("name,kw,floor,ptol",
                         _FUZZ_STACKS, ids=[s[0] for s in _FUZZ_STACKS])
@pytest.mark.parametrize("trial", range(3))
def test_fuzz_vocabulary_fusion_on(name, kw, floor, ptol, trial):
    rng = np.random.Generator(np.random.PCG64(7000 + trial))
    o = QEngineCPU(N, rng=QrackRandom(trial), rand_global_phase=False)
    s = create_quantum_interface(name, N, rng=QrackRandom(trial),
                                 rand_global_phase=False, **kw)
    for step in range(25):
        op, args = _draw_op(rng)
        getattr(o, op)(*args)
        getattr(s, op)(*args)
        if rng.integers(0, 8) == 0:        # mid-stream reads force flushes
            qb = int(rng.integers(0, N))
            assert abs(o.Prob(qb) - s.Prob(qb)) < ptol, (trial, step, op)
    assert _fidelity(s.GetQuantumState(), o.GetQuantumState()) > floor, trial


# ---------------------------------------------------------------------------
# parametric-window contract (CI telemetry assertions)
# ---------------------------------------------------------------------------

def _program_dispatches(counters) -> int:
    """Compiled-program invocations: every per-gate call counts under
    compile.tpu.* (hit or miss), every fused window under
    compile.fuse.window.*."""
    return sum(v for k, v in counters.items()
               if k.startswith("compile.tpu.")
               or k.startswith("compile.fuse.window."))


def test_w16_qft_dispatch_count_drops_4x(monkeypatch):
    from qrack_tpu.models.qft import qft_qcircuit

    circ = qft_qcircuit(16)

    def run(window):
        monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(window))
        tele.reset()
        tele.enable()
        eng = QEngineTPU(16, rng=QrackRandom(9), rand_global_phase=False)
        circ.Run(eng)                      # per-gate stream into the engine
        eng.Prob(0)                        # read boundary flushes the tail
        counters = tele.snapshot(include_events=False)["counters"]
        tele.disable()
        tele.reset()
        return _program_dispatches(counters)

    per_gate = run(1)
    fused = run(32)
    # 136 gates: per-gate pays ~one dispatch each; fused pays
    # ~ceil(136 / 32)
    assert per_gate >= 4 * fused, (per_gate, fused)


def test_same_structure_different_angles_compile_once():
    """Two windows with identical structure but different rotation
    angles: exactly ONE compile.fuse.window miss (the payloads are
    runtime operands, not trace constants)."""
    tele.enable()
    eng = QEngineTPU(9, rng=QrackRandom(10), rand_global_phase=False)
    targets = (0, 2, 4, 6, 8, 1, 3, 5, 7)  # unique structure for this test
    for base in (0.3, 1.1):
        for j, t in enumerate(targets):
            eng.RZ(base + 0.1 * j, t)
        eng.Prob(0)                        # flush one full window
    c = tele.snapshot(include_events=False)["counters"]
    assert c.get("compile.fuse.window.miss", 0) == 1, c
    assert c.get("compile.fuse.window.hit", 0) >= 1, c


def test_fuse_flush_site_registered():
    # the guarded flush site must be part of the fault grammar so soak
    # harnesses can target it (docs/RESILIENCE.md site table)
    assert "tpu.fuse.flush" in faults.SITES
    assert "flush" in faults.CATEGORIES
