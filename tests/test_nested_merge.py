"""A later gate composes onto an earlier gate of the same target whose
control set holds its own (``QCircuitGate.can_merge`` / ``merge``, PR 47;
reference: ``include/qcircuit.hpp`` ``CanCombine`` / ``AddControl``).

What it is for: a Trotter bond, ``CNOT(j, j+1)``, ``RZ(j+1)``,
``CNOT(j, j+1)``, is ``exp(-i theta/2 Z_j Z_j+1)``, a diagonal
operator.  The ``RZ`` composes onto the first CNOT (``{1: X}`` +
``{0: RZ}`` -> ``{0: RZ, 1: RZ X}``), the second CNOT by the rule of
equal controls, and the gate lowers to two controlled ``diag``: no lead,
no exchange, no prologue.  The guards, each held below: the later gate
is a phase gate (an ``RX`` behind a bond would become two controlled
general gates), the merged gate holds no more payloads than the two
together (a phase behind a Toffoli would be four ops for two), and an
earlier gate never takes controls from a later one (a QFT's ``H`` ahead
of its ``cphase``).  The fuser and ``QCircuit.AppendGate`` share the
rule.
"""

import os
import sys

import numpy as np
import pytest

from qrack_tpu import create_quantum_interface
from qrack_tpu import telemetry as tele
from qrack_tpu.layers.qcircuit import QCircuit, QCircuitGate
from qrack_tpu.models.algorithms import trotter_qcircuit
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk
from qrack_tpu.utils.rng import QrackRandom

from helpers import (benchmark_plans, full_unitary, issue, plan_only_pager,
                     trotter_step_gates)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
X2 = np.array([[0, 1], [1, 0]], dtype=np.complex128)
H2 = np.array([[1, 1], [1, -1]], dtype=np.complex128) / np.sqrt(2)


def _rz(theta):
    return np.diag([np.exp(-0.5j * theta), np.exp(0.5j * theta)])


def _rx(theta):
    c, s = np.cos(theta / 2), np.sin(theta / 2)
    return np.array([[c, -1j * s], [-1j * s, c]], dtype=np.complex128)


def _gate(controls, m, target, perm=None):
    if not controls:
        return QCircuitGate.single(target, m)
    perm = (1 << len(controls)) - 1 if perm is None else perm
    return QCircuitGate.controlled(controls, target, m, perm)


def _unitary(n, gate):
    """``gate`` over ``n`` qubits by explicit loops: every payload on
    the basis states its perm selects, the identity on the rest."""
    dim = 1 << n
    u = np.zeros((dim, dim), dtype=np.complex128)
    for i in range(dim):
        perm = sum(((i >> c) & 1) << j for j, c in enumerate(gate.controls))
        m = gate.payloads.get(perm, np.eye(2))
        bit = (i >> gate.target) & 1
        for out in (0, 1):
            u[(i & ~(1 << gate.target)) | (out << gate.target), i] \
                += m[out, bit]
    return u


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("control,target", [(0, 1), (1, 0)],
                         ids=["control-below", "control-above"])
@pytest.mark.parametrize("theta", [0.2, -1.3, np.pi])
def test_a_bond_is_one_gate_of_two_diag(control, target, theta):
    """CNOT, RZ, CNOT compose into one gate equal to their product as a
    4 x 4 (complex128, on the host) that lowers to two controlled
    ``diag``, one a value of the control."""
    three = [_gate((control,), X2, target), _gate((), _rz(theta), target),
             _gate((control,), X2, target)]
    bond = three[0].clone()
    for later in three[1:]:
        assert bond.can_merge(later)
        bond.merge(later)
    want = np.eye(4, dtype=np.complex128)
    cnot = np.eye(4, dtype=np.complex128)
    cnot[np.ix_([1 << control, 3], [1 << control, 3])] = X2
    assert np.array_equal(_unitary(2, three[0]), cnot)
    for g in three:
        want = (_unitary(2, g) if g.controls
                else full_unitary(2, g.payloads[0], (g.target,))) @ want
    assert np.max(np.abs(_unitary(2, bond) - want)) < 1e-15
    # exp(-i theta/2 Z Z): the phase by the parity of the two bits
    zz = np.diag([np.exp(-0.5j * theta * (1 - 2 * (a ^ b)))
                  for b in (0, 1) for a in (0, 1)])
    assert np.max(np.abs(want - zz)) < 1e-15
    assert sorted(bond.payloads) == [0, 1] and bond.is_phase()
    ops = fu.lower_gates([bond])
    assert [(op.kind, op.target, op.cmask) for op in ops] \
        == [("diag", target, 1 << control)] * 2
    assert sorted(op.cval for op in ops) == [0, 1 << control]
    # and as the 4 x 4 the two-qubit record embeds
    pair = fu.TwoQubitGate(0, 1, np.eye(4))
    assert np.max(np.abs(pair.embed(bond) - want)) < 1e-15


def test_a_controlled_phase_nests_under_two_controls_when_it_adds_no_payload():
    """Controls (2, 0) then a phase controlled by 0 alone: the later gate
    is expanded over the earlier one's perms at the positions of its own
    controls, whatever their order."""
    both = QCircuitGate(1, {0: _rz(0.3), 1: _rz(0.4), 2: _rz(0.5),
                            3: _rz(0.6)}, (2, 0))
    later = _gate((0,), _rz(0.7), 1)
    want = _unitary(3, later) @ _unitary(3, both)
    assert both.can_merge(later)
    both.merge(later)
    assert sorted(both.payloads) == [0, 1, 2, 3]
    assert np.max(np.abs(_unitary(3, both) - want)) < 1e-15


GUARDS = {
    # an RX behind a bond: two controlled gen, two leads where one was
    "rx-behind-a-bond": (QCircuitGate(1, {0: _rz(0.2), 1: _rz(-0.2)}, (0,)),
                         _gate((), _rx(0.2), 1)),
    # the closed direction: a QFT's H, then the cphase onto its qubit
    "h-then-cphase": (_gate((), H2, 1),
                      _gate((0,), np.diag([1, 1j]), 1)),
    "rz-then-cnot": (_gate((), _rz(0.2), 1), _gate((0,), X2, 1)),
    # four ops for two
    "phase-behind-a-toffoli": (_gate((0, 2), X2, 1), _gate((), _rz(0.2), 1)),
    "control-outside": (_gate((0,), X2, 1), _gate((2,), _rz(0.2), 1)),
    "controls-overlap": (_gate((0, 3), X2, 1), _gate((0, 2), _rz(0.2), 1, 1)),
    "another-target": (_gate((0,), X2, 1), _gate((), _rz(0.2), 0)),
    # a recorded measurement's projector is diagonal and no phase gate
    "projector-behind-a-cnot": (_gate((0,), X2, 1),
                                _gate((), np.diag([0, np.sqrt(2)]), 1)),
}


@pytest.mark.parametrize("case", sorted(GUARDS))
def test_a_guard_keeps_the_two_gates_apart(case):
    earlier, later = GUARDS[case]
    assert not earlier.can_merge(later)
    circuit = QCircuit(4)
    circuit.AppendGate(earlier)
    circuit.AppendGate(later)
    assert len(circuit.gates) == 2


def test_equal_controls_merge_as_before():
    """Whatever the payloads: an RX onto an RX, a CNOT onto a CNOT."""
    rx = _gate((), _rx(0.2), 1)
    assert rx.can_merge(_gate((), _rx(0.3), 1))
    cnot = _gate((0,), X2, 1)
    assert cnot.can_merge(_gate((0,), X2, 1))
    cnot.merge(_gate((0,), X2, 1))
    assert cnot.is_identity()


def test_append_gate_takes_the_same_rule():
    """``QCircuit.AppendGate`` is the same peephole: a Trotter step's
    circuit holds a gate a bond and a gate an RX, and runs to the ket of
    its gate calls."""
    n = 6
    circuit = trotter_qcircuit(n, steps=1)
    assert circuit.GetGateCount() == (n - 1) + n
    bonds = [g for g in circuit.gates if g.controls]
    assert len(bonds) == n - 1
    assert all(sorted(g.payloads) == [0, 1] and g.is_phase() for g in bonds)
    assert [k for k, _ in circuit._lookahead_entries()] \
        == ["diag"] * (2 * (n - 1)) + ["gen"] * n
    want = _reference().run(n, trotter_step_gates(n), 0b101101)
    q = create_quantum_interface("cpu", n, rng=QrackRandom(1),
                                 rand_global_phase=False)
    q.SetPermutation(0b101101)
    circuit.Run(q)
    assert np.max(np.abs(np.asarray(q.GetQuantumState()) - want)) < 1e-12
    planes = np.zeros((2, 1 << n), np.float32)
    planes[0, 0b101101] = 1.0
    got = np.asarray(circuit.compile_fn(n)(planes))
    assert np.max(np.abs(got[0] + 1j * got[1] - want)) < 2e-6


# ---------------------------------------------------------------------------
# a Trotter step through the engines
# ---------------------------------------------------------------------------

def _reference():
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import reference
    finally:
        sys.path.pop(0)
    return reference


ENGINES = {"tpu": ("tpu", {}),
           "pager": ("pager", {"n_pages": 4}),
           "pager-noremap": ("pager", {"n_pages": 4, "remap": "off"})}


@pytest.mark.parametrize("width,tile", [(10, 6), (11, 6), (12, 6), (12, 10)],
                         ids=["w10", "w11", "w12", "w12-dense-tile"])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_trotter_steps_match_per_gate_dispatch_and_the_reference(
        engine, width, tile, monkeypatch):
    """Two steps (the second from a ket that is no basis state) through
    the window kernel (the Pallas interpreter here, tiles of 2^6 or the
    dense tile of 2^10: bonds in the tile, across its edge, above it and
    on the pager's page bits) against the same calls at
    ``QRACK_TPU_FUSE_WINDOW=1`` and against ``benchmarks/reference.py``,
    amplitudes to float32 rounding."""
    stack, kwargs = ENGINES[engine]
    gates = trotter_step_gates(width)
    start = 0b101100111011 & ((1 << width) - 1)
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setattr(pk, "DEFAULT_BLOCK_POW", tile)
    fu.PROGRAMS.clear()

    def run():
        q = create_quantum_interface(stack, width, rng=QrackRandom(3),
                                     rand_global_phase=False, **kwargs)
        q.SetPermutation(start)
        for _ in range(2):
            issue(q, gates)
            q.GetAmplitude(5)
        return np.asarray(q.GetQuantumState())

    try:
        monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "1")
        per_gate = run()
        monkeypatch.delenv("QRACK_TPU_FUSE_WINDOW")
        tele.reset()
        tele.enable()
        try:
            fused = run()
            c = tele.snapshot(include_events=False)["counters"]
        finally:
            tele.disable()
            tele.reset()
    finally:
        fu.PROGRAMS.clear()
    # a bond a step composed onto its first CNOT, every window a kernel
    # window (but the pager's windows of one op)
    assert c[f"fuse.{stack}.merged.nested"] == 2 * (width - 1)
    assert c["fuse.kernel.windows"] >= 2 and "fuse.xla.windows" not in c
    assert c["fuse.kernel.diag_run.ops"] >= 2 * (width - 3)
    reference = _reference()
    want = reference.evolve(reference.basis_state(width, start), width,
                            gates + gates)
    assert np.max(np.abs(fused - per_gate)) < 2e-6
    assert np.max(np.abs(fused - want)) < 2e-6


# ---------------------------------------------------------------------------
# the counter, by the plan-only engines at the cells' widths
# ---------------------------------------------------------------------------

def _nested(run):
    tele.reset()
    tele.enable()
    try:
        run()
        return {k: v for k, v in tele.snapshot(
            include_events=False)["counters"].items() if ".merged." in k}
    finally:
        tele.disable()
        tele.reset()


@pytest.mark.parametrize("family,want", [("tfim", 27), ("qft", 0), ("rcs", 0)])
def test_dense_cells_count_their_nested_merges(family, want):
    """``fuse.tpu.merged.nested`` an application at w28: one a bond of
    the Trotter step (its ``RZ``), none in a QFT or a random circuit."""
    with benchmark_plans(28) as windows:
        counted = _nested(lambda: windows(family))
    assert counted == ({"fuse.tpu.merged.nested": want} if want else {})


@pytest.mark.parametrize("kwargs", [{}, {"remap": "off"}],
                         ids=["planner", "fixed-placement"])
def test_paged_cells_count_their_nested_merges(kwargs):
    """29 a step at w30 on four pages, whatever the placement; none in
    the paged QFT at w31."""
    q = plan_only_pager(30, **kwargs)

    def step():
        issue(q, trotter_step_gates(30))
        q.GetAmplitude(0)

    assert _nested(step) == {"fuse.pager.merged.nested": 29}
    assert _nested(step) == {"fuse.pager.merged.nested": 29}
    qft = plan_only_pager(31, **kwargs)

    def transform():
        qft.SetPermutation(12345)
        qft.QFT(0, 31)
        qft.GetAmplitude(3)

    assert _nested(transform) == {}
