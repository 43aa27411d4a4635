"""A float32 window's operands are rounded with the window's norm kept
(``fusion.pack_operands`` -> ``_norm_kept_float32``, PR 47).

A unitary rounded to float32 is none: RX(0.2) and RZ(0.2) round to
``1 + 2.3e-8`` times a unitary, the same way every time, so a Trotter
step of 55 such gates multiplied the squared norm by ``1 + 1.26e-6`` at
w28 and an observable read off the planes was off by that times the
steps taken.  Where a window's ops act on all of the ket alike their
floats are rounded from the matrix times the inverse root of the
window's gain so far, which keeps the product within one rounding of 1.
Nothing is carried from window to window, no kind depends on it, and
every path that packs a window's operands takes it.
"""

import numpy as np
import pytest

from qrack_tpu import create_quantum_interface
from qrack_tpu.layers.qcircuit import QCircuitGate
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk
from qrack_tpu.utils.rng import QrackRandom

from helpers import issue, trotter_step_gates

X2 = np.array([[0, 1], [1, 0]], dtype=np.complex128)
S2 = np.diag([1, 1j]).astype(np.complex128)
Z2 = np.diag([1, -1]).astype(np.complex128)
T2 = np.diag([1, np.exp(0.25j * np.pi)])
H2 = np.array([[1, 1], [1, -1]], dtype=np.complex128) / np.sqrt(2)
# what one RX(0.2) or RZ(0.2) rounded to nearest multiplies the squared
# norm by, less 1: float32(cos 0.1)^2 + float32(sin 0.1)^2 - 1
DELTA = float(np.float32(np.cos(0.1))) ** 2 \
    + float(np.float32(np.sin(0.1))) ** 2 - 1.0


def _rx(theta):
    c, s = np.cos(theta / 2), np.sin(theta / 2)
    return np.array([[c, -1j * s], [-1j * s, c]], dtype=np.complex128)


def _rz(theta):
    return np.diag([np.exp(-0.5j * theta), np.exp(0.5j * theta)])


def _step_ops(n):
    """A Trotter step's window as the fuser lowers it: two controlled
    ``diag`` a bond, a ``gen`` an RX."""
    bonds = [QCircuitGate(j + 1, {0: _rz(0.2), 1: X2 @ _rz(0.2) @ X2}, (j,))
             for j in range(n - 1)]
    return fu.lower_gates(
        bonds + [QCircuitGate.single(q, _rx(0.2)) for q in range(n)])


def _floats(ops, fv):
    """Per op its floats as packed, float64."""
    structure = fu.structure_of(ops)
    slots, _, _ = pk._operand_slots(structure, False)
    return [fv[f:f + pk._NFLOATS[kind], 0].astype(np.float64)
            for (kind, _, _), (f, _) in zip(structure, slots)]


def _nearest(ops):
    return [np.array(fu._payload(op.kind, op.m)).astype(np.float32)
            .astype(np.float64) for op in ops]


def _gains(ops, floats):
    """Running product of the gains of the window's whole-ket groups."""
    out = []
    for group in fu._whole_ket_groups(ops):
        by = sum(float(floats[i] @ floats[i]) for i in group) \
            / sum(len(ops[i].m) for i in group)
        out.append(by * (out[-1] if out else 1.0))
    return np.array(out)


def test_nearest_rounding_drifts_and_the_packed_window_does_not():
    assert 2.2e-8 < DELTA < 2.4e-8
    ops = _step_ops(28)
    assert len(ops) == 82
    groups = fu._whole_ket_groups(ops)
    assert [len(g) for g in groups] == [2] * 27 + [1] * 28
    nearest = _gains(ops, _nearest(ops))
    assert abs(nearest[-1] - 1.0 - 55 * DELTA) < 1e-9  # 1.26e-6 a step
    _, fv = fu.pack_operands(ops, np.float32)
    packed = _floats(ops, fv)
    kept = _gains(ops, packed)
    assert np.max(np.abs(kept - 1.0)) < 1.3e-7
    assert abs(kept[-1] - 1.0) < 55 * DELTA / 8
    # no float is more than two ulps from the matrix asked for, and both
    # payloads of a bond are scaled alike
    for op, got, want in zip(ops, packed, _nearest(ops)):
        assert np.max(np.abs(got - want)) <= 2.4e-7
    for group in groups[:27]:
        a, b = (packed[i] for i in group)
        assert a @ a == b @ b


def test_float64_operands_are_the_matrices():
    ops = _step_ops(6)
    _, fv = fu.pack_operands(ops, np.float64)
    for op, got in zip(ops, _floats(ops, fv)):
        assert np.array_equal(got, np.array(fu._payload(op.kind, op.m)))


def test_only_unitary_ops_on_all_of_the_ket_are_scaled():
    """X, S and a CNOT round to themselves; a lone controlled payload, a
    cphase and a projector, whose share of the norm only the ket knows,
    are rounded to nearest whatever the gain before them."""
    cphase = QCircuitGate.controlled((0,), 1, np.diag([1, np.exp(0.3j)]), 1)
    lone = QCircuitGate.controlled((0,), 3, _rx(0.2), 1)
    projector = QCircuitGate.single(2, np.diag([0, np.sqrt(2)]))
    gates = [QCircuitGate.single(0, _rx(0.2)), QCircuitGate.single(1, X2),
             QCircuitGate.single(2, S2), QCircuitGate.single(3, T2),
             QCircuitGate.controlled((1,), 2, X2, 1), cphase, lone,
             projector, fu.TwoQubitGate(0, 1, np.kron(_rx(0.2), _rx(0.2)))]
    ops = fu.lower_gates(gates)
    assert [op.kind for op in ops] == [
        "gen", "inv", "cphase", "cphase", "inv", "cphase", "gen", "diag",
        "u4"]
    assert [list(g) for g in fu._whole_ket_groups(ops)] == [
        [0], [1], [7], [8]]
    _, fv = fu.pack_operands(ops, np.float32)
    packed, nearest = _floats(ops, fv), _nearest(ops)
    for at in (1, 2, 3, 4, 5, 6, 7):
        assert np.array_equal(packed[at], nearest[at]), at
    # the pair's rounding leaves the product no further from 1 than
    # nearest would, behind what the RX ahead of it left
    first = float(packed[0] @ packed[0]) / 2
    assert abs(first - 1.0 - DELTA) < 1e-12
    assert abs(first * float(packed[8] @ packed[8]) / 4 - 1.0) \
        <= abs(first * float(nearest[8] @ nearest[8]) / 4 - 1.0) < 1.3e-7


@pytest.mark.parametrize("behind", range(8))
def test_a_phase_streams_kind_does_not_depend_on_what_ran_before(behind):
    """REVIEW 47: an op's kind, and with it the window program's key, is
    decided on the matrix the gate came with, whatever the H gates ahead
    of it did to the window's gain; a cphase's first entry stays 1."""
    gates = [QCircuitGate.single(q, H2) for q in range(behind)] + [
        QCircuitGate.single(8, Z2), QCircuitGate.single(9, S2),
        QCircuitGate.single(10, T2)]
    ops = fu.lower_gates(gates)
    assert fu.structure_of(ops)[behind:] == (
        ("cphase", 8, False), ("cphase", 9, False), ("cphase", 10, False))
    _, fv = fu.pack_operands(ops, np.float32)
    packed = _floats(ops, fv)
    for at, m in zip(range(behind, behind + 3), (Z2, S2, T2)):
        want = np.complex64(m[1, 1])
        assert np.array_equal(packed[at], [want.real, want.imag])


@pytest.mark.parametrize("stack,kwargs", [("tpu", {}),
                                          ("pager", {"n_pages": 4})])
def test_a_long_evolution_keeps_its_norm(stack, kwargs, monkeypatch):
    """215 Trotter steps at w10, what a 30 s window of the benchmark's
    dense cell holds since PR 47: the fused engine's squared norm stays
    within 2e-5 of 1 (what a window's last rounding leaves, under 1.3e-7
    and the same every step, and the arithmetic's own) where per-gate
    dispatch, a window an op with nothing before it, drifts by 19 DELTA
    a step (9.4e-5), and the two kets agree but for that scale."""
    n, steps = 10, 215
    gates = trotter_step_gates(n)

    def evolve():
        q = create_quantum_interface(stack, n, rng=QrackRandom(2),
                                     rand_global_phase=False, **kwargs)
        q.SetPermutation(0b1011001110)
        for _ in range(steps):
            issue(q, gates)
            q.GetAmplitude(1)
        return np.asarray(q.GetQuantumState())

    fused = evolve()
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "1")
    per_gate = evolve()
    drifted = float(np.vdot(per_gate, per_gate).real) - 1.0
    assert abs(drifted - steps * 19 * DELTA) < 2e-5
    assert abs(float(np.vdot(fused, fused).real) - 1.0) < 2e-5
    scale = np.sqrt(1.0 + drifted)
    assert np.max(np.abs(fused * scale - per_gate)) < 2e-5
