"""The plain reference against the program's CPU oracle and against the
engine under test, and each family's closed form against the reference."""

import numpy as np
import pytest

import harness
import reference
from families import FAMILIES, PARAMS, engine, family, issue


@pytest.mark.parametrize("name", FAMILIES)
@pytest.mark.parametrize("width", [5, 12])
def test_closed_form_is_the_reference(name, width):
    assert harness.self_check(family(name), PARAMS[name], reference,
                              width, seed=width) < 1e-12


@pytest.mark.parametrize("name", FAMILIES)
@pytest.mark.parametrize("stack,width,tol", [("cpu", 12, 1e-12),
                                             ("tpu", 13, 2e-6)])
def test_reference_against_the_engines(name, stack, width, tol, monkeypatch):
    """Three applications on end: multi-step for tfim, and for qft a QFT
    of a QFT, which no closed form covers."""
    fam = family(name)
    gates = fam.gates(width, PARAMS[name])
    x = 0b1011001110 | (1 << (width - 1))
    want = reference.basis_state(width, x)
    q = engine(stack, width)
    q.SetPermutation(x)
    for _ in range(3):
        want = reference.evolve(want, width, gates)
        issue(q, gates)
    assert np.max(np.abs(q.GetQuantumState() - want)) < tol


def test_engine_qft_is_the_family_gate_list():
    """The cell calls the engine's own QFT; the reference gets the
    family's list.  They are the same circuit."""
    width = 12
    fam = family("qft")
    x = 2741
    q = engine("cpu", width)
    q.SetPermutation(x)
    q.QFT(0, width)
    want = reference.run(width, fam.gates(width, PARAMS["qft"]), x)
    assert np.max(np.abs(q.GetQuantumState() - want)) < 1e-12


def test_tfim_gate_list_is_the_repos_trotter_circuit():
    """The source names models/algorithms.trotter_qcircuit's gate order."""
    from qrack_tpu.models.algorithms import trotter_qcircuit

    width = 10
    p = PARAMS["tfim"]
    ours = family("tfim").gates(width, p)
    theirs = trotter_qcircuit(width, steps=1, dt=p["dt"], j=p["J"], h=p["h"])
    assert len(ours) == len(theirs.gates) == 3 * (width - 1) + width
    for (controls, matrix, target), g in zip(ours, theirs.gates):
        assert (tuple(controls), target) == (tuple(g.controls), g.target)
        (perm, m), = g.payloads.items()
        assert perm == (1 if controls else 0)
        assert np.allclose(m, matrix, atol=1e-15)


@pytest.mark.parametrize("name", FAMILIES)
def test_kernel_interpreter_agrees(name, monkeypatch):
    """The path the cells time is the Pallas kernel; on the CPU it runs
    under the interpreter, across tiles too (block_pow 16 < width 17)."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    width = 17
    fam = family(name)
    gates = fam.gates(width, PARAMS[name])
    x = 0b10110011101 | (1 << (width - 1))
    q = engine("tpu", width)
    q.SetPermutation(x)
    issue(q, gates)
    ys = [x, x ^ 1, x ^ (1 << 16), x ^ (1 << 9) ^ (1 << 16), 12345]
    got = np.array([q.GetAmplitude(y) for y in ys])
    want = np.array([fam.amplitude(width, PARAMS[name], x, y) for y in ys])
    assert np.max(np.abs(got - want)) < 2e-6 * np.max(np.abs(want)) + 1e-9


@pytest.mark.parametrize("width,steps", [(6, 1), (9, 5), (11, 17)])
def test_free_fermion_bond_correlations_are_the_reference(width, steps):
    """tfim's exact <Z_j Z_j+1> after many steps, against the ket the
    plain reference evolves, and the reduction that reads them from planes."""
    fam = family("tfim")
    p = PARAMS["tfim"]
    x = 0b10110011101 & ((1 << width) - 1) | 1
    state = reference.basis_state(width, x)
    for _ in range(steps):
        state = reference.evolve(state, width, fam.gates(width, p))
    prob, idx = np.abs(state) ** 2, np.arange(1 << width)
    want = [float(np.sum(prob * (1 - 2 * (((idx >> j) ^ (idx >> (j + 1))) & 1))))
            for j in range(width - 1)]
    assert np.max(np.abs(np.array(fam.bond_zz(width, p, x, steps)) - want)) < 1e-12
    planes = np.stack([state.real, state.imag]).astype(np.float32)
    got = fam.measured_bond_zz(planes, list(range(width)))
    assert np.max(np.abs(np.array(got) - want)) < 1e-5
