"""The plain reference: a gate-by-gate numpy state-vector simulator.

complex128, one gate at a time, no fusion, no kernels, nothing imported
from the program under test.  A gate is ``(controls, matrix, target)``:
``controls`` a tuple of qubits that must all be 1, ``matrix`` a 2x2
array-like, ``target`` the qubit it acts on.  Qubit 0 is the least
significant bit of a basis state's index, as in Qrack.

``precision`` rounds the whole ket to a narrower float after every gate,
which is what a simulator that *holds* its ket in that type does.  It
is the control of "How correct is decided": the reference in the
program's place, one precision below the configuration's float32.
"""

import numpy as np


def _round_to(state, precision):
    if precision is None:
        return state
    if precision == "bfloat16":
        import ml_dtypes

        dt = ml_dtypes.bfloat16
    else:
        dt = np.dtype(precision)
    re = state.real.astype(dt).astype(np.float64)
    im = state.imag.astype(dt).astype(np.float64)
    return re + 1j * im


def basis_state(width, x):
    state = np.zeros(1 << width, dtype=np.complex128)
    state[x] = 1.0
    return state


def apply_gate(state, width, controls, matrix, target):
    """Return the ket after one (multiply controlled) 2x2 gate."""
    m = np.asarray(matrix, dtype=np.complex128).reshape(2, 2)
    idx = np.arange(1 << width)
    lo = (idx >> target) & 1 == 0
    for c in controls:
        lo &= (idx >> c) & 1 == 1
    i0 = idx[lo]
    i1 = i0 | (1 << target)
    a0, a1 = state[i0], state[i1]
    out = state.copy()
    out[i0] = m[0, 0] * a0 + m[0, 1] * a1
    out[i1] = m[1, 0] * a0 + m[1, 1] * a1
    return out


def run(width, gates, x, precision=None):
    """The ket after ``gates`` on the basis state ``|x>``."""
    state = _round_to(basis_state(width, x), precision)
    for controls, matrix, target in gates:
        state = _round_to(apply_gate(state, width, controls, matrix, target),
                          precision)
    return state


def evolve(state, width, gates, precision=None):
    """``gates`` applied to a ket that is already there."""
    for controls, matrix, target in gates:
        state = _round_to(apply_gate(state, width, controls, matrix, target),
                          precision)
    return state
