"""The random-circuit-sampling family: its gate plan, its circuit
builder and the gate-at-a-time reference tests compare against
(reference: test/benchmarks.cpp:4141 test_random_circuit_sampling_nn —
random sqrt-root layers + brick-wall ISwap couplers).
"""

from __future__ import annotations

import numpy as np

from .. import matrices as mat
from ..utils.rng import QrackRandom

_ROOTS = (mat.SQRTX2, mat.SQRTY2, mat.SQRTW2)


def rcs_layers(n: int, depth: int, rng):
    """The family's one gate plan: per layer a random root per qubit
    (0, 1, 2: sqrt X, sqrt Y, sqrt W) and the brick-wall pairing of the
    couplers.  ``rng`` is a ``QrackRandom`` or the seed of one; the
    draws come layer by layer, qubit by qubit."""
    if not hasattr(rng, "randint"):
        rng = QrackRandom(rng)
    plan = []
    for d in range(depth):
        roots = [rng.randint(0, 3) for _ in range(n)]
        off = d & 1
        pairs = [(q, q + 1) for q in range(off, n - 1, 2)]
        plan.append((roots, pairs))
    return plan


def rcs_qcircuit(n: int, depth: int, seed: int):
    """The RCS gate plan as a ``QCircuit`` gate list — the form the
    noisy trajectory engine lowers (qrack_tpu/noise/trajectories.py).
    ``QCircuitGate`` is a controlled-1q payload model and the trajectory
    window lowers nothing else, so here the brick-wall couplers are CZ
    instead of ISwap: same entangling topology, payload-representable.
    (The dense engine's own window holds an ISwap as one op since PR 36,
    ``ops/fusion.TwoQubitGate``; a ``QCircuit`` still cannot.)"""
    from ..layers.qcircuit import QCircuit

    cz = mat.phase_mtrx(1.0, -1.0)
    c = QCircuit(n)
    for roots, pairs in rcs_layers(n, depth, seed):
        for q, g in enumerate(roots):
            c.append_1q(q, _ROOTS[g])
        for a, b in pairs:
            c.append_ctrl((a,), b, cz, 1)
    return c


def reference_rcs_state(n: int, depth: int, seed: int, engine) -> np.ndarray:
    """Same plan through a gate-at-a-time engine (parity checking)."""
    plan = rcs_layers(n, depth, seed)
    for (roots, pairs) in plan:
        for q, g in enumerate(roots):
            engine.Mtrx(_ROOTS[g], q)
        for (a, b) in pairs:
            engine.Apply4x4(mat.ISWAP4, a, b)
    return np.asarray(engine.GetQuantumState())
