"""Independent brute-force oracle utilities for conformance tests.

Deliberately implemented with explicit index loops (not the engine's
vectorized index algebra) so engine bugs can't hide in shared code.
"""

from __future__ import annotations

import contextlib

import numpy as np


def full_unitary(n: int, m: np.ndarray, qubits) -> np.ndarray:
    """Expand unitary `m` over `qubits` (qubits[0] = LSB of m's index) to
    the full 2^n space. O(4^n) — test-size only."""
    k = len(qubits)
    dim = 1 << n
    u = np.zeros((dim, dim), dtype=np.complex128)
    for i in range(dim):
        sub = 0
        for j, q in enumerate(qubits):
            sub |= ((i >> q) & 1) << j
        base = i
        for q in qubits:
            base &= ~(1 << q)
        for sub2 in range(1 << k):
            i2 = base
            for j, q in enumerate(qubits):
                i2 |= ((sub2 >> j) & 1) << q
            u[i2, i] += m[sub2, sub]
    return u


def controlled(m: np.ndarray, n_controls: int, perm: int = None) -> np.ndarray:
    """Controlled expansion: m on target (LSB), controls above it."""
    if perm is None:
        perm = (1 << n_controls) - 1
    dim = 2 << n_controls
    u = np.eye(dim, dtype=np.complex128)
    # target = bit 0, controls = bits 1..n_controls
    for t in (0, 1):
        for t2 in (0, 1):
            u[(perm << 1) | t2, (perm << 1) | t] = m[t2, t]
    return u


def rand_state(n: int, seed: int) -> np.ndarray:
    g = np.random.Generator(np.random.PCG64(seed))
    v = g.normal(size=1 << n) + 1j * g.normal(size=1 << n)
    return (v / np.linalg.norm(v)).astype(np.complex128)


def trotter_step_gates(n: int, dt: float = 0.1, j: float = 1.0, h: float = 1.0):
    """One first-order Trotter step of the open transverse-field Ising
    chain as ``(controls, matrix, target)`` gate calls, in the order of
    ``models/algorithms.trotter_qcircuit`` (and of the benchmark's
    ``circuits/tfim.gates``): CNOT, RZ(2 j dt), CNOT on every bond, then
    RX(2 h dt) on every qubit: 4 n - 3 calls."""
    x2 = np.array([[0, 1], [1, 0]], dtype=np.complex128)
    tz, tx = 2.0 * j * dt, 2.0 * h * dt
    rz = np.diag([np.exp(-0.5j * tz), np.exp(0.5j * tz)])
    c, s = np.cos(tx / 2), np.sin(tx / 2)
    rx = np.array([[c, -1j * s], [-1j * s, c]], dtype=np.complex128)
    out = []
    for i in range(n - 1):
        out += [((i,), x2, i + 1), ((), rz, i + 1), ((i,), x2, i + 1)]
    out += [((), rx, q) for q in range(n)]
    return out


def issue(q, gates) -> None:
    """A gate list through an engine's gate methods."""
    for controls, matrix, target in gates:
        if controls:
            q.MCMtrx(controls, matrix, target)
        else:
            q.Mtrx(matrix, target)


@contextlib.contextmanager
def benchmark_plans(width: int = 28):
    """``windows(family)``: the windows the fuser plans for one
    application of a dense cell's family at ``width``, from the
    benchmark's own gate lists (``benchmarks/tests/structure.py``), no
    ket allocated; each with the ``ops`` it lowered, whose masks say
    where a control sits.  The benchmark's modules are imported for the
    ``with`` alone."""
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    before, path = set(sys.modules), list(sys.path)
    sys.path[:0] = [os.path.join(bench, "tests"), bench]
    try:
        import families
        import structure

        from qrack_tpu.ops import fusion as fu

        class WithOps(structure.PlanOnlyEngine):
            def _fuse_flush(self, gates):
                dispatched = super()._fuse_flush(gates)
                self.windows[-1]["ops"] = fu.lower_gates(gates)
                return dispatched

            def _barrier(self):
                # an ALU call or a register's reduction is a barrier: the
                # pending window flushes as at a read of the planes
                if self._fuser.gates:
                    self._fuser.flush("read")

            def _k_rotate(self, shift, block_bits):
                self._barrier()  # there are no planes to rotate

            def _k_modn(self, name, table, *registers):
                self._barrier()  # nor to write

            def _k_prob_reg_all(self, start, length):
                # with no planes every value is as likely, and the draw
                # is the engine's own
                self._barrier()
                return np.full(1 << length, 1.0 / (1 << length))

            def _k_collapse(self, mask, val, nrm_sq):
                pass

        def windows(name):
            planner, structure.PlanOnlyEngine = structure.PlanOnlyEngine, WithOps
            try:
                return structure.plan_application(
                    families.family(name), width, families.PARAMS[name])
            finally:
                structure.PlanOnlyEngine = planner

        yield windows
    finally:
        sys.path[:] = path
        for name in set(sys.modules) - before:
            if name in ("families", "structure", "harness") \
                    or name.startswith("bench_"):
                del sys.modules[name]


def plan_only_pager(n: int, n_pages: int = 4, **kwargs):
    """A ``QPager`` with no planes, at any width the host can name: the
    real gate funnel, ``GateStreamFuser``, remap planner and kernel
    lowering decide each window (``QPager._plan_window``) and
    ``q.windows`` records what they decided; nothing is allocated and no
    program is built.  A read is ``GetAmplitude``, which flushes."""
    from qrack_tpu.parallel.pager import QPager

    class PlanOnlyPager(QPager):
        def __init__(self):
            self.windows = []
            super().__init__(n, n_pages=n_pages, rand_global_phase=False,
                             **kwargs)

        def SetPermutation(self, perm, phase=None):
            self._state = None  # drops a pending window, as the pager's does
            self._map_reset()

        def GetAmplitude(self, perm):
            self._settle()
            return 0j

        def _dispatch_ops(self, ops, lookahead=None):
            window = self._plan_window(ops, lookahead)
            self.windows.append(window)
            if window.structure is not None:
                self._map_assign(window.new_qmap)
            return 1

    return PlanOnlyPager()
