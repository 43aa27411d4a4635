"""Pallas fused gate-segment sweep (interpret mode on CPU): parity with
the XLA compile_fn path on random circuits."""

import numpy as np
import pytest

from qrack_tpu.layers.qcircuit import QCircuit
from qrack_tpu.models import qft as qftm
from qrack_tpu import matrices as mat
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk
from qrack_tpu.utils.rng import QrackRandom


def through_window_kernel(circ, n, planes, block_pow):
    """The whole circuit as one window of the Pallas kernel under the
    interpreter: what the engine's flush hands the chip."""
    ops = fu.lower_gates(circ.gates)
    wfn = pk.make_window_fn(n, fu.structure_of(ops), block_pow=block_pow,
                            interpret=True)
    return np.asarray(wfn(planes, *fu.pack_operands(ops, planes.dtype)))


def build_circuit(n, seed, gates=30):
    rng = QrackRandom(seed)
    c = QCircuit(n)
    for _ in range(gates):
        kind = rng.randint(0, 5)
        t = rng.randint(0, n)
        if kind == 0:
            c.append_1q(t, mat.H2)
        elif kind == 1:
            c.append_1q(t, mat.T2)
        elif kind == 2:
            c.append_1q(t, np.asarray(mat.X2))
        elif kind == 3:
            ctl = rng.randint(0, n)
            if ctl != t:
                c.append_ctrl((ctl,), t, np.diag([1.0, -1.0 + 0j]), 1)  # CZ
        else:
            ctl = rng.randint(0, n)
            if ctl != t:
                c.append_ctrl((ctl,), t, np.asarray(mat.X2), 1)  # CNOT
    return c


@pytest.mark.parametrize("seed", [3, 4])
def test_pallas_segments_match_xla(seed):
    import jax

    n = 8
    c = build_circuit(n, seed)
    planes = qftm.basis_planes(n, 5)
    want = np.asarray(jax.jit(c.compile_fn(n))(planes))
    # tiny tiles force multi-block grids AND high-target bridges
    for bp in (4, 6, n):
        got = through_window_kernel(c, n, planes, bp)
        np.testing.assert_allclose(got, want, atol=3e-5, err_msg=f"bp={bp}")


def test_pallas_high_diag_and_controls():
    import jax

    n = 7
    c = QCircuit(n)
    c.append_1q(0, mat.H2)
    c.append_1q(n - 1, mat.H2)
    c.append_ctrl((n - 1,), 0, np.diag([1.0, 1j]), 1)   # high control, diag
    c.append_1q(n - 1, mat.T2)                          # high diag target
    c.append_ctrl((0,), 1, np.asarray(mat.X2), 1)
    planes = qftm.basis_planes(n, 0)
    want = np.asarray(jax.jit(c.compile_fn(n))(planes))
    got = through_window_kernel(c, n, planes, 4)
    np.testing.assert_allclose(got, want, atol=3e-5)


# ---------------- fused compressed-ket kernels ----------------
# (ops/pallas_turboquant.py: dequant -> gate -> requant in one pass)


def test_tq_pallas_matches_xla_path(monkeypatch):
    """QRACK_USE_PALLAS=1 routes compressed gates through the fused
    kernel (interpret mode on CPU): state parity with the XLA chunk
    programs across generic/diagonal/controlled/cross-tile gates."""
    import numpy as np

    from qrack_tpu.engines.turboquant import QEngineTurboQuant
    from qrack_tpu.utils.rng import QrackRandom

    def fidelity(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return abs(np.vdot(a, b)) ** 2 / (np.vdot(a, a).real
                                          * np.vdot(b, b).real)

    # kernel-parity test: pin per-gate dispatch on BOTH builds (the
    # pallas path never fuses, and windowed recompression rounds int16
    # codes differently enough to nick the 1e-9 fidelity bar)
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "1")

    def build(use_pallas):
        if use_pallas:
            monkeypatch.setenv("QRACK_USE_PALLAS", "1")
        else:
            monkeypatch.delenv("QRACK_USE_PALLAS", raising=False)
        q = QEngineTurboQuant(8, bits=16, chunk_qb=5, block_pow=2,
                              rng=QrackRandom(70), rand_global_phase=False)
        # small tile so cross-TILE routing (target >= tile) is exercised
        q._PALLAS_TILE_POW = 4
        for i in range(8):
            q.H(i)
        q.CNOT(0, 3)        # generic inside tile
        q.T(2)              # diag inside tile
        q.CZ(1, 6)          # diag: control low, target above tile
        q.CNOT(6, 1)        # control above tile, target low (pallas)
        q.RZ(0.37, 7)       # diag above tile
        q.CNOT(0, 7)        # generic above tile -> XLA pair path
        q.RY(0.8, 2)
        return q.GetQuantumState()

    a = build(False)
    b = build(True)
    assert fidelity(a, b) > 1 - 1e-9


def test_tq_pallas_untouched_tiles_exact(monkeypatch):
    """Tiles failing the high-control test keep their codes bit-for-bit
    through the fused kernel (the XLA path's exactness contract)."""
    import numpy as np

    from qrack_tpu.engines.turboquant import QEngineTurboQuant
    from qrack_tpu.utils.rng import QrackRandom

    monkeypatch.setenv("QRACK_USE_PALLAS", "1")
    q = QEngineTurboQuant(7, bits=8, chunk_qb=4, block_pow=2,
                          rng=QrackRandom(71), rand_global_phase=False)
    q._PALLAS_TILE_POW = 4
    for i in range(7):
        q.H(i)
    before = np.asarray(q._codes).copy()
    # control on qubit 6 (above the 16-amp tile): half the tiles must
    # stay untouched exactly
    q.CNOT(6, 1)
    after = np.asarray(q._codes)
    T = 1 << 4
    rows_per_tile = T // 4
    tiles = before.shape[0] // rows_per_tile
    untouched = 0
    for t in range(tiles):
        sl = slice(t * rows_per_tile, (t + 1) * rows_per_tile)
        # tile t covers amplitudes with bit6 = (t >> 2) & 1 at tile_pow 4
        if ((t << 4) >> 6) & 1 == 0:
            assert np.array_equal(before[sl], after[sl]), t
            untouched += 1
    assert untouched == tiles // 2
