"""The circuit families of qrack_tpu.models through the engine path —
``QCircuit.RunFused`` into QEngineTPU and QPager — vs the gate-at-a-time
oracle."""

import numpy as np
import jax
import jax.numpy as jnp

from qrack_tpu import QEngineCPU
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.models import qft as qftm
from qrack_tpu.models import rcs as rcsm
from qrack_tpu.ops import gatekernels as gk
from qrack_tpu.parallel.pager import QPager
from qrack_tpu.utils.rng import QrackRandom

from helpers import rand_state


def _dense(n, **kw):
    return QEngineTPU(n, rng=QrackRandom(1), rand_global_phase=False, **kw)


def _paged(n):
    return QPager(n, devices=jax.devices("cpu")[:8], n_pages=8,
                  rng=QrackRandom(1), rand_global_phase=False)


def _qft_round_trip(q, psi, atol, atol_back):
    n = q.qubit_count
    o = QEngineCPU(n, rng=QrackRandom(1), rand_global_phase=False)
    o.SetQuantumState(psi)
    o.QFT(0, n)
    q.SetQuantumState(psi)
    qftm.qft_qcircuit(n).RunFused(q)
    np.testing.assert_allclose(q.GetQuantumState(), o.GetQuantumState(),
                               atol=atol)
    qftm.qft_qcircuit(n, inverse=True).RunFused(q)
    np.testing.assert_allclose(q.GetQuantumState(), psi, atol=atol_back)


def test_fused_qft_matches_oracle():
    _qft_round_trip(_dense(7), rand_state(7, 3), 2e-5, 3e-5)


def test_bf16_amplitude_mode_accuracy():
    """bf16 plane storage keeps deep-circuit fidelity: gate contractions
    run at HIGHEST precision, so only storage rounding accumulates
    (measured ~1e-5 infidelity at these depths)."""
    w = 12
    for circ in (qftm.qft_qcircuit(w), rcsm.rcs_qcircuit(w, 8, 3)):
        kets = []
        for dtype in (jnp.float32, jnp.bfloat16):
            q = _dense(w, dtype=dtype)
            q.SetPermutation(5)
            circ.RunFused(q)
            assert q._state.dtype == dtype
            kets.append(gk.from_planes(q._state))
        a, b = kets
        nrm = np.linalg.norm(b)
        assert abs(nrm - 1.0) < 0.02        # norm drift stays percent-level
        fid = abs(np.vdot(a, b / nrm)) ** 2
        assert fid > 0.999, fid


def test_sharded_qft_matches_oracle():
    _qft_round_trip(_paged(8), rand_state(8, 5), 3e-5, 5e-5)


def _rcs_gate_path(n, depth, seed):
    """The gate-at-a-time oracle of both forms of the family: the ISwap
    plan (``reference_rcs_state``) and the CZ ``rcs_qcircuit``."""
    o = QEngineCPU(n, rng=QrackRandom(1), rand_global_phase=False)
    iswap = rcsm.reference_rcs_state(n, depth, seed=seed, engine=o)
    o.SetPermutation(0)
    rcsm.rcs_qcircuit(n, depth, seed).Run(o)
    return iswap, np.asarray(o.GetQuantumState())


def _rcs_on(q, n, depth, seed):
    iswap = rcsm.reference_rcs_state(n, depth, seed=seed, engine=q)
    q.SetPermutation(0)
    rcsm.rcs_qcircuit(n, depth, seed).RunFused(q)
    return iswap, np.asarray(q.GetQuantumState())


def test_fused_rcs_matches_gate_path():
    n, depth = 6, 4
    want = _rcs_gate_path(n, depth, 7)
    got = _rcs_on(_dense(n), n, depth, 7)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=3e-6)


def test_sharded_rcs_matches_single_chip():
    """Brick-wall RCS over 8 pages: couplers inside a page, straddling
    the page boundary and between page bits, and roots on paged qubits,
    must reproduce the single-chip engine."""
    n, depth = 8, 5   # 5 local + 3 page bits; both brick offsets hit
    want = _rcs_on(_dense(n), n, depth, 13)
    got = _rcs_on(_paged(n), n, depth, 13)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=3e-6)


def test_compiled_sharded_circuit_matches_oracle():
    from jax.sharding import Mesh

    from qrack_tpu.layers.qcircuit import QCircuit
    from qrack_tpu import matrices as mat

    n = 7
    rng = QrackRandom(9)
    c = QCircuit(n)
    for _ in range(30):
        t = rng.randint(0, n)
        k = rng.randint(0, 4)
        if k == 0:
            c.append_1q(t, mat.H2)
        elif k == 1:
            c.append_1q(t, mat.u3_mtrx(rng.rand(), rng.rand(), rng.rand()))
        elif k == 2:
            ctl = rng.randint(0, n)
            if ctl != t:
                c.append_ctrl((ctl,), t, mat.X2, 1)
        else:
            ctl = rng.randint(0, n)
            if ctl != t:
                c.append_ctrl((ctl,), t, mat.phase_mtrx(1, np.exp(0.4j)), 1)
    o = QEngineCPU(n, rng=QrackRandom(1), rand_global_phase=False)
    c.Run(o)
    devs = jax.devices("cpu")[:8]
    mesh = Mesh(np.array(devs), ("pages",))
    fn, sharding = c.compile_sharded_fn(mesh, n)
    planes = jax.device_put(gk.to_planes(np.eye(1, 1 << n, 0).ravel()), sharding)
    out = fn(planes)
    np.testing.assert_allclose(gk.from_planes(jax.device_get(out)),
                               o.GetQuantumState(), atol=3e-6)
