"""QPager conformance on the 8-device virtual CPU mesh.

Exercises the reference QPager semantics re-designed as collectives
(SURVEY.md §2.3): in-page broadcast, paged-qubit ppermute exchange,
MetaSwap page permutation, meta-controlled page selection."""

import math

import jax.numpy as jnp

import numpy as np
import pytest

from qrack_tpu import QEngineCPU
from qrack_tpu.parallel.pager import QPager
from qrack_tpu import matrices as mat
from qrack_tpu.utils.rng import QrackRandom

from helpers import rand_state
from test_engine_matrix import random_circuit


def make_pair(n, seed=3, n_pages=8):
    o = QEngineCPU(n, rng=QrackRandom(seed), rand_global_phase=False)
    p = QPager(n, rng=QrackRandom(seed), rand_global_phase=False, n_pages=n_pages)
    return o, p


def assert_match(o, p, atol=3e-5):
    np.testing.assert_allclose(p.GetQuantumState(), o.GetQuantumState(), atol=atol)


def test_local_and_global_gates():
    n = 6  # 3 local bits, 3 global bits on 8 pages
    o, p = make_pair(n)
    for eng in (o, p):
        eng.H(0)        # local
        eng.H(4)        # global (paged)
        eng.CNOT(0, 5)  # local control, global target
        eng.CNOT(5, 1)  # global control, local target
        eng.CZ(3, 4)    # global-global diag
        eng.T(5)        # global diag
    assert_match(o, p)


def test_random_circuits_match():
    n = 7
    for seed in (1, 2):
        o, p = make_pair(n, seed)
        random_circuit(o, QrackRandom(200 + seed), 50, n)
        random_circuit(p, QrackRandom(200 + seed), 50, n)
        assert_match(o, p)


def test_qft_across_pages():
    n = 8
    o, p = make_pair(n)
    for eng in (o, p):
        eng.SetPermutation(0b10110101)
        eng.QFT(0, n)
    assert_match(o, p)
    for eng in (o, p):
        eng.IQFT(0, n)
    assert_match(o, p)
    assert abs(p.GetAmplitude(0b10110101)) == pytest.approx(1.0, abs=1e-4)


def test_meta_swap_and_mixed_swap():
    n = 7
    o, p = make_pair(n, seed=9)
    psi = rand_state(n, 77)
    o.SetQuantumState(psi)
    p.SetQuantumState(psi)
    for eng in (o, p):
        eng.Swap(4, 6)  # global-global: pure page permutation
        eng.Swap(0, 2)  # local-local
        eng.Swap(1, 5)  # mixed
    assert_match(o, p)


def test_measurement_and_prob():
    n = 6
    o, p = make_pair(n, seed=11)
    for eng in (o, p):
        eng.H(0)
        eng.CNOT(0, 5)  # entangle across the page boundary
    assert p.Prob(5) == pytest.approx(o.Prob(5), abs=1e-6)
    assert p.ProbMask(0b100001, 0b100001) == pytest.approx(
        o.ProbMask(0b100001, 0b100001), abs=1e-6)
    for eng in (o, p):
        eng.rng.seed(5)
    assert p.M(5) == o.M(5)
    assert_match(o, p)
    # MAll two-stage sampling
    o2, p2 = make_pair(n, seed=13)
    for eng in (o2, p2):
        eng.H(0)
        eng.CNOT(0, 5)
        eng.rng.seed(21)
    assert p2.MAll() in (0, 0b100001)


def test_alu_and_diag_through_pager():
    n = 7
    o, p = make_pair(n, seed=15)
    for eng in (o, p):
        eng.HReg(0, 4)
        eng.INC(11, 0, 6)          # register crosses the page boundary
        eng.PhaseFlipIfLess(9, 0, 4)
        eng.UniformParityRZ(0b1010001, 0.4)
        eng.ROL(2, 0, 6)
    assert_match(o, p)


def test_expectation_and_clone():
    n = 6
    o, p = make_pair(n, seed=17)
    random_circuit(o, QrackRandom(31), 30, n)
    random_circuit(p, QrackRandom(31), 30, n)
    assert p.ExpectationBitsAll(list(range(n))) == pytest.approx(
        o.ExpectationBitsAll(list(range(n))), abs=1e-3)
    c = p.Clone()
    assert p.ApproxCompare(c, 1e-6)
    assert p.SumSqrDiff(o) < 1e-6


def test_fewer_pages_than_qubits_devices():
    # 4 pages on the 8-device pool (degenerate placement allowed)
    o = QEngineCPU(5, rng=QrackRandom(1), rand_global_phase=False)
    p = QPager(5, rng=QrackRandom(1), rand_global_phase=False, n_pages=4)
    for eng in (o, p):
        eng.H(0)
        eng.CNOT(0, 4)
        eng.T(4)
    assert_match(o, p)


def test_compose_decompose_through_pager():
    o, p = make_pair(4, seed=19, n_pages=4)
    for eng, mk in ((o, None), (p, None)):
        eng.H(0)
        eng.CNOT(0, 1)
    other_o = QEngineCPU(2, rng=QrackRandom(7), rand_global_phase=False)
    other_o.X(0)
    other_p = QEngineCPU(2, rng=QrackRandom(7), rand_global_phase=False)
    other_p.X(0)
    o.Compose(other_o)
    p.Compose(other_p)
    assert p.GetQubitCount() == 6
    assert_match(o, p)


def test_hybrid_switching():
    from qrack_tpu.engines.hybrid import QHybrid

    q = QHybrid(3, rng=QrackRandom(5), rand_global_phase=False,
                tpu_threshold_qubits=5, pager_threshold_qubits=8)
    from qrack_tpu.engines.cpu import QEngineCPU as CPU
    assert isinstance(q._engine, CPU)
    q.H(0)
    q.CNOT(0, 1)
    state_before = q.GetQuantumState()
    # grow past the TPU threshold
    q.Allocate(3, 3)
    from qrack_tpu.engines.tpu import QEngineTPU as TPU
    assert isinstance(q._engine, TPU)
    assert q.qubit_count == 6
    np.testing.assert_allclose(q.GetQuantumState()[:8], state_before, atol=1e-6)
    # gates keep working after the switch
    q.CNOT(0, 5)
    assert q.Prob(5) == pytest.approx(0.5, abs=1e-5)
    # shrink back below the threshold
    q.ForceM(5, False) if q.Prob(5) < 2 else None
    q.Dispose(3, 3, None)
    assert isinstance(q._engine, CPU)
    assert q.qubit_count == 3


def test_hybrid_compose_into_pager_mode():
    # regression: composing a small hybrid past the pager threshold must
    # not construct a pager at the (too small) current width
    from qrack_tpu.engines.hybrid import QHybrid
    from qrack_tpu.parallel.pager import QPager as _QP

    q = QHybrid(2, rng=QrackRandom(1), rand_global_phase=False,
                tpu_threshold_qubits=4, pager_threshold_qubits=7)
    q.H(0)
    other = QEngineCPU(7, rng=QrackRandom(2), rand_global_phase=False)
    other.X(0)
    start = q.Compose(other)
    assert start == 2 and q.qubit_count == 9
    assert isinstance(q._engine, _QP)
    assert q.Prob(0) == pytest.approx(0.5, abs=1e-5)
    assert q.Prob(2) == pytest.approx(1.0, abs=1e-5)


def test_pager_dispose_below_page_count():
    # regression: shrinking below the page count rebuilds the mesh
    p = QPager(8, rng=QrackRandom(3), rand_global_phase=False, n_pages=8)
    p.H(0)
    p.Dispose(2, 6)
    assert p.GetQubitCount() == 2
    assert p.n_pages <= 4
    assert p.Prob(0) == pytest.approx(0.5, abs=1e-5)


def test_pager_rejects_more_pages_than_devices():
    with pytest.raises(ValueError):
        QPager(10, n_pages=16)


def test_structural_ops_stay_on_device():
    """Compose/Decompose/Dispose/Allocate must not stage the full ket
    through the host when the page mesh survives (reference rebalances
    pages device-side, src/qpager.cpp:316-367)."""
    n = 7
    o, p = make_pair(n, seed=9, n_pages=4)
    for eng in (o, p):
        random_circuit(eng, QrackRandom(321), 25, n)
    # trip-wire: any full-ket host read during the structural ops fails
    def boom():
        raise AssertionError("full-ket host staging in structural op")
    p.GetQuantumState = lambda: boom()
    o2 = QEngineCPU(2, rng=QrackRandom(5), rand_global_phase=False)
    p2 = QEngineCPU(2, rng=QrackRandom(5), rand_global_phase=False)
    for eng in (o2, p2):
        eng.H(0)
        eng.T(0)
        eng.CNOT(0, 1)
    o.Compose(o2)
    p.Compose(p2)
    del p.__dict__["GetQuantumState"]
    assert_match(o, p)
    # dispose a definite qubit (allocate + dispose round trip)
    for eng in (o, p):
        eng.Allocate(3, 1)
    p.GetQuantumState = lambda: boom()
    for eng in (o, p):
        eng.Dispose(3, 1, 0)
    del p.__dict__["GetQuantumState"]
    assert_match(o, p)


def test_decompose_separable_span_device_side():
    n = 8
    o, p = make_pair(n, seed=11, n_pages=4)
    for eng in (o, p):
        # entangle {0,1,2} and {3,4} separately, leave the rest cached
        eng.H(0); eng.CNOT(0, 1); eng.T(1); eng.CNOT(1, 2)
        eng.H(3); eng.CNOT(3, 4); eng.S(4)
    od = QEngineCPU(2, rng=QrackRandom(1), rand_global_phase=False)
    pd = QEngineCPU(2, rng=QrackRandom(1), rand_global_phase=False)
    p.GetQuantumState = (lambda: (_ for _ in ()).throw(AssertionError("host staging")))
    o.Decompose(3, od)
    p.Decompose(3, pd)
    del p.__dict__["GetQuantumState"]
    assert_match(o, p)
    np.testing.assert_allclose(pd.GetQuantumState(), od.GetQuantumState(), atol=3e-5)


def test_mesh_shrinks_and_regrows():
    n = 5
    o, p = make_pair(n, seed=13, n_pages=4)
    for eng in (o, p):
        random_circuit(eng, QrackRandom(77), 15, n)
        eng.Dispose(1, 4)   # width 1 < page count: mesh shrinks
    assert p.g_bits < 2
    assert_match(o, p)
    o2 = QEngineCPU(5, rng=QrackRandom(2), rand_global_phase=False)
    p2 = QEngineCPU(5, rng=QrackRandom(2), rand_global_phase=False)
    for eng in (o2, p2):
        random_circuit(eng, QrackRandom(88), 10, 5)
    o.Compose(o2)
    p.Compose(p2)
    assert p.g_bits == 2  # mesh re-grew to construction page count
    assert_match(o, p)


def test_runfused_lowers_onto_pager_mesh():
    """Buffered circuits materialize through ONE sharded executable when
    the stack bottoms out on a paged ket (ROADMAP: compile_sharded_fn
    wired into RunFused)."""
    from qrack_tpu.layers.qcircuit import QCircuit
    from qrack_tpu import matrices as mat_

    n = 7
    o, p = make_pair(n, seed=21, n_pages=4)
    c = QCircuit(n)
    c.append_1q(0, mat_.H2)
    c.append_ctrl((0,), n - 1, mat_.X2, 1)   # local ctrl -> paged target
    c.append_ctrl((n - 1,), 2, mat_.X2, 1)   # paged ctrl -> local target
    c.append_1q(n - 1, mat_.T2)
    # trip-wire: the fused path must not fall back to per-gate dispatch
    calls = []
    orig = type(p)._k_apply_2x2
    type(p)._k_apply_2x2 = lambda self, *a, **k: calls.append(1) or orig(self, *a, **k)
    try:
        c.RunFused(p)
    finally:
        type(p)._k_apply_2x2 = orig
    assert not calls, "pager RunFused fell back to per-gate dispatch"
    c.Run(o)
    assert_match(o, p)


def test_tensornetwork_over_pager_materializes_fused():
    from qrack_tpu.layers.qtensornetwork import QTensorNetwork

    n = 6
    o = QEngineCPU(n, rng=QrackRandom(3), rand_global_phase=False)
    t = QTensorNetwork(
        n, stack_factory=lambda m, **kw: QPager(m, n_pages=4, **kw),
        rng=QrackRandom(3), rand_global_phase=False)
    for eng in (o, t):
        eng.H(0)
        eng.CNOT(0, n - 1)
        eng.T(n - 1)
        eng.CNOT(n - 1, 1)
    # measurement materializes the buffered segment through RunFused
    t.rng.seed(5)
    o.rng.seed(5)
    assert t.M(1) == o.M(1)
    np.testing.assert_allclose(t.GetQuantumState(), o.GetQuantumState(),
                               atol=3e-5)


def test_compose_ring_all_starts_and_no_allgather():
    """The ring Compose kernel (reference CombineEngines discipline,
    src/qpager.cpp:316-367): exact at every insertion point on 8 pages,
    and the compiled HLO contains no all-gather of the paged ket —
    cross-page movement rides collective-permute only."""
    import jax
    from jax.sharding import PartitionSpec as P

    from qrack_tpu.ops import sharded as shb
    from qrack_tpu.ops import gatekernels as gk

    n1, n2 = 6, 3
    for start in (0, 2, 3, 5, 6):
        o, p = make_pair(n1)
        other_o = QEngineCPU(n2, rng=QrackRandom(31), rand_global_phase=False)
        other_p = QEngineCPU(n2, rng=QrackRandom(31), rand_global_phase=False)
        for eng in (o, p):
            eng.H(1)
            eng.CNOT(1, 4)
            eng.T(4)
        for eng in (other_o, other_p):
            eng.H(0)
            eng.CNOT(0, 2)
        o.Compose(other_o, start)
        p.Compose(other_p, start)
        assert_match(o, p)

    # HLO inspection: jit the ring body at an unaligned start (crosses
    # pages) with B replicated — no all-gather may appear
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("pages",))
    L = n1 - 3

    def f(a, b):
        return shb.compose_ring(a, b, 8, L, n1, n1, n2)

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(None, "pages"), P()),
        out_specs=P(None, "pages")))
    a = jnp.zeros((2, 1 << n1), dtype=jnp.float32)
    a = jax.device_put(a, jax.sharding.NamedSharding(mesh, P(None, "pages")))
    b = jnp.zeros((2, 1 << n2), dtype=jnp.float32)
    hlo = fn.lower(a, b).compile().as_text()
    assert "all-gather" not in hlo, "ring compose must not all-gather the ket"
    assert "collective-permute" in hlo, "ring compose should ppermute"


def test_pager_devices_env_selection():
    """QRACK_QPAGER_DEVICES (via the config tier) selects the mesh
    device subset (reference: src/qpager.cpp:170); unknown ids fail
    loudly."""
    import pytest

    from qrack_tpu import set_config

    try:
        set_config(pager_devices="2,3")
        p = QPager(4, rng=QrackRandom(9), rand_global_phase=False,
                   n_pages=2)
        assert [d.id for d in p.mesh.devices.flat] == [2, 3]
        set_config(pager_devices="99")
        with pytest.raises(ValueError, match="unknown device ids"):
            QPager(4, rng=QrackRandom(9), rand_global_phase=False,
                   n_pages=1)
    finally:
        set_config(pager_devices="")


@pytest.mark.parametrize("controlled", [False, True], ids=["bare", "controlled"])
@pytest.mark.parametrize("npg,gpos", [(2, 0), (4, 0), (4, 1), (8, 1), (8, 2)])
def test_pair_exchange_against_float64(npg, gpos, controlled):
    """``apply_global_2x2`` alone, every page picking its coefficients by
    its side of the pair: a random 2x2 on a paged target, bare and under
    an in-page and a page-level control, against numpy's float64 on the
    whole ket (PR 39: the exchange keeps no (a, b) halves)."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from qrack_tpu.ops import sharded as shb

    L = 5
    rng = np.random.default_rng(100 * npg + 10 * gpos + controlled)
    m = rng.standard_normal((2, 2)) + 1j * rng.standard_normal((2, 2))
    ket = rng.standard_normal(npg << L) + 1j * rng.standard_normal(npg << L)
    lmask = lval = gmask = gval = 0
    if controlled:
        lmask, lval = 0b10010, 0b10000
        gmask = (npg - 1) & ~(1 << gpos)
        gval = gmask & 0b101
    mesh = Mesh(np.array(jax.devices()[:npg]), ("pages",))
    fn = jax.jit(jax.shard_map(
        lambda local, mp: shb.apply_global_2x2(local, mp, npg, gpos, lmask,
                                               lval, gmask, gval),
        mesh=mesh, in_specs=(P(None, "pages"), P()),
        out_specs=P(None, "pages"), check_vma=False))
    planes = np.stack([ket.real, ket.imag]).astype(np.float32)
    mp = np.stack([m.real, m.imag]).astype(np.float32)
    out = np.asarray(fn(planes, mp))
    ket32 = planes[0].astype(np.float64) + 1j * planes[1]
    m32 = mp[0].astype(np.float64) + 1j * mp[1]
    want = ket32.copy()
    bit = 1 << (L + gpos)
    for i in range(npg << L):
        if i & bit or (i & lmask) != lval or ((i >> L) & gmask) != gval:
            continue
        a, b = ket32[i], ket32[i | bit]
        want[i] = m32[0, 0] * a + m32[0, 1] * b
        want[i | bit] = m32[1, 0] * a + m32[1, 1] * b
    assert np.any(want != ket32) and (not controlled or np.any(want == ket32))
    np.testing.assert_allclose(out[0] + 1j * out[1], want, atol=2e-6)
