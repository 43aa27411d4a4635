"""The two-target op of the fused window (ops/fusion.py kind ``u4``):
``QEngineTPU``'s uncontrolled two-qubit gates — Swap, ISwap, IISwap,
SqrtSwap, ISqrtSwap, FSim, Apply4x4 — enter the pending window as one
op each instead of flushing it for an eager whole-ket program.

Parity is against a plain gate-by-gate numpy reference written here,
for the kernel's three placements (both targets in the tile, one above
it on the pair grid, both above on the quad grid) at small ``block_pow``
under the Pallas interpreter, on the flat and on the dense tile, and on
the XLA chain; then the fuser's merge rules, the structure's
independence of the values, the telemetry contract of a random circuit,
and the random-circuit family against ``models/rcs`` on ``QEngineCPU``.
"""

import numpy as np
import pytest

from qrack_tpu import QEngineCPU, create_quantum_interface
from qrack_tpu import matrices as mat
from qrack_tpu import telemetry as tele
from qrack_tpu.models import algorithms, rcs
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk
from qrack_tpu.utils.rng import QrackRandom

from test_pallas_window import DONATE, ORBIT_SHAPES, led_segment_against_numpy


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("QRACK_TPU_FUSE_KERNEL", raising=False)
    yield
    tele.disable()
    tele.reset()


@pytest.fixture
def kernel_on(monkeypatch):
    """The window kernel under the interpreter, at a small tile."""
    def at(block_pow):
        monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
        monkeypatch.setattr(pk, "DEFAULT_BLOCK_POW", block_pow)
    return at


def _engine(n, stack="tpu"):
    return create_quantum_interface(stack, n, rng=QrackRandom(3),
                                    rand_global_phase=False)


def _su(rng, k):
    q, _ = np.linalg.qr(rng.normal(size=(k, k)) + 1j * rng.normal(size=(k, k)))
    return q


# -- the plain reference: one gate at a time on a complex128 ket ------------

def _ref_1q(state, n, m, t):
    v = state.reshape(1 << (n - t - 1), 2, 1 << t)
    return np.einsum("ab,hbl->hal", m, v).reshape(-1)


def _ref_2q(state, n, m4, q1, q2):
    """``m4`` indexed ``(bit q2 << 1) | bit q1``, any two qubits."""
    idx = np.arange(1 << n)
    row = (((idx >> q2) & 1) << 1) | ((idx >> q1) & 1)
    base = idx & ~((1 << q1) | (1 << q2))
    out = np.zeros_like(state)
    for c in range(4):
        src = base | (((c >> 1) & 1) << q2) | ((c & 1) << q1)
        out += m4[row, c] * state[src]
    return out


_FSIM = (0.37, 1.1)


def _fsim4(theta, phi):
    c, s = np.cos(theta), np.sin(theta)
    return np.array([[1, 0, 0, 0], [0, c, -1j * s, 0], [0, -1j * s, c, 0],
                     [0, 0, 0, np.exp(-1j * phi)]])


_SQRT_SWAP = np.array([[1, 0, 0, 0], [0, 0.5 + 0.5j, 0.5 - 0.5j, 0],
                       [0, 0.5 - 0.5j, 0.5 + 0.5j, 0], [0, 0, 0, 1]])
# gate name -> (the call's arguments before the qubits, its 4x4)
_NAMED = {
    "Swap": ((), mat.SWAP4), "ISwap": ((), mat.ISWAP4),
    "IISwap": ((), mat.IISWAP4), "SqrtSwap": ((), _SQRT_SWAP),
    "ISqrtSwap": ((), _SQRT_SWAP.conj().T), "FSim": (_FSIM, _fsim4(*_FSIM)),
}


def _spread(q, ref, n, rng):
    """A ket with no symmetry between qubits, on both sides."""
    for t in range(n):
        m = _su(rng, 2)
        q.Mtrx(m, t)
        ref = _ref_1q(ref, n, m, t)
    q.GetAmplitude(0)  # its own window: the gates under test start clean
    return ref


def _basis(n):
    state = np.zeros(1 << n, dtype=np.complex128)
    state[0] = 1.0
    return state


# block_pow, width, then (q1, q2) in the tile / on the pair grid / on the
# quad grid; both orders of the two qubits.  4: the flat tile; 10: the
# dense (8, 128) tile, lane and sublane partners
_PLACEMENTS = [
    pytest.param(4, 8, (1, 3), id="flat-intile"),
    pytest.param(4, 8, (3, 1), id="flat-intile-swapped"),
    pytest.param(4, 8, (2, 6), id="flat-pair"),
    pytest.param(4, 8, (7, 0), id="flat-pair-swapped"),
    pytest.param(4, 8, (4, 6), id="flat-quad"),
    pytest.param(4, 8, (7, 5), id="flat-quad-swapped"),
    pytest.param(10, 13, (3, 8), id="dense-intile-lane-sublane"),
    pytest.param(10, 13, (7, 9), id="dense-intile-sublanes"),
    pytest.param(10, 13, (5, 11), id="dense-pair"),
    pytest.param(10, 13, (10, 12), id="dense-quad"),
]


@pytest.mark.parametrize("bp,n,pair", _PLACEMENTS)
def test_random_su4_in_every_placement(kernel_on, bp, n, pair):
    kernel_on(bp)
    rng = np.random.default_rng(bp * 100 + pair[0] * 10 + pair[1])
    q = _engine(n)
    ref = _spread(q, _basis(n), n, rng)
    m4 = _su(rng, 4)
    tele.enable()
    # a 2x2 on another qubit beside it: a window of two ops, so the kernel
    other = next(t for t in range(n) if t not in pair)
    m2 = _su(rng, 2)
    q.Apply4x4(m4, *pair)
    q.Mtrx(m2, other)
    got = q.GetQuantumState()
    ref = _ref_1q(_ref_2q(ref, n, m4, *pair), n, m2, other)
    assert np.max(np.abs(got - ref)) < 5e-6
    c = tele.snapshot(include_events=False)["counters"]
    lo, hi = sorted(pair)
    placement = "intile" if hi < bp else "pair" if lo < bp else "quad"
    assert c["fuse.kernel.twoq.ops"] == 1
    assert c[f"fuse.kernel.twoq.sweeps.{placement}"] == 1
    assert c["fuse.kernel.sweeps"] == 1 + (placement != "intile" and other >= bp)


@pytest.mark.parametrize("name", sorted(_NAMED))
@pytest.mark.parametrize("pair", [(1, 2), (2, 6), (5, 7)],
                         ids=["intile", "pair", "quad"])
def test_named_gates_in_every_placement(kernel_on, name, pair):
    kernel_on(4)
    n = 8
    rng = np.random.default_rng(11)
    q = _engine(n)
    ref = _spread(q, _basis(n), n, rng)
    args, m4 = _NAMED[name]
    tele.enable()
    getattr(q, name)(*args, *pair)
    q.H(0)
    ref = _ref_1q(_ref_2q(ref, n, m4, *pair), n, mat.H2, 0)
    assert np.max(np.abs(q.GetQuantumState() - ref)) < 5e-6
    c = tele.snapshot(include_events=False)["counters"]
    assert c["fuse.kernel.twoq.ops"] == 1
    assert not any(k.startswith(("gate.tpu.swap", "gate.tpu.4x4")) for k in c)


def _mixed_stream(rng, n, count):
    """Two-qubit gates among controlled and plain 2x2s, as calls and as
    the reference's steps."""
    calls = []
    for _ in range(count):
        a, b = (int(x) for x in rng.choice(n, 2, replace=False))
        kind = int(rng.integers(0, 7))
        if kind == 0:
            m4 = _su(rng, 4)
            calls.append(("Apply4x4", (m4, a, b), ("2q", m4, a, b)))
        elif kind == 1:
            name = sorted(_NAMED)[int(rng.integers(0, len(_NAMED)))]
            args, m4 = _NAMED[name]
            calls.append((name, (*args, a, b), ("2q", m4, a, b)))
        elif kind == 2:
            m = _su(rng, 2)
            calls.append(("Mtrx", (m, a), ("1q", m, a)))
        elif kind == 3:
            cx = np.eye(4)[[0, 3, 2, 1]]  # control a (low bit), target b
            calls.append(("CNOT", (a, b), ("2q", cx, a, b)))
        elif kind == 4:
            cz = np.diag([1, 1, 1, -1])
            calls.append(("CZ", (a, b), ("2q", cz, a, b)))
        elif kind == 5:
            calls.append(("T", (a,), ("1q", mat.T2, a)))
        else:
            calls.append(("X", (a,), ("1q", mat.X2, a)))
    return calls


def _reference_of(calls, n):
    ref = _basis(n)
    for _, _, step in calls:
        ref = (_ref_2q(ref, n, *step[1:]) if step[0] == "2q"
               else _ref_1q(ref, n, *step[1:]))
    return ref


@pytest.mark.parametrize("lowering", ["kernel-flat", "kernel-dense", "xla"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_windows_that_mix_them_with_controlled_2x2s(kernel_on, lowering, seed):
    """Order is kept through every merge: two-qubit gates next to gates
    on their own qubits, before and behind them."""
    n = {"kernel-flat": 8, "kernel-dense": 12, "xla": 8}[lowering]
    if lowering != "xla":  # off the TPU, mode auto takes the XLA chain
        kernel_on(4 if lowering == "kernel-flat" else 10)
    calls = _mixed_stream(np.random.default_rng(seed), n, 60)
    q = _engine(n)
    for name, args, _ in calls:
        getattr(q, name)(*args)
    got = q.GetQuantumState()
    assert np.max(np.abs(got - _reference_of(calls, n))) < 2e-5


def test_a_single_qubit_gate_composes_into_its_coupler():
    """Roots before and behind a coupler ride in its 4x4: one op."""
    q = _engine(6)
    q.Mtrx(mat.SQRTX2, 2)
    q.Mtrx(mat.SQRTY2, 3)
    q.Mtrx(mat.H2, 0)
    q.ISwap(2, 3)            # takes the two roots behind it
    q.Mtrx(mat.SQRTW2, 3)    # composes onto the coupler
    q.CZ(2, 3)               # as does a controlled gate inside the pair
    gates = q._fuser.gates
    assert [type(g).__name__ for g in gates] == ["QCircuitGate",
                                                 "TwoQubitGate"]
    want = (np.diag([1, 1, 1, -1]) @ np.kron(mat.SQRTW2, mat.I2) @ mat.ISWAP4
            @ np.kron(mat.SQRTY2, mat.SQRTX2))
    assert np.allclose(gates[1].m, want)
    ref = _basis(6)
    for m, t in ((mat.SQRTX2, 2), (mat.SQRTY2, 3), (mat.H2, 0)):
        ref = _ref_1q(ref, 6, m, t)
    ref = _ref_2q(ref, 6, mat.ISWAP4, 2, 3)
    ref = _ref_1q(ref, 6, mat.SQRTW2, 3)
    ref = _ref_2q(ref, 6, np.diag([1, 1, 1, -1]), 2, 3)
    assert np.max(np.abs(q.GetQuantumState() - ref)) < 2e-6


def test_a_gate_between_keeps_its_place():
    """A root is not taken past a gate that acts on its qubit, and a
    coupler does not merge past one that overlaps it."""
    q = _engine(6)
    q.Mtrx(mat.SQRTX2, 2)
    q.CNOT(2, 4)             # the last to touch qubit 2: the root stays
    q.ISwap(2, 3)
    q.ISwap(3, 4)            # overlaps: the next coupler on (2, 3) stays apart
    q.ISwap(2, 3)
    assert [g.qubits() for g in q._fuser.gates] == [
        (2,), (4, 2), (2, 3), (3, 4), (2, 3)]
    q.Swap(0, 1)
    q.Swap(1, 0)             # merges to the identity and leaves the window
    assert len(q._fuser.gates) == 5


def test_a_full_window_is_flushed_whole_before_a_coupler_takes_from_it():
    """Exactly once: the coupler that finds the window full takes no
    root out of the window it flushes."""
    q = _engine(8)
    q._fuser.window = 4
    flushed = []
    real = q._fuse_flush
    q._fuse_flush = lambda gates: (flushed.append(
        [g.qubits() for g in gates]), real(gates))[1]
    for t in (0, 1, 2, 3):
        q.H(t)
    q.CNOT(4, 5)             # grows a full window: flushes it, stays
    assert flushed == [[(0,), (1,), (2,), (3,)]]
    q.H(6)
    q.H(7)
    q.CZ(0, 1)               # the window is full again
    q.ISwap(6, 7)            # takes two roots: 4 - 2 + 1, no flush
    assert len(flushed) == 1
    assert [g.qubits() for g in q._fuser.gates] == [(5, 4), (1, 0), (6, 7)]


def _rcs(q, n, cycles, seed):
    """Arute et al.'s rule: never the same root twice running."""
    rng = np.random.default_rng(seed)
    roots = (mat.SQRTX2, mat.SQRTY2, mat.SQRTW2)
    last = [-1] * n
    for c in range(cycles):
        for t in range(n):
            g = int(rng.integers(0, 3))
            while g == last[t]:
                g = int(rng.integers(0, 3))
            last[t] = g
            q.Mtrx(roots[g], t)
        for t in range(c & 1, n - 1, 2):
            q.ISwap(t, t + 1)
    return q.GetAmplitude(0)


def test_two_draws_of_one_shape_build_one_set_of_programs(kernel_on):
    kernel_on(4)
    q = _engine(8)
    tele.enable()
    _rcs(q, 8, 4, seed=1)
    built = len(fu.PROGRAMS)
    misses = tele.snapshot(include_events=False)["counters"].get(
        "compile.fuse.window.miss", 0)
    q.SetPermutation(0)
    _rcs(q, 8, 4, seed=2)
    assert len(fu.PROGRAMS) == built
    assert tele.snapshot(include_events=False)["counters"].get(
        "compile.fuse.window.miss", 0) == misses


def test_a_random_circuit_forces_no_flush_and_no_eager_program(kernel_on):
    kernel_on(4)
    q = _engine(8)
    tele.enable()
    _rcs(q, 8, 6, seed=5)
    c = tele.snapshot(include_events=False)["counters"]
    flushes = {k: v for k, v in c.items() if k.startswith("fuse.tpu.flush.")}
    assert set(flushes) <= {"fuse.tpu.flush.read", "fuse.tpu.flush.window_full"}
    assert flushes["fuse.tpu.flush.read"] == 1  # the one read, no coupler
    assert not any(k.startswith(("gate.tpu.swap", "gate.tpu.4x4")) for k in c)
    assert c["fuse.kernel.twoq.ops"] > 0
    placed = sum(c.get(f"fuse.kernel.twoq.sweeps.{p}", 0)
                 for p in ("intile", "pair", "quad"))
    assert 0 < placed <= c["fuse.kernel.sweeps"]


def test_a_window_of_one_coupler_keeps_the_eager_program():
    q = _engine(6)
    tele.enable()
    q.FSim(0.3, 0.2, 1, 4)
    ref = _ref_2q(_basis(6), 6, _fsim4(0.3, 0.2), 1, 4)
    assert np.max(np.abs(q.GetQuantumState() - ref)) < 1e-6
    c = tele.snapshot(include_events=False)["counters"]
    assert c["gate.tpu.4x4.w6"] == 1 and "fuse.kernel.windows" not in c


def test_without_a_window_the_eager_programs_run(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "1")
    q = _engine(6)
    assert q._fuser is None
    tele.enable()
    q.H(1)
    q.Swap(1, 4)
    q.ISwap(0, 1)
    ref = _ref_2q(_ref_2q(_ref_1q(_basis(6), 6, mat.H2, 1), 6, mat.SWAP4, 1, 4),
                  6, mat.ISWAP4, 0, 1)
    assert np.max(np.abs(q.GetQuantumState() - ref)) < 1e-6
    c = tele.snapshot(include_events=False)["counters"]
    assert c["gate.tpu.swap.w6"] == 1 and c["gate.tpu.4x4.w6"] == 1


def test_the_compressed_engine_keeps_its_routes():
    """No funnel into a window whose bodies hold no two-target op."""
    from qrack_tpu.engines.qengine import QEngine
    from qrack_tpu.engines.turboquant import QEngineTurboQuant

    for name in ("Swap", "ISwap", "IISwap", "Apply4x4"):
        assert getattr(QEngineTurboQuant, name) is getattr(QEngine, name)


@pytest.mark.parametrize("n", [10, 12, 14])
def test_rcs_family_against_the_cpu_engine(kernel_on, n):
    """``models/algorithms.random_circuit_sampling`` through the window
    kernel against ``models/rcs.reference_rcs_state`` on ``QEngineCPU``:
    one plan (``rcs.rcs_layers``), two routes."""
    kernel_on(10)
    q = _engine(n)
    algorithms.random_circuit_sampling(q, 6, QrackRandom(21))
    want = rcs.reference_rcs_state(
        n, 6, 21, QEngineCPU(n, rng=QrackRandom(3), rand_global_phase=False))
    assert np.max(np.abs(q.GetQuantumState() - want)) < 5e-6


# -- a u4 that leads its segment, on the orbit grid (PR 37) ------------------

def _led_u4_cases():
    cases = []
    for n, bp in ORBIT_SHAPES:
        pairs = {(5, bp), (2, n - 1)}                    # the pair grid
        if n - bp >= 2:                                  # four tiles
            pairs |= {(bp, bp + 1), (bp, n - 1), (n - 2, n - 1)}
        for lo, hi in sorted(pairs):
            for behind in (False, True):
                cases.append(pytest.param(
                    n, bp, lo, hi, behind,
                    id=f"w{n}-bp{bp}-{'pair' if lo < bp else 'quad'}{lo}.{hi}"
                       + ("-riders" if behind else "-bare")))
    return cases


@DONATE
@pytest.mark.parametrize("n,bp,lo,hi,behind", _led_u4_cases())
def test_led_u4_segment_is_numpy_bit_for_bit(n, bp, lo, hi, behind, donate):
    """The two-target lead on the pair grid and on four tiles, alone and
    with in-tile ops that read the tile id behind it: the float32 bits of
    ``tile_quad_mix``'s order in numpy (tests/test_pallas_window.py)."""
    lead = fu.FusedOp("u4", (lo, hi), 0, 0,
                      _su(np.random.default_rng(lo * 16 + hi), 4))
    got, want = led_segment_against_numpy(n, bp, lead, behind, seed=lo + hi,
                                          donate=donate)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))
