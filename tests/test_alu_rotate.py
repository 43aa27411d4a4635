"""The ALU on ``QEngineTPU`` (PR 49): an add of a constant on a contiguous
register is one rotation of the ket (``engines/tpu.qrack_alu_rotate``,
module ``jit_qrack_alu_rotate``), a comparator's phase flip is a few
gates of the pending window, and upstream's Grover (``DEC``,
``ZeroPhaseFlip``, ``INC``, the diffusion, ``PhaseFlip``) runs on both.

The rotation is held bit for bit to ``alu_kernels.inc_src`` on numpy (the
index map the gather lowering computes); the queued flips and a whole
iteration to ``benchmarks/reference.py``, which shares nothing with the
engine; Grover to its closed form at every iteration.  Counters say
which lowering ran: ``alu.tpu.rotate``, ``.gather``, ``.phase_fn``,
``.phase_queued`` (docs/OBSERVABILITY.md).
"""

import math
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from qrack_tpu import QEngineCPU
from qrack_tpu import telemetry as tele
from qrack_tpu.engines import tpu as tpu_engine
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.models.algorithms import grover_iteration, grover_search
from qrack_tpu.ops import alu_kernels as alu
from qrack_tpu.utils.rng import QrackRandom

from helpers import rand_state
from test_engine_matrix import ALU_FACTORIES, ENGINE_FACTORIES, align_phase

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _engine(n, cls=QEngineTPU, seed=5):
    return cls(n, rng=QrackRandom(seed), rand_global_phase=False)


@pytest.fixture
def counters():
    """The program's counters over the test, telemetry on for it alone."""
    tele.reset()
    tele.enable()
    try:
        yield lambda: dict(tele.snapshot(include_events=False)["counters"])
    finally:
        tele.disable()
        tele.reset()


def _cases():
    """``(n, start, length, to_add)`` over widths 3-12: the whole register,
    a register above qubit 0, one that ends below the top, both, and
    registers under 2^7 amplitudes (which keep the gather)."""
    rng = np.random.default_rng(49)
    out = []
    for n in range(3, 13):
        shapes = {(0, n), (n // 2, n - n // 2), (0, max(1, n - 2)),
                  (1, max(1, n - 3)), (0, min(n, 3)), (min(2, n - 1), 1)}
        if n >= 9:
            shapes |= {(2, 5), (3, 6), (0, 7)}
        for start, length in sorted(shapes):
            for to_add in {1, (1 << length) - 1,
                           int(rng.integers(0, 1 << length))}:
                out.append((n, start, length, to_add))
    return out


CASES = _cases()


@pytest.mark.parametrize("n,start,length,to_add", CASES)
def test_inc_is_the_index_map_bit_for_bit(n, start, length, to_add, counters):
    psi = rand_state(n, 100 * n + start).astype(np.complex64)
    q = _engine(n)
    q.SetQuantumState(psi)
    q.INC(to_add, start, length)
    got = np.asarray(q.GetQuantumState())
    src = alu.inc_src(np, np.arange(1 << n), to_add & ((1 << length) - 1),
                      start, length)
    assert np.array_equal(got, psi.astype(got.dtype)[src])
    # which lowering ran: the rotation from 2^7 amplitudes a block up
    c = counters()
    moved = bool(to_add & ((1 << length) - 1))
    rotated = start + length >= tpu_engine.ROTATE_MIN_BITS
    assert c.get("alu.tpu.rotate", 0) == int(moved and rotated)
    assert c.get("alu.tpu.gather", 0) == int(moved and not rotated)
    # and DEC undoes it, bit for bit
    q.DEC(to_add, start, length)
    assert np.array_equal(np.asarray(q.GetQuantumState()),
                          psi.astype(got.dtype))


@pytest.mark.parametrize("n,block_bits,shift", [
    (8, 8, 1), (8, 8, 255), (9, 7, 77), (10, 8, 129), (12, 12, 4095),
    (12, 9, 300), (12, 7, 5)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rotate_program_against_numpy(n, block_bits, shift, dtype):
    """The program itself, whole ket and block by block, in both plane
    types the configurations and their control hold."""
    planes = np.arange(2 << n, dtype=np.float32).reshape(2, 1 << n) % 251
    want = np.roll(planes.reshape(2, -1, 1 << block_bits), shift, axis=2)
    for into in (None, jnp.zeros(planes.shape, dtype)):  # fresh, written over
        got = np.asarray(tpu_engine.qrack_alu_rotate(
            into, jnp.asarray(planes, dtype=dtype), np.int32(shift),
            block_bits), dtype=np.float32)
        assert np.array_equal(got, want.reshape(2, -1))


def test_a_rotation_writes_over_the_planes_the_last_one_read(counters):
    """The host runs ahead of the device, so a result allocated at every
    dispatch stands beside every ket still in flight (three at the
    oracle's ``INC``: 6 GiB at w28, my chip run, PR 49).  The planes a
    rotation read are the next one's destination, donated; a host read
    proves the device done and lets them go; pinned planes are never
    written over."""
    n = 9
    psi = rand_state(n, 1).astype(np.complex64)
    q = _engine(n)
    q.SetQuantumState(psi)
    first = q._state_raw
    q.DEC(300, 0, n)
    assert q._alu_spare is first and not first.is_deleted()
    second = q._state_raw
    q.ZeroPhaseFlip(0, n)  # a window between the two, as in the oracle
    q.INC(300, 0, n)
    assert first.is_deleted()  # donated: the result took its buffer
    assert q._alu_spare is not None and q._alu_spare is not second
    got = q.GetAmplitude(5)
    assert q._alu_spare is None  # the read let it go
    want = psi.copy()
    want[300] = -want[300]
    assert got == want[5]
    np.testing.assert_array_equal(np.asarray(q.GetQuantumState()), want)
    assert counters()["alu.tpu.rotate"] == 2
    # planes a cache entry shares are read, never a destination
    shared = q._state
    tpu_engine.pin_planes(shared)
    try:
        q.INC(1, 0, n)
        assert q._alu_spare is None
        q.INC(1, 0, n)
        assert not shared.is_deleted()
    finally:
        tpu_engine.unpin_planes(shared)


@pytest.mark.parametrize("n,start,length,to_add", [
    (9, 0, 8, 200), (9, 0, 8, 300), (10, 2, 6, 77), (12, 3, 8, 511)])
def test_a_carry_on_top_of_its_register_rotates(n, start, length, to_add,
                                                counters):
    """``INCDECC`` with the carry at ``start + length`` is the add on the
    register one bit longer; any other carry keeps the gather."""
    psi = rand_state(n, 7 * n + to_add)
    q, o = _engine(n), _engine(n, QEngineCPU)
    for eng in (q, o):
        eng.SetQuantumState(psi)
        eng.INCDECC(to_add, start, length, start + length)
    np.testing.assert_allclose(q.GetQuantumState(), o.GetQuantumState(),
                               atol=1e-6)
    assert counters().get("alu.tpu.rotate") == 1
    for eng in (q, o):
        eng.INCDECC(to_add, 0, 3, n - 1)
    np.testing.assert_allclose(q.GetQuantumState(), o.GetQuantumState(),
                               atol=1e-6)
    assert counters().get("alu.tpu.gather") == 1


def test_the_rest_of_the_alu_still_gathers(counters):
    n = 9
    psi = rand_state(n, 3)
    q, o = _engine(n), _engine(n, QEngineCPU)
    for eng in (q, o):
        eng.SetQuantumState(psi)
        eng.CINC(5, 0, 7, (8,))
        eng.Hash(0, 2, [2, 0, 3, 1])
        eng.ROL(2, 1, 7)
        eng.MUL(3, 0, 4, 4)
        eng.ZMask(0b1011)
    np.testing.assert_allclose(q.GetQuantumState(), o.GetQuantumState(),
                               atol=1e-6)
    c = counters()
    assert c.get("alu.tpu.rotate", 0) == 0
    assert c["alu.tpu.gather"] == 3 and c["alu.tpu.out_of_place"] == 1
    assert c["alu.tpu.phase_fn"] == 1 and "alu.tpu.phase_queued" not in c
    spans = tele.snapshot(include_events=False)["spans"]
    assert spans["engine.alu"]["count"] == 5


@pytest.mark.parametrize("n,start,length,greater", [
    (6, 0, 6, 1), (6, 0, 6, 37), (7, 2, 4, 11), (7, 1, 5, 32), (5, 0, 3, 0),
    (8, 3, 5, 31)])
def test_comparator_flips_queue_as_gates(n, start, length, greater, counters,
                                         monkeypatch):
    """``PhaseFlipIfLess``, its controlled form, ``ZeroPhaseFlip`` and
    ``PhaseFlip`` against the oracle's kernels: queued where the engine
    has a fuser (no ``_k_phase_fn``), the factor arrays where it has not."""
    psi = rand_state(n, 11 * n + greater)
    flag = 0 if start else n - 1

    def run(eng):
        eng.SetQuantumState(psi)
        eng.H(0)
        eng.PhaseFlipIfLess(greater, start, length)
        if flag < start or flag >= start + length:
            eng.CPhaseFlipIfLess(greater, start, length, flag)
        eng.ZeroPhaseFlip(start, length)
        eng.PhaseFlip()
        return np.asarray(eng.GetQuantumState())

    want = run(_engine(n, QEngineCPU))
    np.testing.assert_allclose(run(_engine(n)), want, atol=1e-6)
    c = counters()
    assert c.get("alu.tpu.phase_fn", 0) == 0 and c["alu.tpu.phase_queued"] >= 3
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "1")
    eager = _engine(n)
    assert eager._fuser is None
    np.testing.assert_allclose(run(eager), want, atol=1e-6)
    assert counters().get("alu.tpu.phase_fn", 0) >= 2


@pytest.mark.parametrize("name", sorted(ENGINE_FACTORIES))
def test_zero_phase_flip_on_every_stack(name):
    """Upstream's ``ZeroPhaseFlip(start, length)`` is ``PhaseFlipIfLess(1,
    start, length)``: on every stack of the engine matrix, against the
    oracle's and (where the stack has the comparator) its own."""
    n = 6
    psi = rand_state(n, 23)
    for start, length in ((0, n), (1, 3), (2, 4)):
        o = QEngineCPU(n, rng=QrackRandom(2), rand_global_phase=False)
        o.SetQuantumState(psi)
        o.PhaseFlipIfLess(1, start, length)
        want = np.asarray(o.GetQuantumState())
        assert np.sum(np.abs(want - psi) > 1e-9) == 1 << (n - length)
        calls = ["ZeroPhaseFlip"] + (
            ["PhaseFlipIfLess"] if name in ALU_FACTORIES else [])
        for call in calls:
            q = ENGINE_FACTORIES[name](n, rng=QrackRandom(2),
                                       rand_global_phase=False)
            q.SetQuantumState(psi)
            if call == "ZeroPhaseFlip":
                q.ZeroPhaseFlip(start, length)
            else:
                q.PhaseFlipIfLess(1, start, length)
            got = align_phase(np.asarray(q.GetQuantumState()), want)
            np.testing.assert_allclose(got, want, atol=2e-5,
                                       err_msg=f"{name} {call}")


def _reference_iteration(n, target, state):
    """One iteration by ``benchmarks/reference.py`` on the family's gate
    list: controlled 2x2s in complex128, nothing of the engine's ALU."""
    before, path = set(sys.modules), list(sys.path)
    sys.path[:0] = [BENCH]
    try:
        import harness
        import reference

        family = harness.load_module("circuits", "grover")
        return reference.evolve(state, n, family.gates(n, {"target": target}))
    finally:
        sys.path[:] = path
        for name in set(sys.modules) - before:
            if name in ("harness", "reference") or name.startswith("bench_"):
                del sys.modules[name]


@pytest.mark.parametrize("kernel", ["off", "on"], ids=["xla", "kernel"])
@pytest.mark.parametrize("target", [0, 1, 2748, 4095])
def test_one_iteration_at_w12_is_the_reference(target, kernel, counters,
                                               monkeypatch):
    """One Grover iteration through the engine's own calls: two rotations,
    no gather, no factor arrays, three flips queued, and the whole ket
    that of the plain reference; float32 through the XLA chain and through
    the interpreted window kernel."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", kernel)
    n = 12
    q = _engine(n)
    for i in range(n):
        q.H(i)
    q.GetAmplitude(0)
    before = counters()
    grover_iteration(q, target, n)
    got = np.asarray(q.GetQuantumState())
    c = {k: v - before.get(k, 0) for k, v in counters().items()}
    moved = int(target != 0)  # an add of 0 is no call
    assert c.get("alu.tpu.rotate", 0) == 2 * moved
    assert c.get("alu.tpu.gather", 0) == 0 == c.get("alu.tpu.phase_fn", 0)
    assert c["alu.tpu.phase_queued"] == 3
    if kernel == "on":
        assert c.get("fuse.kernel.windows", 0) >= 1
    uniform = np.full(1 << n, 2.0 ** (-n / 2), dtype=np.complex128)
    want = _reference_iteration(n, target, uniform)
    assert np.max(np.abs(got - want)) < 1e-6 * 2.0 ** (-n / 2) * 64
    np.testing.assert_allclose(got[target], math.sin(3 * math.asin(
        2.0 ** (-n / 2))), rtol=1e-5)


@pytest.mark.parametrize("target", [3, 700])
def test_grover_at_w10_follows_its_closed_form_to_the_optimum(target):
    n = 10
    theta = math.asin(2.0 ** (-n / 2))
    q = _engine(n)
    for i in range(n):
        q.H(i)
    iterations = int(math.floor(math.pi / 4 * math.sqrt(1 << n)))
    other = (target + 5) % (1 << n)
    for k in range(1, iterations + 1):
        grover_iteration(q, target, n)
        angle = (2 * k + 1) * theta
        assert q.GetAmplitude(target) == pytest.approx(math.sin(angle),
                                                       abs=2e-6)
        assert q.GetAmplitude(other) == pytest.approx(
            math.cos(angle) / math.sqrt((1 << n) - 1), abs=2e-6)
    assert q.ProbAll(target) > 0.999
    assert q.MAll() == target
    # and the packaged search is the same loop
    assert grover_search(_engine(n), target) == target
