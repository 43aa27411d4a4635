"""A register measured as upstream's dense engine measures it (PR 53):
one reduction to its ``2^length`` probabilities (``_k_prob_reg_all``),
one draw on the host, one collapse with the register's mask
(``engines/qengine.py ForceMReg``), on ``QEngineCPU`` and ``QEngineTPU``
alike.  The pager, the compressed engine and the stacks above keep a
qubit at a time."""

import numpy as np
import pytest

import jax.numpy as jnp

from qrack_tpu import create_quantum_interface
from qrack_tpu import telemetry as tele
from qrack_tpu.engines import tpu as tpu_engine
from qrack_tpu.engines.cpu import QEngineCPU
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.interface.base import QInterfaceBase
from qrack_tpu.ops import register_kernels as rk
from qrack_tpu.utils.rng import QrackRandom

from helpers import rand_state

ENGINES = {"cpu": QEngineCPU, "tpu": QEngineTPU}
# (width, start, length): start > 0, length 1 to 8, registers at the top
REGISTERS = [(6, 0, 1), (6, 2, 3), (8, 0, 8), (9, 1, 8), (10, 3, 4),
             (10, 5, 5), (7, 6, 1), (12, 0, 6), (12, 4, 7)]


def _engine(kind, width, seed=5, state=None):
    q = ENGINES[kind](width, rand_global_phase=False, rng=QrackRandom(seed))
    q.SetQuantumState(rand_state(width, width + seed) if state is None
                      else state)
    return q


def _binned(probs, width, start, length):
    out = np.zeros(1 << length)
    idx = np.arange(1 << width)
    np.add.at(out, (idx >> start) & ((1 << length) - 1), probs)
    return out


@pytest.mark.parametrize("kind", sorted(ENGINES))
@pytest.mark.parametrize("reg", REGISTERS, ids=lambda r: "-".join(map(str, r)))
def test_prob_reg_all_is_getprobs_binned(kind, reg):
    width, start, length = reg
    q = _engine(kind, width)
    want = _binned(q.GetProbs(), width, start, length)
    assert np.allclose(q._k_prob_reg_all(start, length), want, atol=2e-7)
    assert np.allclose(q.ProbBitsAll(list(range(start, start + length))),
                       want, atol=2e-7)
    assert np.allclose(q.ProbMaskAll(((1 << length) - 1) << start), want,
                       atol=2e-7)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_a_mask_that_is_no_register_keeps_the_host_binning(kind):
    q = _engine(kind, 8)
    probs = q.GetProbs()
    idx = np.arange(256)
    want = np.zeros(4)
    np.add.at(want, ((idx >> 1) & 1) | (((idx >> 5) & 1) << 1), probs)
    assert np.allclose(q.ProbMaskAll(0b100010), want, atol=2e-7)


@pytest.mark.parametrize("kind", sorted(ENGINES))
@pytest.mark.parametrize("reg", [(8, 2, 4), (9, 0, 5), (10, 4, 6)],
                         ids=lambda r: "-".join(map(str, r)))
def test_forced_value_leaves_the_ket_of_the_loop(kind, reg):
    """``ForceMReg`` forced to a value against the qubit-at-a-time loop
    it replaced (``QInterfaceBase.ForceMReg``) on the same ket."""
    width, start, length = reg
    value = 0b101101 & ((1 << length) - 1)
    a, b = _engine(kind, width), _engine(kind, width)
    assert QInterfaceBase.ForceMReg(a, start, length, value) == value
    assert b.ForceMReg(start, length, value) == value
    assert np.allclose(b.GetQuantumState(), a.GetQuantumState(), atol=3e-7)
    assert abs(np.linalg.norm(b.GetQuantumState()) - 1.0) < 1e-6
    # not applied: the value, and the ket as it was
    c = _engine(kind, width)
    before = c.GetQuantumState()
    assert c.ForceMReg(start, length, value, do_apply=False) == value
    assert np.array_equal(c.GetQuantumState(), before)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_forcing_an_impossible_value_raises(kind):
    state = np.zeros(64, dtype=np.complex128)
    state[0b000100] = 1.0
    q = _engine(kind, 6, state=state)
    with pytest.raises(RuntimeError):
        q.ForceMReg(1, 3, 0b001)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_mreg_draws_the_kets_own_distribution(kind):
    """2000 seeded draws of ``MReg(2, 4)`` at w8 (seed 1234): every count
    within 4.5 standard deviations of its binomial mean, and a chi-square
    over the 16 values under 45 (15 degrees of freedom: 45 is past the
    99.99th percentile)."""
    width, start, length, draws = 8, 2, 4, 2000
    state = rand_state(width, 99)
    probs = _binned(np.abs(state) ** 2, width, start, length)
    q = ENGINES[kind](width, rand_global_phase=False, rng=QrackRandom(1234))
    counts = np.zeros(1 << length)
    for _ in range(draws):
        q.SetQuantumState(state)
        counts[q.MReg(start, length)] += 1
    sigma = np.sqrt(draws * probs * (1 - probs))
    assert np.all(np.abs(counts - draws * probs) <= 4.5 * sigma)
    assert np.sum((counts - draws * probs) ** 2 / (draws * probs)) < 45


def test_cpu_and_tpu_draw_alike():
    """One seed, one ket: the two dense engines measure the same value
    and leave the same ket."""
    for seed in range(5):
        a, b = _engine("cpu", 9, seed=seed), _engine("tpu", 9, seed=seed)
        assert a.MReg(1, 6) == b.MReg(1, 6)
        assert np.allclose(a.GetQuantumState(), b.GetQuantumState(),
                           atol=3e-7)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_a_certain_register_draws_nothing(kind):
    """A deterministic outcome leaves the random stream where it was, as
    ``ForceM``'s does."""
    state = np.zeros(256, dtype=np.complex128)
    state[0b01011010] = 1.0
    q = _engine(kind, 8, seed=42, state=state)
    fresh = QrackRandom(42)
    assert q.MReg(1, 5) == 0b01101
    assert q.Rand() == fresh.rand()
    assert np.allclose(q.GetQuantumState(), state, atol=1e-7)


def test_one_mreg_is_two_passes():
    q = _engine("tpu", 8)
    loop = _engine("tpu", 8)
    tele.enable()
    try:
        tele.reset()
        q.MReg(0, 6)
        counters = tele.snapshot(include_events=False)["counters"]
        tele.reset()
        QInterfaceBase.ForceMReg(loop, 0, 6, 0, do_force=False)
        looped = tele.snapshot(include_events=False)["counters"]
    finally:
        tele.disable()
    assert counters["measure.tpu.reg"] == 1
    assert counters["measure.tpu.passes"] == 2
    assert "measure.tpu.bit" not in counters
    assert looped["measure.tpu.bit"] == 6 and looped["measure.tpu.passes"] == 12
    # the ledger: one read for the reduction, a read and a write behind it
    ket = 2 * 4 << 8
    assert counters["roofline.tpu.measure.planned_bytes"] == 3 * ket


@pytest.mark.parametrize("stack", ["pager", "turboquant"])
def test_the_other_engines_keep_a_qubit_at_a_time(stack):
    if stack == "pager":
        q = create_quantum_interface("pager", 8, n_pages=2,
                                     rng=QrackRandom(3),
                                     rand_global_phase=False)
    else:
        from qrack_tpu.engines.turboquant import QEngineTurboQuant

        q = QEngineTurboQuant(8, rng=QrackRandom(3), rand_global_phase=False)
    assert not q._reduces_register(0, 4)
    for i in range(4):
        q.H(i)
    y = q.MReg(0, 4)
    probs = q.ProbBitsAll([0, 1, 2, 3])
    assert probs[y] == pytest.approx(1.0, abs=1e-2)


# (n, start, length): rows of 2^10 to 2^18 amplitudes, blocks of part of
# a row and of many rows, bits under the register summed behind the kernel
KERNEL_CASES = [(18, 0, 10), (18, 0, 14), (20, 0, 17), (18, 3, 8),
                (16, 2, 14), (18, 0, 18)]


@pytest.mark.parametrize("reg", KERNEL_CASES,
                         ids=lambda r: "-".join(map(str, r)))
def test_kernel_body_is_the_view_body(reg):
    n, start, length = reg
    assert rk.prob_kernel_fits(*reg)
    planes = jnp.asarray(np.random.default_rng(n).normal(
        size=(2, 1 << n)).astype(np.float32))
    view = np.asarray(tpu_engine.qrack_prob_reg(planes, n, start, length, None))
    kernel = np.asarray(tpu_engine.qrack_prob_reg(planes, n, start, length, True))
    assert np.max(np.abs(kernel - view) / view) < 2e-6


def test_kernel_takes_the_cells_register_and_no_narrow_row():
    assert rk.prob_kernel_fits(28, 0, 14) and rk.prob_kernel_fits(30, 0, 15)
    assert not rk.prob_kernel_fits(12, 0, 6)
    assert not rk.prob_kernel_fits(28, 14, 14)


@pytest.mark.parametrize("mask,val", [(0b111100, 0b010100), (1, 1),
                                      (0b1000000000, 0)])
def test_collapse_keeps_scales_and_zeroes(mask, val):
    """The collapse as one fusion (the plane's number on the index's sign
    bit) against numpy, bit for bit."""
    planes = np.random.default_rng(7).normal(size=(2, 1 << 10)).astype(
        np.float32)
    got = tpu_engine.qrack_collapse(jnp.asarray(planes), np.int32(mask),
                                    np.int32(val), np.float32(0.37))
    keep = (np.arange(1 << 10) & mask) == val
    scale = np.float32(1.0) / np.sqrt(np.float32(0.37))
    assert np.array_equal(np.asarray(got),
                          np.where(keep, planes * scale, np.float32(0.0)))
