"""``QEngineTPU.SetPermutation`` is one program that writes one ket
(``jit_qrack_fill``, PR 43): over the ket the engine owns, donated, or a
fresh one where it owns none; the old ket is never alive beside the new.
And the window of one op, which takes the kernel wherever windows do.

On the CPU and at small widths: values against ``QEngineCPU`` and the
plain numpy simulator, and what the process holds by
``jax.live_arrays()``.  What the chip's compiler makes of the fill and of
the lone op at w30 is held in tests/test_chip_compile.py."""

import gc
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from qrack_tpu import create_quantum_interface, telemetry as tele
from qrack_tpu.engines import tpu
from qrack_tpu.engines.cpu import QEngineCPU
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk
from qrack_tpu.utils.rng import QrackRandom

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 10
TOL = {"float32": 1e-7, "float64": 1e-15, "bfloat16": 4e-3}


@pytest.fixture(autouse=True)
def _clean_telemetry():
    tele.disable()
    tele.reset()
    yield
    tele.disable()
    tele.reset()


@pytest.fixture(autouse=True)
def _x64_as_found():
    """An engine with float64 planes switches ``jax_enable_x64`` on for
    the process: the tests behind this file get the flag as it was."""
    was = jax.config.jax_enable_x64
    yield
    jax.config.update("jax_enable_x64", was)


def _engine(width=W, **kwargs):
    kwargs.setdefault("rand_global_phase", False)
    return QEngineTPU(width, **kwargs)


def _kets_alive(width, dtype="float32"):
    """Arrays of the ket's size that the process holds on a device."""
    shape = (2, 1 << width)
    return [a for a in jax.live_arrays()
            if a.shape == shape and a.dtype == jnp.dtype(dtype)]


# -- values ----------------------------------------------------------------

@pytest.mark.parametrize("dtype", sorted(TOL))
@pytest.mark.parametrize("perm", [0, 1, (1 << W) - 1], ids=["0", "1", "last"])
@pytest.mark.parametrize("phase", [None, 0.6 - 0.8j], ids=["unit", "given"])
def test_fill_matches_the_cpu_oracle(dtype, perm, phase):
    q = _engine(dtype=jnp.dtype(dtype))
    oracle = QEngineCPU(W, rand_global_phase=False)
    for p in (3, perm):  # a fresh fill at construction, then two in place
        q.SetPermutation(p, phase)
        oracle.SetPermutation(p, phase)
    assert q._state_raw.dtype == jnp.dtype(dtype)
    got, want = q.GetQuantumState(), oracle.GetQuantumState()
    assert np.count_nonzero(got) == 1
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=0)
    assert q.GetAmplitude(perm) == pytest.approx(want[perm], abs=TOL[dtype])
    assert q.running_norm == 1.0


def test_random_global_phase_follows_the_engines_rng():
    """``rand_global_phase=True`` draws the phase from the engine's own
    stream, as the oracle does from the same seed."""
    q = _engine(rand_global_phase=True, rng=QrackRandom(11))
    oracle = QEngineCPU(W, rand_global_phase=True, rng=QrackRandom(11))
    for perm in (5, 700):
        q.SetPermutation(perm)
        oracle.SetPermutation(perm)
        want = oracle.GetAmplitude(perm)
        assert abs(abs(want) - 1.0) < 1e-12 and abs(want - 1.0) > 1e-3
        assert q.GetAmplitude(perm) == pytest.approx(want, abs=1e-6)


def test_fill_drops_a_pending_window_unflushed():
    """A blind overwrite: the queued gates acted on a ket that no longer
    exists, so they are dropped, not run."""
    tele.enable()
    q = _engine()
    q.H(0)
    q.CNOT(0, 1)
    assert q._fuser.gates
    q.SetPermutation(6)
    assert not q._fuser.gates
    assert "fuse.tpu.programs" not in tele.snapshot()["counters"]
    assert q.GetAmplitude(6) == pytest.approx(1.0)


# -- one ket -----------------------------------------------------------------

def test_the_old_ket_is_deleted_and_one_ket_lives():
    width = 13  # a shape no other test of this worker keeps alive
    assert not _kets_alive(width)
    q = _engine(width)
    assert len(_kets_alive(width)) == 1
    # the spacer a fresh ket is allocated behind is gone with the fill
    assert not [a for a in jax.live_arrays()
                if a.shape == (tpu._KET_STAGGER_BYTES,)]
    q.SetPermutation(9)  # construction + fill: still one
    assert len(_kets_alive(width)) == 1
    for perm in range(10):
        held = q._state_raw
        q.SetPermutation(perm)
        assert held.is_deleted() and not q._state_raw.is_deleted()
    assert len(_kets_alive(width)) == 1
    assert q.GetAmplitude(9) == pytest.approx(1.0)
    del q, held
    gc.collect()  # the engine and its fuser refer to each other
    assert not _kets_alive(width)


def test_a_fill_behind_gates_still_holds_one_ket():
    """Gates run and read, then a fill: the window's result is donated
    to the fill like any ket the engine owns."""
    width = 14
    q = _engine(width)
    q.QFT(0, width)
    assert abs(q.GetAmplitude(3)) == pytest.approx(2 ** (-width / 2), rel=1e-5)
    held = q._state_raw
    q.SetPermutation(3)
    assert held.is_deleted() and len(_kets_alive(width)) == 1
    assert q.GetAmplitude(3) == pytest.approx(1.0)


def test_pinned_planes_are_let_go_never_donated():
    """Planes the prefix cache handed out as shared (``pin_planes``) are
    not the engine's to write over: it lets its reference go and fills
    a fresh ket; the other engine that aliases them reads on."""
    tele.enable()
    a, b = _engine(), _engine()
    a.SetPermutation(37)
    a.H(2)
    shared = a._state  # flushed
    want = np.asarray(shared).copy()
    tpu.pin_planes(shared)
    b._state = shared  # the seeded session's alias
    before = dict(tele.snapshot()["counters"])
    a.SetPermutation(5)
    after = tele.snapshot()["counters"]
    assert not shared.is_deleted()
    assert a._state_raw is not shared
    np.testing.assert_array_equal(np.asarray(shared), want)
    np.testing.assert_array_equal(np.asarray(b._state), want)
    assert b.GetAmplitude(37) == pytest.approx(-(2 ** -0.5), rel=1e-6)
    assert a.GetAmplitude(5) == pytest.approx(1.0)
    assert after["engine.fill.fresh"] - before.get("engine.fill.fresh", 0) == 1
    assert after.get("engine.fill.in_place", 0) \
        == before.get("engine.fill.in_place", 0)
    # the fresh ket is the engine's own: the next fill writes over it
    held = a._state_raw
    a.SetPermutation(6)
    assert held.is_deleted() and not shared.is_deleted()
    tpu.unpin_planes(shared)


# -- the program -------------------------------------------------------------

def test_a_new_basis_state_or_phase_traces_nothing():
    """Keyed by width, plane type and whether a ket is handed in:
    ``perm`` and the phase are runtime operands."""
    width = 9
    q = _engine(width)
    q.SetPermutation(1)
    programs = tpu._j_fill._cache_size()
    tele.enable()
    for perm, phase in ((2, None), (300, 1j), ((1 << width) - 1, -1.0)):
        q.SetPermutation(perm, phase)
    assert tpu._j_fill._cache_size() == programs
    counters = tele.snapshot()["counters"]
    assert counters["compile.tpu.fill.hit"] == 3
    assert "compile.tpu.fill.miss" not in counters
    other = _engine(width)  # another engine, the same two programs
    other.SetPermutation(4)
    assert tpu._j_fill._cache_size() == programs


def test_counters_say_what_each_fill_did():
    tele.enable()
    q = _engine()  # no ket yet: fresh
    q.SetPermutation(1)
    q.SetPermutation(2)
    counters = tele.snapshot()["counters"]
    assert counters["engine.fill.fresh"] == 1
    assert counters["engine.fill.in_place"] == 2
    spans = [s for s in tele.local_trace_source()["spans"]
             if s["name"] == "engine.set_permutation"]
    assert len(spans) == 3


def test_the_fills_module_name_is_what_a_trace_finds_it_by():
    lowered = tpu._j_fill.lower(
        jax.ShapeDtypeStruct((2, 1 << W), jnp.float32), np.int32(0),
        np.zeros(2, np.float32), W, jnp.dtype("float32"))
    text = lowered.as_text()
    assert "module @jit_qrack_fill" in text
    # the donated ket is an argument the result may alias
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text


# -- the window of one op ----------------------------------------------------

@pytest.fixture
def kernel_on(monkeypatch):
    """The kernel lowering under the interpreter, tiles of 2^6: a w12
    ket has cross-tile targets as a w30 ket has."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setattr(pk, "DEFAULT_BLOCK_POW", 6)
    fu.PROGRAMS.clear()
    yield
    fu.PROGRAMS.clear()


def _reference():
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import reference
    finally:
        sys.path.pop(0)
    return reference


def _qft_gates(width):
    """``benchmarks/circuits/qft.gates``: Qrack's order, no final swaps."""
    h = np.array([[1, 1], [1, -1]], dtype=np.complex128) / np.sqrt(2)
    out = []
    for i in range(width):
        h_bit = width - 1 - i
        for j in range(i):
            out.append(((h_bit,), np.diag([1.0, np.exp(1j * np.pi / (1 << (j + 1)))]),
                        h_bit + 1 + j))
        out.append(((), h, h_bit))
    return out


def test_qft_whose_last_window_is_one_op_matches_the_reference(
        kernel_on, monkeypatch):
    """A window whose bound divides the stream but for one op ends it on
    a window of that op alone: QFT(0, 30)'s 465 ops at the bound of 16
    (29 windows and the last ``H``; 15 windows and no lone op at the 32
    of PR 46), QFT(0, 29)'s 435 at 31.  At w12 a window of 11 gives the
    same shape: 78 = 7 x 11 + 1.  Every
    window, the lone op's too, is a kernel window, and the ket is the
    plain simulator's."""
    width, x = 12, 2741
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "11")
    tele.enable()
    q = create_quantum_interface("tpu", width, rand_global_phase=False)
    q.SetPermutation(x)
    q.QFT(0, width)
    got = q.GetQuantumState()
    counters = tele.snapshot()["counters"]
    assert counters["fuse.kernel.windows"] == 8
    assert counters["fuse.kernel.ops"] == 78
    assert counters["fuse.tpu.programs"] == 8 * 3  # two columns + the program
    assert not [k for k in counters if k.startswith("fuse.kernel.fallback.")
                or k.startswith("fuse.xla.")]
    want = _reference().run(width, _qft_gates(width), x)
    assert np.max(np.abs(got - want)) < 5e-7


@pytest.mark.parametrize("gate,target", [
    ("H", 0), ("H", 11), ("X", 3), ("T", 9), ("ISwap", (2, 10))],
    ids=lambda v: str(v))
def test_a_lone_gate_after_a_flush_is_a_kernel_window(kernel_on, gate, target):
    """Every op kind alone in its window, in the tile and across tiles,
    against the CPU oracle."""
    width = 12
    tele.enable()
    q = create_quantum_interface("tpu", width, rand_global_phase=False)
    oracle = QEngineCPU(width, rand_global_phase=False)
    for e in (q, oracle):
        e.SetPermutation(0b101101)
        for k in range(width):
            e.H(k)
            e.RZ(0.3 + 0.1 * k, k)
    q.GetAmplitude(0)  # flush: the next gate stands alone
    before = dict(tele.snapshot()["counters"])
    args = target if isinstance(target, tuple) else (target,)
    getattr(q, gate)(*args)
    getattr(oracle, gate)(*args)
    got = q.GetQuantumState()
    after = tele.snapshot()["counters"]
    assert after["fuse.kernel.windows"] - before["fuse.kernel.windows"] == 1
    assert after["fuse.kernel.ops"] - before["fuse.kernel.ops"] == 1
    assert not [k for k in after if k.startswith("fuse.kernel.fallback.")]
    np.testing.assert_allclose(got, oracle.GetQuantumState(), atol=2e-6, rtol=0)


def test_a_lone_op_keeps_the_eager_program_where_no_kernel_lowers():
    """On the CPU backend (mode auto) windows take the XLA chain and a
    lone op the shared per-gate program, as before: one program, no
    window counted, no fallback recorded for it."""
    tele.enable()
    q = create_quantum_interface("tpu", W, rand_global_phase=False)
    q.H(4)
    assert q.GetAmplitude(0) == pytest.approx(2 ** -0.5, rel=1e-6)
    counters = tele.snapshot()["counters"]
    assert counters["fuse.tpu.programs"] == 1
    assert not [k for k in counters if k.startswith(("fuse.kernel.", "fuse.xla."))]
