"""The out-of-place modular calls as one write of the ket from a table
(``engines/tpu.py`` ``qrack_alu_modn``, PR 53): ``POWModNOut``,
``MULModNOut`` and ``IMULModNOut`` on contiguous registers against the
CPU engine's ``_k_out_of_place`` (the scatter they had), bit for bit.

Off the chip the write takes its view body whatever the registers; the
Pallas body the chip takes is held to the view under the interpreter,
and its programs compile for a described v5e in
``tests/test_register_programs_compile.py``.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from qrack_tpu import telemetry as tele
from qrack_tpu.engines import tpu as tpu_engine
from qrack_tpu.engines.cpu import QEngineCPU
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.ops import alu_kernels as alu
from qrack_tpu.ops import register_kernels as rk

from helpers import rand_state

# (width, in_start, out_start, length, base or multiplier, N)
CASES = [
    (4, 0, 2, 2, 2, 3),      # the smallest: registers of two bits
    (6, 0, 3, 3, 3, 7),      # the deployment's shape: in below, out on top
    (8, 0, 4, 4, 7, 15),
    (8, 1, 5, 3, 3, 7),      # in_start > 0
    (9, 0, 5, 3, 5, 13),     # a gap between the registers
    (9, 1, 5, 2, 3, 8),      # N a power of two: an out register of 3 bits
    (8, 4, 0, 4, 7, 15),     # out register below the in register
    (10, 6, 1, 3, 5, 7),     # below it, off 0, a gap, a qubit above
    (12, 0, 6, 6, 7, 55),    # the cell's rehearsal
    (12, 2, 7, 4, 11, 21),
    (11, 0, 8, 3, 2, 5),     # a wide gap
    (10, 5, 0, 5, 3, 32),    # N = 2^5 below the in register
]


def _pair(width, seed, zero_out=None):
    """A CPU oracle and a TPU-class engine on one random ket; with
    ``zero_out`` (start, length) the ket lives where that register is 0,
    else it has amplitude everywhere (dropped, as upstream drops it)."""
    state = rand_state(width, seed)
    if zero_out is not None:
        idx = np.arange(1 << width)
        state = np.where((idx >> zero_out[0]) & ((1 << zero_out[1]) - 1),
                         0.0, state)
        state /= np.linalg.norm(state)
    a = QEngineCPU(width, rand_global_phase=False, dtype=np.complex64)
    b = QEngineTPU(width, rand_global_phase=False)
    a.SetQuantumState(state.astype(np.complex64))
    b.SetQuantumState(state.astype(np.complex64))
    return a, b


def _same(a, b):
    """Bit for bit: both only move float32 amplitudes or write zeros."""
    got, want = b.GetQuantumState(), a.GetQuantumState()
    return np.array_equal(got.astype(np.complex64), want.astype(np.complex64))


@pytest.mark.parametrize("call", ["POWModNOut", "MULModNOut"])
@pytest.mark.parametrize("case", CASES, ids=lambda c: "-".join(map(str, c)))
def test_table_write_is_the_scatter(case, call):
    width, in_start, out_start, length, k, N = case
    a, b = _pair(width, seed=width + in_start)
    tele.enable()
    try:
        tele.reset()
        getattr(a, call)(k, N, in_start, out_start, length)
        getattr(b, call)(k, N, in_start, out_start, length)
        counters = tele.snapshot(include_events=False)["counters"]
    finally:
        tele.disable()
    assert _same(a, b)
    assert counters.get("alu.tpu.modn") == 1
    assert "alu.tpu.out_of_place" not in counters


@pytest.mark.parametrize("case", CASES, ids=lambda c: "-".join(map(str, c)))
def test_imul_is_the_scatter_and_inverts_mul(case):
    width, in_start, out_start, length, k, N = case
    ol = (N - 1).bit_length() if N & (N - 1) else N.bit_length() - 1
    # a ket in the call's domain: amplitude where the out register is 0
    a, b = _pair(width, seed=3 * width, zero_out=(out_start, ol))
    before = b.GetQuantumState()
    a.MULModNOut(k, N, in_start, out_start, length)
    b.MULModNOut(k, N, in_start, out_start, length)
    assert _same(a, b)
    a.IMULModNOut(k, N, in_start, out_start, length)
    b.IMULModNOut(k, N, in_start, out_start, length)
    assert _same(a, b)
    assert np.array_equal(b.GetQuantumState(), before)


@pytest.mark.parametrize("call,args", [
    ("CMULModNOut", (3, 7, 0, 3, 3, (7,))),
    ("CIMULModNOut", (3, 7, 0, 3, 3, (7,))),
    ("CPOWModNOut", (3, 7, 0, 3, 3, (6, 7))),
    ("MUL", (3, 0, 3, 3)),
])
def test_the_rest_of_the_family_keeps_the_scatter(call, args):
    a, b = _pair(8, seed=17)
    tele.enable()
    try:
        tele.reset()
        getattr(a, call)(*args)
        getattr(b, call)(*args)
        counters = tele.snapshot(include_events=False)["counters"]
    finally:
        tele.disable()
    assert np.allclose(b.GetQuantumState(), a.GetQuantumState(), atol=1e-6)
    assert counters.get("alu.tpu.out_of_place") == 1
    assert "alu.tpu.modn" not in counters


def test_overlapping_registers_keep_the_scatter():
    b = QEngineTPU(8, rand_global_phase=False)
    assert not b._modn_writes(0, 4, 2, 4)
    assert b._modn_writes(0, 4, 4, 4) and b._modn_writes(4, 4, 0, 4)


@pytest.mark.parametrize("base,N", [(7, 15943), (2, 16383), (5, 8193),
                                    (3, 16384), (16382, 16383)])
def test_host_table_is_pow_at_every_entry(base, N):
    """Doubling over numpy int64 against Python's ``pow`` at every one of
    the 2^14 entries a w28 application builds."""
    want = [pow(base, x, N) for x in range(1 << 14)]
    got = alu.powmod_table(base, N, 14)
    assert got.dtype == np.int32 and got.tolist() == want
    assert alu.mulmod_table(base, N, 14).tolist() == [
        x * base % N for x in range(1 << 14)]


def test_the_table_is_a_runtime_operand():
    """Every base and modulus of one register pair share one program."""
    b = QEngineTPU(8, rand_global_phase=False)
    b.POWModNOut(3, 11, 0, 4, 4)
    size = tpu_engine._j_alu_modn._cache_size()
    for base, N in [(2, 15), (7, 13), (5, 9)]:
        b.SetPermutation(0)
        b.POWModNOut(base, N, 0, 4, 4)
    assert tpu_engine._j_alu_modn._cache_size() == size


# (n, in_start, length, out_start, out_length): rows of 2^10 amplitudes
# and more below the out register, blocks of one row part and of many rows
KERNEL_CASES = [(18, 0, 10, 10, 8), (18, 2, 8, 10, 5), (20, 0, 6, 12, 4),
                (17, 0, 12, 12, 5), (19, 1, 9, 17, 2)]


@pytest.mark.parametrize("geom", KERNEL_CASES,
                         ids=lambda g: "-".join(map(str, g)))
def test_kernel_body_is_the_view_body(geom):
    """The Pallas write the chip takes, under the interpreter, against
    the view: the same planes, bit for bit."""
    n, in_start, length, out_start, ol = geom
    assert rk.modn_kernel_fits(*geom)
    rng = np.random.default_rng(n)
    planes = jnp.asarray(rng.normal(size=(2, 1 << n)).astype(np.float32))
    table = jnp.asarray(rng.integers(0, 1 << ol, 1 << length).astype(np.int32))
    sl = tpu_engine.qrack_alu_modn_slice(planes, None, *geom)
    view = tpu_engine.qrack_alu_modn(planes, sl, table, *geom, None)
    kernel = tpu_engine.qrack_alu_modn(planes, sl, table, *geom, True)
    assert np.array_equal(np.asarray(kernel), np.asarray(view))
    assert float(jnp.sum(view * view)) == pytest.approx(
        float(jnp.sum(sl * sl)), rel=1e-5)


def test_kernel_takes_the_cells_registers_and_no_narrow_row():
    assert rk.modn_kernel_fits(28, 0, 14, 14, 14)
    assert rk.modn_kernel_fits(30, 0, 15, 15, 15)
    assert not rk.modn_kernel_fits(12, 0, 6, 6, 6)     # a row of 2^6
    assert not rk.modn_kernel_fits(28, 14, 14, 0, 14)  # out below in


# -- the whole order-finding circuit (the cell ``shor_w28.library``) ---------

@pytest.fixture(scope="module")
def shor_reference():
    """The benchmark's plain reference and its family ``shor``: gate by
    gate in complex128, ``f`` from Python's ``pow``."""
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    before, path = set(sys.modules), list(sys.path)
    sys.path[:0] = [bench]
    try:
        import harness
        import reference

        yield reference, harness.load_module("circuits", "shor")
    finally:
        sys.path[:] = path
        for name in set(sys.modules) - before:
            if name in ("harness", "reference") or name.startswith("bench_"):
                del sys.modules[name]


@pytest.mark.parametrize("kernel", ["auto", "on"], ids=["xla", "interpreted"])
@pytest.mark.parametrize("N", [33, 35, 39, 51, 55, 57])
def test_order_finding_circuit_is_the_reference_at_w12(shor_reference, N,
                                                       kernel, monkeypatch):
    """``shor_period_state`` through the engine's own calls against the
    plain reference over all 4096 amplitudes, in float32, the windows as
    XLA chains and through the interpreted window kernel."""
    from qrack_tpu.models import algorithms

    reference, family = shor_reference
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", kernel)
    base = next(a for a in range(N // 3, N) if np.gcd(a, N) == 1)
    want = reference.run(12, family.gates(12, {"N": N, "a": base}), 0)
    q = QEngineTPU(12, rand_global_phase=False)
    algorithms.shor_period_state(q, base, N, 6)
    assert np.max(np.abs(q.GetQuantumState() - want)) < 2e-6
    # and the register's distribution the measurement draws from
    probs = q.ProbBitsAll(list(range(6)))
    assert np.allclose(probs, np.sum(np.abs(want.reshape(64, 64)) ** 2, axis=0),
                       atol=2e-6)


def test_shor_order_find_still_factors():
    from qrack_tpu.models import algorithms
    from qrack_tpu.utils.rng import QrackRandom

    found = set()
    for seed in range(12):
        q = QEngineTPU(8, rand_global_phase=False, rng=QrackRandom(seed))
        found.add(algorithms.shor_order_find(q, 7, 15, 4))
    assert found - {None} and found - {None} <= {3, 5}
