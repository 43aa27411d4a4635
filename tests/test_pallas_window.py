"""Single-sweep Pallas window kernels (ops/pallas_kernels.py +
ops/fusion.py kernel lowering): interpret-mode parity vs the CPU oracle
across the fuser vocabulary on every stack, the fuzz soak with the
kernel forced on, corruption detect-and-repair and exactly-once
escalation THROUGH the kernel flush, the ``off`` byte-for-byte
restoration of the PR 5 XLA path, the one-sweep telemetry contract,
and the w20/block_pow=8 planner regression (cross-tile targets split
into pair-grid segments instead of raising mid-plan).

Off-TPU the kernel runs under the Pallas interpreter — correctness
grade, not perf grade (docs/PERFORMANCE.md) — which is exactly what
these tests exercise.
"""

import functools

import numpy as np
import pytest

from qrack_tpu import QEngineCPU, create_quantum_interface
from qrack_tpu import resilience as res
from qrack_tpu import telemetry as tele
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk
from qrack_tpu.resilience import faults
from qrack_tpu.resilience import integrity as integ
from qrack_tpu.utils.rng import QrackRandom

from test_fuzz_api import _ops

N = 6


@pytest.fixture(autouse=True)
def _clean_layers(monkeypatch):
    monkeypatch.delenv("QRACK_TPU_FUSE_KERNEL", raising=False)
    faults.clear()
    res.reset_breaker()
    res.configure(max_retries=2, backoff_s=0.0, timeout_s=0.0)
    integ.reset()
    yield
    faults.clear()
    res.reset_breaker()
    res.configure()
    res.disable()
    integ.reset()
    tele.disable()
    tele.reset()


def _fidelity(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return abs(np.vdot(a, b)) ** 2 / (np.vdot(a, a).real * np.vdot(b, b).real)


# The whole fuser vocabulary in one stream: generic 2x2 (H/RY), invert
# (X/CNOT), diag (RZ/T/S), cphase (CZ), with controls and targets both
# low and HIGH — at n_pages=4 qubits 4/5 are page bits, so the pager
# rows exercise page-folded payloads and the global ppermute path too.
_VOCAB = [
    ("H", (0,)), ("H", (5,)),
    ("RZ", (0.3, 2)), ("T", (4,)), ("S", (1,)),
    ("CZ", (1, 3)), ("CZ", (5, 0)),
    ("CNOT", (0, 1)), ("CNOT", (5, 2)),
    ("X", (3,)), ("RY", (0.7, 3)),
    ("RZ", (1.1, 5)), ("CNOT", (2, 4)),
]

_STACKS = [
    ("tpu", {}, 1 - 1e-6),
    ("pager", {"n_pages": 4}, 1 - 1e-6),
    ("turboquant", {"bits": 16, "chunk_qb": 3, "block_pow": 2}, 1 - 1e-5),
]


# ---------------------------------------------------------------------------
# parity matrix: vocabulary stream, kernel ON, windows 1 and 16
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [1, 16])
@pytest.mark.parametrize("name,kw,floor", _STACKS,
                         ids=[s[0] for s in _STACKS])
def test_kernel_parity_matrix(name, kw, floor, window, monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(window))
    tele.enable()
    o = QEngineCPU(N, rng=QrackRandom(3), rand_global_phase=False)
    s = create_quantum_interface(name, N, rng=QrackRandom(3),
                                 rand_global_phase=False, **kw)
    for op, args in _VOCAB:
        getattr(o, op)(*args)
        getattr(s, op)(*args)
    assert _fidelity(s.GetQuantumState(), o.GetQuantumState()) > floor
    if window == 16 and name in ("tpu", "pager"):
        # the window really flushed through the kernel, not a fallback
        c = tele.snapshot(include_events=False)["counters"]
        assert c.get("fuse.kernel.windows", 0) >= 1, c


# ---------------------------------------------------------------------------
# fuzz soak: the fusion soak vocabulary with the kernel forced on
# ---------------------------------------------------------------------------

def _draw_op(rng):
    # SetBit measures: cross-stack rng streams legitimately diverge on
    # measuring ops (working notes), so the soak skips it.
    while True:
        name, args = _ops(rng)
        if name != "SetBit":
            return name, args


_FUZZ_STACKS = [
    ("tpu", {}, 1 - 1e-6, 3e-5),
    ("pager", {"n_pages": 4}, 1 - 1e-6, 3e-5),
    ("turboquant", {"bits": 16, "chunk_qb": 3, "block_pow": 2},
     1 - 1e-5, 5e-4),                      # lossy int16 codes
]


@pytest.mark.parametrize("name,kw,floor,ptol",
                         _FUZZ_STACKS, ids=[s[0] for s in _FUZZ_STACKS])
@pytest.mark.parametrize("trial", range(2))
def test_fuzz_vocabulary_kernel_on(name, kw, floor, ptol, trial,
                                   monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    rng = np.random.Generator(np.random.PCG64(9100 + trial))
    o = QEngineCPU(N, rng=QrackRandom(trial), rand_global_phase=False)
    s = create_quantum_interface(name, N, rng=QrackRandom(trial),
                                 rand_global_phase=False, **kw)
    for step in range(25):
        op, args = _draw_op(rng)
        getattr(o, op)(*args)
        getattr(s, op)(*args)
        if rng.integers(0, 8) == 0:        # mid-stream reads force flushes
            qb = int(rng.integers(0, N))
            assert abs(o.Prob(qb) - s.Prob(qb)) < ptol, (trial, step, op)
    assert _fidelity(s.GetQuantumState(), o.GetQuantumState()) > floor, trial


# ---------------------------------------------------------------------------
# integrity: a one-shot amp-corrupt on the KERNEL flush is detected at
# the flush verify and repaired by scoped window replay
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stack,kw", [("tpu", {}),
                                      ("pager", {"n_pages": 4})],
                         ids=["tpu", "pager"])
def test_detect_and_repair_through_kernel_flush(stack, kw, monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "16")
    tele.enable()
    res.enable()
    o = QEngineCPU(N, rng=QrackRandom(3), rand_global_phase=False)
    s = create_quantum_interface(stack, N, rng=QrackRandom(3),
                                 rand_global_phase=False, **kw)
    faults.inject("tpu.fuse.flush", "amp-corrupt", after_n=0, times=1)
    for name, args in _VOCAB:
        getattr(o, name)(*args)
        getattr(s, name)(*args)
    _ = s.Prob(0)   # drain the fuser OUTSIDE suspension
    c = tele.snapshot()["counters"]
    assert sum(sp.fired for sp in faults.specs()) == 1
    assert c.get("integrity.violation", 0) >= 1
    assert c.get("integrity.replay.repaired", 0) >= 1
    assert c.get("fuse.kernel.windows", 0) >= 1
    with faults.suspended():
        a = np.asarray(o.GetQuantumState())
        b = np.asarray(s.GetQuantumState())
    assert _fidelity(a, b) > 1 - 1e-6


# ---------------------------------------------------------------------------
# exactly-once under escalation: a persistently-failing kernel flush
# escalates (CPU failover / pager shrink) without losing or
# double-applying any queued gate
# ---------------------------------------------------------------------------

def test_failover_exactly_once_kernel_on(monkeypatch):
    """The failover snapshot (taken under faults.suspended()) re-runs
    the flush on the CPU engine — same contract as the XLA path."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    res.enable()
    q = create_quantum_interface("tpu", N, rng=QrackRandom(3),
                                 rand_global_phase=False)
    o = QEngineCPU(N, rng=QrackRandom(3), rand_global_phase=False)
    for e in (q, o):
        e.H(0)
        e.CNOT(0, 1)
        e.RZ(0.7, 2)
        e.X(3)
    faults.inject("tpu.fuse.flush", "raise", after_n=0, times=None)
    p = q.Prob(1)                          # read flushes; the fault fires here
    assert type(q.engine).__name__ == "QEngineCPU"
    assert abs(p - o.Prob(1)) < 1e-6
    assert _fidelity(q.GetQuantumState(), o.GetQuantumState()) > 1 - 1e-6


def test_pager_shrink_midwindow_kernel_on(monkeypatch):
    """A device flap mid-flight of a kernel-lowered pager window shrinks
    the mesh, the job finishes degraded, and the final state matches the
    oracle — the shrunk layout recompiles its own kernel programs."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "16")
    tele.enable()
    res.enable()
    q = create_quantum_interface("pager", N, n_pages=4, rng=QrackRandom(3),
                                 rand_global_phase=False)
    cut = len(_VOCAB) // 2
    for name, args in _VOCAB[:cut]:
        getattr(q, name)(*args)
    faults.inject("*", "flap", after_n=0, times=1)
    for name, args in _VOCAB[cut:]:
        getattr(q, name)(*args)
    q.GetAmplitude(0)   # read boundary: flush + failover
    q.Prob(0)           # post-recovery boundary: the probe grows back
    c = tele.snapshot()["counters"]
    assert c.get("elastic.repage.shrink", 0) >= 1
    assert type(q.engine).__name__ == "QPager"
    with faults.suspended():
        got = np.asarray(q.GetQuantumState())
    o = QEngineCPU(N, rng=QrackRandom(3), rand_global_phase=False)
    for name, args in _VOCAB:
        getattr(o, name)(*args)
    assert _fidelity(got, o.GetQuantumState()) > 1 - 1e-6


# ---------------------------------------------------------------------------
# the off-switch: QRACK_TPU_FUSE_KERNEL=off IS the PR 5 XLA window path
# ---------------------------------------------------------------------------

def test_kernel_off_is_pr5_xla_path_byte_for_byte(monkeypatch):
    """``off`` and the auto-mode CPU fallback both dispatch the SAME
    cached dense XLA window program — byte-identical states — and the
    fallback reasons are distinguishable in telemetry."""
    def run(mode):
        if mode is None:
            monkeypatch.delenv("QRACK_TPU_FUSE_KERNEL", raising=False)
        else:
            monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", mode)
        tele.reset()
        tele.enable()
        eng = QEngineTPU(N, rng=QrackRandom(5), rand_global_phase=False)
        for name, args in _VOCAB:
            getattr(eng, name)(*args)
        eng.Prob(0)
        c = tele.snapshot(include_events=False)["counters"]
        tele.disable()
        return np.asarray(eng.GetQuantumState()), c

    s_off, c_off = run("off")
    s_auto, c_auto = run(None)             # auto on a CPU backend
    assert np.array_equal(s_off, s_auto)   # byte-for-byte, not allclose
    for c in (c_off, c_auto):
        assert c.get("fuse.kernel.windows", 0) == 0
        assert c.get("fuse.xla.windows", 0) >= 1
    assert c_off.get("fuse.kernel.fallback.mode_off", 0) >= 1
    assert c_auto.get("fuse.kernel.fallback.cpu_backend", 0) >= 1
    # and the interpret kernel agrees numerically with that path
    s_on, c_on = run("on")
    assert c_on.get("fuse.kernel.windows", 0) >= 1
    assert np.allclose(s_on, s_off, atol=1e-5)


# ---------------------------------------------------------------------------
# telemetry contract: a 16-gate diagonal window pays ONE HBM sweep
# ---------------------------------------------------------------------------

def test_sixteen_gate_window_records_one_sweep(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "16")
    tele.enable()
    eng = QEngineTPU(N, rng=QrackRandom(8), rand_global_phase=False)
    for q in range(N):                     # amplitude everywhere first
        eng.H(q)
    eng.Prob(0)                            # flush the H window out of the way
    tele.reset()
    tele.enable()
    # a 16-gate CNOT ladder: each gate's control is the previous gate's
    # target, so nothing commutes past anything and no merge fires —
    # all in-tile inverts, ONE planned segment
    for j in range(16):
        t = j % N
        eng.CNOT(t, (t + 1) % N)
    eng.Prob(0)
    c = tele.snapshot(include_events=False)["counters"]
    assert c.get("fuse.kernel.windows", 0) == 1, c
    assert c.get("fuse.kernel.ops", 0) == 16, c
    assert c.get("fuse.kernel.sweeps", 0) == 1, c   # one HBM pass, 16 gates
    # the XLA chain would have paid ~one sweep per op
    assert c.get("fuse.xla.windows", 0) == 0


# ---------------------------------------------------------------------------
# the choice on the TPU: every window of two or more ops takes the
# kernel, a window of bare cross-tile gen (as many segments as ops)
# included — on the chip a chain op costs three passes over the ket
# where a one-op kernel sweep costs one (PERF.md §6, PR 35)
# ---------------------------------------------------------------------------

def _gen_structure(targets):
    return tuple(("gen", t, False) for t in targets)


# (lowering, width or local bits, targets, sweeps, cross-tile): the last
# window of the dense Trotter step at w28 (RX on 15-27: q15 in-tile),
# of the paged one (RX on 25-29 at 2^28 pages: 28 and 29 exchange),
# and bare cross-tile windows of 2, 3 and 13 ops
_TPU_WINDOWS = [
    ("dense", 28, range(15, 28), 13, 12),
    ("paged", 28, range(25, 30), 5, 3),
    ("dense", 28, (16, 27), 2, 2),
    ("dense", 18, (16, 17), 2, 2),
    ("dense", 22, (19, 20, 21), 3, 3),
    ("dense", 30, range(16, 29), 13, 13),
    ("paged", 28, (26, 27), 2, 2),
]


@pytest.mark.parametrize("lowering,n,targets,sweeps,cross", _TPU_WINDOWS,
                         ids=[f"{w[0]}-w{w[1]}-{len(w[2])}gen"
                              for w in _TPU_WINDOWS])
def test_tpu_takes_the_kernel_for_bare_cross_tile_windows(lowering, n, targets,
                                                          sweeps, cross):
    lower = {"dense": fu.kernel_lowering,
             "paged": fu.sharded_kernel_lowering}[lowering]
    plan, why = lower(n, _gen_structure(targets), backend="tpu")
    assert why is None and not plan["interpret"]
    assert (plan["sweeps"], plan["cross"]) == (sweeps, cross)
    assert sweeps == len(targets)       # the window the old rule refused


@pytest.mark.parametrize("lower", [fu.kernel_lowering,
                                   fu.sharded_kernel_lowering],
                         ids=["dense", "paged"])
@pytest.mark.parametrize("mode,backend,targets,reason", [
    (None, "tpu", (20,), "single_op"),
    (None, "cpu", (20, 21), "cpu_backend"),
    ("off", "tpu", (20, 21), "mode_off"),
])
def test_the_reasons_that_keep_the_chain(lower, mode, backend, targets,
                                         reason, monkeypatch):
    if mode is not None:
        monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", mode)
    assert lower(24, _gen_structure(targets), backend=backend) == (None, reason)


# (width, block_pow, targets): every target at or above block_pow; the
# first two on the dense (rows, 128) tile (tests/test_trace_spans.py has
# the flat tile's small windows beside their spans), the last the dense
# Trotter step's 13 ops, all of them cross-tile
_BARE_CROSS = [(12, 10, (11, 10)), (13, 10, (10, 12, 11)),
               (15, 2, tuple(range(2, 15)))]


@pytest.mark.parametrize("n,bp,targets", _BARE_CROSS,
                         ids=[f"w{n}-{len(t)}gen" for n, _, t in _BARE_CROSS])
def test_bare_cross_tile_gen_window_matches_cpu(n, bp, targets, monkeypatch):
    """The window the rule kept on the chain, through the engine's gate
    calls and the forced kernel: one window, a sweep an op, all
    cross-tile, nothing on the chain, the CPU engine's amplitudes."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setattr(pk, "DEFAULT_BLOCK_POW", bp)
    tele.enable()
    eng = QEngineTPU(n, rng=QrackRandom(7), rand_global_phase=False)
    o = QEngineCPU(n, rng=QrackRandom(7), rand_global_phase=False)
    for q in (eng, o):
        q.SetPermutation(0b10110 & ((1 << n) - 1))
        for j, t in enumerate(targets):
            q.RX(0.3 + 0.17 * j, t)
    got = np.asarray(eng.GetQuantumState())
    assert np.max(np.abs(got - np.asarray(o.GetQuantumState()))) < 1e-6
    c = tele.snapshot(include_events=False)["counters"]
    k = len(targets)
    assert (c["fuse.kernel.windows"], c["fuse.kernel.ops"],
            c["fuse.kernel.sweeps"], c["fuse.kernel.sweeps.cross"]) \
        == (1, k, k, k), c
    assert c.get("fuse.xla.windows", 0) == 0
    assert not [name for name in c if name.startswith("fuse.kernel.fallback")]


# ---------------------------------------------------------------------------
# planner regression: cross-tile non-diagonal targets SPLIT, never raise
# ---------------------------------------------------------------------------

def test_segment_compatible_is_a_predicate_not_a_raise():
    assert pk.segment_compatible("cphase", 19, 8)
    assert pk.segment_compatible("diag", 19, 8)
    assert not pk.segment_compatible("gen", 10, 8)   # False, no ValueError
    assert pk.segment_compatible("gen", 7, 8)


def test_w20_qft_block_pow8_plans_and_builds():
    """The PR 5 path raised ValueError mid-plan on any w20 circuit at
    block_pow=8 (cross-tile H targets); the planner now leads each
    cross-tile gen with its own pair-grid segment."""
    from qrack_tpu.models.qft import qft_qcircuit

    circ = qft_qcircuit(20)
    ops = fu.lower_gates(circ.gates)
    structure = fu.structure_of(ops)
    fn = pk.make_window_fn(20, structure, block_pow=8, interpret=True)
    assert 1 <= fn.sweeps < len(ops)
    # the plan covers every op exactly once, in order
    plan = pk.plan_window(structure, 8)
    covered = [s[0] for seg in plan
               for s in ([seg["xgen"]] if seg["xgen"] else []) + seg["ops"]]
    assert covered == list(range(len(ops)))


def test_w12_qft_block_pow8_numeric_parity():
    import jax.numpy as jnp
    from qrack_tpu.models.qft import basis_planes, qft_qcircuit

    circ = qft_qcircuit(12)
    ops = fu.lower_gates(circ.gates)
    structure = fu.structure_of(ops)
    operands = fu.pack_operands(ops, jnp.float32)
    planes = jnp.asarray(basis_planes(12, 1234 & ((1 << 12) - 1)))
    want = np.asarray(fu.window_fn(12, structure)(planes, *operands))
    fn = pk.make_window_fn(12, structure, block_pow=8, interpret=True)
    got = np.asarray(fn(jnp.asarray(basis_planes(12, 1234 & ((1 << 12) - 1))),
                        *operands))
    assert fn.sweeps < len(ops)
    assert float(np.max(np.abs(want - got))) < 3e-5


# ---------------------------------------------------------------------------
# the dense tile: from block_pow 10 on the kernel body computes on a
# (2, rows, 128) view of its block and reads a pair partner by a lane
# roll (target < 7) or a sublane roll (7 <= target < block_pow); below
# that it keeps the flat (2, block) tile.  Every kind x where the target
# lies x where the controls lie, against the XLA window chain.
# ---------------------------------------------------------------------------

_DENSE_MATRICES = {
    "cphase": np.diag([1.0, np.exp(0.37j)]),
    "diag": np.diag([np.exp(-0.21j), np.exp(0.53j)]),
    "inv": np.array([[0, np.exp(0.3j)], [np.exp(-0.8j), 0]]),
    "gen": np.array([[np.cos(0.4), -np.exp(0.6j) * np.sin(0.4)],
                     [np.exp(0.2j) * np.sin(0.4),
                      np.exp(0.8j) * np.cos(0.4)]]),
}


def _dense_cases():
    """(width, block_pow, kind, target, cmask, cval, behind).  A
    cross-tile inv/gen leads its segment: ``behind`` puts an in-tile gen
    after it, so that the mixed value goes on through in-tile ops; the
    bare cases leave the mix the whole body."""
    cases = []
    for n, bp, targets in (
            (18, 16, {"lane": 3, "sublane": 8, "vreg": 12, "cross": 17}),
            (12, 10, {"lane": 6, "sublane": 9, "cross": 11})):
        # controls: a lane bit and a sublane bit below the block, and
        # the block's lowest bit above it (no target is one of them)
        low, high = (1 << 1) | (1 << 7), 1 << bp
        for cls, target in targets.items():
            for kind in ("cphase", "diag", "inv", "gen"):
                leads = cls == "cross" and kind in ("inv", "gen")
                for cname, cmask in (("none", 0), ("low", low),
                                     ("high", high), ("both", low | high)):
                    # cphase is the all-ones control by definition; the
                    # others also test an anti-control on the lowest bit
                    cval = cmask if kind == "cphase" else cmask & (cmask - 1)
                    bare = leads and cname in ("none", "both")
                    for behind in (True, False) if bare else (leads,):
                        cases.append(pytest.param(
                            n, bp, kind, target, cmask, cval, behind,
                            id=f"w{n}-{kind}-{cls}{target}-{cname}"
                               + ("-bare" if leads and not behind else "")))
    return cases


@functools.lru_cache(maxsize=None)
def _window_programs(n, bp, structure):
    """(XLA chain, interpreted kernel) of a structure, jitted once: the
    masks are operands, so the controlled cases of a target share both."""
    import jax

    return (jax.jit(fu.window_fn(n, structure)),
            jax.jit(pk.make_window_fn(n, structure, block_pow=bp,
                                      interpret=True)))


def _window_against_chain(n, bp, ops, seed):
    import jax.numpy as jnp

    structure = fu.structure_of(ops)
    operands = fu.pack_operands(ops, jnp.float32)
    rng = np.random.default_rng(seed)
    ket = rng.standard_normal((2, 1 << n)).astype(np.float32)
    ket /= np.sqrt((ket ** 2).sum())
    chain, kernel = _window_programs(n, bp, structure)
    want = np.asarray(chain(jnp.asarray(ket), *operands))
    got = np.asarray(kernel(jnp.asarray(ket), *operands))
    return structure, float(np.max(np.abs(want - got)))


@pytest.mark.parametrize("n,bp,kind,target,cmask,cval,behind", _dense_cases())
def test_dense_tile_parity(n, bp, kind, target, cmask, cval, behind):
    ops = [fu.FusedOp(kind, target, cmask, cval, _DENSE_MATRICES[kind])]
    if behind:
        ops.append(fu.FusedOp("gen", 5, 1 << 4, 1 << 4,
                              _DENSE_MATRICES["gen"]))
    assert fu.classify(ops[0].m, cmask, cval) == kind
    structure, err = _window_against_chain(n, bp, ops, seed=target + cmask)
    cross = kind in ("inv", "gen") and target >= bp
    assert pk.plan_counts(structure, bp) == (1, cross, 1)
    assert err < 2e-7


def test_flat_tile_below_block_pow_10():
    """A block of 512 amplitudes has four rows of 128: the body keeps
    the flat tile, the plan counts no dense sweep, the result holds."""
    assert pk.dense_tile(9) is None
    assert pk.dense_tile(10) == (8, 128)
    assert pk.dense_tile(16) == (512, 128)
    ops = [fu.FusedOp("gen", 8, 1 << 1, 1 << 1, _DENSE_MATRICES["gen"]),
           fu.FusedOp("inv", 10, 1 << 7, 0, _DENSE_MATRICES["inv"]),
           fu.FusedOp("cphase", 3, 1 << 9, 1 << 9, _DENSE_MATRICES["cphase"])]
    structure, err = _window_against_chain(11, 9, ops, seed=5)
    assert pk.plan_counts(structure, 9) == (2, 1, 0)
    assert err < 2e-7


@pytest.fixture
def benchmark_plans():
    """``helpers.benchmark_plans``: the windows of one application of a
    cell's family at w28."""
    from helpers import benchmark_plans

    with benchmark_plans() as windows:
        yield windows


@pytest.mark.parametrize("family,sweeps,carry_ops", [("qft", 37, 37),
                                                     ("tfim", 41, 17)])
def test_benchmark_cells_sweep_dense(benchmark_plans, family, sweeps,
                                     carry_ops):
    """What ``fuse.kernel.sweeps.dense`` reads in a traced run of each
    cell: every planned kernel segment of an application at w28.  Of
    TFIM's 36 cross-tile segments 12 carry an in-tile op behind the mix
    (11 a ``diag``, one a window's 15 ``gen``), 12 are the controlled
    ``inv`` alone, whose select is what the dense tile shortens, and 12
    the last window's bare ``gen`` (its 13th, on qubit 15, is in-tile)."""
    dense = with_ops = 0
    for w in benchmark_plans(family):
        if w["path"] != "kernel":
            continue
        plan, _ = fu.kernel_lowering(28, w["structure"], backend="tpu")
        segments = pk.plan_window(w["structure"], plan["block_pow"])
        assert plan["dense"] == plan["sweeps"] == len(segments)
        dense += plan["dense"]
        with_ops += sum(bool(seg["ops"]) for seg in segments)
    assert (dense, with_ops) == (sweeps, carry_ops)


# ---------------------------------------------------------------------------
# led segments: the grid walks an orbit at a time (PR 37).  A led
# segment's grid is (orbit, member); a step reads one tile of its orbit
# into a VMEM scratch and writes one tile of the orbit before, computed
# from the scratch; the body computes what the parent's did, in its
# order.  Held here bit for bit against numpy's float32, one IEEE
# operation at a time, and (the property the gain rests on) by reading
# the index maps.
# ---------------------------------------------------------------------------

def _exact(fn, *args, donate=False):
    """``fn(*args)`` compiled with XLA's CPU backend at optimization
    level 0, the first argument donated or kept.  At its default level that backend contracts ``a * b + c``
    into one rounding where its fusions happen to allow it, so two
    bodies with the same arithmetic differ in a last bit here and there
    (29 of 116 led windows, new grid against old, and none at level 0
    or with FMA off: PR 37); at level 0 every product and sum rounds on
    its own, which is numpy's arithmetic and the TPU's."""
    import jax

    jitted = jax.jit(fn, donate_argnums=(0,) if donate else ())
    return np.asarray(jitted.lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 0})(*args))


def random_ket(rng, n):
    """A normalised ``(2, 2^n)`` float32 ket."""
    ket = rng.standard_normal((2, 1 << n)).astype(np.float32)
    return ket / np.sqrt((ket ** 2).sum(dtype=np.float32))


def run_window(n, bp, ops, ket, donate):
    """The kernel window of ``ops`` under the interpreter on a device
    copy of the numpy ``ket``.  Every launch aliases its planes to its
    result (PR 39): donated, the copy is consumed; kept, it has to come
    back as it went in (XLA copies it ahead of the first launch)."""
    import jax.numpy as jnp

    fn = pk.make_window_fn(n, fu.structure_of(ops), block_pow=bp,
                           interpret=True)
    planes = jnp.array(ket, copy=True)
    got = _exact(fn, planes, *fu.pack_operands(ops, jnp.float32),
                 donate=donate)
    if donate:
        assert planes.is_deleted()
    else:
        assert np.array_equal(np.asarray(planes), ket)
    return got


def lead_in_numpy(ket, op, n):
    """A leading op on the whole ``(2, 2^n)`` float32 ket, in the order
    the kernel computes it: a 2 x 2 as its own row over the (bit 0,
    bit 1) pair, column 0 first; a 4 x 4 as its own row over the quad's
    members in the order the amplitude meets them, itself first
    (``tile_quad_mix``)."""
    idx = np.arange(1 << n)
    m = np.asarray(op.m)
    re, im = m.real.astype(np.float32), m.imag.astype(np.float32)
    if op.kind == "u4":
        lo, hi = op.target
        row = (((idx >> hi) & 1) << 1) | ((idx >> lo) & 1)
        acc = None
        for x in range(4):
            v = ket[:, idx ^ ((x & 1) << lo) ^ ((x >> 1) << hi)]
            cre, cim = re[row, row ^ x], im[row, row ^ x]
            term = (v[0] * cre - v[1] * cim, v[0] * cim + v[1] * cre)
            acc = term if acc is None else (acc[0] + term[0], acc[1] + term[1])
        return np.stack(acc)
    bit = 1 << op.target
    b = (idx >> op.target) & 1
    lo, hi = ket[:, idx & ~bit], ket[:, idx | bit]
    m0r, m0i, m1r, m1i = re[b, 0], im[b, 0], re[b, 1], im[b, 1]
    nv = np.stack([m0r * lo[0] - m0i * lo[1] + m1r * hi[0] - m1i * hi[1],
                   m0r * lo[1] + m0i * lo[0] + m1r * hi[1] + m1i * hi[0]])
    return np.where((idx & op.cmask) == op.cval, nv, ket)


def _su(rng, k):
    q, _ = np.linalg.qr(rng.normal(size=(k, k)) + 1j * rng.normal(size=(k, k)))
    return q


def riders(n, bp):
    """In-tile ops to ride behind a lead, each reading the tile id: a
    cphase controlled on the two highest qubits above the tile (one,
    where there is one), a diag on the top qubit, an inv controlled on
    the lowest bit above the tile; a gen and a u4 between them."""
    rng = np.random.default_rng(n * 31 + bp)
    high = (1 << (n - 1)) | (1 << max(n - 2, bp))
    return [fu.FusedOp("gen", 3, 1 << 1, 1 << 1, _su(rng, 2)),
            fu.FusedOp("cphase", 2, high, high, _DENSE_MATRICES["cphase"]),
            fu.FusedOp("diag", n - 1, 1 << 5, 0, _DENSE_MATRICES["diag"]),
            fu.FusedOp("u4", (0, 4), 0, 0, _su(rng, 4)),
            fu.FusedOp("inv", 6, 1 << bp, 1 << bp, _DENSE_MATRICES["inv"])]


def led_segment_against_numpy(n, bp, lead, behind, seed, donate=False):
    """``(got, want)``: the kernel window ``[lead] + riders`` under the
    interpreter, its ket donated or kept (``run_window``), and the lead
    in numpy with the riders applied by the unled kernel, whose tile id
    is its grid step."""
    behind = riders(n, bp) if behind else []
    assert pk.plan_window(fu.structure_of([lead] + behind), bp)[0]["xgen"][0] == 0
    ket = random_ket(np.random.default_rng(seed), n)
    want = lead_in_numpy(ket, lead, n)
    if behind:
        assert len(pk.plan_window(fu.structure_of(behind), bp)) == 1
        want = run_window(n, bp, behind, want, donate)
    return run_window(n, bp, [lead] + behind, ket, donate), want


# (width, block_pow): 2, 4 and 16 tiles, on the flat tile and the dense
ORBIT_SHAPES = [(8, 7), (9, 7), (11, 7), (11, 10), (12, 10), (14, 10)]


def _led_2x2_cases():
    cases = []
    for n, bp in ORBIT_SHAPES:
        for target in sorted({bp, n - 1}):
            # controls: a bit inside the tile, and above it the highest
            # qubit that is not the target (none where the tile id is
            # the target bit alone); gen's is an anti-control in the tile
            above = [q for q in range(bp, n) if q != target][-1:]
            cmask = (1 << 1) | sum(1 << q for q in above)
            for kind in ("gen", "inv"):
                for ctrl in (False, True):
                    for behind in (False, True):
                        cases.append(pytest.param(
                            n, bp, kind, target, cmask if ctrl else 0, behind,
                            id=f"w{n}-bp{bp}-{'c' if ctrl else ''}{kind}{target}"
                               + ("-riders" if behind else "-bare")))
    return cases


DONATE = pytest.mark.parametrize("donate", [False, True],
                                 ids=["kept", "donated"])


@DONATE
@pytest.mark.parametrize("n,bp,kind,target,cmask,behind", _led_2x2_cases())
def test_led_2x2_segment_is_numpy_bit_for_bit(n, bp, kind, target, cmask,
                                              behind, donate):
    cval = cmask & ~2 if kind == "gen" else cmask
    lead = fu.FusedOp(kind, target, cmask, cval, _DENSE_MATRICES[kind])
    got, want = led_segment_against_numpy(n, bp, lead, behind, seed=n + target,
                                          donate=donate)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))


def launches_of(fn, *args):
    """The equation of every pallas_call ``fn`` traces to, in order."""
    import jax

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    return list(calls(jax.make_jaxpr(fn)(*args).jaxpr))


def grids_of(fn, *args):
    """``(grid, input index maps, output index map)`` of every
    pallas_call ``fn`` traces to; a map takes the grid indices to a
    block's column (every block here is ``(2, 2^block_pow)``, row 0),
    the two scalar columns left out."""
    import jax

    def column(mapping):
        closed = mapping.index_map_jaxpr

        def at(*ids):
            row, col = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *ids)
            assert int(row) == 0
            return int(col)
        return at

    out = []
    for gm in (eqn.params["grid_mapping"] for eqn in launches_of(fn, *args)):
        maps = [column(bm) for bm in gm.block_mappings[2:]]
        assert gm.num_outputs == 1
        out.append((tuple(gm.grid), maps[:-1], maps[-1]))
    return out


def _u4(lo, hi):
    return fu.FusedOp("u4", (lo, hi), 0, 0, np.eye(4))


_ORBIT_LEADS = [
    pytest.param(8, 7, fu.FusedOp("gen", 7, 0, 0, np.eye(2)), (0,),
                 id="2tiles-gen"),
    pytest.param(9, 7, fu.FusedOp("inv", 8, 1, 1, np.eye(2)), (1,),
                 id="4tiles-cinv"),
    pytest.param(9, 7, _u4(7, 8), (0, 1), id="4tiles-quad"),
    pytest.param(12, 8, fu.FusedOp("gen", 10, 0, 0, np.eye(2)), (2,),
                 id="16tiles-gen"),
    pytest.param(12, 8, _u4(3, 11), (3,), id="16tiles-pair-u4"),
    pytest.param(12, 8, _u4(9, 11), (1, 3), id="16tiles-quad"),
    pytest.param(14, 10, _u4(10, 13), (0, 3), id="16tiles-dense-quad"),
]


@pytest.mark.parametrize("n,bp,lead,lead_bits", _ORBIT_LEADS)
def test_a_led_segment_moves_one_tile_in_and_one_out_a_step(n, bp, lead,
                                                            lead_bits):
    """What the gain rests on, held without a chip: a led launch has one
    input and one output block a step, as an unled one has; over the
    grid it reads every tile once, an orbit at a time, and writes every
    tile once, an orbit behind (the read clamped on the added last
    orbit, the write on the first, whose blocks are written again by
    the steps that own them)."""
    import jax.numpy as jnp

    ops = [lead, fu.FusedOp("cphase", 2, 1 << (n - 1), 1 << (n - 1),
                            _DENSE_MATRICES["cphase"])]
    fn = pk.make_window_fn(n, fu.structure_of(ops), block_pow=bp,
                           interpret=True)
    (grid, (read,), write), = grids_of(fn, jnp.zeros((2, 1 << n), jnp.float32),
                                       *fu.pack_operands(ops, jnp.float32))
    nblk, m = 1 << (n - bp), 1 << len(lead_bits)
    orbits = nblk // m
    assert grid == (orbits + 1, m)
    mask = sum(1 << h for h in lead_bits)
    for orbit in range(orbits):
        tiles = [read(orbit, member) for member in range(m)]
        assert tiles == [pk.orbit_tile(lead_bits, orbit, member)
                         for member in range(m)]
        # what the next orbit's steps write, each its own
        assert [write(orbit + 1, member) for member in range(m)] == tiles
        # the tiles the lead mixes: one value of the other bits, every
        # value of its own
        assert len({t & ~mask for t in tiles}) == 1
        assert sorted(t & mask for t in tiles) == sorted(
            sum(((k >> p) & 1) << h for p, h in enumerate(lead_bits))
            for k in range(m))
    steps = [(o, j) for o in range(orbits + 1) for j in range(m)]
    assert sorted(read(o, j) for o, j in steps[:nblk]) == list(range(nblk))
    assert sorted(write(o, j) for o, j in steps[m:]) == list(range(nblk))
    # the clamped ends stay inside the ket and inside their own orbit
    assert [read(orbits, j) for j in range(m)] \
        == [read(orbits - 1, j) for j in range(m)]
    assert [write(0, j) for j in range(m)] == [write(1, j) for j in range(m)]


def test_an_unled_segment_keeps_its_grid():
    import jax.numpy as jnp

    ops = [fu.FusedOp("gen", 3, 0, 0, np.eye(2))]
    fn = pk.make_window_fn(12, fu.structure_of(ops), block_pow=8,
                           interpret=True)
    (grid, (tile,), out), = grids_of(fn, jnp.zeros((2, 1 << 12), jnp.float32),
                                     *fu.pack_operands(ops, jnp.float32))
    assert grid == (16,)
    assert [tile(i) for i in range(16)] == [out(i) for i in range(16)] \
        == list(range(16))


# ---------------------------------------------------------------------------
# in place (PR 39): every launch aliases its planes to its result, so a
# window program that was handed a donated ket writes no second one.
# What no interpreter shows is the order of the chip's DMAs (PERF.md
# section 6, PR 39: the probe on the chip); what it does show is here:
# the alias on every grid, the bits, and a kept ket left as it was.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bp,ops,grids", [
    pytest.param(12, 8, [fu.FusedOp("gen", 3, 0, 0, np.eye(2))], [1],
                 id="unled"),
    pytest.param(12, 8, [fu.FusedOp("inv", 10, 1, 1, np.eye(2)),
                         fu.FusedOp("gen", 2, 0, 0, np.eye(2))], [2],
                 id="pair"),
    pytest.param(12, 8, [_u4(3, 11)], [2], id="pair-u4"),
    pytest.param(14, 10, [_u4(10, 13)], [2], id="four-tiles"),
    # a window of three launches: each takes the one before's result
    pytest.param(12, 8, [fu.FusedOp("gen", 5, 0, 0, np.eye(2)),
                         fu.FusedOp("diag", 5, 2, 2, np.eye(2)),
                         fu.FusedOp("gen", 9, 0, 0, np.eye(2)), _u4(8, 11)],
                 [1, 2, 2], id="unled-pair-quad"),
])
def test_every_launch_aliases_its_planes_to_its_result(n, bp, ops, grids):
    import jax.numpy as jnp

    fn = pk.make_window_fn(n, fu.structure_of(ops), block_pow=bp,
                           interpret=True)
    assert fn.sweeps == len(grids)
    launches = launches_of(fn, jnp.zeros((2, 1 << n), jnp.float32),
                           *fu.pack_operands(ops, jnp.float32))
    assert [len(eqn.params["grid_mapping"].grid) for eqn in launches] == grids
    for eqn in launches:
        # iv, fv, planes -> the one result, of the planes' shape and type
        assert tuple(eqn.params["input_output_aliases"]) == ((2, 0),)
        planes, = eqn.invars[2:]
        out, = eqn.outvars
        assert planes.aval.shape == out.aval.shape == (2, 1 << n)
        assert planes.aval.dtype == out.aval.dtype


def unled_in_numpy(ket, op, n):
    """An op whose pair lies inside the tile (or which has none) on the
    whole ``(2, 2^n)`` float32 ket in the kernel's order: ``tile_cphase``,
    ``tile_diag``, ``tile_local_invert``, ``tile_local_2x2`` (its own
    entry of the matrix first, then the partner's)."""
    idx = np.arange(1 << n)
    m = np.asarray(op.m)
    re, im = m.real.astype(np.float32), m.imag.astype(np.float32)
    b = (idx >> op.target) & 1
    v, o = ket, ket[:, idx ^ (1 << op.target)]
    if op.kind in ("cphase", "diag"):
        if op.kind == "cphase":
            sel = (idx & (op.cmask | (1 << op.target))) \
                == (op.cmask | (1 << op.target))
            fre, fim = re[1, 1], im[1, 1]
        else:
            sel = (idx & op.cmask) == op.cval
            fre, fim = re[b, b], im[b, b]
        fre = np.where(sel, fre, np.float32(1))
        fim = np.where(sel, fim, np.float32(0))
        return np.stack([v[0] * fre - v[1] * fim, v[0] * fim + v[1] * fre])
    if op.kind == "inv":
        fre, fim = re[b, 1 - b], im[b, 1 - b]
        nv = np.stack([fre * o[0] - fim * o[1], fre * o[1] + fim * o[0]])
    else:
        dre, dim, ore, oim = re[b, b], im[b, b], re[b, 1 - b], im[b, 1 - b]
        nv = np.stack([dre * v[0] - dim * v[1] + ore * o[0] - oim * o[1],
                       dre * v[1] + dim * v[0] + ore * o[1] + oim * o[0]])
    return np.where((idx & op.cmask) == op.cval, nv, ket)


def _unled_cases():
    """The cases of ``_dense_cases`` at w12 that no op leads: every kind
    with its target on a lane or a sublane bit, and the two diagonal
    kinds with theirs above the tile, under each placement of controls."""
    def unled(n, bp, kind, target, *_):
        return n == 12 and not (kind in ("inv", "gen") and target >= bp)

    return [c for c in _dense_cases() if unled(*c.values)]


@DONATE
@pytest.mark.parametrize("n,bp,kind,target,cmask,cval,behind", _unled_cases())
def test_unled_segment_is_numpy_bit_for_bit(n, bp, kind, target, cmask, cval,
                                            behind, donate):
    op = fu.FusedOp(kind, target, cmask, cval, _DENSE_MATRICES[kind])
    assert pk.plan_window(fu.structure_of([op]), bp)[0]["xgen"] is None
    ket = random_ket(np.random.default_rng(target + cmask), n)
    got = run_window(n, bp, [op], ket, donate)
    want = unled_in_numpy(ket, op, n)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))


@DONATE
def test_a_window_of_many_launches_in_place(donate):
    """Led and unled launches in one program, each on the result of the
    one before: the bits of the same ops a launch at a time."""
    n, bp = 12, 8
    rng = np.random.default_rng(39)
    ops = [fu.FusedOp("gen", 9, 0, 0, _su(rng, 2)),
           fu.FusedOp("u4", (8, 11), 0, 0, _su(rng, 4)),
           fu.FusedOp("gen", 5, 0, 0, _su(rng, 2)),
           fu.FusedOp("inv", 10, 1 << 5, 1 << 5, _DENSE_MATRICES["inv"]),
           fu.FusedOp("u4", (2, 9), 0, 0, _su(rng, 4))]
    segments = pk.plan_window(fu.structure_of(ops), bp)
    assert len(segments) >= 3
    ket = random_ket(rng, n)
    want, at = ket, 0
    for seg in segments:
        count = len(seg["ops"]) + (seg["xgen"] is not None)
        want = run_window(n, bp, ops[at:at + count], want, donate)
        at += count
    assert at == len(ops)
    assert np.array_equal(run_window(n, bp, ops, ket, donate), want)
