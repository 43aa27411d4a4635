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


def kernel_operands(ops, dtype, bp, split_at=None):
    """A kernel window's two columns as the engines' flush packs them:
    the plans of its runs of diagonal ops, for tiles of ``bp`` bits,
    behind the masks."""
    structure = (fu.structure_of(ops) if split_at is None
                 else fu.sharded_structure_of(ops))
    bp = bp if split_at is None else min(bp, split_at)
    return fu.pack_operands(ops, dtype, split_at=split_at,
                            runs=fu.kernel_runs(structure, bp, split_at))


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
# parity matrix: vocabulary stream, kernel ON, windows 1, 16 and 32
# (per gate, PR 5's bound, the bound since PR 46)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [1, 16, 32])
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
    if window > 1 and name in ("tpu", "pager"):
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
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "32")
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
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "32")
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
# telemetry contract: a full window of in-tile gates pays ONE HBM sweep,
# at PR 5's bound of 16 and at the 32 it is since PR 46
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gates", [16, 32])
def test_full_window_records_one_sweep(gates, monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(gates))
    tele.enable()
    eng = QEngineTPU(N, rng=QrackRandom(8), rand_global_phase=False)
    for q in range(N):                     # amplitude everywhere first
        eng.H(q)
    eng.Prob(0)                            # flush the H window out of the way
    tele.reset()
    tele.enable()
    # a CNOT ladder: each gate's control is the previous gate's
    # target, so nothing commutes past anything and no merge fires —
    # all in-tile inverts, ONE planned segment
    for j in range(gates):
        t = j % N
        eng.CNOT(t, (t + 1) % N)
    eng.Prob(0)
    c = tele.snapshot(include_events=False)["counters"]
    assert c.get("fuse.kernel.windows", 0) == 1, c
    assert c.get("fuse.kernel.ops", 0) == gates, c
    assert c.get("fuse.kernel.sweeps", 0) == 1, c   # one HBM pass, a window
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
# and bare cross-tile windows of 2, 3 and 13 ops.  Two bare leads share
# a launch since PR 50 (13 / 12, 5 / 3, 2 / 2, 2 / 2, 3 / 3, 13 / 13 and
# 2 / 2 sweeps and led sweeps until then: a sweep an op)
_TPU_WINDOWS = [
    ("dense", 28, range(15, 28), 7, 6),
    ("paged", 28, range(25, 30), 4, 2),
    ("dense", 28, (16, 27), 1, 1),
    ("dense", 18, (16, 17), 1, 1),
    ("dense", 22, (19, 20, 21), 2, 2),
    ("dense", 30, range(16, 29), 7, 7),
    ("paged", 28, (26, 27), 1, 1),
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
    # the window the old rule refused: a sweep a lead then, a sweep for
    # two now
    assert sweeps + plan["paired"] == len(targets)


@pytest.mark.parametrize("lower", [fu.kernel_lowering,
                                   fu.sharded_kernel_lowering],
                         ids=["dense", "paged"])
def test_a_lone_op_is_a_kernel_window_on_the_tpu(lower):
    """The rule ``single_op`` went with PR 43: the eager program of a
    lone gate may hold two kets beside the donated one, a one-op sweep
    holds none."""
    plan, why = lower(24, _gen_structure((20,)), backend="tpu")
    assert why is None and not plan["interpret"]
    assert (plan["sweeps"], plan["cross"]) == (1, 1)


@pytest.mark.parametrize("lower", [fu.kernel_lowering,
                                   fu.sharded_kernel_lowering],
                         ids=["dense", "paged"])
@pytest.mark.parametrize("mode,backend,targets,reason", [
    (None, "cpu", (20,), "cpu_backend"),
    (None, "cpu", (20, 21), "cpu_backend"),
    ("off", "tpu", (20, 21), "mode_off"),
])
def test_the_reasons_that_keep_the_chain(lower, mode, backend, targets,
                                         reason, monkeypatch):
    if mode is not None:
        monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", mode)
    assert lower(24, _gen_structure(targets), backend=backend) == (None, reason)


@pytest.mark.parametrize("lower,kind,ops,fits", [
    (fu.kernel_lowering, "u4", 32, True), (fu.kernel_lowering, "u4", 47, True),
    (fu.kernel_lowering, "u4", 48, False), (fu.kernel_lowering, "u4", 64, False),
    (fu.kernel_lowering, "gen", 64, True), (fu.kernel_lowering, "gen", 256, False),
    # the pager queues no two-qubit op
    (fu.sharded_kernel_lowering, "gen", 64, True),
    (fu.sharded_kernel_lowering, "gen", 256, False)],
    ids=lambda v: getattr(v, "__name__", str(v)))
def test_a_window_too_wide_for_smem_keeps_the_chain(lower, kind, ops, fits):
    """A window's operands reach a launch as two ``(N, 1)`` columns in
    SMEM, 512 bytes an entry: the default bound's widest window (32
    ``u4``, 1024 rows) is a kernel window, and one that only a
    ``QRACK_TPU_FUSE_WINDOW`` above 32 builds takes the chain (reason
    ``smem_operands``) before the chip's compiler refuses it
    (``tests/test_chip_compile.py`` holds the refusal at 64 ``u4``).
    The interpreter has no SMEM."""
    structure = tuple((kind, (j % 20, 20 + j % 4) if kind == "u4" else j % 24,
                       False) for j in range(ops))
    plan, why = lower(24, structure, backend="tpu")
    assert (why is None) == fits
    assert why is None or (plan, why) == (None, "smem_operands")
    assert fu.kernel_lowering(24, structure, backend="cpu")[1] == "cpu_backend"


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
    calls and the forced kernel: one window, a sweep for every two ops
    since two bare leads share a launch (PR 50; a sweep an op until
    then) and one for the odd one left, all cross-tile, nothing on the
    chain, the CPU engine's amplitudes."""
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
            c["fuse.kernel.sweeps"], c["fuse.kernel.sweeps.cross"],
            c["fuse.kernel.leads.paired"]) \
        == (1, k, k - k // 2, k - k // 2, k // 2), c
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
    # the chain reads the masks by offset: the plans behind them ride along
    operands = kernel_operands(ops, jnp.float32, 8)
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
    operands = kernel_operands(ops, jnp.float32, bp)
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
    assert pk.plan_counts(structure, bp) == (1, cross, 1, 0)
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
    assert pk.plan_counts(structure, 9) == (2, 1, 0, 0)
    assert err < 2e-7


@pytest.fixture
def benchmark_plans():
    """``helpers.benchmark_plans``: the windows of one application of a
    cell's family at w28."""
    from helpers import benchmark_plans

    with benchmark_plans() as windows:
        yield windows


def lowered_counts(ops, bp, split_at=None):
    """``fusion.count_kernel_window`` as ``(runs' four, stretches'
    four)``: what ``fuse.kernel.diag_runs`` / ``.diag_run.ops`` /
    ``.diag_run.tile_ops`` / ``.diag_run.folded_ops`` and
    ``fuse.kernel.stretches`` /
    ``.stretch.ops`` / ``.stretch.passes`` / ``.whole_tile_ops`` read."""
    counts = fu.count_kernel_window(ops, bp, split_at=split_at)
    assert tuple(counts) == fu.KERNEL_WINDOW_COUNTERS
    values = tuple(counts.values())
    return values[:4], values[4:]


@pytest.mark.parametrize("family,sweeps,carry_ops,diag_runs,stretches", [
    ("qft", 24, 24, (36, 375, 119, 256), (9, 9, 9, 10)),
    ("tfim", 8, 2, (1, 54, 30, 24), (1, 9, 2, 7)),
    ("rcs", 51, 10, (0, 0, 0, 0), (9, 32, 25, 28))])
def test_benchmark_cells_sweep_dense(benchmark_plans, family, sweeps,
                                     carry_ops, diag_runs, stretches):
    """What ``fuse.kernel.sweeps.dense`` reads in a traced run of each
    cell: every planned kernel segment of an application at w28, at the
    fuser's bound of 32 ops a window (37 / 41 / 102 at the 16 it had
    until PR 46).  A Trotter step is 8 since two bare leads share a
    launch (PR 50; 14 since a bond is one gate of two controlled
    ``diag``, PR 47; 40 while its CNOTs led 24 launches): its first
    window's 54 ``diag`` and the RX on qubits 0 to 4 in one unled
    launch, then the RX on 5 to 15 in one and the bare ``gen`` on 16 to
    27, six launches led by two each.  A random
    circuit's 108 ops are all ``u4`` (every root composed into the
    coupler behind it on the host): 48 lead a launch, 60 ride in 10.

    And what ``fuse.kernel.diag_runs`` / ``.diag_run.ops`` /
    ``.diag_run.tile_ops`` read there (PR 42): QFT's 378 ``cphase`` sit
    in 36 runs of two or more but for three, and 119 of those in runs
    have both bits in the tile; the Trotter step's 54 ``diag`` are one
    run, the 30 of the bonds up to qubit 15 in its phase tile; no
    segment of a random circuit holds two diagonal ops in a row.

    And ``fuse.kernel.stretches`` / ``.stretch.ops`` / ``.stretch.passes``
    / ``.whole_tile_ops`` (PR 44): every in-tile op outside a run is in
    a stretch: the random circuit's 60 ``u4`` in 10 launches, the
    Trotter step's 16 in-tile RX in 2, QFT's 16 ``gen`` and 3 lone
    ``cphase`` in 19.  The ops
    that take a partner by lane rotation (a target on qubits 0 to 6) are
    applied on the whole tile with the diagonal ops behind them, and so
    is a diagonal op that is its segment's only op: 28 / 7 / 10 of
    them; the others chunk by chunk, 32 / 9 / 9, a pass where a chunk
    holds every op's partners (all of QFT's) and more where it does
    not."""
    dense = with_ops = 0
    runs, found = (0, 0, 0, 0), (0, 0, 0, 0)
    for w in benchmark_plans(family):
        if w["path"] != "kernel":
            continue
        plan, _ = fu.kernel_lowering(28, w["structure"], backend="tpu")
        segments = pk.plan_window(w["structure"], plan["block_pow"])
        assert plan["dense"] == plan["sweeps"] == len(segments)
        dense += plan["dense"]
        with_ops += sum(bool(seg["ops"]) for seg in segments)
        in_runs, in_stretches = lowered_counts(w["ops"], plan["block_pow"])
        assert in_runs[0] == sum(len(pk.diag_runs(seg["ops"]))
                                 for seg in segments)
        # every in-tile op is in a run or in a stretch
        assert in_runs[1] + in_stretches[1] + in_stretches[3] \
            == sum(len(seg["ops"]) for seg in segments)
        runs = tuple(a + b for a, b in zip(runs, in_runs))
        found = tuple(a + b for a, b in zip(found, in_stretches))
    assert (dense, with_ops) == (sweeps, carry_ops)
    assert runs == diag_runs
    assert found == stretches


@pytest.mark.parametrize("kwargs", [{"remap": "off"}, {}],
                         ids=["tfim_w30.pager4_noremap", "tfim_w30.pager4"])
def test_paged_cells_hold_their_bonds_in_runs(kwargs):
    """The per-page kernel runs of the paged Trotter step at w30, on the
    fixed placement (two windows a step, 61 and 27 ops) and on the
    pager's own through its settled steps (seven: a gate that needs a
    prologue heads its window; five in the first step): a bond is one
    gate of two controlled ``diag`` (PR 47), so the step's 58 ``diag``
    sit in runs (one on the fixed placement; three where the first CNOT
    of a bond onto a page bit still closes a window), 32 of them in a
    phase tile; no run until then, every ``RZ`` between two CNOTs.  The
    in-tile RX are the stretches: 9 applied chunk by chunk in 2 passes,
    the 7 on qubits 0 to 6 on the whole tile."""
    from helpers import issue, plan_only_pager, trotter_step_gates

    q = plan_only_pager(30, **kwargs)
    for step in range(6):
        q.windows.clear()
        issue(q, trotter_step_gates(30))
        q.GetAmplitude(0)
        assert len(q.windows) == (2 if kwargs else 5 if step == 0 else 7)
        runs, stretches = (0, 0, 0, 0), (0, 0, 0, 0)
        for w in q.windows:
            if w.structure is None:  # a lone RX: the shared one-op program
                continue
            plan, _ = fu.sharded_kernel_lowering(q.local_bits, w.structure,
                                                 backend="tpu")
            in_runs, in_stretches = lowered_counts(
                w.tops, plan["block_pow"], split_at=q.local_bits)
            runs = tuple(a + b for a, b in zip(runs, in_runs))
            stretches = tuple(a + b for a, b in zip(stretches, in_stretches))
        assert runs == ((1, 58, 32, 26) if kwargs else (3, 58, 32, 26))
        assert stretches == (1, 9, 2, 7)


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


def nearest_operands(ops, bp, split_at=None):
    """A kernel window's operands with every float rounded to nearest,
    as the numpy these tests hold the kernel's arithmetic to rounds
    them: where ops act on all of the ket the packing keeps a float32
    window's norm, so a float is what came before it in the window
    too (fusion._norm_kept_float32, tests/test_norm_kept_operands.py)."""
    iv, fv = kernel_operands(ops, np.float64, bp, split_at=split_at)
    return iv, fv.astype(np.float32)


def run_window(n, bp, ops, ket, donate):
    """The kernel window of ``ops`` under the interpreter on a device
    copy of the numpy ``ket``.  Every launch aliases its planes to its
    result (PR 39): donated, the copy is consumed; kept, it has to come
    back as it went in (XLA copies it ahead of the first launch)."""
    import jax.numpy as jnp

    fn = pk.make_window_fn(n, fu.structure_of(ops), block_pow=bp,
                           interpret=True)
    planes = jnp.array(ket, copy=True)
    got = _exact(fn, planes, *nearest_operands(ops, bp), donate=donate)
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


# ---------------------------------------------------------------------------
# two leads a launch (PR 50): a cross-tile inv/gen that directly follows
# a bare cross-tile inv/gen on another qubit joins its segment
# (plan_window), whose orbits are the four tiles over both targets.
# Which leads pair is held here; that a pair is the two launches it
# stands for, bit for bit, in tests/test_pallas_kernels.py.
# ---------------------------------------------------------------------------

_G, _I, _U = "gen", "inv", "u4"
# structure (kind, target, controlled?) at block_pow 8 -> the leads of
# every segment, by op index
PAIR_PLANS = {
    "bare-pair": ([(_G, 8, False), (_G, 9, False)], [(0, 1)]),
    "controlled-pair": ([(_I, 10, True), (_G, 8, True)], [(0, 1)]),
    # riders ride behind the second lead as behind a single one
    "pair-with-riders": ([(_G, 8, False), (_G, 9, False), (_G, 3, False),
                          ("cphase", 9, True)], [(0, 1)]),
    # the fuser merges two gates on one qubit upstream; the planner
    # does not assume it
    "same-qubit": ([(_G, 8, False), (_G, 8, False)], [(0,), (1,)]),
    "same-qubit-inv": ([(_I, 9, False), (_G, 9, True)], [(0,), (1,)]),
    # only an op that directly follows a bare lead joins it
    "lead-with-riders": ([(_G, 8, False), (_G, 3, False), (_G, 9, False)],
                         [(0,), (2,)]),
    "lead-with-a-diag-behind": ([(_G, 8, False), ("diag", 9, False),
                                 (_G, 9, False)], [(0,), (2,)]),
    # a u4 lead neither joins nor is joined, over two tiles or four
    "u4-then-gen": ([(_U, (8, 9), False), (_G, 10, False)], [(0,), (1,)]),
    "gen-then-u4": ([(_G, 10, False), (_U, (8, 9), False)], [(0,), (1,)]),
    "gen-then-u4-pair": ([(_G, 10, False), (_U, (3, 9), False)], [(0,), (1,)]),
    "u4-pair-then-inv": ([(_U, (3, 9), False), (_I, 10, False)], [(0,), (1,)]),
    # the rule stops at two: three bare leads are a pair and a single,
    # four are two pairs
    "three-bare": ([(_G, 8, False), (_G, 9, False), (_G, 10, False)],
                   [(0, 1), (2,)]),
    "four-bare": ([(_G, 8, False), (_I, 9, False), (_G, 10, False),
                   (_G, 11, True)], [(0, 1), (2, 3)]),
    # in-tile ops ahead of the pair are a segment of their own
    "unled-then-pair": ([(_G, 2, False), (_G, 8, False), (_G, 9, False)],
                        [(), (1, 2)]),
    # a pair behind a lead with riders; then the third lead stands alone
    "riders-pair-single": ([(_G, 11, False), (_G, 1, False), (_G, 8, False),
                            (_G, 9, False), ("cphase", 2, True),
                            (_G, 10, False)], [(0,), (2, 3), (5,)]),
    # an in-tile inv/gen is no lead whatever stands ahead of it
    "in-tile-behind-a-bare-lead": ([(_G, 8, False), (_G, 7, False)], [(0,)]),
}


@pytest.mark.parametrize("case", sorted(PAIR_PLANS))
def test_which_leads_pair(case):
    structure, leads = PAIR_PLANS[case]
    structure = tuple(structure)
    segments = pk.plan_window(structure, 8)
    assert [tuple(slot[0] for slot in seg["leads"]) for seg in segments] \
        == leads
    for seg in segments:
        assert seg["xgen"] == (seg["leads"][0] if seg["leads"] else None)
        assert all(slot == (slot[0],) + structure[slot[0]]
                   for slot in list(seg["leads"]) + seg["ops"])
    # every op in one segment, in the window's order
    assert [slot[0] for seg in segments
            for slot in list(seg["leads"]) + seg["ops"]] \
        == list(range(len(structure)))
    paired = sum(len(pair) == 2 for pair in leads)
    assert pk.plan_counts(structure, 8) == (
        len(segments), sum(bool(pair) and structure[pair[0]][0] != _U
                           for pair in leads), 0, paired)
    fn = pk.make_window_fn(12, structure, block_pow=8, interpret=True)
    assert fn.sweeps == len(segments)


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
    # a pair of 2 x 2 leads: the quad's grid, whichever target came first
    pytest.param(12, 8, [fu.FusedOp("gen", 9, 0, 0, np.eye(2)),
                         fu.FusedOp("gen", 11, 0, 0, np.eye(2))], (1, 3),
                 id="16tiles-two-leads"),
    pytest.param(14, 10, [fu.FusedOp("inv", 13, 1, 1, np.eye(2)),
                          fu.FusedOp("gen", 10, 2, 0, np.eye(2))], (0, 3),
                 id="16tiles-dense-two-leads-descending"),
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

    leads = lead if isinstance(lead, list) else [lead]
    ops = leads + [fu.FusedOp("cphase", 2, 1 << (n - 1), 1 << (n - 1),
                              _DENSE_MATRICES["cphase"])]
    fn = pk.make_window_fn(n, fu.structure_of(ops), block_pow=bp,
                           interpret=True)
    (grid, (read,), write), = grids_of(fn, jnp.zeros((2, 1 << n), jnp.float32),
                                       *kernel_operands(ops, jnp.float32, bp))
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
                                     *kernel_operands(ops, jnp.float32, 8))
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
    pytest.param(14, 10, [fu.FusedOp("gen", 12, 0, 0, np.eye(2)),
                          fu.FusedOp("inv", 10, 1, 1, np.eye(2)),
                          fu.FusedOp("gen", 2, 0, 0, np.eye(2))], [2],
                 id="four-tiles-two-leads"),
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
                           *kernel_operands(ops, jnp.float32, bp))
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


# ---------------------------------------------------------------------------
# a run of diagonal ops costs one phase tile (PR 42).  Two or more
# consecutive cphase / diag of a segment are one diagonal operator: an
# op with a high part is applied by its own code, and only on the tiles
# whose id admits it; the ops that read no bit above the tile are
# multiplied together once a launch, on a tile of 1 + 0i, into a VMEM
# scratch that every step multiplies its value by, once.  The product is associated differently from
# the parent's (the factor takes the roundings the amplitude took), so
# the kernel is held bit for bit to numpy float32 in the NEW order, and
# to the parent's order within the roundings both make.
# ---------------------------------------------------------------------------

def _cmul(v, f):
    return np.stack([v[0] * f[0] - v[1] * f[1], v[0] * f[1] + v[1] * f[0]])


def _scalar_cmul(a, f):
    """``a * f`` on float32 scalars, one IEEE operation at a time."""
    return (np.float32(a[0] * f[0]) - np.float32(a[1] * f[1]),
            np.float32(a[0] * f[1]) + np.float32(a[1] * f[0]))


def run_in_numpy(ket, run, n, bp):
    """A run of diagonal ops on the whole ``(2, 2^n)`` float32 ket in
    the kernel's order (``_apply_run``, the host's ``run_planner``).
    An op with every bit in the tile goes, in its order, onto the phase
    tile, built on a tile of ``1 + 0i``.  An op with a bit above the
    tile has a signature (``fold_signature``); the first ``FOLD_SLOTS``
    signatures of the run are its slots, and on every tile a slot's
    factor is the product, in op order from ``1 + 0i`` and in float32
    scalars, of its ops that the tile id admits: one scalar, or two
    where an op picks an entry by its target's bit of the in-tile
    index.  An op of a later signature is applied to the ket by its own
    code, in its order (on a tile that does not admit it its factor is
    ``1 + 0i``, and multiplying by that changes no bit).  Then one
    pass: the ket times the table, times each used slot's factor where
    the in-tile index matches its mask and ``1 + 0i`` elsewhere."""
    one, zero = np.float32(1), np.float32(0)
    table = np.stack([np.ones(1 << bp, np.float32),
                      np.zeros(1 << bp, np.float32)])
    slots = [(i, op.kind, op.target, op.cmask != 0) for i, op in enumerate(run)]
    rows, _ = pk.run_planner(slots, [(op.cmask, op.cval) for op in run], bp)
    ids = rows[pk._PLAN_HEAD:]
    for op, slot in zip(run, ids):
        if pk.in_tile(op.kind, op.target, op.cmask, op.cval, bp):
            assert slot == pk.FOLD_SLOTS
            table = unled_in_numpy(table, op, bp)
        elif slot == pk.FOLD_SLOTS:
            ket = unled_in_numpy(ket, op, n)
    ket = _cmul(ket, np.tile(table, (1, 1 << (n - bp))))
    lidx = np.arange(1 << bp)
    for s in range(pk.FOLD_SLOTS):
        mask, value, pick = rows[3 * s:3 * s + 3]
        if value < 0:
            continue
        hit = (lidx & mask) == value
        odd = (lidx & pick) != 0
        for blk in range(1 << (n - bp)):
            acc = [(one, zero), (one, zero)]
            for op, slot in zip(run, ids):
                m = np.asarray(op.m)
                d = [(np.float32(m[b, b].real), np.float32(m[b, b].imag))
                     for b in (0, 1)]
                if op.kind == "cphase":
                    high = ((1 << op.target) | op.cmask) >> bp
                    admits, by = (blk & high) == high, (d[1], d[1])
                else:
                    admits = (blk & (op.cmask >> bp)) == op.cval >> bp
                    by = d if op.target < bp \
                        else (d[(blk >> (op.target - bp)) & 1],) * 2
                if slot == s and admits:
                    acc = [_scalar_cmul(a, f) for a, f in zip(acc, by)]
            f_re = np.where(hit, np.where(odd, acc[1][0], acc[0][0]), one)
            f_im = np.where(hit, np.where(odd, acc[1][1], acc[0][1]), zero)
            tile = slice(blk << bp, (blk + 1) << bp)
            ket[:, tile] = _cmul(ket[:, tile], (f_re, f_im))
    return ket


def segment_in_numpy(ket, ops, n, bp):
    """The in-tile ops of one segment in the kernel's order: a run of
    two or more diagonal ops through ``run_in_numpy``, any other op
    through ``unled_in_numpy``."""
    runs = dict(pk.diag_runs([(i, op.kind, op.target, op.cmask != 0)
                              for i, op in enumerate(ops)]))
    at = 0
    while at < len(ops):
        if at in runs:
            ket = run_in_numpy(ket, ops[at:runs[at]], n, bp)
            at = runs[at]
        else:
            ket = op_in_numpy(ket, ops[at], n)
            at += 1
    return ket


def op_in_numpy(ket, op, n):
    """One in-tile op in the kernel's order: a ``u4`` as its own row
    over its quad, itself first (``lead_in_numpy``: the same sum
    wherever its targets sit), any other by ``unled_in_numpy``."""
    return (lead_in_numpy if op.kind == "u4" else unled_in_numpy)(ket, op, n)


def _phase(angle):
    return np.diag([1.0, np.exp(1j * angle)])


def _two_phases(a0, a1):
    return np.diag([np.exp(1j * a0), np.exp(1j * a1)])


def diagonal_ops(n, bp):
    """Every class of diagonal op by where its bits lie against the
    tile, by name; ``n - bp >= 2``.  The last four hold an anti-control
    (``cval != cmask``)."""
    top, low = n - 1, 1 << bp
    ops = {
        "cphase.tile": ("cphase", 5, 1 << 2, 1 << 2, _phase(0.37)),
        "cphase.tile.bare": ("cphase", 3, 0, 0, _phase(-0.91)),
        "cphase.mixed": ("cphase", top, 1 << 3, 1 << 3, _phase(0.71)),
        "cphase.mixed2": ("cphase", bp, 1 << 6, 1 << 6, _phase(-1.3)),
        "cphase.high": ("cphase", top, low, low, _phase(1.13)),
        "cphase.high.bare": ("cphase", top, 0, 0, _phase(0.29)),
        "cphase.tile.2c": ("cphase", 7, 0b11, 0b11, _phase(2.1)),
        "cphase.mixed.2c": ("cphase", 4, low | 2, low | 2, _phase(-0.43)),
        "diag.tile.bare": ("diag", 4, 0, 0, _two_phases(-0.21, 0.53)),
        "diag.high.bare": ("diag", top, 0, 0, _two_phases(0.8, -0.33)),
        "diag.tile": ("diag", 6, 1 << 1, 1 << 1, _two_phases(0.15, 0.95)),
        "diag.high": ("diag", top, 1 << 5, 1 << 5, _two_phases(-0.6, 0.4)),
        "diag.tile.anti": ("diag", 1, 1 << 4, 0, _two_phases(0.5, -1.7)),
        "diag.mixed.anti": ("diag", 2, low | 1, 1, _two_phases(1.9, 0.07)),
        "diag.high.anti": ("diag", bp, (low << 1) | 8, 8,
                           _two_phases(-0.77, 0.66)),
        "diag.mixed.anti2": ("diag", 0, (low << 1) | 4, low << 1,
                             _two_phases(0.23, -0.12)),
        # an in-tile target under a control above the tile, with the
        # in-tile mask and value of "diag.high.anti"
        "diag.mixed.pick": ("diag", 5, low | 8, low | 8,
                            _two_phases(-1.1, 0.31)),
    }
    out = {}
    for name, (kind, target, cmask, cval, m) in ops.items():
        assert fu.classify(m, cmask, cval) == kind, name
        out[name] = fu.FusedOp(kind, target, cmask, cval, m)
    return out


# (name, the run's ops): runs of 2, 3 and 16, a run that is all table
# (no op may read the tile id: no slot engages), one that is all rest;
# and by the fold (PR 54): four signatures (the fourth keeps its pass,
# the op behind it shares the third's slot), ops that pick one of two
# entries by their target's bit under a control above the tile (two
# accumulators a slot; one of the controls an anti-control above the
# tile), and an op that picks nothing in the slot of one that picks
DIAG_RUNS = {
    "2": ("cphase.tile", "cphase.mixed"),
    "2-table": ("cphase.tile.bare", "diag.tile.bare"),
    "2-rest": ("cphase.high", "diag.high.bare"),
    "3": ("diag.tile", "cphase.high", "diag.mixed.anti"),
    "3-anti": ("diag.tile.anti", "diag.high.anti", "diag.mixed.anti2"),
    "16": tuple(diagonal_ops(12, 10))[:16],
    "4-signatures": ("cphase.mixed", "cphase.mixed2", "cphase.high",
                     "diag.high", "cphase.high.bare", "cphase.tile"),
    "3-twin": ("diag.mixed.anti", "diag.mixed.anti2", "cphase.mixed.2c",
               "diag.mixed.anti"),
    "2-shared": ("diag.high.anti", "diag.mixed.pick", "diag.high.anti"),
}
# what the host plans for them: the slots' (mask, value, pick) and the


RUN_PLANS = {
    "2-table": ([], [3, 3]),
    "2-rest": ([(0, 0, 0)], [0, 0]),
    "4-signatures": ([(1 << 3, 1 << 3, 0), (1 << 6, 1 << 6, 0), (0, 0, 0)],
                     [0, 1, 2, 3, 2, 3]),
    "3-twin": ([(1, 1, 1 << 2), (4, 0, 1), ((1 << 4) | 2, (1 << 4) | 2, 0)],
               [0, 1, 2, 0]),
    "2-shared": ([(8, 8, 1 << 5)], [0, 0, 0]),
}
RUN_SHAPES = [(10, 8), (12, 10), (18, 16)]


@functools.lru_cache(maxsize=None)
def _diag_run_case(n, bp, name):
    """``(ket, ops, got)`` of a run under the interpreter, once."""
    ops = [diagonal_ops(n, bp)[key] for key in DIAG_RUNS[name]]
    structure = fu.structure_of(ops)
    segment, = pk.plan_window(structure, bp)
    assert pk.diag_runs(segment["ops"]) == [(0, len(ops))]
    ket = random_ket(np.random.default_rng(n * 100 + len(name)), n)
    return ket, ops, run_window(n, bp, ops, ket, donate=False)


_RUN_CASES = pytest.mark.parametrize(
    "n,bp,name", [pytest.param(n, bp, name, id=f"w{n}-bp{bp}-run{name}")
                  for n, bp in RUN_SHAPES for name in DIAG_RUNS])


@pytest.mark.parametrize("n,bp", RUN_SHAPES, ids=lambda v: str(v))
@pytest.mark.parametrize("name", sorted(RUN_PLANS))
def test_the_host_plans_a_runs_slots(n, bp, name):
    """``run_planner`` on the masks the host packs: the first
    ``FOLD_SLOTS`` signatures in op order get a slot, an op of a fourth
    keeps its pass, an op with every bit in the tile goes to the table;
    and the rows ride the tail of ``iv`` at the offset the kernel reads
    them from."""
    ops = [diagonal_ops(n, bp)[key] for key in DIAG_RUNS[name]]
    structure = fu.structure_of(ops)
    segment, = pk.plan_window(structure, bp)
    masks = [(op.cmask, op.cval) for op in ops]
    rows, folded = pk.run_planner(segment["ops"], masks, bp)
    slots, ids = RUN_PLANS[name]
    want = [row for slot in slots for row in slot] \
        + [0, -1, 0] * (pk.FOLD_SLOTS - len(slots))
    passed = sum(at == pk.FOLD_SLOTS and not pk.in_tile(
        op.kind, op.target, op.cmask, op.cval, bp) for op, at in zip(ops, ids))
    assert rows == want + [folded, passed] + ids   # an op's row: its slot
    assert passed == (name == "4-signatures")
    assert folded == sum(at < pk.FOLD_SLOTS for at in ids)
    assert pk.diag_run_counts(structure, masks, bp) == (
        1, len(ops), sum(pk.in_tile(op.kind, op.target, op.cmask, op.cval, bp)
                         for op in ops), folded)
    iv, _ = kernel_operands(ops, np.float32, bp)
    offsets, length = pk._run_plan_slots(structure, bp)
    assert length == len(iv) and pk.run_plan_len(structure, bp) == len(rows)
    assert iv[offsets[0]:, 0].tolist() == rows
    # without the runs the columns are the chain's: no plan behind them
    assert len(fu.pack_operands(ops, np.float32)[0]) == offsets[0]


@pytest.mark.parametrize("family", ["qft", "tfim", "rcs", "grover"])
def test_the_host_plan_covers_every_run_of_a_family(benchmark_plans, family):
    """Every window of an application at w28: the plans behind the masks
    are one a run of ``diag_runs``, at the offsets the kernel reads, and
    every op of a run is table or folded: none keeps a pass of its own
    (a QFT run needs one slot, the Trotter step's three)."""
    runs = slots_used = 0
    for w in benchmark_plans(family):
        if w["path"] != "kernel":
            continue
        structure, ops = w["structure"], w["ops"]
        plan, _ = fu.kernel_lowering(28, structure, backend="tpu")
        bp = plan["block_pow"]
        masks = [(op.cmask, op.cval) for op in ops]
        # the lowering plans the window once: the packer takes its runs
        iv, _ = fu.pack_operands(ops, np.float32, runs=plan["runs"])
        assert plan["runs"] == fu.kernel_runs(structure, bp)
        offsets, length = pk._run_plan_slots(structure, bp)
        assert length == len(iv)
        found = [seg["ops"][a:b] for seg in pk.plan_window(structure, bp)
                 for a, b in pk.diag_runs(seg["ops"])]
        assert found == pk.window_runs(structure, bp)
        assert sorted(offsets) == [run[0][0] for run in found]
        for run in found:
            rows, folded = pk.run_planner(run, masks, bp)
            at = offsets[run[0][0]]
            assert iv[at:at + len(rows), 0].tolist() == rows
            ids = rows[pk._PLAN_HEAD:]
            for (idx, kind, target, _), slot in zip(run, ids):
                assert (slot == pk.FOLD_SLOTS) == pk.in_tile(
                    kind, target, *masks[idx], bp)
            runs += 1
            slots_used = max(slots_used, sum(
                rows[3 * s + 1] >= 0 for s in range(pk.FOLD_SLOTS)))
    assert (runs, slots_used) == {"qft": (36, 1), "tfim": (1, 3),
                                  "rcs": (0, 0), "grover": (0, 0)}[family]


@_RUN_CASES
def test_diag_run_is_numpy_bit_for_bit_in_the_new_order(n, bp, name):
    ket, ops, got = _diag_run_case(n, bp, name)
    want = run_in_numpy(ket, ops, n, bp)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))
    assert got.dtype == np.float32


@_RUN_CASES
def test_diag_run_is_the_parents_order_within_its_roundings(n, bp, name):
    """Against the ops one after the other (the parent's order): both
    orders round once an op, so an amplitude moves by at most two ulp
    of its magnitude for every op of the run."""
    ket, ops, got = _diag_run_case(n, bp, name)
    want = ket
    for op in ops:
        want = unled_in_numpy(want, op, n)
    ulp = np.spacing(np.hypot(want[0], want[1]))
    assert np.all(np.abs(got - want) <= 2 * len(ops) * ulp)
    # and no op was dropped: each moves the ket by far more than that
    for skip in range(len(ops)):
        less = ket
        for op in ops[:skip] + ops[skip + 1:]:
            less = unled_in_numpy(less, op, n)
        assert np.max(np.abs(got - less)) > 1e-3 * np.max(np.abs(want))


@pytest.mark.parametrize("n,bp", RUN_SHAPES, ids=lambda v: str(v))
def test_a_stretch_of_cphase_is_one_loop_over_its_operands(n, bp):
    """Eleven controlled ``cphase`` in a row, an uncontrolled one and a
    ``diag`` after them: the first eleven are one stretch, one traced
    body that reads each op's masks and phase at its offset in the
    columns and its target from a packed constant (targets out of
    order, across the words' boundaries), the other two a stretch
    each.  Bit for bit the run's numpy model."""
    top = n - 1
    targets = [5, top, 3, bp, 7, 0, top - 1, top, 2, 6, 4]
    controls = [1, 2, top, 6, bp, 3, 1, bp, top - 1, 0, 7]
    ops = [fu.FusedOp("cphase", t, 1 << c, 1 << c, _phase(0.2 + 0.37 * k))
           for k, (t, c) in enumerate(zip(targets, controls))]
    named = diagonal_ops(n, bp)
    ops += [named["cphase.high.bare"], named["diag.mixed.anti"]]
    segment, = pk.plan_window(fu.structure_of(ops), bp)
    assert [len(s) for s in pk._run_groups(segment["ops"])] == [11, 1, 1]
    ket = random_ket(np.random.default_rng(n), n)
    got = run_window(n, bp, ops, ket, donate=False)
    want = run_in_numpy(ket, ops, n, bp)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))


def test_two_runs_split_by_a_gen_in_one_segment():
    """Each run has its own phase tile; the gen between them reads the
    first run's result and the second run the gen's."""
    n, bp = 12, 10
    named = diagonal_ops(n, bp)
    ops = [named[k] for k in ("cphase.tile", "diag.high", "cphase.mixed")] \
        + [fu.FusedOp("gen", 3, 1 << 1, 1 << 1, _DENSE_MATRICES["gen"])] \
        + [named[k] for k in ("diag.tile.anti", "cphase.tile.2c")] \
        + [fu.FusedOp("inv", 6, 0, 0, _DENSE_MATRICES["inv"]),
           named["cphase.high"]]                     # a diagonal op alone
    segment, = pk.plan_window(fu.structure_of(ops), bp)
    assert pk.diag_runs(segment["ops"]) == [(0, 3), (4, 6)]
    ket = random_ket(np.random.default_rng(42), n)
    got = run_window(n, bp, ops, ket, donate=True)
    want = segment_in_numpy(ket, ops, n, bp)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))


def _run_leads():
    rng = np.random.default_rng(7)
    return [
        pytest.param(12, 10, fu.FusedOp("gen", 11, 0, 0, _su(rng, 2)),
                     id="gen"),
        pytest.param(12, 10, fu.FusedOp("gen", 10, (1 << 11) | 2, 1 << 11,
                                        _su(rng, 2)), id="cgen"),
        pytest.param(11, 8, fu.FusedOp("inv", 9, 1 << 3, 1 << 3,
                                       _DENSE_MATRICES["inv"]), id="cinv"),
        pytest.param(12, 10, fu.FusedOp("u4", (4, 11), 0, 0, _su(rng, 4)),
                     id="u4-pair"),
        pytest.param(12, 10, fu.FusedOp("u4", (10, 11), 0, 0, _su(rng, 4)),
                     id="u4-quad"),
        pytest.param(18, 16, fu.FusedOp("gen", 17, 0, 0, _su(rng, 2)),
                     id="gen-bp16"),
    ]


@DONATE
@pytest.mark.parametrize("n,bp,lead", _run_leads())
def test_a_run_behind_a_lead_is_numpy_bit_for_bit(n, bp, lead, donate):
    """The phases that ride behind a led 2 x 2 or u4: the table is built
    at the launch's first computing step, orbit 1 member 0, and the
    tile id the others read is the orbit's, not a grid index."""
    named = diagonal_ops(n, bp)
    behind = [named[k] for k in ("cphase.tile", "cphase.mixed", "diag.high",
                                 "cphase.high", "diag.mixed.anti")]
    segment, = pk.plan_window(fu.structure_of([lead] + behind), bp)
    assert segment["xgen"][0] == 0
    assert pk.diag_runs(segment["ops"]) == [(0, len(behind))]
    ket = random_ket(np.random.default_rng(n + bp), n)
    got = run_window(n, bp, [lead] + behind, ket, donate)
    want = run_in_numpy(lead_in_numpy(ket, lead, n), behind, n, bp)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))


def _page_ops(ops, L, pid):
    """The ops of a per-page kernel run as page ``pid`` sees them
    (``fusion._sharded_run_structure`` / ``_sharded_run_operands``):
    local masks, a test on page bits folded into the payload (the
    identity where this page misses it), an op on a page bit a ``diag``
    on bit 0 whose two factors are equal, an ``inv`` a ``gen``."""
    lbits = (1 << L) - 1
    out = []
    for op in ops:
        m = np.asarray(op.m)
        if op.kind == "cphase":
            hit = (pid & ((op.cmask | (1 << op.target)) >> L)) \
                == (op.cmask | (1 << op.target)) >> L
        else:
            hit = (pid & (op.cmask >> L)) == op.cval >> L
        if op.kind in ("cphase", "diag") and op.target >= L:
            d = m[1, 1] if (pid >> (op.target - L)) & 1 or op.kind == "cphase" \
                else m[0, 0]
            kind, target, m = "diag", 0, np.diag([d, d])
        else:
            # the sharded layout holds an inv as the gen it is
            kind, target = {"inv": "gen"}.get(op.kind, op.kind), op.target
        if not hit:
            m = np.eye(2)
        out.append(fu.FusedOp(kind, target, op.cmask & lbits,
                              op.cval & lbits, m))
    return out


@pytest.mark.parametrize("bp", [8, 10])
def test_the_per_page_kernel_runs_a_diag_run_in_the_new_order(bp):
    """``sharded_kernel_window_body`` on four pages: every op of a run
    is controlled by its local masks, page-level tests are in the
    payload, and an op goes into the phase tile exactly when its local
    mask has no bit above the tile."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    n, L, npg = 12, 10, 4
    page = 1 << L
    ops = [
        fu.FusedOp("cphase", 5, 1 << 2, 1 << 2, _phase(0.37)),       # tile
        fu.FusedOp("cphase", 11, 1 << 3, 1 << 3, _phase(0.71)),      # page target
        fu.FusedOp("cphase", 4, (1 << 10) | (1 << 8) | 2,
                   (1 << 10) | (1 << 8) | 2, _phase(-0.4)),
        fu.FusedOp("diag", 11, 1 << 9, 1 << 9, _two_phases(0.2, -0.5)),
        fu.FusedOp("diag", 6, (1 << 11) | 1, 1, _two_phases(0.5, -1.7)),
        fu.FusedOp("cphase", 9, 1 << 10, 1 << 10, _phase(1.2)),      # above tile
        fu.FusedOp("gen", 3, 1 << 10, 1 << 10, _DENSE_MATRICES["gen"]),
        fu.FusedOp("diag", 2, 0, 0, _two_phases(-0.21, 0.53)),
        fu.FusedOp("cphase", 10, 1 << 11, 1 << 11, _phase(0.9)),     # pages only
    ]
    structure = fu.sharded_structure_of(ops)
    (kind, run), = fu._sharded_segments(structure, L)
    assert kind == "run"
    segment, = pk.plan_window(fu._sharded_run_structure(run, L), bp)
    assert pk.diag_runs(segment["ops"]) == [(0, 6), (7, 9)]
    expected = {8: (2, 8, 5, 3), 10: (2, 8, 8, 0)}[bp]
    assert lowered_counts(ops, bp, split_at=L)[0] == expected
    body = fu.sharded_kernel_window_body(L, npg, structure, block_pow=bp,
                                         interpret=True)
    mesh = Mesh(np.array(jax.devices()[:npg]), ("pages",))
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, "pages"), P(), P()),
                       out_specs=P(None, "pages"), check_vma=False)
    ket = random_ket(np.random.default_rng(bp), n)
    got = _exact(fn, jnp.asarray(ket),
                 *kernel_operands(ops, jnp.float32, bp, split_at=L))
    for pid in range(npg):
        local = ket[:, pid * page:(pid + 1) * page]
        want = segment_in_numpy(local, _page_ops(ops, L, pid), L, bp)
        assert np.array_equal(got[:, pid * page:(pid + 1) * page], want), pid


# ---------------------------------------------------------------------------
# every in-tile op walks the tile chunk by chunk (PR 44).  The ops of a
# segment that are in no run are a stretch; on a tile of more than 64
# rows the stretch's value lives in a VMEM scratch tile and a pass over
# it is one rolled loop over chunks in whose body the pass's ops are
# applied one after the other.  A chunk holds the lane bits, the sublane
# bits and two or three bits from the vreg on, those its ops' partners
# sit across; a stretch that needs more is split into passes.  Every
# amplitude's arithmetic and its order are those of the whole-tile body:
# bit for bit numpy's float32, one op after the other.
# ---------------------------------------------------------------------------

# (width, block_pow): 128 rows, two chunks of 64 and four of 32; the
# cells' own 512 rows
CHUNK_SHAPES = [(16, 14), (18, 16)]


def _stretches(n, bp):
    """``name -> ops`` of one unled segment with no run in it."""
    rng = np.random.default_rng(n + bp)
    top, low = n - 1, 1 << bp

    def gen(target, cmask=0, cval=0):
        return fu.FusedOp("gen", target, cmask, cval, _su(rng, 2))

    def u4(lo, hi):
        return fu.FusedOp("u4", (lo, hi), 0, 0, _su(rng, 4))

    def inv(target, cmask, cval):
        return fu.FusedOp("inv", target, cmask, cval, _DENSE_MATRICES["inv"])

    named = diagonal_ops(n, bp)
    out = {
        # lane targets first (on the whole tile), then sublane and vreg
        # targets in no order; a control in the tile, one above it, an
        # anti-control on a vreg bit
        "16gen": [gen(t) for t in (0, 3)] + [gen(6, low, low)]
        + [gen(t) for t in (1, 5, 2, 4, 13, 7, 10, 9)]
        + [gen(12, 1 << 2, 1 << 2), gen(11, 1 << 12, 0)]
        + [gen(t) for t in (8, 13, 12)],
        # the same kinds of target taking turns: the value goes between
        # the scratch and the whole tile at every turn
        "turns": [gen(t) for t in (0, 13, 7, 3, 10, 9)]
        + [gen(12, 1 << 2, 1 << 2), gen(6, low, low), gen(11, 1 << 12, 0)]
        + [gen(t) for t in (1, 8, 5, 13, 2, 4, 12)],
        # lo on a lane bit and hi on a vreg bit (whole tile); both on
        # vreg bits; lo on a sublane bit; a diagonal op alone between
        "u4": [u4(3, 12), named["diag.high"], u4(10, 11), u4(8, 13), gen(5),
               u4(0, 1)],
        # controlled inv, the control above the tile, on a vreg bit, on
        # a lane bit beside an anti-control
        "cinv": [inv(11, 1 << top, 1 << top), inv(4, 1 << 12, 1 << 12),
                 named["cphase.mixed"], inv(13, (1 << 3) | low, 1 << 3),
                 inv(9, low << 1, 0)],
        # more partners' bits from the vreg on than a chunk holds, out of
        # order: the passes' chunks are pieces of whole vregs
        "split": [gen(t) for t in range(bp - 1, 9, -2)]
        + [named["diag.tile.anti"]] + [gen(t) for t in range(10, bp, 2)]
        + [u4(10, bp - 1), named["cphase.high"], u4(11, 12), gen(13)],
    }
    for ops in out.values():
        segment, = pk.plan_window(fu.structure_of(ops), bp)
        assert segment["xgen"] is None and not pk.diag_runs(segment["ops"])
    return out


def _stretch_cases():
    return [pytest.param(n, bp, name, id=f"w{n}-bp{bp}-{name}")
            for n, bp in CHUNK_SHAPES
            for name in (("16gen", "turns", "u4", "cinv", "split")
                         if bp < 16 else ("16gen", "split"))]


@DONATE
@pytest.mark.parametrize("n,bp,name", _stretch_cases())
def test_a_stretch_is_numpy_bit_for_bit(n, bp, name, donate):
    ops = _stretches(n, bp)[name]
    structure = fu.structure_of(ops)
    stretches, in_chunks, passes, whole = pk.stretch_counts(structure, bp)
    assert (stretches, in_chunks + whole) == (1, len(ops))
    # (ops on the whole tile, passes)
    assert (whole, passes) == {"16gen": (7, 2), "turns": (7, 6), "u4": (4, 2),
                               "cinv": (2, 2), "split": (0, 5)}[name]
    ket = random_ket(np.random.default_rng(n * 7 + len(name)), n)
    got = run_window(n, bp, ops, ket, donate)
    want = ket
    for op in ops:
        want = op_in_numpy(want, op, n)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))
    assert got.dtype == np.float32


def test_a_pass_holds_its_ops_partners():
    """``stretch_passes``: a pass's chunk holds three bits from the vreg
    on (two where a ``u4`` is in the pass), its non-diagonal ops'
    targets there first and the lowest bits left after them; a pass ends
    ahead of the op that would ask for one more, and ahead of an op that
    rolls lanes, which is applied on the whole tile (``held`` None)."""
    tile = pk.dense_tile(16)
    slots = [(i, kind, target, False) for i, (kind, target) in enumerate([
        ("gen", 8), ("gen", 15), ("diag", 14), ("gen", 10), ("inv", 15),
        ("gen", 12),                     # 15, 10, 12: full
        ("gen", 13), ("cphase", 11),     # a new pass: 13 so far
        ("u4", (9, 14)),                 # with a u4 a pass holds two: 13, 14
        ("u4", (14, 15)), ("gen", 7),    # 15 would be a third
        ("u4", (11, 12)),
        ("gen", 2), ("diag", 3), ("u4", (5, 12)),   # these roll lanes
        ("gen", 9)])]
    passes = pk.stretch_passes(slots, tile)
    assert [(len(ops), held) for ops, held in passes] \
        == [(6, (10, 12, 15)), (3, (13, 14)), (2, (14, 15)), (1, (11, 12)),
            (3, None), (1, (10, 11, 12))]
    assert [slot for ops, _ in passes for slot in ops] == slots
    assert [pk.rolls_lanes(slot) for slot in slots[12:]] \
        == [True, False, True, False]
    # a diagonal op goes as the op ahead of it, the first as the next
    assert pk.stretch_passes(slots[13:15], tile) == [(slots[13:15], None)]
    assert pk.stretch_passes(slots[13:14], tile) == [(slots[13:14], None)]
    assert pk.stretch_passes(slots[6:8], tile) \
        == [(slots[6:8], (10, 11, 13))]
    # a pass that asks for fewer bits than its chunk holds takes the
    # lowest ones left
    assert pk.stretch_passes(slots[6:7], tile) == [(slots[6:7], (10, 11, 13))]
    assert not pk.chunked(pk.dense_tile(13)) and not pk.chunked((512,))
    assert pk.segment_pieces(slots, pk.dense_tile(13)) \
        == [("stretch", slots, [(slots, None)])]


def _stretch_leads():
    rng = np.random.default_rng(44)
    return [
        pytest.param(16, 14, fu.FusedOp("gen", 15, 0, 0, _su(rng, 2)),
                     id="gen"),
        pytest.param(16, 14, fu.FusedOp("inv", 14, (1 << 15) | 2, (1 << 15) | 2,
                                        _DENSE_MATRICES["inv"]), id="cinv"),
        pytest.param(16, 14, fu.FusedOp("u4", (4, 15), 0, 0, _su(rng, 4)),
                     id="u4-pair"),
        pytest.param(16, 14, fu.FusedOp("u4", (11, 14), 0, 0, _su(rng, 4)),
                     id="u4-pair-vreg"),
        pytest.param(16, 14, fu.FusedOp("u4", (14, 15), 0, 0, _su(rng, 4)),
                     id="u4-quad"),
    ]


@DONATE
@pytest.mark.parametrize("n,bp,lead", _stretch_leads())
def test_a_stretch_behind_a_lead_is_numpy_bit_for_bit(n, bp, lead, donate):
    """The mix of a led step goes into the scratch as it is, and the
    tile id the stretch's masks read is the orbit's."""
    gen, cphase, diag, u4, inv = riders(n, bp)
    behind = [gen, cphase, u4, diag, inv] + _stretches(n, bp)["split"][:5]
    segment, = pk.plan_window(fu.structure_of([lead] + behind), bp)
    assert segment["xgen"][0] == 0 and not pk.diag_runs(segment["ops"])
    ket = random_ket(np.random.default_rng(n + bp), n)
    got = run_window(n, bp, [lead] + behind, ket, donate)
    want = lead_in_numpy(ket, lead, n)
    for op in behind:
        want = op_in_numpy(want, op, n)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))


@DONATE
def test_a_stretch_between_two_runs_shares_the_values_tile(donate):
    """Run, stretch, run, stretch in one unled segment on a tile of two
    chunks: the value is stored once, ahead of the first run, and loaded
    once, behind the last stretch."""
    n, bp = 16, 14
    named = diagonal_ops(n, bp)
    stretch = _stretches(n, bp)
    ops = [named[k] for k in ("cphase.tile", "diag.high", "cphase.mixed")] \
        + stretch["16gen"][7:14] \
        + [named[k] for k in ("diag.tile.anti", "cphase.tile.2c")] \
        + stretch["u4"][:3]
    segment, = pk.plan_window(fu.structure_of(ops), bp)
    assert pk.diag_runs(segment["ops"]) == [(0, 3), (10, 12)]
    assert [(kind, len(slots), len(passes)) for kind, slots, passes in
            pk.segment_pieces(segment["ops"], pk.dense_tile(bp))] \
        == [("run", 3, 0), ("stretch", 7, 2), ("run", 2, 0), ("stretch", 3, 2)]
    # the last stretch begins with a u4 that rolls lanes and a diagonal
    # op behind it: on the whole tile, then one pass
    assert pk.stretch_counts(fu.structure_of(ops), bp) == (2, 8, 3, 2)
    ket = random_ket(np.random.default_rng(4), n)
    got = run_window(n, bp, ops, ket, donate)
    want = segment_in_numpy(ket, ops, n, bp)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))


@DONATE
def test_the_per_page_kernel_walks_a_stretch_chunk_by_chunk(donate):
    """``sharded_kernel_window_body`` on four pages of four tiles of two
    chunks: the stretch's masks are each page's local halves, the
    page-level tests are in the payloads."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    n, L, npg, bp = 18, 16, 4, 14
    page = 1 << L
    rng = np.random.default_rng(18)
    ops = [
        fu.FusedOp("gen", 12, 1 << 17, 1 << 17, _su(rng, 2)),    # page control
        fu.FusedOp("gen", 3, 1 << 13, 0, _su(rng, 2)),
        fu.FusedOp("inv", 10, (1 << 16) | (1 << 15), 1 << 15,    # page anti-control
                   _DENSE_MATRICES["inv"]),
        fu.FusedOp("diag", 17, 1 << 11, 1 << 11, _two_phases(0.2, -0.5)),
        fu.FusedOp("gen", 6, 1 << 14, 1 << 14, _su(rng, 2)),     # above the tile
        fu.FusedOp("gen", 11, 0, 0, _su(rng, 2)),
        fu.FusedOp("gen", 13, 0, 0, _su(rng, 2)),
        fu.FusedOp("cphase", 2, 1 << 16, 1 << 16, _phase(0.9)),
        fu.FusedOp("inv", 9, 0, 0, _DENSE_MATRICES["inv"]),
    ]
    structure = fu.sharded_structure_of(ops)
    (kind, run), = fu._sharded_segments(structure, L)
    assert kind == "run"
    segment, = pk.plan_window(fu._sharded_run_structure(run, L), bp)
    assert not pk.diag_runs(segment["ops"])
    assert lowered_counts(ops, bp, split_at=L) == ((0, 0, 0, 0), (1, 7, 3, 2))
    body = fu.sharded_kernel_window_body(L, npg, structure, block_pow=bp,
                                         interpret=True)
    mesh = Mesh(np.array(jax.devices()[:npg]), ("pages",))
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, "pages"), P(), P()),
                       out_specs=P(None, "pages"), check_vma=False)
    ket = random_ket(np.random.default_rng(bp), n)
    got = _exact(fn, jnp.array(ket, copy=True),
                 *nearest_operands(ops, bp, split_at=L),
                 donate=donate)
    for pid in range(npg):
        local = ket[:, pid * page:(pid + 1) * page]
        want = segment_in_numpy(local, _page_ops(ops, L, pid), L, bp)
        assert np.array_equal(got[:, pid * page:(pid + 1) * page], want), pid


def _scratch_conds_loops(fn, *args):
    """``[(scratch operands, cond equations, loops)]`` of every launch;
    a rolled loop is a ``scan`` (``fori_loop`` over a static range)."""
    import jax

    def count(jaxpr, name):
        total = 0
        for eqn in jaxpr.eqns:
            total += eqn.primitive.name == name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += count(sub, name)
        return total

    return [(eqn.params["grid_mapping"].num_scratch_operands,
             count(eqn.params["jaxpr"], "cond"),
             count(eqn.params["jaxpr"], "scan"))
            for eqn in launches_of(fn, *args)]


def _placeholder_ops(structure):
    return [fu.FusedOp(kind, target, int(ctrl), int(ctrl),
                       np.eye(4 if kind == "u4" else 2))
            for kind, target, ctrl in structure]


@pytest.mark.parametrize("family", ["tfim", "rcs"])
def test_a_window_without_a_run_traces_as_before(benchmark_plans, family,
                                                 monkeypatch):
    """The bypass since PR 44, held without a chip: a segment with no op
    behind its lead lowers to the launch it has with the chunk path
    taken out, and that is the parent's: its orbit scratch, the two
    ``pl.when`` of its grid (read in, compute out) and no loop.  So
    does a segment whose ops all roll lanes or whose one op is diagonal
    (the Trotter step had such until a bond became one gate, PR 47):
    they stay on the whole tile's value.  Every other segment of the
    two families holds its value in one more scratch tile and applies
    the ops that roll no lane in one rolled loop a pass
    (``stretch_passes``).  One segment here holds a run, the Trotter
    step's first window (54 ``diag``, then the RX on qubits 0 to 4 on
    the whole tile): its loops are the run's, which the chunk path has
    no part in; every other segment's loops are its passes."""
    import jax
    import jax.numpy as jnp

    structures = list(dict.fromkeys(
        w["structure"] for w in benchmark_plans(family)
        if w["path"] == "kernel"))
    assert len(structures) == {"tfim": 2, "rcs": 4}[family]
    planes = jax.ShapeDtypeStruct((2, 1 << 28), jnp.float32)
    bare = whole = chunked = with_run = 0
    for structure in structures:
        args = (planes, *kernel_operands(_placeholder_ops(structure),
                                         jnp.float32, pk.DEFAULT_BLOCK_POW))
        with monkeypatch.context() as patch:
            patch.setattr(pk, "chunked", lambda tile: False)
            unchunked = [str(eqn.params["jaxpr"]) for eqn in launches_of(
                pk.make_window_fn(28, structure), *args)]
        fn = pk.make_window_fn(28, structure)
        tile = pk.dense_tile(fn.block_pow)
        launches = launches_of(fn, *args)
        shapes = _scratch_conds_loops(fn, *args)
        segments = pk.plan_window(structure, fn.block_pow)
        assert len(segments) == len(launches) == len(unchunked)
        for seg, eqn, was, shape in zip(segments, launches, unchunked, shapes):
            led = seg["xgen"] is not None
            pieces = pk.segment_pieces(seg["ops"], tile)
            loops = sum(held is not None
                        for _, _, passes in pieces for _, held in passes)
            assert (str(eqn.params["jaxpr"]) == was) == (not loops)
            if pk.diag_runs(seg["ops"]):
                with_run += 1
                continue
            assert shape == (led + bool(loops), 2 * led, loops)
            if not seg["ops"]:
                bare += 1
                assert shape == (1, 2, 0)
            elif loops:
                chunked += 1
                (kind, slots, passes), = pieces
                assert kind == "stretch"
                assert all(pk.rolls_lanes(slot) or slot[1] in ("cphase", "diag")
                           for ops, held in passes if held is None
                           for slot in ops)
            else:
                whole += 1
    # the distinct structures' segments: 6 of the step's 8 launches
    # are led (by two ``gen`` each, PR 50: the quad's scratch and the
    # same two ``pl.when``), all of them bare; a sample's 4 windows are
    # 4 structures, 48 of its 51 launches led and 41 of those bare
    assert (bare, whole, chunked, with_run) \
        == {"tfim": (6, 0, 1, 1), "rcs": (41, 1, 9, 0)}[family]


def test_a_window_of_one_op_is_one_pass():
    """A window of one op is a stretch of one op like any other.
    QFT(0, 30)'s last window, the lone ``H`` on qubit 0, rolls lanes and
    stays on the whole tile's value: no scratch, no loop.  A lone ``H``
    on qubit 12 is one pass over the value's scratch tile."""
    import jax
    import jax.numpy as jnp

    for target, shape, counts in [(0, (0, 0, 0), (0, 0, 0, 1)),
                                  (12, (1, 0, 1), (1, 1, 1, 0))]:
        structure = (("gen", target, False),)
        fn = pk.make_window_fn(30, structure)
        args = (jax.ShapeDtypeStruct((2, 1 << 30), jnp.float32),
                *fu.pack_operands(_placeholder_ops(structure), jnp.float32))
        assert _scratch_conds_loops(fn, *args) == [shape]
        assert pk.stretch_counts(structure, fn.block_pow) == counts


def _chunked_cases():
    """``(ops, [(scratch operands, conds, loops)], scratch tiles)`` on a
    tile of two chunks (w16, ``block_pow`` 14: 128 rows)."""
    n, bp = 16, 14
    named = diagonal_ops(n, bp)
    gen = [fu.FusedOp("gen", t, 0, 0, _DENSE_MATRICES["gen"])
           for t in range(bp)]
    inv = fu.FusedOp("inv", 6, 1 << 15, 1 << 15, _DENSE_MATRICES["inv"])
    mixed = [named["cphase.tile"], named["cphase.mixed"]]
    table_only = [named["cphase.tile.bare"], named["diag.tile.bare"]]
    leads = {"gen": fu.FusedOp("gen", 15, 0, 0, _DENSE_MATRICES["gen"]),
             "cinv": fu.FusedOp("inv", 14, 1 << 3, 1 << 3,
                                _DENSE_MATRICES["inv"]),
             "u4-pair": _u4(4, 15), "u4-quad": _u4(14, 15)}
    cases = [
        # a stretch without a run: the value's tile alone, one loop for
        # the op that rolls no lane behind the two that do
        ("stretch", [gen[3], inv, gen[12]], [(1, 0, 1)], 1),
        ("lanes-only", [gen[3], inv, named["diag.high"]], [(0, 0, 0)], 0),
        # a diagonal op that is the segment's only op stays on the value
        ("lone-diag", [named["diag.high"]], [(0, 0, 0)], 0),
        # four partners' bits from the vreg on are one more than a
        # chunk of 64 rows holds: two passes
        ("split", [gen[10], gen[11], gen[12], gen[13]], [(1, 0, 2)], 1),
        # a u4 takes 32 rows a chunk, two bits: (9, 10) and (11, 12) ask
        # for three
        ("split-u4", [_u4(9, 10), _u4(11, 12)], [(1, 0, 2)], 1),
        # a stretch between two runs works on the tile the runs hold the
        # value in, and the stretch's one loop.  A run's conds: the
        # table's start, the walk over its ops (at the first step, or
        # where an op keeps a pass), in it two a group of like ops (onto
        # the table or the value; laid out for the fold), the branch
        # to the pass by the table alone or to the fold and its pass,
        # and in that pass a scalar branch a fold slot behind the first:
        # 3 + 2 x groups + FOLD_SLOTS, 8 for the first run's two like
        # cphase and 10 for the second's two unlike ops.  Its rolled
        # loops: one over a group's ops and the pass traced in it, and
        # the two passes, by the table alone and by the table and the
        # slots (the fold's loop has the plan's trip count: a ``while``)
        ("between-runs", mixed + [gen[12]] + table_only, [(3, 18, 9)], 3),
        # an op that rolls lanes between them is applied on the value,
        # loaded behind the first run and stored again by the second
        ("lanes-between-runs", mixed + [gen[3]] + table_only,
         [(3, 18, 8)], 3),
        ("run-then-stretch", mixed + [gen[3], gen[13]], [(3, 8, 5)], 2),
    ]
    for name, lead in leads.items():
        # behind a lead: the orbits, the value's tile, the grid's two conds
        cases.append((f"led-{name}", [lead, gen[3], inv, gen[11]],
                      [(2, 2, 1)], 1))
    return [pytest.param(ops, shapes, tiles, id=name)
            for name, ops, shapes, tiles in cases]


@pytest.mark.parametrize("ops,shapes,tiles", _chunked_cases())
def test_a_stretch_holds_its_value_in_the_scratch(ops, shapes, tiles):
    """On a chunked tile a segment with in-tile ops has one scratch of
    tiles, the value's first and a phase tile a run, whatever mix of
    runs and stretches it holds; a stretch adds one loop a pass of ops
    that roll no lane and no ``pl.when``, and a segment whose every op
    rolls lanes has no scratch at all."""
    import jax.numpy as jnp

    n, bp = 16, 14
    fn = pk.make_window_fn(n, fu.structure_of(ops), block_pow=bp,
                           interpret=True)
    args = (jnp.zeros((2, 1 << n), jnp.float32),
            *kernel_operands(ops, jnp.float32, bp))
    assert _scratch_conds_loops(fn, *args) == shapes
    eqn, = launches_of(fn, *args)
    assert tuple(eqn.params["input_output_aliases"]) == ((2, 0),)
    if tiles:
        # behind the tiles where the segment holds a run: the fold
        # slots' accumulators, a vreg a plane
        scratch = [v.aval.shape
                   for v in eqn.params["jaxpr"].invars[-shapes[0][0]:]]
        segment, = pk.plan_window(fu.structure_of(ops), bp)
        runs = [segment["ops"][a:b] for a, b in pk.diag_runs(segment["ops"])]
        # behind the tiles where the segment holds a run: what its fold
        # reads, a place an op of the runs that may fold, whatever its
        # masks hold (four float vregs, three int32: all but a bare
        # diag in the tile), and one more for the slots' accumulators
        if runs:
            may_fold = sum(kind == "cphase" or target >= bp or ctrl
                           for run in runs for _, kind, target, ctrl in run)
            assert scratch[-2:] == [(may_fold + 1, 4, 8, 128),
                                    (max(may_fold, 1), 3, 8, 128)]
            scratch = scratch[:-2]
        assert scratch[-1] == (tiles, 2) + pk.dense_tile(bp)


def test_a_window_with_a_run_holds_its_scratch_in_vmem():
    """One scratch of tiles: the value's and a phase tile a run, and
    two of vregs for what the runs' folds read; the planes stay the
    launch's one result
    (``test_every_launch_aliases_its_planes_to_its_result``).  A run's
    ``pl.when``: one for the table's start, one around the walk over
    its ops (the launch's first step, or where the plan says an op
    keeps a pass), in it two a group (consecutive ``cphase`` alike in
    having controls, or one ``diag``: one traced body in a loop over
    its ops, onto the table at the first step where the op has no high
    part, onto the value where it has one that folds into no slot and
    the tile admits it; and laid out for the fold at the first step
    where it folds), the branch to the pass by the table alone (the
    plan folds no op) or to the fold and its pass, and in that pass a
    scalar branch a fold slot behind the first (a slot the plan does
    not use costs no vector work): 3 + 2 x groups + ``FOLD_SLOTS`` a
    run.  The tile here is one chunk (eight rows): a pass is
    its body, not a loop, the only rolled loops are those over a run's
    like ops, and the ops outside runs are applied on the tile's value
    (the last case: no scratch at all)."""
    import jax
    import jax.numpy as jnp

    n, bp = 12, 10
    named = diagonal_ops(n, bp)
    gen = fu.FusedOp("gen", 3, 0, 0, _DENSE_MATRICES["gen"])
    table_only = [named["cphase.tile.bare"], named["diag.tile.bare"]]
    mixed = [named["cphase.tile"], named["cphase.mixed"]]
    lead = fu.FusedOp("gen", 11, 0, 0, _DENSE_MATRICES["gen"])
    for ops, expected in [
            (table_only, [(3, 10, 0)]),
            (mixed, [(3, 8, 1)]),
            (mixed + [gen] + table_only, [(3, 18, 1)]),
            # + the orbits, the grid's two
            ([lead] + mixed, [(4, 10, 1)]),
            ([named["cphase.mixed"], gen, named["cphase.tile"]], [(0, 0, 0)])]:
        fn = pk.make_window_fn(n, fu.structure_of(ops), block_pow=bp,
                               interpret=True)
        args = (jnp.zeros((2, 1 << n), jnp.float32),
                *kernel_operands(ops, jnp.float32, bp))
        assert _scratch_conds_loops(fn, *args) == expected
        assert pk.stretch_counts(fu.structure_of(ops), bp)[:3] == (0, 0, 0)
        for eqn in launches_of(fn, *args):
            assert tuple(eqn.params["input_output_aliases"]) == ((2, 0),)


@pytest.mark.parametrize("n,bp", [(12, 8), (16, 14)],
                         ids=["flat-tile", "two-chunks"])
@pytest.mark.parametrize("stack,kw", [("tpu", {}), ("pager", {"n_pages": 4})],
                         ids=["tpu", "pager"])
def test_diag_run_counters_read_what_the_kernel_lowered(stack, kw, n, bp,
                                                        monkeypatch):
    """``fuse.kernel.diag_runs`` / ``.diag_run.ops`` / ``.diag_run.tile_ops``
    and ``fuse.kernel.stretches`` / ``.stretch.ops`` / ``.stretch.passes``
    / ``.whole_tile_ops`` of a QFT through the engine's gate calls,
    beside ``fuse.kernel.ops`` and ``.sweeps``, which neither lowering
    moves; the ket is the CPU engine's.  On the flat tile every op
    outside a run is applied on the whole tile and counted so; on a tile
    of two chunks only those that roll lanes are, the others chunk by
    chunk."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setattr(pk, "DEFAULT_BLOCK_POW", bp)
    tele.enable()
    q = create_quantum_interface(stack, n, rng=QrackRandom(5),
                                 rand_global_phase=False, **kw)
    o = QEngineCPU(n, rng=QrackRandom(5), rand_global_phase=False)
    for e in (q, o):
        e.SetPermutation(0b101101110011)
        e.QFT(0, n)
    assert _fidelity(q.GetQuantumState(), o.GetQuantumState()) > 1 - 1e-6
    c = tele.snapshot(include_events=False)["counters"]
    runs, in_runs, in_tile = (c["fuse.kernel.diag_runs"],
                              c["fuse.kernel.diag_run.ops"],
                              c["fuse.kernel.diag_run.tile_ops"])
    assert 0 < runs and 2 * runs <= in_runs <= c["fuse.kernel.ops"]
    # a page of one tile has no bit above the tile to read
    tiles = 1 << (n - (2 if stack == "pager" else 0) - bp)
    assert 0 < in_tile and (in_tile < in_runs if tiles > 1
                            else in_tile == in_runs)
    stretches, in_stretches, passes, whole = (
        c.get(f"fuse.kernel.{key}", 0)
        for key in ("stretches", "stretch.ops", "stretch.passes",
                    "whole_tile_ops"))
    if bp < 10:
        assert (stretches, in_stretches, passes) == (0, 0, 0) and whole > 0
    else:
        # QFT's H on qubits 0 to 6 roll lanes: on the whole tile, with
        # a lone cphase behind one of them and those that stand alone
        assert 7 <= whole <= 12 and 0 < stretches <= passes <= in_stretches
    if stack == "tpu":
        # the replay of the same gate list, window by window; every op
        # of the engine's one shard is in a run, in a stretch or applied
        # on the whole tile, but those that lead a segment
        from helpers import benchmark_plans

        with benchmark_plans(n) as windows:
            want, led = ((0, 0, 0), (0, 0, 0, 0)), 0
            for w in windows("qft"):
                if w["path"] == "kernel":
                    want = tuple(tuple(a + b for a, b in zip(have, more))
                                 for have, more in zip(
                                     want, lowered_counts(w["ops"], bp)))
                    led += sum(seg["xgen"] is not None for seg in
                               pk.plan_window(w["structure"], bp))
        assert ((runs, in_runs, in_tile),
                (stretches, in_stretches, passes, whole)) == want
        assert in_runs + in_stretches + whole + led == c["fuse.kernel.ops"]
