"""The fuser's pending-window bound (``ops/fusion.DEFAULT_WINDOW``: 32
ops since PR 46, 16 before), the gate that opens a window on a paged
engine (``GateStreamFuser._heads_a_window``) and what the two plan for
the benchmark's nine cells, held without a chip.

The bound sets how many launches an application is: a window that fills
at 16 flushes a random circuit's roots bare before the coupler they
compose into arrives (218 ops, 102 sweeps; at 32 every root is composed
on the host: 108 ops, all ``u4``, 51 sweeps), and makes one launch of a
QFT's ``gen`` with its fifteen ``cphase`` where 32 makes one of two.
On the pager a non-diagonal gate whose qubit sits on a page bit closes
the pending window and heads the next, whatever the count: a prologue
runs at a window's head alone.  The plans below are counts from the real
gate funnel, fuser, remap planner and kernel lowering
(``helpers.benchmark_plans``, ``helpers.plan_only_pager``); no ket is
allocated and nothing runs.  16 is what ``QRACK_TPU_FUSE_WINDOW=16``
still gives (the curve 16 / 24 / 32 on the chip: PERF.md section 5); 64
is not offered: a window of 64 ``u4`` exhausts the kernel's SMEM
(``tests/test_chip_compile.py``).
"""

import numpy as np
import pytest

from qrack_tpu import create_quantum_interface
from qrack_tpu import telemetry as tele
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import sharded as shb
from qrack_tpu.utils.rng import QrackRandom

from helpers import (benchmark_plans, issue, plan_only_pager,
                     trotter_step_gates)

PAGES = 4

# cell -> bound -> (ops, windows, sweeps, cross-tile sweeps), an
# application (a chip, where the ket is paged; there a window of one op
# and no prologue is no kernel window and no sweep: the pager's shared
# one-op program runs it).  Since PR 50 a cross-tile 2 x 2 that directly
# follows a bare one on another qubit shares its launch
# (``pallas_kernels.plan_window``; PAIRED below): the Trotter step's
# twelve ``RX`` launches on qubits 16-27 are six (14 sweeps, 12 of them
# led, until then; 16 / 12 and 15 / 12 on the pager), a Grover
# iteration's 24 ``H`` launches twelve (27 / 24); a QFT's leads carry
# their ``cphase`` and a random circuit's are ``u4``: not one pairs
PLANS = {
    # at 16 a cycle's roots flush bare ahead of their coupler, and 21 of
    # those ``gen`` leads pair (102 sweeps, 92 of them led, until PR 50)
    "rcs_w28.library": {16: (218, 14, 81, 71), 32: (108, 4, 51, 48)},
    "qft_w28.library": {16: (406, 26, 37, 12), 32: (406, 13, 24, 12)},
    "qft_w30.library": {16: (465, 30, 43, 14), 32: (465, 15, 28, 14)},
    # a bond (CNOT, RZ, CNOT) is one gate of two controlled ``diag`` since
    # PR 47 (``QCircuitGate.can_merge``): 109 ops, 4 windows, 40 sweeps,
    # 36 of them led, until then; on the pager 117 / 11 / 43 / 40 and, on
    # the fixed placement, 117 / 4 / 49 / 36
    "tfim_w28.library": {16: (82, 4, 10, 7), 32: (82, 2, 8, 6)},
    # a Grover iteration (PR 49): 59 ops (56 H, two ZeroPhaseFlip as one
    # controlled ``diag`` each, PhaseFlip as a ``diag``) in the windows
    # its two ALU rotations and the bound cut: the lone ZeroPhaseFlip
    # between DEC and INC, then the diffusion, 12 leads a layer in six
    # launches (at 16 a window's edge leaves one lead single)
    "grover_w28.library": {16: (59, 5, 16, 13), 32: (59, 3, 15, 12)},
    # an order-finding attempt (PR 53): 14 H, which the table write's
    # barrier flushes as one window, and IQFT(0, 14)'s 14 H and 91 cphase,
    # which the measurement's reduction flushes: every target under the
    # tile's 16 bits, so every window is one in-tile sweep and none is led
    "shor_w28.library": {16: (119, 8, 8, 0), 32: (119, 5, 5, 0)},
    "qft_w31.pager4": {16: (496, 34, 45, 15), 32: (496, 19, 30, 15)},
    # the pager's own placement pairs ten of its twelve: the ``RX`` on
    # 26 and 27 stand in one-op windows behind their prologues
    "tfim_w30.pager4": {16: (88, 9, 12, 7), 32: (88, 7, 10, 7)},
    "tfim_w30.pager4_noremap": {16: (88, 4, 12, 7), 32: (88, 2, 10, 6)},
}
# cell -> bound -> the second leads that joined a segment, an
# application: what ``fuse.kernel.leads.paired`` counts and
# ``kernel.paired_leads_per_circuit`` reads on the chip
PAIRED = {
    "rcs_w28.library": {16: 21, 32: 0},
    "qft_w28.library": {16: 0, 32: 0},
    "qft_w30.library": {16: 0, 32: 0},
    "tfim_w28.library": {16: 5, 32: 6},
    "grover_w28.library": {16: 11, 32: 12},
    "shor_w28.library": {16: 0, 32: 0},
    "qft_w31.pager4": {16: 0, 32: 0},
    "tfim_w30.pager4": {16: 5, 32: 5},
    "tfim_w30.pager4_noremap": {16: 5, 32: 6},
}
# paged cell -> bound -> (prologues, pairs, pages sent a chip, prologues
# with a shuffle of the page before and after, gates left on a paged
# qubit, windows of one op and no prologue).  Every prologue takes its
# victims on the carrier bits at either bound: its window begins at the
# gate that needs it
EXCHANGES = {
    "qft_w31.pager4": {16: (2, 4, 1.5, 0, 0, 0), 32: (2, 4, 1.5, 0, 0, 0)},
    # the bonds onto 28 and 29 are diagonal and need no prologue: the two
    # left are the RX's (4 prologues, 8 pairs, 3.0 pages until PR 47; on
    # the fixed placement 6 paged gates, four of them those bonds' CNOTs)
    "tfim_w30.pager4": {16: (2, 4, 1.5, 0, 0, 2), 32: (2, 4, 1.5, 0, 0, 2)},
    "tfim_w30.pager4_noremap": {16: (0, 0, 0.0, 0, 2, 0),
                                32: (0, 0, 0.0, 0, 2, 0)},
}
# the ops a window holds at the committed bound: full windows of 32
# gates (a bond is one gate of two ops), and the short ones a gate on a
# page bit closed (on the pager the first CNOT of a bond onto a page bit
# still closes one: the bond is not whole when ``_heads_a_window`` sees it)
SIZES = {
    "qft_w31.pager4": [2, 3, 4] + [32] * 15 + [7],
    "tfim_w28.library": [59, 23],
    "grover_w28.library": [1, 32, 26],
    "shor_w28.library": [14, 32, 32, 32, 9],
    "tfim_w30.pager4": [50, 2, 32, 1, 1, 1, 1],
    "tfim_w30.pager4_noremap": [61, 27],
}
DENSE = {"rcs_w28.library": ("rcs", 28), "qft_w28.library": ("qft", 28),
         "qft_w30.library": ("qft", 30), "tfim_w28.library": ("tfim", 28),
         "grover_w28.library": ("grover", 28),
         "shor_w28.library": ("shor", 28)}


def _dense_plan(family, width):
    with benchmark_plans(width) as windows:
        planned = windows(family)
    assert all(w["path"] == "kernel" or len(w["structure"]) == 1
               for w in planned)
    return planned, (sum(len(w["structure"]) for w in planned), len(planned),
                     sum(w["sweeps"] for w in planned),
                     sum(w["cross_tile"] for w in planned))


def _paged_windows(cell):
    """The windows of the application the cell's window times: the QFT's
    second (every one plans the same: ``SetPermutation`` resets the
    table), the Trotter chain's seventh step (a warm-up, four settled
    steps, then the window)."""
    if cell == "qft_w31.pager4":
        q = plan_only_pager(31, n_pages=PAGES)
        plans = []
        for x in (12345, (1 << 31) - 7):
            q.windows.clear()
            q.SetPermutation(x)
            q.QFT(0, 31)
            q.GetAmplitude(3)
            plans.append([(w.structure, w.swaps) for w in q.windows])
        assert plans[0] == plans[1]
        return q, list(q.windows)
    kwargs = {"remap": "off"} if cell.endswith("noremap") else {}
    q = plan_only_pager(30, n_pages=PAGES, **kwargs)
    tables = []
    for _ in range(7):
        q.windows.clear()
        issue(q, trotter_step_gates(30))
        q.GetAmplitude(0)
        tables.append(q.placement())
    # what the driver requires of the settled steps
    # (placement_is_periodic), and sooner: the table recurs from the
    # second step on, every step
    assert tables[1:] == [tables[1]] * 6 or cell.endswith("noremap")
    assert tables[4] == tables[2] and tables[6] == tables[4]
    return q, list(q.windows)


@pytest.mark.parametrize("bound", [16, 32])
@pytest.mark.parametrize("cell", sorted(PLANS))
def test_cell_plans_at_the_bound(cell, bound, monkeypatch):
    """Ops, windows, sweeps, cross-tile sweeps and paired leads of one
    application of every cell and, where the ket is paged, its
    prologues, pairs, pages sent and paged gates.  The committed bound is the last column: what
    ``fuser.sweeps_per_circuit`` (``kernel.twoq_sweeps_per_circuit``,
    ``remap.prologues_per_circuit``, ``remap.pages_sent_per_circuit``)
    read on the chip."""
    if bound == fu.DEFAULT_WINDOW:
        monkeypatch.delenv("QRACK_TPU_FUSE_WINDOW", raising=False)
    else:
        monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(bound))
    if cell in DENSE:
        family, width = DENSE[cell]
        planned, counts = _dense_plan(family, width)
        assert counts == PLANS[cell][bound]
        assert sum(fu.kernel_lowering(width, w["structure"],
                                      backend="tpu")[0]["paired"]
                   for w in planned) == PAIRED[cell][bound]
        sizes = [len(w["structure"]) for w in planned]
        if family == "tfim":
            # the bound counts gates: 27 bonds of two ops and 5 RX
            assert bound < max(sizes) <= 2 * bound
            assert bound != fu.DEFAULT_WINDOW or sizes == SIZES[cell]
        elif family == "grover":
            # the circuit cuts the windows before the bound does: an ALU
            # call is a barrier, and the oracle's flip stands alone
            assert sizes[0] == 1 and max(sizes) == bound
            assert planned[0]["structure"] == (("diag", 0, True),)
            assert bound != fu.DEFAULT_WINDOW or sizes == SIZES[cell]
        elif family == "shor":
            # the table write cuts the H layer off; the bound then cuts
            # the IQFT; no window has a target above the tile
            assert sizes[0] == 14 and set(sizes[1:-1]) == {bound}
            assert all(t < 16 for w in planned for _, t, _ in w["structure"])
            assert bound != fu.DEFAULT_WINDOW or sizes == SIZES[cell]
        else:
            assert max(sizes) == bound and set(sizes[:-1]) == {bound}
        if family == "rcs":
            # every root composed into the coupler behind it, on the
            # host: only once a window holds a cycle's 28 roots
            kinds = {k for w in planned for k, _, _ in w["structure"]}
            assert (kinds == {"u4"}) == (bound == 32)
            assert len({w["structure"] for w in planned}) \
                == {16: 12, 32: 4}[bound]
        return
    q, windows = _paged_windows(cell)
    local = q.local_bits
    lone = [w for w in windows if w.structure is None]
    assert all(len(w.tops) == 1 and not w.swaps for w in lone)
    kernel = [w for w in windows if w.structure is not None]
    plans = [fu.sharded_kernel_lowering(local, w.structure, backend="tpu")
             for w in kernel]
    assert all(why is None for _, why in plans)
    assert (sum(len(w.tops) for w in windows), len(windows),
            sum(p["sweeps"] for p, _ in plans),
            sum(p["cross"] for p, _ in plans)) == PLANS[cell][bound]
    assert sum(p["paired"] for p, _ in plans) == PAIRED[cell][bound]
    exchanges = [shb.plan_exchange(local, 2, w.swaps)
                 for w in windows if w.swaps]
    paged_gates = sum(op.kind in ("gen", "inv") and op.target >= local
                      for w in windows for op in w.tops)
    assert (len(exchanges), sum(e.k for e in exchanges),
            sum(shb.exchange_cost(local, 2, w.swaps) for w in windows),
            sum(bool(e.pre) for e in exchanges),
            paged_gates, len(lone)) == EXCHANGES[cell][bound]
    assert all(e.page_dest is None for e in exchanges)
    if bound == fu.DEFAULT_WINDOW:
        assert [len(w.tops) for w in windows] == SIZES[cell]
    if cell == "tfim_w30.pager4":
        assert {w.swaps for w in windows if w.swaps} \
            == {((27, 29), (26, 28))}


def test_the_bound_is_32_and_the_variable_overrides_it(monkeypatch):
    monkeypatch.delenv("QRACK_TPU_FUSE_WINDOW", raising=False)
    assert fu.DEFAULT_WINDOW == fu.window_len() == 32
    for value, want in [("16", 16), ("1", 1), ("0", 1), ("-3", 1),
                        ("many", 32), ("", 32)]:
        monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", value)
        assert fu.window_len() == want, value
    monkeypatch.delenv("QRACK_TPU_FUSE_WINDOW")
    for name, kwargs in [("tpu", {}), ("pager", {"n_pages": PAGES})]:
        q = create_quantum_interface(name, 6, rng=QrackRandom(1),
                                     rand_global_phase=False, **kwargs)
        assert q._fuser.window == 32, name


# ---------------------------------------------------------------------------
# a non-diagonal gate on a page bit opens a window
# ---------------------------------------------------------------------------

H2 = np.array([[1, 1], [1, -1]], dtype=np.complex128) / np.sqrt(2)
Z2 = np.diag([1.0, -1.0]).astype(np.complex128)
TOP = 13  # a page bit of a w14 ket on four pages
# calls behind five local gates -> (windows' ops, their prologues' pairs)
OPENERS = {
    # the gate closes the five and heads its own window, whose prologue
    # brings its qubit onto a carrier bit
    "gen-on-a-page-bit": ([((), H2, TOP)], ([5, 1], [0, 1])),
    "cnot-onto-a-page-bit": ([((0,), np.fliplr(np.eye(2)), TOP)],
                             ([5, 1], [0, 1])),
    # a diagonal gate needs no prologue wherever its qubit sits
    "phase-on-a-page-bit": ([((), Z2, TOP)], ([6], [0])),
    "cphase-onto-a-page-bit": ([((0,), Z2, TOP)], ([6], [0])),
    "gen-on-a-local-bit": ([((), H2, TOP - 2)], ([6], [0])),
    # the second gate on the qubit rides the prologue the first opened
    # (a controlled phase between the two: nothing merges away)
    "twice-on-one-page-bit": ([((), H2, TOP), ((0,), Z2, TOP),
                               ((), H2, TOP)], ([5, 3], [0, 1])),
    # the other page bit closes the first's window, which is planned
    # with it in view and takes both qubits in one exchange
    "both-page-bits": ([((), H2, TOP), ((), H2, TOP - 1)], ([5, 1, 1],
                                                            [0, 2, 0])),
}


@pytest.mark.parametrize("case", sorted(OPENERS))
def test_a_gate_that_needs_a_prologue_heads_a_window(case):
    calls, (sizes, pairs) = OPENERS[case]
    q = plan_only_pager(TOP + 1, n_pages=PAGES)
    issue(q, [((), H2, t) for t in range(5)] + calls)
    assert [len(w.tops) for w in q.windows] == sizes[:-1]  # the rest pends
    q.GetAmplitude(0)
    assert [len(w.tops) for w in q.windows] == sizes
    assert [len(w.swaps) for w in q.windows] == pairs
    assert not any(op.kind in ("gen", "inv") and op.target >= q.local_bits
                   for w in q.windows for op in w.tops)
    assert all(a >= q.local_bits - 2 for w in q.windows for a, _ in w.swaps)


def test_a_primed_lookahead_leaves_the_window_to_the_planner():
    """``QCircuit.Run`` (and the serve path) hand the fuser the stream
    ahead: the planner sees past the window, so the count alone ends it
    and one prologue at its head takes both page bits."""
    from qrack_tpu.layers.qcircuit import QCircuit

    circuit = QCircuit(TOP + 1)
    for t in list(range(5)) + [TOP, TOP - 1]:
        circuit.append_1q(t, H2)
    q = plan_only_pager(TOP + 1, n_pages=PAGES)
    circuit.Run(q)
    assert not q.windows and len(q._fuser.gates) == 7
    q.GetAmplitude(0)
    assert [(len(w.tops), len(w.swaps)) for w in q.windows] == [(7, 2)]


@pytest.mark.parametrize("stack,kwargs", [
    ("pager", {"n_pages": PAGES, "remap": "off"}), ("tpu", {})],
    ids=["remap-off", "dense"])
def test_no_table_no_early_flush(stack, kwargs):
    """Where no planner moves a table (the pager with ``remap="off"``,
    the dense engine) a window ends at its bound or a read alone."""
    q = create_quantum_interface(stack, TOP + 1, rng=QrackRandom(1),
                                 rand_global_phase=False, **kwargs)
    tele.reset()
    tele.enable()
    try:
        issue(q, [((), H2, t) for t in range(5)] + [((), H2, TOP),
                                                    ((), H2, TOP - 1)])
        assert len(q._fuser.gates) == 7
        q.GetAmplitude(0)
        c = tele.snapshot(include_events=False)["counters"]
    finally:
        tele.disable()
        tele.reset()
    assert {k for k in c if k.startswith(f"fuse.{stack}.flush.")} \
        == {f"fuse.{stack}.flush.read"}


# ---------------------------------------------------------------------------
# a full window of 32 mixed ops through the kernel (the Pallas
# interpreter here) against per-gate dispatch
# ---------------------------------------------------------------------------

N = 10


def _mixed_stream(rng, pairs):
    """100 gate calls of every kind a window holds: general and
    anti-diagonal 2 x 2, diagonal and controlled phases, controlled
    general gates and, where the engine queues them, two-qubit gates.
    Some merge at queue time (a single-qubit gate onto the last on its
    qubit, or into a pair); more than 64 ops are left."""
    calls = []
    for i in range(100):
        t = int(rng.integers(0, N))
        c = (t + 1 + int(rng.integers(0, N - 1))) % N
        pick = i % (7 if pairs else 6)
        angle = float(rng.uniform(0.1, 3.0))
        if pick == 0:
            calls.append(("U", (t, angle, angle / 2, angle / 3)))
        elif pick == 1:
            calls.append(("CNOT", (c, t)))
        elif pick == 2:
            calls.append(("RZ", (angle, t)))
        elif pick == 3:
            calls.append(("CZ", (c, t)))
        elif pick == 4:
            calls.append(("CH", (c, t)))
        elif pick == 5:
            calls.append(("Y", (t,)))
        else:
            calls.append(("ISwap", (min(c, t), max(c, t))))
    return calls


@pytest.mark.parametrize("seed", [3, 4])
@pytest.mark.parametrize("name,kwargs", [("tpu", {}),
                                         ("pager", {"n_pages": PAGES})],
                         ids=["tpu", "pager"])
def test_full_windows_of_32_match_per_gate_dispatch(name, kwargs, seed,
                                                    monkeypatch):
    """The same calls at the committed bound, kernel on, and at
    ``QRACK_TPU_FUSE_WINDOW=1``: the bound forces two flushes of 32
    merged ops before the read's (on the pager, whose ket of 10 qubits
    has two on page bits, most windows end sooner, at a gate that needs
    a prologue), every window takes the kernel, and the kets agree to
    float32 rounding with each other and the CPU engine."""
    calls = _mixed_stream(np.random.default_rng(seed), pairs=name == "tpu")
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")

    def run(stack, **kw):
        q = create_quantum_interface(stack, N, rng=QrackRandom(seed),
                                     rand_global_phase=False, **kw)
        q.SetPermutation(0b1011001101)
        for op, args in calls:
            getattr(q, op)(*args)
        return np.asarray(q.GetQuantumState())

    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "1")
    per_gate = run(name, **kwargs)
    monkeypatch.delenv("QRACK_TPU_FUSE_WINDOW")
    tele.reset()
    tele.enable()
    try:
        fused = run(name, **kwargs)
        snap = tele.snapshot(include_events=False)
    finally:
        tele.disable()
        tele.reset()
    c = snap["counters"]
    full = c.get(f"fuse.{name}.flush.window_full", 0)
    # the pager's windows also end where a gate lands on a page bit
    early = c.get(f"fuse.{name}.flush.paged_target", 0)
    assert (full >= 2 and not early) if name == "tpu" else early >= 2
    assert c["fuse.kernel.windows"] >= 3 and "fuse.xla.windows" not in c
    assert c["fuse.kernel.ops"] >= 64
    lengths = snap["hists"][f"fuse.{name}.window_len"]
    assert lengths["count"] == full + early + 1
    assert lengths["max"] >= 32
    assert np.max(np.abs(fused - per_gate)) < 2e-6
    assert np.max(np.abs(fused - run("cpu"))) < 2e-6
