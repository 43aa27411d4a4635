"""The pager's own placement under the Trotter step of the benchmark's
cell ``tfim_w30.pager4``, on four host devices at w12 to w14: the
planner's table moves under the first steps, then recurs; the remap
prologue (ops/sharded.apply_remap) is a relabelling and nothing else.

What the cell's driver (benchmarks/drivers/library_settled.py) assumes
is held here without a chip: after four settled steps the table is
periodic and no step builds a program."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from qrack_tpu import QEngineCPU, create_quantum_interface
from qrack_tpu import telemetry as tele
from qrack_tpu.ops import sharded as shb
from qrack_tpu.parallel import pager
from qrack_tpu.utils.rng import QrackRandom

from helpers import issue, plan_only_pager, trotter_step_gates

PAGES = 4
SETTLE = 4  # benchmarks/traffic/pager4.json: settle_applications


def _pager(width):
    return create_quantum_interface("pager", width, n_pages=PAGES,
                                    rng=QrackRandom(3),
                                    rand_global_phase=False)


@pytest.mark.parametrize("width,kernel", [(12, "off"), (13, "off"),
                                          (14, "off"), (12, "on")])
def test_six_steps_match_the_cpu_engine(width, kernel, monkeypatch):
    """Every amplitude after each of six steps, one read a step as the
    cell makes it; ``on`` takes the per-page kernel under the Pallas
    interpreter, ``off`` the XLA chain, both behind the same prologue."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", kernel)
    gates = trotter_step_gates(width)
    x = 0b101100111010 & ((1 << width) - 1)
    o = QEngineCPU(width, rng=QrackRandom(3), rand_global_phase=False)
    q = _pager(width)
    o.SetPermutation(x)
    q.SetPermutation(x)
    moved = False
    for step in range(6):
        issue(o, gates)
        issue(q, gates)
        assert abs(q.GetAmplitude(x) - o.GetAmplitude(x)) < 1e-6
        moved |= q.placement() != tuple(range(width))
        want = np.asarray(o.GetQuantumState())
        got = np.asarray(q.GetQuantumState())  # undoes the remap: a read
        assert np.max(np.abs(got - want)) < 2e-6, step
    assert moved


@pytest.mark.parametrize("width", [12, 13, 14, 22, 30])
def test_the_table_recurs_and_no_later_step_plans_anew(width):
    """The planner replayed on the host (no ket): the table after the
    last of the driver's settled steps is the one two steps before (the
    one a step before, from the second step on), and from step 2 on
    every window is one an earlier step planned, prologue and all (from
    step 3 until PR 46), so a window opened after four steps builds
    nothing.  w30 is
    the cell's own width."""
    q = plan_only_pager(width, n_pages=PAGES)
    gates = trotter_step_gates(width)
    q.SetPermutation(1)
    tables, planned, new = [], set(), []
    for step in range(SETTLE + 4):
        q.windows.clear()
        issue(q, gates)
        q.GetAmplitude(0)
        tables.append(q.placement())
        keys = {(w.structure, w.swaps) for w in q.windows}
        new.append(len(keys - planned))
        planned |= keys
        # no gate is left on a paged qubit, at any width: one that lands
        # on a page bit opens a window (``GateStreamFuser.
        # _heads_a_window``), so its prologue finds the victims cold
        paged = sum(op.kind in ("gen", "inv") and op.target >= width - 2
                    for w in q.windows for op in w.tops)
        assert paged == 0
    # what the driver requires: a step's plan follows from the table it
    # starts on, so once a table recurs two steps on, every later step
    # starts where a settled one did
    assert tables[SETTLE - 1] == tables[SETTLE - 3]
    assert tables[SETTLE - 1] != tuple(range(width))
    assert new[0] > 0 and new[2:] == [0] * (len(new) - 2), new
    assert tables[1:] == [tables[1]] * (len(tables) - 1)
    for step in range(SETTLE, len(tables)):
        assert tables[step] == tables[step - 2]


def test_no_program_is_built_from_step_two_on():
    """The same on the engine: the pager's program cache takes no miss
    once two steps have run (program keys carry the swaps)."""
    width = 14
    q = _pager(width)
    gates = trotter_step_gates(width)
    q.SetPermutation(5)
    pager._PROGRAMS.clear()  # an earlier test's steps on the same mesh
    misses = []
    for step in range(SETTLE + 2):
        before = pager._PROGRAMS.misses
        issue(q, gates)
        q.GetAmplitude(step)
        misses.append(pager._PROGRAMS.misses - before)
    assert misses[0] > 0 and misses[2:] == [0] * (len(misses) - 2), misses


def test_placement_is_the_table_and_flushes_nothing():
    width = 12
    q = _pager(width)
    assert q.placement() == tuple(range(width))
    gates = trotter_step_gates(width)
    issue(q, gates)
    q.GetAmplitude(0)
    issue(q, gates[:5])  # a pending window
    pending = len(q._fuser.gates)
    assert pending > 0
    table = q.placement()
    assert isinstance(table, tuple) and table == tuple(q._qmap)
    assert table != tuple(range(width))
    assert len(q._fuser.gates) == pending  # read, not flushed
    tele.reset()  # whatever an earlier file of this worker left counted
    tele.enable()
    try:
        assert q.placement() == table
        counters = tele.snapshot(include_events=False)["counters"]
    finally:
        tele.disable()
        tele.reset()
    assert not any(k.startswith(("remap.", "exchange.")) for k in counters)


def test_prologue_counters_follow_the_lowering():
    """``remap.pager.prologues.k<k>`` and ``page_perms`` count what
    ``plan_exchange`` lowers, and the bytes are the benchmark's sum:
    state bytes x sum of (1 - 2^-k) over the prologues."""
    width = 14
    q = _pager(width)
    gates = trotter_step_gates(width)
    q.SetPermutation(9)
    tele.enable()
    try:
        for step in range(SETTLE):
            issue(q, gates)
            q.GetAmplitude(step)
        counters = tele.snapshot(include_events=False)["counters"]
    finally:
        tele.disable()
        tele.reset()
    by_k = {int(k.rsplit(".k", 1)[1]): v for k, v in counters.items()
            if k.startswith("remap.pager.prologues.k")}
    assert by_k and set(by_k) <= {1, 2}
    assert sum(by_k.values()) == counters["remap.pager.windows"]
    assert counters.get("remap.pager.page_perms", 0) == 0
    assert counters.get("exchange.pager.global_2x2", 0) == 0
    state_bytes = 2 * 4 << width
    assert counters["exchange.pager.bytes"] == state_bytes * sum(
        n * (1 - 2.0 ** -k) for k, n in by_k.items())


# ---------------------------------------------------------------------------
# the relabelling, bit for bit
# ---------------------------------------------------------------------------

L, G = 5, 2
N = L + G


def _sharded(fn, state):
    mesh = Mesh(np.array(jax.devices()[:1 << G]), ("pages",))
    prog = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(None, "pages"),
                                 out_specs=P(None, "pages")))
    return np.asarray(prog(jax.device_put(
        state, NamedSharding(mesh, P(None, "pages")))))


def _relabelled(state, swaps):
    """numpy's answer: amplitude i goes where the transpositions of its
    index's bits, in order, send i."""
    src = shb.compose_swaps(N, swaps)
    j = np.zeros(1 << N, dtype=np.int64)
    for p in range(N):
        j |= ((np.arange(1 << N) >> p) & 1) << src[p]
    return state[:, j]


def _state():
    # every amplitude its own value: a relabelling cannot hide
    return np.arange(2 << N, dtype=np.float32).reshape(2, 1 << N)


@pytest.mark.parametrize("gpos", range(G))
@pytest.mark.parametrize("lpos", range(L))
def test_mixed_swap_is_a_permutation(lpos, gpos):
    """One in-page bit against one page bit: the k = 1 plan, with the
    shuffle onto the carrier bit and back where ``lpos`` is below it."""
    swaps = ((lpos, L + gpos),)
    got = _sharded(lambda x: shb.apply_remap(x, 1 << G, L, swaps), _state())
    np.testing.assert_array_equal(got, _relabelled(_state(), swaps))


@pytest.mark.parametrize("gpos", [(0,), (1,), (0, 1), (1, 0)],
                         ids=lambda g: "g" + "".join(map(str, g)))
def test_batched_mixed_swap_is_a_permutation(gpos):
    """Carrier bit ``L - k + j`` against page bit ``gpos[j]``: every k
    and pairing four pages allow."""
    k = len(gpos)
    got = _sharded(lambda x: shb.batched_mixed_swap(x, 1 << G, k, gpos),
                   _state())
    swaps = tuple((L - k + j, L + gp) for j, gp in enumerate(gpos))
    np.testing.assert_array_equal(got, _relabelled(_state(), swaps))


def _planner_swaps():
    """Every prologue the planner emits over six Trotter steps at w12 to
    w14, as transpositions of a page of ``L`` bits: the victim's offset
    from the top of the page kept."""
    found = set()
    for width in (12, 13, 14):
        q = plan_only_pager(width, n_pages=PAGES)
        shift = (width - G) - L
        for _ in range(6):
            issue(q, trotter_step_gates(width))
            q.GetAmplitude(0)
        found |= {tuple((max(a - shift, 0), b - shift) for a, b in w.swaps)
                  for w in q.windows if w.swaps}
    return sorted(found)


# a prologue's window begins at the gate that needs it, so the planner's
# victims are the carrier bits; elsewhere by hand
_BY_HAND = [((0, L), (1, L + 1)), ((2, L + 1),), ((L - 3, L + 1), (1, L)),
            ((L - 1, L + 1),), ((L - 1, L), (L - 2, L + 1))]


@pytest.mark.parametrize("source", ["planner", "by_hand"])
def test_the_planners_prologues_are_permutations(source):
    swaps_seen = _planner_swaps() if source == "planner" else _BY_HAND
    assert len(swaps_seen) >= 2
    for swaps in swaps_seen:
        got = _sharded(lambda x: shb.apply_remap(x, 1 << G, L, swaps),
                       _state())
        np.testing.assert_array_equal(got, _relabelled(_state(), swaps),
                                      err_msg=str(swaps))


def test_a_victim_on_a_carrier_bit_needs_no_shuffle():
    """``plan_exchange`` lets a crossing content that sits on one of the
    top k local bits ride that carrier: no pass over the page before the
    exchange and none after; a victim elsewhere is moved there and
    back."""
    for swaps in [((L - 1, L + 1),), ((L - 1, L),),
                  ((L - 2, L), (L - 1, L + 1)), ((L - 1, L), (L - 2, L + 1))]:
        plan = shb.plan_exchange(L, G, swaps)
        assert (plan.pre, plan.post, plan.page_dest) == ((), (), None), swaps
        assert plan.k == len(swaps)
    plan = shb.plan_exchange(L, G, ((1, L + 1),))
    assert plan.pre == plan.post == ((1, L - 1),) and plan.gpos == (1,)
