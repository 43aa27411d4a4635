"""Compile the main path's window programs for a described TPU v5e at
w28 (a 2 GiB float32 ket; the pager's at w30, a 2 GiB page, and at w31,
a 4 GiB page: the cell ``qft_w31.pager4``), without a chip.

Nothing here runs: each case hands the chip's own compiler the shapes
and asserts that it accepts them (section 2 of the on-chip-measurement
guide).  The interpret-mode parity tests cannot see what this sees: a
view Mosaic cannot lay out, a temporary that does not fit 16 GiB, a
copy of the ket that XLA puts beside a launch.  A kernel window sweeps
its donated ket in place (every launch aliases its planes to its
result, PR 39): its program holds no ket of temporaries and no ket-sized
``copy``, which the dense cases assert; the pager's programs keep the
exchange's buffers.

The plans are the fuser's at its bound of 32 ops a window (16 until
PR 46): a QFT's window holds two of its runs (``gen``, fifteen
``cphase``, twice), a random circuit's is 32 ``u4``, the widest operand
column the kernel's SMEM holds (64 are refused: the last case of the
w28 part).

The topology is described inside a module-scoped fixture, never at
import: only the worker that is handed this file loads the TPU library.
"""

import os
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk

W = 28
KET_BYTES = 2 * 4 << W  # (2, 2^28) float32
SLACK = KET_BYTES >> 6    # operands, masks and the compiler's own scratch

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _ops(structure):
    """Placeholder ops of a window structure: the fuser's own packer
    then gives the two operand columns' shapes, so they cannot drift."""
    return [fu.FusedOp(kind, target, int(has_ctrl), int(has_ctrl),
                       np.eye(4 if kind == "u4" else 2))
            for kind, target, has_ctrl in structure]


def _args(operands, state_sharding, operand_sharding, n=W):
    """Shapes of (planes, *operands), nothing allocated on a device."""
    return [jax.ShapeDtypeStruct((2, 1 << n), jnp.float32,
                                 sharding=state_sharding)] + [
        jax.ShapeDtypeStruct(np.shape(o), o.dtype, sharding=operand_sharding)
        for o in operands]


def _kernel_operands(structure, split_at=None):
    """A kernel window's two columns, the runs' plans behind the masks."""
    ops, bp = _ops(structure), pk.DEFAULT_BLOCK_POW
    if split_at is not None:
        structure, bp = fu.sharded_structure_of(ops), min(bp, split_at)
    return fu.pack_operands(ops, jnp.float32, split_at=split_at,
                            runs=fu.kernel_runs(structure, bp, split_at))


def _dense_args(structure, sharding, n=W):
    return _args(_kernel_operands(structure), sharding, sharding, n=n)


def _compile(fn, args):
    """Compiled for the described chip, the planes donated as the engine
    and the pager donate them; the compiler's seconds (Mosaic's, for a
    kernel window: XLA's own part is the operands' slices and nothing of
    the ket's size) go to the test's output, where ``pytest -rP`` or a
    failure shows them, so that a change which multiplies them is seen
    without a chip."""
    lowered = jax.jit(fn, donate_argnums=(0,)).lower(*args)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    print(f"compile_s={time.perf_counter() - t0:.2f} "
          f"{getattr(fn, '__name__', fn)} "
          f"temp_bytes={compiled.memory_analysis().temp_size_in_bytes}")
    return compiled


def _launches(compiled):
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _in_place(compiled, n=W):
    """No ket of temporaries and no ket-sized copy: every launch of the
    program writes the donated ket it reads."""
    copies = re.findall(r"f32\[2,%d\]\S* copy(?:-start)?\(" % (1 << n),
                        compiled.as_text())
    return (not copies
            and compiled.memory_analysis().temp_size_in_bytes <= SLACK)


# the tail of the Trotter step's last window at w28 (RX on 15-27: 7
# planned sweeps for 13 ops, 6 of them cross-tile, each led by two RX
# since PR 50; 13 and 12 until then) and of the paged step's at 2^28
# pages (RX on 25-29: 28 and 29 are paged); PR 35 sent both to the
# kernel, and they were whole windows until a bond became one gate
# (PR 47: the windows now begin at the RX on 5 and on 3)
TFIM_LAST = tuple(("gen", t, False) for t in range(15, 28))
TFIM_LAST_PAGED = tuple(("gen", t, False) for t in range(25, 30))



def _qft_runs(top, runs=2):
    """``runs`` times a ``gen`` and fifteen ``cphase`` below it, the
    ``gen`` one qubit lower each time: what a QFT's window of 32 holds
    (one run was PR 5's window of 16)."""
    return tuple(op for r in range(runs) for op in (
        (("gen", top - r, False),)
        + tuple(("cphase", (top - r - 1 - k) % top, True) for k in range(15))))


QFT32 = _qft_runs(27)
# the same window with every target inside the tile: what most of a
# QFT's sweeps are, and the body with the most arithmetic a tile
QFT32_INTILE = _qft_runs(15)


@pytest.mark.parametrize("kind,target", [
    ("gen", 0), ("gen", 3), ("gen", 6), ("gen", 7), ("gen", 27),
    ("inv", 0), ("inv", 6)])
def test_xla_one_op_window(one_chip, kind, target):
    structure = ((kind, target, False),)
    compiled = _compile(fu.window_fn(W, structure),
                        _dense_args(structure, one_chip))
    # at most two kets beside the donated one
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * KET_BYTES + SLACK


@pytest.mark.parametrize("structure", [
    (("gen", 0, False),), (("gen", 3, False),), (("gen", 6, False),),
    (("gen", 7, False),), (("gen", 15, False),),
    (("inv", 2, True),), (("inv", 12, True),),
    (("gen", 20, True), ("cphase", 3, True)),
    # the pager's per-page run at w28 / 4 pages that asked for 17 MiB of
    # VMEM: a controlled cross-tile gen with five cphases behind it
    (("gen", 17, True),) + (("cphase", 18, True),) * 5,
    QFT32_INTILE,
    # a controlled cross-tile gen whose mixed value goes on through
    # lane, sublane and whole-vreg pair ops and cphases
    (("gen", 21, True), ("gen", 3, False), ("cphase", 20, True),
     ("inv", 8, True), ("cphase", 5, True), ("gen", 12, True)),
], ids=lambda s: "-".join(f"{k}{t}{'c' if c else ''}" for k, t, c in s))
def test_kernel_window(one_chip, structure):
    compiled = _compile(pk.make_window_fn(W, structure),
                        _dense_args(structure, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    assert _in_place(compiled)


def test_qft_window_xla(one_chip):
    compiled = _compile(fu.window_fn(W, QFT32), _dense_args(QFT32, one_chip))
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * KET_BYTES + SLACK


@pytest.mark.parametrize("n", [W, 30], ids=["w28", "w30"])
def test_qft_window_kernel(one_chip, n):
    """The widest QFT window the bound of 32 builds: two led launches,
    each a ``gen`` above the tile with its run of fifteen ``cphase``
    behind it, at w28 and on the 8 GiB ket of w30."""
    structure = _qft_runs(n - 1)
    assert len(structure) == fu.DEFAULT_WINDOW == 32
    compiled = _compile(pk.make_window_fn(n, structure),
                        _dense_args(structure, one_chip, n=n))
    assert _launches(compiled) == 2
    assert compiled.memory_analysis().alias_size_in_bytes == 2 * 4 << n
    assert _in_place(compiled, n)


def test_tfim_last_window_kernel(one_chip):
    """7 launches in one program (13 until two leads shared one,
    PR 50), each on the result of the one before: none beside the
    donated ket (two kets in flight until PR 39)."""
    plan, why = fu.kernel_lowering(W, TFIM_LAST, backend="tpu")
    assert why is None
    assert (plan["sweeps"], plan["cross"], plan["paired"]) == (7, 6, 6)
    compiled = _compile(pk.make_window_fn(W, TFIM_LAST),
                        _dense_args(TFIM_LAST, one_chip))
    assert _launches(compiled) == 7
    assert _in_place(compiled)


def _sharded_program(topo, structure, n, npg=4, remap=()):
    """``(fn, args)``: the pager's per-page kernel body of a window
    under ``shard_map`` on a 2x2 mesh, with the planner's transpositions
    ``remap`` as its prologue, and the shapes of its arguments."""
    L = n - 2
    mesh = Mesh(np.array(topo.devices[:npg]), ("pages",))
    ops = _ops(structure)
    body = fu.sharded_kernel_window_body(L, npg, fu.sharded_structure_of(ops),
                                         remap=remap)
    args = _args(_kernel_operands(structure, split_at=L),
                 NamedSharding(mesh, P(None, "pages")),
                 NamedSharding(mesh, P()), n=n)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, "pages"),) + (P(),) * (len(args) - 1),
                       out_specs=P(None, "pages"), check_vma=False)
    return fn, args


def _compile_sharded(topo, structure, n, npg=4, remap=(), exchanges=True):
    """That program compiled for the described chips."""
    compiled = _compile(*_sharded_program(topo, structure, n, npg, remap))
    text = compiled.as_text()
    assert ("collective-permute" in text) == exchanges
    assert "tpu_custom_call" in text
    return compiled


def test_sharded_kernel_window_four_pages(topo):
    """One global gen (pair exchange over the pages axis) and one local
    low-lane gen."""
    compiled = _compile_sharded(
        topo, (("gen", 27, False), ("gen", 3, False)), W)
    # bytes of one device: two and a half pages by the compiler's count,
    # the exchange's (the partner's half, the eight half-planes of the
    # pair arithmetic).  Three until PR 39; the alias alone read three
    # and a half, the donated page's buffer standing idle while both
    # halves of the mixed page (the launch's input, bound to that
    # buffer) were made beside it: the exchange now picks coefficients
    # by page instead of operands and keeps no (a, b) halves
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 5 * KET_BYTES // 8 + SLACK


def test_tfim_last_window_sharded_kernel(topo):
    """The paged Trotter step's last window at w30: two local
    cross-tile launches on a 2 GiB page (the RX on 25 and 26 share one
    since PR 50), then two exchanges."""
    plan, why = fu.sharded_kernel_lowering(W, TFIM_LAST_PAGED, backend="tpu")
    assert why is None
    assert (plan["sweeps"], plan["cross"], plan["paired"]) == (4, 2, 1)
    t0 = time.perf_counter()
    compiled = _compile_sharded(topo, TFIM_LAST_PAGED, W + 2)
    # 2 s with the exchange's halves sliced off the minor axis; 1172 s
    # with a (planes, 2, half) view of a launch's result (PR 35)
    assert time.perf_counter() - t0 < 120
    assert _launches(compiled) == 2
    # a page is a w28 ket here: two and a half by the compiler's count
    # (three and a half until PR 39; the step's sixth window, launches
    # between controlled exchanges, reads 3.06, 3.63 before)
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 5 * KET_BYTES // 2 + SLACK


# the paged Trotter step's windows at w30 since a bond is one gate of two
# controlled ``diag`` (PR 47): the fixed placement's two (58 ``diag`` and
# the RX on 0-2 in one launch and no exchange: the bonds onto 28 and 29
# are phases by page; then the RX on 3-29, 7 launches and the step's
# two exchanges) and the settled planner step's three that begin with
# no prologue (50 ``diag``; the two ``diag`` a bond onto a page bit
# leaves behind the window its first CNOT closed; the RX on 0-25 and the
# bonds between them, 6 launches).  The RX on 16-27 (16-25) reach the
# per-page kernel controlled (``fusion._sharded_run_structure``) and
# share a launch two by two since PR 50: 13 and 11 launches until then.
# name -> (pager's keywords, window, ops, launches, exchanges?, pages of
# temporaries in eighths)
TFIM_PAGED_WINDOWS = {
    "fixed-61op": ({"remap": "off"}, 0, 61, 1, False, 0),
    "fixed-27op": ({"remap": "off"}, 1, 27, 7, True, 20),
    "planner-50op": ({}, 0, 50, 1, False, 0),
    "planner-2op": ({}, 1, 2, 1, False, 0),
    "planner-32op": ({}, 2, 32, 6, False, 0),
}


@pytest.mark.parametrize("name", sorted(TFIM_PAGED_WINDOWS))
def test_tfim_step_window_sharded_kernel(topo, name):
    """Each compiles in seconds under ``shard_map`` at a 2 GiB page.  A
    window of ``diag`` alone sweeps the donated page in place: no
    collective, nothing of the page's size beside it (the fixed
    placement's third window of 32, launches between the four controlled
    exchanges of the CNOTs onto 28 and 29, held 3.06 pages of
    temporaries until those CNOTs went).  The window with the step's two
    exchanges keeps their two and a half pages."""
    from helpers import issue, plan_only_pager, trotter_step_gates

    kwargs, index, ops, launches, exchanges, eighths = TFIM_PAGED_WINDOWS[name]
    q = plan_only_pager(W + 2, **kwargs)
    for _ in range(2):  # the planner's table recurs from the second step
        q.windows.clear()
        issue(q, trotter_step_gates(W + 2))
        q.GetAmplitude(0)
    window = q.windows[index]
    assert len(window.structure) == ops and not window.swaps
    assert exchanges == any(op[0] == "gen" and op[1] >= W
                            for op in window.structure)
    plan, why = fu.sharded_kernel_lowering(W, window.structure, backend="tpu")
    assert why is None and plan["sweeps"] == launches + 2 * exchanges
    t0 = time.perf_counter()
    compiled = _compile_sharded(topo, window.structure, W + 2,
                                exchanges=exchanges)
    assert time.perf_counter() - t0 < 120
    assert _launches(compiled) == launches
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == KET_BYTES
    assert memory.temp_size_in_bytes <= eighths * KET_BYTES // 8 + SLACK


# every window of the paged Trotter step at w30 that begins with a remap
# prologue, on the pager's default placement.  A gate that needs one
# heads its window (``GateStreamFuser._heads_a_window``, PR 46), so
# these windows are short and their victims sit on the carrier bits:
# step 0 brings 28 and 29 in ahead of the RX on 28; from step 1 on
# every step runs the same program twice (a lone RX behind two pairs:
# the bonds onto the page bits are diagonal since PR 47 and need none).
# A step through ``QCircuit.Run`` primes the planner's lookahead, so
# the count alone ends a window: its settled first window is 61 ops
# behind two pairs.  Beside them what other circuits make
# the planner emit: one pair whose victim sits on a sublane bit, two
# pairs with victims on lane bits (shuffles of the whole page before and
# after the exchange), one pair and two pairs on the carrier bits
# themselves (no shuffle at all)
REMAP_PROGRAMS = ("step0-rx", "settled-rx", "qcircuit-61op", "k1-sublane",
                  "k2-lanes", "k1-carrier", "k2-carriers")
PAGED_W = W + 2


@pytest.fixture(scope="module")
def remap_programs():
    """``name -> (structure, swaps)``: the planner replayed on the host
    through the pager's own gate funnel, no ket allocated."""
    from helpers import issue, plan_only_pager, trotter_step_gates

    q = plan_only_pager(PAGED_W)
    gates = trotter_step_gates(PAGED_W)
    steps = []
    for _ in range(4):
        q.windows.clear()
        issue(q, gates)
        q.GetAmplitude(0)
        steps.append([(w.structure, w.swaps) for w in q.windows])
    # the settled step: every step from the second on plans the same,
    # table and all
    assert steps[1] == steps[2] == steps[3]
    settled, = {w for w in steps[1] if w[1]}
    first, = [w for w in steps[0] if w[1]]
    assert len(settled[0]) == len(first[0]) == 1 and first != settled
    assert sum(bool(w[1]) for w in steps[1]) == 2
    from qrack_tpu.models.algorithms import trotter_qcircuit

    circuit = trotter_qcircuit(PAGED_W, steps=1)
    q = plan_only_pager(PAGED_W)
    for _ in range(3):
        q.windows.clear()
        circuit.Run(q)
        q.GetAmplitude(0)
    primed = q.windows[0]
    assert len(primed.structure) == 61 and len(primed.swaps) == 2
    out = {"step0-rx": first, "settled-rx": settled,
           "qcircuit-61op": (primed.structure, primed.swaps)}
    local = tuple(("gen", t, False) for t in (3, 12, 20, 27))
    out["k1-sublane"] = (local, ((7, 29),))
    out["k2-lanes"] = (local, ((0, 28), (1, 29)))
    out["k1-carrier"] = (local, ((27, 28),))
    out["k2-carriers"] = (local, ((26, 28), (27, 29)))
    assert sorted(out) == sorted(REMAP_PROGRAMS)
    return out


@pytest.mark.parametrize("name", REMAP_PROGRAMS)
def test_remap_prologue_sharded_kernel(topo, remap_programs, name):
    """A remap prologue ahead of the window's launches, at a 2 GiB page:
    seconds to compile (935 s while ``batched_mixed_swap`` viewed the
    page as ``(planes, 2^k, -1)``: PERF.md §6, PR 30; 1.5 to 3.7 s with
    the sub-blocks sliced off the minor axis, PR 38) and at most three
    pages of temporaries beside the donated page (three and a half
    until the launches wrote in place, PR 39)."""
    from qrack_tpu.ops import sharded as shb

    structure, swaps = remap_programs[name]
    plan = shb.plan_exchange(PAGED_W - 2, 2, swaps)
    expected = {"k1-sublane": (1, ((7, 27),)), "k2-lanes": (2, ((0, 26), (1, 27))),
                "k1-carrier": (1, ()), "k2-carriers": (2, ())}.get(name)
    if expected is not None:
        assert (plan.k, plan.pre) == expected and plan.post == plan.pre
    else:
        # the step's prologues ride the carrier bits: no pass over the
        # page before the exchange or after
        assert plan.k == 2 and plan.page_dest is None and not plan.pre
    t0 = time.perf_counter()
    compiled = _compile_sharded(topo, structure, PAGED_W, remap=swaps)
    assert time.perf_counter() - t0 < 120
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 3 * KET_BYTES + SLACK


def _u4(lo, hi):
    return ("u4", (lo, hi), False)


# windows that hold each segment shape a two-target op has: in the tile,
# leading the pair grid, leading the four-tile grid (a random-circuit
# sample's own four windows of 32 ``u4`` are ``CELL_WINDOWS``' below; these
# are what its stream flushed at the bound of 16, and what a circuit
# whose roots do not all merge still builds)
RCS_WINDOWS = {
    # couplers with their roots composed in, on the quad grid, the last
    # with the next cycle's in-tile couplers riding behind it
    "intile-and-quad": tuple(_u4(a, a + 1) for a in (8, 10, 12, 14))
    + tuple(_u4(a, a + 1) for a in range(16, 28, 2))
    + tuple(_u4(a, a + 1) for a in (1, 3, 5, 7, 9, 11)),
    # bare cross-tile roots, then the coupler across the tile's edge
    "cross-pair-quad": tuple(("gen", t, False) for t in range(21, 28))
    + tuple(_u4(a, a + 1) for a in (1, 3, 5, 7, 9, 11, 13))
    + (_u4(15, 16), _u4(17, 18)),
    # sixteen ops in one tile, eight of them 4x4: the most arithmetic
    "intile-16": tuple(_u4(a, a + 1) for a in range(0, 16, 2))
    + tuple(("gen", t, False) for t in range(8)),
    # the bound's worth of 4x4 in one tile, on every pairing of lane,
    # sublane and vreg bits: the most arithmetic and the most operands
    "intile-32": tuple(_u4(a, a + 1) for a in range(0, 16, 2))
    + tuple(_u4(a, a + 1) for a in range(1, 15, 2))
    + tuple(_u4(a, a + 8) for a in range(8))
    + tuple(_u4(a, 15 - a) for a in range(8)) + (_u4(0, 15),),
}


@pytest.mark.parametrize("window", sorted(RCS_WINDOWS))
def test_two_qubit_window_kernel(one_chip, window):
    """Each new segment shape compiles for the chip within 60 s (1.5 to
    5 s when written, PR 36) and sweeps the donated ket in place."""
    structure = RCS_WINDOWS[window]
    plan, why = fu.kernel_lowering(W, structure, backend="tpu")
    assert why is None
    expected = {"intile-and-quad": (1, 0, 6), "cross-pair-quad": (0, 1, 1),
                "intile-16": (1, 0, 0), "intile-32": (1, 0, 0)}[window]
    assert tuple(plan["twoq"][f"sweeps.{k}"]
                 for k in ("intile", "pair", "quad")) == expected
    t0 = time.perf_counter()
    compiled = _compile(pk.make_window_fn(W, structure),
                        _dense_args(structure, one_chip))
    assert time.perf_counter() - t0 < 60
    text = compiled.as_text()
    assert _launches(compiled) == plan["sweeps"]
    for name, count in zip((pk.TWOQ_INTILE_KERNEL_NAME, pk.TWOQ_PAIR_KERNEL_NAME,
                            pk.TWOQ_QUAD_KERNEL_NAME), expected):
        assert (name in text) == bool(count)
    assert _in_place(compiled)


# the window programs of one application of the three dense cells at
# w28, by the families' own gate lists: QFT's 13 windows (7, 4, 3
# launches, then ten of one), the Trotter step's 2 (1, 7: 59 ops, 54
# of them the bonds' ``diag``, and 23, the RX on 16-27 two a launch
# since PR 50; 1, 13 until then and 4 of 1, 14, 12, 13 until PR 47)
# and a random circuit's 4 (13, 15, 16, 7), every one a program of its
# own and every one compiled (26, 7 and 12 structures in 14 windows at
# the bound of 16, of which 27 were)
CELL_WINDOWS = [("qft", i) for i in range(13)] \
    + [("tfim", i) for i in range(2)] + [("rcs", i) for i in range(4)]
CELL_SWEEPS = {"qft": [7, 4, 3] + [1] * 10, "tfim": [1, 7],
               "rcs": [13, 15, 16, 7]}


def _plan_shape(structure):
    """What a window's program is made of, the targets left out: each
    segment's kernel, its lead and the kinds that ride behind it."""
    return tuple(
        (pk.segment_kernel_name(seg, pk.DEFAULT_BLOCK_POW),
         tuple((kind, ctrl) for _, kind, _, ctrl in seg["leads"]),
         tuple(sorted((kind, ctrl) for _, kind, _, ctrl in seg["ops"])))
        for seg in pk.plan_window(structure, pk.DEFAULT_BLOCK_POW))


@pytest.fixture(scope="module")
def cell_windows():
    """``family -> its distinct window structures``, in the order the
    fuser flushes them (``helpers.benchmark_plans``)."""
    from helpers import benchmark_plans

    with benchmark_plans(W) as windows:
        out = {family: list(dict.fromkeys(
                   w["structure"] for w in windows(family)
                   if w["path"] == "kernel"))
               for family in CELL_SWEEPS}
    assert {f: len(structures) for f, structures in out.items()} \
        == {f: len(sweeps) for f, sweeps in CELL_SWEEPS.items()}
    assert {_plan_shape(s) for structures in out.values() for s in structures} \
        == {_plan_shape(out[f][i]) for f, i in CELL_WINDOWS}
    return out


@pytest.mark.parametrize("family,index", CELL_WINDOWS,
                         ids=[f"{f}-w{i + 1:02d}" for f, i in CELL_WINDOWS])
def test_cell_window_sweeps_its_ket_in_place(one_chip, cell_windows, family,
                                             index):
    """What ``xla.ms_per_circuit`` lost with PR 39, held without a chip:
    a window program of a dense cell is its planned launches and
    nothing of the ket's size beside them (a one-sweep window held one
    ``copy`` of ``f32[2,268435456]`` back into the donated ket, 6.53 ms
    on the chip: 21 of QFT's 26 windows of 16, three of the Trotter
    step's 7, one of a random circuit's)."""
    structure = cell_windows[family][index]
    plan, why = fu.kernel_lowering(W, structure, backend="tpu")
    assert why is None and plan["sweeps"] == CELL_SWEEPS[family][index]
    compiled = _compile(pk.make_window_fn(W, structure),
                        _dense_args(structure, one_chip))
    assert _launches(compiled) == plan["sweeps"]
    assert _in_place(compiled)


SMEM_BYTES = 1 << 20  # a v5e core's scalar memory
SMEM_ROW_BYTES = 512   # what one entry of an (N, 1) operand column pads to


def test_window_of_32_u4_is_the_smem_ceiling(one_chip, cell_windows):
    """Why the bound is 32 and not 64 (PR 46).  A window's float
    operands reach every launch as one ``(N, 1)`` column in SMEM, which
    pads each entry to a row of 128 words: a ``u4`` brings 32 floats, so
    a random circuit's window of 32 ``u4`` is 1024 rows, half of SMEM,
    and compiles (the case ``rcs-w01`` above); 64 ``u4`` are 2048 rows,
    the whole of it, and the chip's compiler refuses the program (built
    here by hand: the lowering itself keeps such a window on the chain,
    ``fusion.SMEM_OPERAND_ROWS``).  When the column is packed as a row
    (a kernel-layer change) the refusal goes, this case fails, and the
    bound can be asked again."""
    structure = cell_windows["rcs"][0]
    assert [kind for kind, _, _ in structure] == ["u4"] * fu.DEFAULT_WINDOW
    for window, share in ((structure, 2), (structure * 2, 1)):
        iv, fv = fu.pack_operands(_ops(window), jnp.float32)
        print(f"operand rows at {len(window)} u4: iv={iv.shape} fv={fv.shape}")
        assert fv.shape == (32 * len(window), 1)
        assert fv.shape[0] * SMEM_ROW_BYTES == SMEM_BYTES // share
    with pytest.raises(Exception, match="(?i)smem"):
        _compile(pk.make_window_fn(W, structure * 2),
                 _dense_args(structure * 2, one_chip))


# QFT's windows at w28 whose bodies hold its runs of controlled phases
# (PR 42; the indices are cell_windows'): two gen among thirty cphase in
# one tile (three runs), one gen among thirty-one (two runs: nine of the
# ten one-launch windows are one or the other), the last window's gen
# and twenty-one cphase (one run), and a window of three launches, two of
# them led with phases behind the lead, the last with a gen between runs
QFT_RUN_WINDOWS = {"2gen+30cphase": (3, [(3, 3)]), "gen+31cphase": (8, [(3, 2)]),
                   "gen+21cphase": (12, [(3, 1)]),
                   "led": (2, [(0, 0), (4, 1), (4, 2)])}


@pytest.mark.parametrize("name", sorted(QFT_RUN_WINDOWS))
def test_qft_run_window_kernel(one_chip, cell_windows, name):
    """A run's phase tile and the tile its value is held in are one
    VMEM scratch of the launch, what the runs' folds read two more (PR 54:
    seven vregs an op of a run, 28 KiB): beside the blocks (one in, one out, each
    double-buffered) and a led launch's two orbits they stay far under
    the limit the launch asks for, the program holds nothing of the
    ket's size beside the donated ket, and the compiler takes under a
    second (the backend alone, this sandbox, the windows of 16 ops this
    case held until PR 46, sixteen cphase / gen and fifteen cphase /
    led: 0.82, 0.78 and 0.62 s without the run lowering, 0.11, 0.23 and
    0.23 s with it: a run's ops are a loop, a sixth of the code)."""
    from test_pallas_window import launches_of

    index, expected = QFT_RUN_WINDOWS[name]
    structure = cell_windows["qft"][index]
    fn = pk.make_window_fn(W, structure)
    args = _dense_args(structure, one_chip)
    block = 2 * 4 << pk.DEFAULT_BLOCK_POW
    launches = launches_of(fn, *args)
    found = []
    for eqn, seg in zip(launches, pk.plan_window(structure,
                                                 pk.DEFAULT_BLOCK_POW)):
        count = eqn.params["grid_mapping"].num_scratch_operands
        scratch = [v.aval for v in eqn.params["jaxpr"].invars[-count:]] \
            if count else []
        assert all(a.dtype in (jnp.float32, jnp.int32) for a in scratch)
        vmem = 4 * block + sum(4 * int(np.prod(a.shape)) for a in scratch)
        assert vmem <= pk._VMEM_LIMIT_BYTES // 4
        found.append((count, len(pk.diag_runs(seg["ops"]))))
    assert found == expected
    # the runs' plans ride the tail of iv (PR 54: the slots' rows and a
    # slot id an op), inside the SMEM budget with the masks and floats
    iv, fv = _kernel_operands(structure)
    assert len(iv) == pk._run_plan_slots(structure, pk.DEFAULT_BLOCK_POW)[1]
    assert len(iv) - pk._operand_slots(structure)[2] == sum(
        pk._PLAN_HEAD + stop - start
        for seg in pk.plan_window(structure, pk.DEFAULT_BLOCK_POW)
        for start, stop in pk.diag_runs(seg["ops"]))
    assert len(iv) + len(fv) <= fu.SMEM_OPERAND_ROWS // 4
    t0 = time.perf_counter()
    compiled = _compile(fn, args)
    assert time.perf_counter() - t0 < 60
    assert _launches(compiled) == len(expected)
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    assert _in_place(compiled)


# jaxpr equations of qft_w28.library's window programs, nested bodies
# counted: 13 522 over the 13 structures and 2 413 the widest (the first
# window: seven launches) since a run folds its high-part ops (PR 54:
# ~180 equations a run more, 36 runs); 7 006 and 1 327 before, 16 540
# over 26 programs before a run's ops were a loop (PR 42).  What a
# program costs to trace and lower a cold machine pays once
# (``first_setup_s``: PERF.md section 6, PR 54); a kernel change that
# passes a ceiling should know it does
QFT_EQUATIONS = {"widest": 2500, "all": 14000}


def test_qft_w28_programs_stay_under_their_equation_ceiling(cell_windows):
    """Nothing here needs the chip's compiler."""
    def count(jaxpr):
        return sum(1 + sum(count(sub)
                           for sub in jax.core.jaxprs_in_params(eqn.params))
                   for eqn in jaxpr.eqns)

    found = []
    for structure in dict.fromkeys(cell_windows["qft"]):
        args = [jax.ShapeDtypeStruct((2, 1 << W), jnp.float32)] + [
            jax.ShapeDtypeStruct(np.shape(o), o.dtype)
            for o in _kernel_operands(structure)]
        found.append(count(jax.make_jaxpr(
            pk.make_window_fn(W, structure))(*args).jaxpr))
    print(f"equations: {sum(found)} in {len(found)} programs, {found}")
    assert len(found) == 13
    assert max(found) <= QFT_EQUATIONS["widest"]
    assert sum(found) <= QFT_EQUATIONS["all"]
    # and the ceiling is not slack: within a tenth of what is
    assert max(found) > 0.9 * QFT_EQUATIONS["widest"]


# the cells' windows whose launches hold a stretch of in-tile ops (PR 44;
# the indices are cell_windows'): a random circuit's first window (eight
# u4 in the tile in three passes, then two cycles' couplers leading four
# tiles, the sixth with seven u4 behind it and the last with five: one
# more scratch tile beside the orbits) and its last (six u4 in the tile,
# six bare leads), the Trotter step's first window (its run of 54 diag,
# then the RX on qubits 0-4, which roll lanes: the run's scratch and no
# pass) and its second (the RX on 5-15 in two passes behind the two that
# roll lanes, then twelve bare leads in six launches).  Each launch:
# (scratch operands, passes of its stretch)
STRETCH_WINDOWS = {
    "rcs-w1": ("rcs", 0, [(1, 3)] + [(1, 0)] * 5 + [(2, 3)] + [(1, 0)] * 5
               + [(2, 1)]),
    "rcs-w4": ("rcs", 3, [(1, 3)] + [(1, 0)] * 6),
    "tfim-54diag-5gen": ("tfim", 0, [(3, 0)]),  # + what the run's fold reads
    "tfim-23gen": ("tfim", 1, [(1, 2)] + [(1, 0)] * 6),
}


def _stretch_launches(fn, args, structure, expected):
    """Each launch's scratch is float32 tiles that, with its blocks (one
    in, one out, each double-buffered), stay under a quarter of the VMEM
    the launch asks for; a stretch is one loop a pass."""
    from test_pallas_window import _scratch_conds_loops, launches_of

    bp = fn.block_pow
    block = 2 * 4 << bp
    found = []
    for eqn, seg, (_, _, loops) in zip(
            launches_of(fn, *args), pk.plan_window(structure, bp),
            _scratch_conds_loops(fn, *args)):
        count = eqn.params["grid_mapping"].num_scratch_operands
        scratch = [v.aval for v in eqn.params["jaxpr"].invars[-count:]] \
            if count else []
        assert all(a.dtype in (jnp.float32, jnp.int32) for a in scratch)
        vmem = 4 * block + sum(4 * int(np.prod(a.shape)) for a in scratch)
        assert vmem <= pk._VMEM_LIMIT_BYTES // 4
        pieces = pk.segment_pieces(seg["ops"], pk.dense_tile(bp))
        passes = sum(held is not None for _, _, split in pieces
                     for _, held in split)
        if not pk.diag_runs(seg["ops"]):
            assert loops == passes
        found.append((count, passes))
    assert found == expected


@pytest.mark.parametrize("name", sorted(STRETCH_WINDOWS))
def test_stretch_window_kernel(one_chip, cell_windows, name):
    """The value a stretch's passes work on is a VMEM scratch tile of
    the launch (512 KiB), beside a led launch's two orbits; the program
    holds nothing of the ket's size beside the donated ket, its result
    takes the ket's buffer, and the compiler takes a fraction of a
    second (the backend alone, this sandbox, PR 44's parent / change on
    the windows of 16 ops this case held until PR 46: fifteen u4 and a
    gen 2.06 / 0.51 s, sixteen gen 1.70 / 0.21, the step's first sixteen
    1.28 / 0.20, its inv-led launch with 15 gen 1.96 / 0.32, the w30 QFT
    window below 0.26 / 0.15; the three families' 45 programs 32.6 /
    13.6 s: a pass is a loop, its body a chunk's code and not a
    tile's)."""
    family, index, expected = STRETCH_WINDOWS[name]
    structure = cell_windows[family][index]
    fn = pk.make_window_fn(W, structure)
    args = _dense_args(structure, one_chip)
    _stretch_launches(fn, args, structure, expected)
    t0 = time.perf_counter()
    compiled = _compile(fn, args)
    assert time.perf_counter() - t0 < 60
    assert _launches(compiled) == len(expected)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes == 0
    assert memory.alias_size_in_bytes == KET_BYTES
    assert _in_place(compiled)


def test_a_run_of_diag_is_one_traced_body():
    """The Trotter step's first window is a run of 54 controlled
    ``diag`` (PR 47).  The ops of a run's group are one traced body in a
    loop over their operands (``pallas_kernels._run_groups``): its
    launch traces to the equations of a run of three and the words of
    the target pick (``_static_pick``: four targets a word, four
    equations a word), where a body an op would be some fifty equations
    each, 0.2 s an op to trace and lower in a benchmark run and a second
    under ``shard_map`` (PERF.md section 6, PR 42).  A pair keeps its two
    bodies (``DIAG_GROUP_MIN``: a loop's op is dearer on the device, and
    the per-page QFT's ``diag`` come in pairs).  Nothing here needs the
    chip's compiler."""
    from test_pallas_window import launches_of

    def count(jaxpr):
        return sum(1 + sum(count(sub)
                           for sub in jax.core.jaxprs_in_params(eqn.params))
                   for eqn in jaxpr.eqns)

    def equations(ops):
        structure = tuple(("diag", 1 + j // 2, True) for j in range(ops))
        assert [len(g) for g in pk._run_groups(
            pk.plan_window(structure, pk.DEFAULT_BLOCK_POW)[0]["ops"])] \
            == ([ops] if ops >= pk.DIAG_GROUP_MIN else [1] * ops)
        args = [jax.ShapeDtypeStruct((2, 1 << W), jnp.float32)] + [
            jax.ShapeDtypeStruct(np.shape(o), o.dtype)
            for o in _kernel_operands(structure)]
        launch, = launches_of(pk.make_window_fn(W, structure), *args)
        return count(launch.params["jaxpr"])

    alone, two, three, step = (equations(k) for k in (1, 2, 3, 54))
    print(f"equations: one diag {alone}, a run of 2 {two}, of 3 {three}, "
          f"of 54 {step}")
    assert step - three == 4 * (-(-54 // 4) - 1)
    # the parent's bound was ``step < 4 * alone`` (60: a run of 54 under
    # 240 equations).  Since PR 54 a run also lays its folded ops out
    # for the fold, folds them and ends with one of two passes, by the
    # table alone or by the table and the slots: ~300 equations a run
    # whatever its length (one diag 60, a run of 2 472, of 3 373, of 54
    # 425), so the bound that still says "one body, not one an op" is
    # that 54 ops are fewer equations than the two bodies of a pair,
    # and within a body's length of a run of three
    assert step < two and three < two
    assert step - three < alone


# -- w30: the widest ket one chip holds (PR 43) --------------------------------
# An 8 GiB ket fits a 16 GB chip only while no program of the path holds
# a second: the fill, each of QFT(0, 30)'s 15 windows (14 of 32 ops and
# the last 17; at the bound of 16 the thirtieth was the last H alone, a
# kernel window of one since the rule ``single_op`` went, which a case of
# its own still compiles) and the read.  SLACK stays the w28 cases' 32 MiB.
W30 = 30
KET30_BYTES = 2 * 4 << W30
QFT30_WINDOWS = 15
QFT30_SWEEPS = [7, 4, 4, 2] + [1] * 11


@pytest.fixture(scope="module")
def qft30_windows():
    """The structures of QFT(0, 30)'s windows in the order the fuser
    flushes them, from the benchmark's own gate list, no ket allocated."""
    from helpers import benchmark_plans

    with benchmark_plans(W30) as windows:
        out = [w["structure"] for w in windows("qft")]
    assert len(out) == QFT30_WINDOWS
    assert [len(s) for s in out] == [32] * 14 + [17]
    assert out[-1][-1] == ("gen", 0, False)
    return out


@pytest.mark.parametrize("owned", [True, False], ids=["in-place", "fresh"])
def test_fill_writes_one_ket_w30(one_chip, owned):
    """``SetPermutation``'s program as the engine jits it: with a ket
    handed in the result takes its buffer and nothing of the ket's size
    stands beside it (64 000 bytes of temporaries when written); with
    none it allocates the one ket.  Zeros and a two-element update: a
    select on an iota held a predicate of 1 GiB."""
    from qrack_tpu.engines import tpu

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    planes = shape((2, 1 << W30), jnp.float32) if owned else None
    t0 = time.perf_counter()
    compiled = tpu._j_fill.lower(
        planes, shape((), jnp.int32), shape((2,), jnp.float32), W30,
        jnp.dtype("float32")).compile()
    memory = compiled.memory_analysis()
    print(f"compile_s={time.perf_counter() - t0:.2f} qrack_fill "
          f"temp_bytes={memory.temp_size_in_bytes}")
    text = compiled.as_text()
    assert text.startswith("HloModule jit_qrack_fill")
    assert memory.output_size_in_bytes == KET30_BYTES
    assert memory.alias_size_in_bytes == (KET30_BYTES if owned else 0)
    assert memory.temp_size_in_bytes <= SLACK
    assert _in_place(compiled, W30)
    if owned:
        assert "input_output_alias={ {}: (0, {}, may-alias) }" in text


@pytest.mark.parametrize("index", range(QFT30_WINDOWS),
                         ids=[f"w{i + 1:02d}" for i in range(QFT30_WINDOWS)])
def test_qft_w30_window_sweeps_its_ket_in_place(one_chip, qft30_windows, index):
    """Every window of ``qft_w30.library``'s application: its planned
    launches (2^14 tiles a plane, led segments on targets 16 to 29),
    the result aliased to the donated ket, no temporary and no copy of
    the ket's size."""
    structure = qft30_windows[index]
    plan, why = fu.kernel_lowering(W30, structure, backend="tpu")
    assert why is None and not plan["interpret"]
    assert plan["sweeps"] == QFT30_SWEEPS[index]
    compiled = _compile(pk.make_window_fn(W30, structure),
                        _dense_args(structure, one_chip, n=W30))
    assert _launches(compiled) == plan["sweeps"]
    assert compiled.memory_analysis().alias_size_in_bytes == KET30_BYTES
    assert _in_place(compiled, W30)


@pytest.mark.parametrize("target,passes", [(0, 0), (12, 1)],
                         ids=["lane", "vreg"])
def test_one_op_window_w30_sweeps_in_place(one_chip, target, passes):
    """A window of one op is a kernel window (PR 43: the eager program
    of a lone gate holds one to three kets of temporaries, refused at
    w30).  QFT(0, 30) ended in one, the lone ``H`` on qubit 0, until the
    bound of 32 took it into the window before; a read behind a single
    gate still flushes one."""
    structure = (("gen", target, False),)
    fn = pk.make_window_fn(W30, structure)
    args = _dense_args(structure, one_chip, n=W30)
    _stretch_launches(fn, args, structure, [(passes, passes)])
    compiled = _compile(fn, args)
    assert _launches(compiled) == 1
    assert compiled.memory_analysis().alias_size_in_bytes == KET30_BYTES
    assert _in_place(compiled, W30)


def test_qft_w30_stretch_window_kernel(one_chip, qft30_windows):
    """A window of ``qft_w30.library`` with a ``gen`` between two runs
    of ``cphase`` (its ninth: the ``gen`` on qubit 7 among thirty-one
    ``cphase``): the stretch of one op is one pass on the tile the runs
    hold the value in, one scratch of three tiles (and what the runs'
    folds read, two scratches of vregs: PR 54)."""
    structure = qft30_windows[8]
    kinds = [kind for kind, _, _ in structure]
    assert sorted(set(kinds)) == ["cphase", "gen"] and kinds.count("gen") == 1
    assert all(t < pk.DEFAULT_BLOCK_POW for k, t, _ in structure if k == "gen")
    fn = pk.make_window_fn(W30, structure)
    args = _dense_args(structure, one_chip, n=W30)
    _stretch_launches(fn, args, structure, [(3, 1)])
    assert pk.stretch_counts(structure, fn.block_pow) == (1, 1, 1, 0)
    compiled = _compile(fn, args)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes == 0
    assert memory.alias_size_in_bytes == KET30_BYTES
    assert _in_place(compiled, W30)


def test_qft_w30_plans_28_sweeps(qft30_windows):
    """43 at the bound of 16: the 14 led launches stay, and of the
    in-tile sweeps two windows' worth now ride in one."""
    plans = [fu.kernel_lowering(W30, s, backend="tpu")[0]
             for s in qft30_windows]
    assert [p["sweeps"] for p in plans] == QFT30_SWEEPS
    assert sum(QFT30_SWEEPS) == 28 and sum(p["cross"] for p in plans) == 14


def test_amplitude_read_w30_holds_no_ket(one_chip):
    """``GetAmplitude``'s slice of the planes (an eager
    ``dynamic_slice``: the index is an operand, a new ``perm`` compiles
    nothing) reads two elements and holds nothing of the ket's size."""
    def read(planes, perm):
        return planes[:, perm]

    args = [jax.ShapeDtypeStruct((2, 1 << W30), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)]
    compiled = jax.jit(read).lower(*args).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= SLACK
    assert memory.output_size_in_bytes < 4096


# -- the first ket no one chip holds: ``qft_w31.pager4`` (PR 45) -------------
# A w31 ket is 16 GiB, 4 GiB a page on the 2x2 mesh: whatever a program
# of the application keeps beside the page has to fit the 15.75 GiB the
# runtime gives a chip.  The fill, the one-amplitude read, and nine of
# QFT(0, 31)'s 19 windows: the three short ones that the ``H`` on 30, 29
# and 28 head (the first brings 30 and 29 onto the carrier bits, the
# third sends them back for 28 and 27; no shuffle of the page before or
# after either exchange), the first four of the sixteen the bound cuts
# (no exchange: launches alone, 32 ops; 5, 4, 4 and 2 launches, the
# leads above the tile), the first of one launch (``H`` on 14, in the
# tile) and the last (7 ops).  The ten between hold one or two ``H`` on
# lower bits of the tile and differ by those targets alone.
W31 = 31
PAGE31_BYTES = (2 * 4 << W31) // 4
HBM_BYTES = int(15.75 * 2 ** 30)
QFT31_WINDOWS = {"w01-prologue": 0, "w02-plain": 1, "w03-prologue": 2,
                 "w04-plain-32": 3, "w05-plain-32": 4, "w06-plain-32": 5,
                 "w07-plain-32": 6, "w08-intile-32": 7, "w19-last-7": 18}


@pytest.fixture(scope="module")
def pager31(topo):
    """A pager on the described chips with no planes (its programs are
    built, never run) after one application of the cell: ``windows``
    holds what ``_plan_window`` decided for each of the 19."""
    from helpers import plan_only_pager

    q = plan_only_pager(W31, devices=list(topo.devices[:4]))
    q.SetPermutation(5)
    q.QFT(0, W31)
    q.GetAmplitude(3)
    # H on 30 alone, H on 29, H on 28 (a page bit by then) each head a
    # window; sixteen by the bound behind them
    assert [len(w.tops) for w in q.windows] == [2, 3, 4] + [32] * 15 + [7]
    assert [bool(w.swaps) for w in q.windows] \
        == [True, False, True] + [False] * 16
    return q


def _page_shapes(q):
    from jax.sharding import NamedSharding as NS

    rep = NS(q.mesh, P())
    return (jax.ShapeDtypeStruct((2, 1 << W31), jnp.float32,
                                 sharding=q.sharding),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((2,), jnp.float32, sharding=rep))


@pytest.mark.parametrize("owned", [True, False], ids=["in-place", "fresh"])
def test_page_fill_writes_one_page_w31(pager31, owned):
    """``QPager.SetPermutation``'s program: with a ket handed in every
    page's result takes its buffer (one write, no read) and under 1 MiB
    stands beside the 4 GiB page; with none it allocates the one ket."""
    ket, i32, amp = _page_shapes(pager31)
    t0 = time.perf_counter()
    compiled = pager31._p_page_fill(owned).lower(
        *((ket,) if owned else ()), i32, i32, amp).compile()
    memory = compiled.memory_analysis()
    print(f"compile_s={time.perf_counter() - t0:.2f} qrack_page_fill "
          f"temp_bytes={memory.temp_size_in_bytes}")
    text = compiled.as_text()
    assert text.startswith("HloModule jit_qrack_page_fill")
    assert memory.output_size_in_bytes == PAGE31_BYTES
    assert memory.alias_size_in_bytes == (PAGE31_BYTES if owned else 0)
    assert memory.temp_size_in_bytes < 1 << 20
    assert not re.findall(r"f32\[2,%d\]\S* copy(?:-start)?\(" % (1 << 29), text)
    if owned:
        assert "input_output_alias={ {}: (0, {}, may-alias) }" in text


def test_page_read_w31_holds_no_page(pager31):
    """``GetAmplitude``'s program: (page, offset) in, two elements summed
    over the mesh out; nothing of a page's size beside the page."""
    ket, i32, _ = _page_shapes(pager31)
    compiled = pager31._p_page_read().lower(ket, i32, i32).compile()
    memory = compiled.memory_analysis()
    assert compiled.as_text().startswith("HloModule jit_qrack_page_read")
    assert memory.temp_size_in_bytes < 1 << 20
    assert memory.output_size_in_bytes < 4096


@pytest.mark.parametrize("name", sorted(QFT31_WINDOWS))
def test_qft_w31_window_fits_beside_its_page(topo, pager31, name):
    """Each compiles in seconds and page + temporaries stay under what
    the runtime gives a chip."""
    window = pager31.windows[QFT31_WINDOWS[name]]
    plan, why = fu.sharded_kernel_lowering(W31 - 2, window.structure,
                                           backend="tpu")
    assert why is None and not plan["interpret"]
    if window.swaps:
        from qrack_tpu.ops import sharded as shb

        exchange = shb.plan_exchange(W31 - 2, 2, window.swaps)
        assert exchange.k == 2 and exchange.page_dest is None
        assert not exchange.pre  # both ride the carrier bits
    t0 = time.perf_counter()
    compiled = _compile_sharded(topo, window.structure, W31,
                                remap=window.swaps,
                                exchanges=bool(window.swaps))
    assert time.perf_counter() - t0 < 120
    assert _launches(compiled) == plan["sweeps"]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == PAGE31_BYTES
    assert PAGE31_BYTES + memory.temp_size_in_bytes < HBM_BYTES
    if not window.swaps:  # launches alone sweep the page in place
        assert memory.temp_size_in_bytes <= SLACK


def test_kernel_launches_carry_their_names(one_chip):
    """What a device trace finds the two launches by.  The kernel's
    ``metadata=`` rides the custom call's frontend attributes whatever
    the locations carry; its ``name=`` is the name of the HLO
    instruction, and the scope part of its ``op_name``, only while
    locations keep the name stack (not with
    ``jax_include_full_tracebacks_in_locations=False``).  The target
    stays ``tpu_custom_call`` (benchmarks/kernels/*.json match both)."""
    structure = (("gen", 3, False), ("gen", 20, True), ("cphase", 3, True))
    assert [seg["xgen"] is not None
            for seg in pk.plan_window(structure, pk.DEFAULT_BLOCK_POW)] \
        == [False, True]
    fn = pk.make_window_fn(W, structure)
    args = _dense_args(structure, one_chip)
    text = _compile(fn, args).as_text()
    assert text.startswith("HloModule jit_qrack_kernel_window")
    was = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        bare = _compile(pk.make_window_fn(W, structure), args).as_text()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
    assert bare.startswith("HloModule jit_qrack_kernel_window")
    for name in (pk.INTILE_KERNEL_NAME, pk.CROSS_KERNEL_NAME):
        # the metadata's JSON breaks the instruction over several lines
        launch = re.findall(
            r'%%%s[.\d]* = \S+ custom-call\([^)]*\), '
            r'custom_call_target="tpu_custom_call"' % name, text)
        assert len(launch) == 1, name
        assert f"qrack.fuse.kernel_window/{name}/pallas_call" in text
        for compiled in (text, bare):
            assert len(re.findall(
                r'kernel_metadata=\{\s*"qrack_kernel":"%s"' % name,
                compiled)) == 1
    assert 'op_name="pallas_call"' in bare  # no name stack, so no scope
    assert not re.search(r'op_name="[^"]*qrack\.fuse\.kernel_window', bare)
    assert "%" + pk.CROSS_KERNEL_NAME not in bare


def _lowered_at_one_site(args, structure):
    return jax.jit(pk.make_window_fn(20, structure)).lower(*args).as_text()


def _lowered_at_another_site(args, structure):
    # other lines, and one more frame, on the way to the same kernel
    return (lambda: jax.jit(pk.make_window_fn(20, structure))
            .lower(*args).as_text())()


def test_compile_cache_key_does_not_hold_the_call_stack(
        one_chip, monkeypatch, tmp_path):
    """A Mosaic kernel is serialized with its operations' locations.
    With the default ten frames the lowered text, and so the persistent
    cache's key, changes with the call site (telemetry on takes another
    branch of ``_JitProgram.__call__``: PERF.md, PR 27);
    ``enable_compile_cache()`` keeps one frame, the operation's own, and
    the names stay."""
    from qrack_tpu.checkpoint import warmstart

    structure = (("gen", 19, False), ("cphase", 3, True))
    args = _args(_kernel_operands(structure), one_chip, one_chip, n=20)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_traceback_in_locations_limit",
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        # the default, whatever an earlier test of this worker left: a
        # QrackService with a warm start keeps one frame for the process
        jax.config.update("jax_traceback_in_locations_limit", 10)
        assert _lowered_at_one_site(args, structure) \
            != _lowered_at_another_site(args, structure)
        monkeypatch.setattr(warmstart, "_ENABLED_DIR", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        warmstart.enable_compile_cache()
        assert jax.config.jax_traceback_in_locations_limit == 1
        one = _lowered_at_one_site(args, structure)
        assert one == _lowered_at_another_site(args, structure)
        assert pk.CROSS_KERNEL_NAME in one
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


# ---------------------------------------------------------------------------
# PR 49: Grover's search with an arithmetic oracle at w28 (the cell
# ``grover_w28.library``): the ALU's add as one rotation of the ket, and
# the windows its barriers cut
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("destination", [True, False],
                         ids=["written-over", "fresh"])
@pytest.mark.parametrize("block_bits", [28, 24, 7],
                         ids=["whole-ket", "block-2^24", "block-2^7"])
def test_alu_rotate_w28(one_chip, block_bits, destination):
    """``INC``/``DEC`` on a contiguous register: one program, the amount a
    runtime operand, no ``gather`` and no index array of the ket's length;
    the whole-register form (the deployment's) is one fusion with no
    temporary, a block form keeps a predicate of the ket's length
    (256 MiB).  Its result takes the buffer of a second ket, donated and
    never read (the planes the rotation before it read), or a fresh one;
    never that of the planes it reads, which would cost a whole-ket
    copy."""
    from qrack_tpu.engines import tpu as tpu_engine

    planes = jax.ShapeDtypeStruct((2, 1 << W), jnp.float32, sharding=one_chip)
    shift = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        tpu_engine.qrack_alu_rotate, static_argnums=(3,), donate_argnums=(0,),
        keep_unused=True).lower(planes if destination else None, planes,
                                shift, block_bits).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    print(f"temp_bytes={memory.temp_size_in_bytes}")
    assert text.startswith("HloModule jit_qrack_alu_rotate")
    assert "gather" not in text and "scatter" not in text
    assert not re.findall(r"f32\[2,%d\]\S* copy\(" % (1 << W), text)
    assert memory.temp_size_in_bytes <= (KET_BYTES >> 3) + (1 << 20)
    assert memory.output_size_in_bytes == KET_BYTES
    assert memory.alias_size_in_bytes == (KET_BYTES if destination else 0)
    if block_bits == W:
        assert memory.temp_size_in_bytes <= 1 << 20
        assert not re.findall(r"s32\[(?:2,)?%d\]" % (1 << W), text)


@pytest.fixture(scope="module")
def grover_windows():
    """The windows of one Grover iteration at w28, as the fuser flushes
    them between the rotations (``helpers.benchmark_plans``)."""
    from helpers import benchmark_plans

    with benchmark_plans(W) as windows:
        return [w["structure"] for w in windows("grover")]


@pytest.mark.parametrize("index,sweeps", [(0, 1), (1, 7), (2, 7)],
                         ids=["zero-phase-flip-alone", "diffusion-first-32",
                              "diffusion-rest"])
def test_grover_window_sweeps_its_ket_in_place(one_chip, grover_windows,
                                               index, sweeps):
    """The oracle's ``ZeroPhaseFlip`` (one ``diag`` under 27 controls,
    every one at 0: its masks are runtime operands) stands alone between
    ``DEC`` and ``INC`` and is one in-tile sweep; the diffusion's first
    window holds a layer of ``H``, the second flip and three more ``H``.
    The twelve ``H`` above the tile of a layer are six launches since
    PR 50 (13 sweeps a window until then)."""
    assert [len(s) for s in grover_windows] == [1, 32, 26]
    structure = grover_windows[index]
    if index == 0:
        assert structure == (("diag", 0, True),)
    plan, why = fu.kernel_lowering(W, structure, backend="tpu")
    assert why is None and plan["sweeps"] == sweeps
    compiled = _compile(pk.make_window_fn(W, structure),
                        _dense_args(structure, one_chip))
    assert _launches(compiled) == sweeps
    assert _in_place(compiled)


# ---------------------------------------------------------------------------
# PR 50: two leads a launch.  A cross-tile 2 x 2 that directly follows a
# bare one on another qubit shares its launch: the quad's grid and its
# scratch of two orbits of four cast tiles (4 MiB), the mix the first
# lead's row on two tiles and the second's over those.  The programs the
# four gaining cells launch, at w28 (the per-page run of a w30 ket over
# four pages is a w28 ket, every op controlled)
# ---------------------------------------------------------------------------

def _gens(*targets, ctrl=False):
    return tuple(("gen", t, ctrl) for t in targets)


PAIRED_WINDOWS = {
    # the Trotter step's and the Grover layer's first pair, bare
    "bare-16-17": (_gens(16, 17), 1),
    "bare-apart-16-27": (_gens(16, 27), 1),
    "bare-descending-27-16": (_gens(27, 16), 1),
    # a Grover layer's last pair with the second flip and the next
    # layer's first ``H`` behind it (its window's ``Lgen27+4``)
    "riders-26-27": (_gens(26, 27) + (("diag", 27, True),) + _gens(0, 1, 2), 1),
    # as the pager's per-page run hands them over: every op controlled
    "controlled-16-17": (_gens(16, 17, ctrl=True), 1),
    "controlled-26-27-riders": (_gens(26, 27, ctrl=True)
                                + (("diag", 3, True), ("diag", 4, True),
                                   ("gen", 5, True)), 1),
    "inv-gen-20-18": ((("inv", 20, True), ("gen", 18, False)), 1),
    # three bare leads: a pair and a single
    "three-bare": (_gens(16, 17, 18), 2),
}


@pytest.mark.parametrize("name", sorted(PAIRED_WINDOWS))
def test_paired_leads_window_kernel(one_chip, name):
    """Each compiles for the chip in seconds, its launch's VMEM (the
    blocks, one in and one out, each double-buffered; two orbits of four
    cast tiles; a tile or two for the riders) stays under a quarter of
    the limit the launch asks for, and the program sweeps the donated
    ket in place."""
    from test_pallas_window import launches_of

    structure, sweeps = PAIRED_WINDOWS[name]
    plan, why = fu.kernel_lowering(W, structure, backend="tpu")
    assert why is None and plan["sweeps"] == plan["cross"] == sweeps
    assert plan["paired"] == 1
    fn = pk.make_window_fn(W, structure)
    args = _dense_args(structure, one_chip)
    block = 2 * 4 << pk.DEFAULT_BLOCK_POW
    first = launches_of(fn, *args)[0]
    count = first.params["grid_mapping"].num_scratch_operands
    scratch = [v.aval for v in first.params["jaxpr"].invars[-count:]]
    assert scratch[0].shape == (2, 4, 2) + pk.dense_tile(pk.DEFAULT_BLOCK_POW)
    assert tuple(first.params["grid_mapping"].grid) == ((1 << (W - 18)) + 1, 4)
    vmem = 4 * block + sum(4 * int(np.prod(a.shape)) for a in scratch)
    print(f"vmem_bytes={vmem}")
    assert vmem <= pk._VMEM_LIMIT_BYTES // 4
    t0 = time.perf_counter()
    compiled = _compile(fn, args)
    assert time.perf_counter() - t0 < 60
    assert _launches(compiled) == sweeps
    assert pk.CROSS_KERNEL_NAME in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes == 0
    assert memory.alias_size_in_bytes == KET_BYTES
    assert _in_place(compiled)


# -- the program store: a reloaded program is the direct one -------------------------

# a window of each cell family the store serves (checkpoint/warmstart.
# stored_program, PR 52): name -> (family, index into cell_windows), and
# a window of ``qft_w31.pager4`` by its name in QFT31_WINDOWS
STORED_WINDOWS = {"qft-3-launches": ("qft", 2), "tfim-59-ops": ("tfim", 0),
                  "rcs-7-launches": ("rcs", 3), "qft31-prologue": None}
KERNEL_NAMES = (pk.INTILE_KERNEL_NAME, pk.CROSS_KERNEL_NAME,
                pk.TWOQ_INTILE_KERNEL_NAME, pk.TWOQ_PAIR_KERNEL_NAME,
                pk.TWOQ_QUAD_KERNEL_NAME)


@pytest.mark.parametrize("name", sorted(STORED_WINDOWS))
def test_a_reloaded_program_compiles_as_the_direct_one(request, topo, name):
    """Exported for the TPU, serialized, read back and jitted as the
    store jits it (``warmstart._jit_exported``: the name and the
    donation stated again, a paged program's result on the arguments'
    mesh), a window program compiles for the described v5e to what the
    direct ``jax.jit(fn)`` compiles to: the module's name, the launches
    and their kernels' names, the donated ket swept in place, the same
    bytes of temporaries (none where launches alone sweep the ket; the
    exchange's where a prologue runs) and the collective."""
    from jax import export

    from qrack_tpu.checkpoint import warmstart

    if STORED_WINDOWS[name] is None:
        q = request.getfixturevalue("pager31")
        window = q.windows[QFT31_WINDOWS["w01-prologue"]]
        assert window.swaps
        fn, args = _sharded_program(topo, window.structure, W31,
                                    remap=window.swaps)
        n, module = W31 - 2, "jit_qrack_sharded_kernel_window"
    else:
        family, index = STORED_WINDOWS[name]
        structure = request.getfixturevalue("cell_windows")[family][index]
        assert len(structure) == {"qft-3-launches": 32, "tfim-59-ops": 59,
                                  "rcs-7-launches": 12}[name]
        fn = pk.make_window_fn(W, structure)
        args = _dense_args(structure,
                           request.getfixturevalue("one_chip"))
        n, module = W, "jit_qrack_kernel_window"
    direct = _compile(fn, args)
    t0 = time.perf_counter()
    exported = export.export(jax.jit(fn, donate_argnums=(0,)),
                             platforms=["tpu"])(*args)
    blob = exported.serialize()
    reloaded = export.deserialize(blob)
    lowered = warmstart._jit_exported(
        reloaded, args, {"donate_argnums": (0,)}).lower(*args)
    print(f"export_and_reload_s={time.perf_counter() - t0:.2f} "
          f"bytes={len(blob)} devices={reloaded.nr_devices}")
    stored = lowered.compile()
    assert reloaded.nr_devices == (4 if STORED_WINDOWS[name] is None else 1)
    texts = direct.as_text(), stored.as_text()
    for text in texts:
        assert text.startswith("HloModule " + module)
    assert _launches(stored) == _launches(direct) >= 1
    for kernel in KERNEL_NAMES:
        assert texts[0].count(f'"qrack_kernel":"{kernel}"') \
            == texts[1].count(f'"qrack_kernel":"{kernel}"')
    assert ("collective-permute" in texts[1]) \
        == ("collective-permute" in texts[0]) \
        == (STORED_WINDOWS[name] is None)
    was, now = direct.memory_analysis(), stored.memory_analysis()
    assert now.temp_size_in_bytes == was.temp_size_in_bytes
    assert now.alias_size_in_bytes == was.alias_size_in_bytes \
        == (PAGE31_BYTES if STORED_WINDOWS[name] is None else KET_BYTES)
    assert now.output_size_in_bytes == was.output_size_in_bytes
    if STORED_WINDOWS[name] is not None:
        assert _in_place(stored, n) and now.temp_size_in_bytes == 0
    # the result lies over the pages as the direct program's does: the
    # next window's program meets the sharding it was compiled for
    assert stored.output_shardings == direct.output_shardings
