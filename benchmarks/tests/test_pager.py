"""A ket paged over four chips: the table the reductions read, the
per-chip arithmetic of the device-time readers, the exchange's byte
count, and the readers of the pager exchange on a cut of the first
four-chip trace of ``tfim_w30.pager4_noremap`` (TPU v5e 2x2, PR 30;
``tools/cut_program_spans.py``)."""

import argparse
import importlib
import json
import os

import numpy as np
import pytest

import harness
import program_spans
import reference
import roofline
import tracing
from conftest import ROOT, TESTS
from families import PARAMS, engine, family

CELL = "tfim_w30.pager4_noremap"
W, PAGES, L = 30, 4, 28
PAGE = roofline.ket_bytes(W) // PAGES
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    LISTED = [m["name"] for m in json.load(_f)["per_layer"]
              if CELL in m.get("workloads", ())]
# one Trotter step at w30 on 4 pages with the placement held (the first
# four-chip trace and its counters): 8 windows, 7 through the kernel in 41
# launches a chip, 33 of them cross-tile; the program counts 45 sweeps, a
# gate on a paged qubit among the kernel's being one; the last 5 ops on
# the chain; 6 gates on paged qubits, two half-page transfers each
LAUNCHES, SWEEPS, CROSS, KERNEL_OPS, CHAIN_OPS = 41, 45, 33, 112, 5
PROGRAMS, PAGED_GATES = 357, 6


# -- the table ------------------------------------------------------------------

def test_bond_correlations_follow_the_table():
    """A ket whose bits are permuted reads right by its table and wrong
    by the identity: what the pager's planes need."""
    fam, width, p = family("tfim"), 8, PARAMS["tfim"]
    state = reference.basis_state(width, 0b10110101)
    for _ in range(3):
        state = reference.evolve(state, width, fam.gates(width, p))
    want = fam.bond_zz(width, p, 0b10110101, 3)
    table = [6, 7, 2, 0, 1, 5, 3, 4]  # logical qubit -> bit of the index
    idx = np.arange(1 << width)
    physical = np.zeros_like(idx)
    for q, pos in enumerate(table):
        physical |= ((idx >> q) & 1) << pos
    moved = np.zeros_like(state)
    moved[physical] = state
    planes = np.stack([moved.real, moved.imag]).astype(np.float32)
    got = fam.measured_bond_zz(planes, table)
    assert np.max(np.abs(np.array(got) - want)) < 1e-5
    blind = fam.measured_bond_zz(planes, list(range(width)))
    assert np.max(np.abs(np.array(blind) - want)) > 1e-2


def test_bit_positions_of_an_engine_without_a_table():
    class Dense:
        qubit_count = 5

    assert harness.bit_positions(Dense()) == [0, 1, 2, 3, 4]
    Dense._qmap = [4, 0, 1, 2, 3]
    assert harness.bit_positions(Dense()) == [4, 0, 1, 2, 3]


def _args(trace=0):
    return argparse.Namespace(workload=CELL, seed=2147483777, seconds=0.5,
                              trace=trace, rehearse_cpu=True)


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    return importlib.import_module("run")


def _said(capsys):
    lines = [json.loads(t) for t in capsys.readouterr().out.splitlines()]
    return lines, {k: v for t in lines for k, v in t.items()}


def test_the_cell_as_committed_holds_its_table(run, capsys):
    code, line, checks = run.execute(_args(trace=1))
    assert checks.correct, checks.failures
    lines, said = _said(capsys)
    assert said["window_compiles"] == 0 and said["identity"] is True
    names = {t["check"] for t in lines if "check" in t}
    assert {"engine_is_QPager", "planes_on_device_float32",
            "device_planes_in_trace_equal_chips"} <= names


def _evolve_on_a_moving_table(steps=3):
    """The family's own calls on the program's pager with its planner on
    (the factory's default): the table moves under the first steps."""
    cfg = harness.Cell(CELL).config
    fam, width = family("tfim"), cfg["rehearse_qubit_count"]
    plan = fam.Plan(width, PARAMS["tfim"], 11)
    q = engine("pager", width, n_pages=PAGES)
    checks, spans = harness.Checks(cfg["limits"]), harness.Spans()
    fam.start(q, plan, spans)
    for i in range(steps):
        fam.enqueue(q, plan, i, spans)
        q.GetAmplitude(fam.read_index(plan, i))
    fam.final_check(q, plan, steps - 1, spans, checks)
    return checks


def test_an_evolved_ket_is_read_by_a_table_that_has_moved(capsys):
    checks = _evolve_on_a_moving_table()
    assert checks.correct, checks.failures
    _, said = _said(capsys)
    assert said["identity"] is False
    assert sorted(said["evolved_ket_bit_positions"]) == list(range(14))


def test_a_reduction_that_ignores_the_table_is_not_correct(monkeypatch):
    monkeypatch.setattr(harness, "bit_positions",
                        lambda q: list(range(q.qubit_count)))
    checks = _evolve_on_a_moving_table()
    assert checks.failures == ["evolved_ket.bond_zz"]


# -- bytes between chips ------------------------------------------------------------

def test_paged_gate_bytes_of_a_step():
    """Six gates of a step mix on qubits 28 and 29 (CNOT, CNOT and RX on
    each): a page a gate; the RZ between the CNOTs is diagonal."""
    gates = family("tfim").gates(W, PARAMS["tfim"])
    assert roofline.paged_gate_bytes(gates, L, PAGE) == PAGED_GATES * PAGE
    assert roofline.paged_gate_bytes(gates, W, PAGE) == 0


def test_a_pages_launch_moves_a_page():
    assert roofline.launch_bytes(28) == roofline.sweep_bytes(28)
    assert roofline.launch_bytes(30, 4) == roofline.sweep_bytes(28)


# -- one chip's share, on events small enough to read ----------------------------------

def _four_planes():
    """Four chips that each run a launch of 100 ns and a collective of
    40 ns, the last chip 20 ns late."""
    launch = ('%tpu_custom_call.1 = f32[2,64]{1,0} custom-call(%p), '
              'custom_call_target="tpu_custom_call"')
    permute = "%collective-permute.1 = f32[2,1,32]{2,1,0} collective-permute(%x)"
    devices = {f"/device:TPU:{c}": [[launch, 100 + 20 * (c == 3), 100],
                                    [permute, 300 + 20 * (c == 3), 40]]
               for c in range(4)}
    return tracing.Trace.from_events(
        {"devices": devices, "spans": [["window", 0, 1000]]})


def test_a_readers_time_is_one_chips():
    trace = _four_planes()
    assert trace.chips == 4
    launches = trace.kernel_events("window_kernel")
    assert len(launches) == 4 and trace.chip_count(launches) == 1
    assert trace.chip_ns(launches) == 100
    assert trace.busy_s() == pytest.approx(140e-9)
    ctx = {"trace": trace, "attempted": 1, "width": 6, "pages": 4,
           "peaks": {"hbm_bytes_per_s": 1e9},
           "window_counters": {"fuse.kernel.sweeps": 1, "fuse.kernel.ops": 2}}
    read = lambda m: harness.load_module("per_layer", m).read(ctx)  # noqa: E731
    assert read("kernel.ms_per_circuit") == pytest.approx(100e-6)
    assert read("kernel.ms_per_op") == pytest.approx(50e-6)
    # a page is (2, 16) float32: 128 bytes read and 128 written in 100 ns
    assert read("window_kernel_roofline") == pytest.approx(
        100 * (256 / 1e9) / 100e-9)
    assert read("xla.ms_per_circuit") == 0  # the collective is the exchange's
    assert read("pager.collective_ms_per_circuit") == pytest.approx(40e-6)
    assert sum(s for _, s in trace.idle_gaps()) == pytest.approx(860e-9)


def test_one_plane_reads_as_before():
    rec = json.load(open(os.path.join(TESTS, "data", "trace_tfim_w28.json")))
    trace = tracing.Trace.from_events(rec)
    events = trace.kernel_events("window_kernel")
    assert trace.chips == 1
    assert trace.chip_ns(events) == sum(d for _, _, d in events)
    assert trace.chip_count(events) == len(events)


# -- the first four-chip trace, one application of it ----------------------------------

@pytest.fixture(scope="module")
def ctx():
    with open(os.path.join(TESTS, "data",
                           "program_spans_tfim_w30_pager4_noremap.json")) as f:
        rec = json.load(f)
    n = rec["applications"]
    trace = tracing.Trace.from_events({
        "devices": {k: [e[:3] for e in v] for k, v in rec["devices"].items()},
        "spans": [[s[0][len("bench."):]] + s[1:3] for s in rec["spans"]
                  if s[0].startswith("bench.")]})
    return {
        "trace": trace, "attempted": n, "width": W, "pages": PAGES,
        "program_spans": program_spans.ProgramSpans.from_events(rec),
        "cell": harness.Cell(CELL),
        "peaks": harness.load_json("peaks.json")["TPU v5 lite"],
        "window_counters": {
            "fuse.kernel.sweeps": SWEEPS * n, "fuse.xla.sweeps": CHAIN_OPS * n,
            "fuse.kernel.sweeps.cross": CROSS * n,
            "fuse.kernel.ops": KERNEL_OPS * n,
            "fuse.pager.programs": PROGRAMS * n,
            "exchange.pager.bytes": PAGED_GATES * PAGES * PAGE * n},
        "host_spans": {"gate_calls": [1.2]},
        "compiles_before_window": (17, 0.47), "window_compiles": 0,
    }


def _read(metric, ctx):
    return harness.load_module("per_layer", metric).read(ctx)


def test_every_listed_reader_reads_the_four_chip_trace(ctx):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        mine = [m["name"] for m in json.load(f)["per_layer"]
                if CELL in m.get("workloads", [CELL])]
    assert set(LISTED) < set(mine)
    for metric in mine:
        value = _read(metric, ctx)
        assert value is not None and value >= 0, metric
        if metric.endswith(("_roofline", "_share")):
            assert value <= 100, metric


def test_four_planes_and_a_chips_launches(ctx):
    trace = ctx["trace"]
    assert trace.chips == PAGES
    launches = trace.kernel_events("window_kernel")
    assert len(launches) == LAUNCHES * PAGES
    assert trace.chip_count(launches) == LAUNCHES
    assert trace.chip_count(trace.kernel_events("window_cross")) == CROSS


def test_the_rooflines_are_a_chips_arithmetic_done_by_hand(ctx, capsys):
    trace = ctx["trace"]
    launches = trace.kernel_events("window_kernel")
    seconds = sum(d for _, _, d in launches) / PAGES / 1e9
    # a launch reads and writes the chip's 2 GiB page
    by_hand = 100 * LAUNCHES * 2 * PAGE / 819e9 / seconds
    assert _read("window_kernel_roofline", ctx) == pytest.approx(by_hand,
                                                                 rel=1e-12)
    assert 35 < by_hand < 45  # four times that, reckoned at the whole ket
    share = _read("pager_exchange_roofline", ctx)
    said = [json.loads(t) for t in capsys.readouterr().out.splitlines()][-1]
    assert said["equal"] is True
    assert said["sent_bytes_a_chip_an_application"] == PAGED_GATES * PAGE
    assert said["transfers_a_chip_an_application"] == 2 * PAGED_GATES
    flight = _read("pager.collective_ms_per_circuit", ctx) / 1e3
    assert share == pytest.approx(
        100 * PAGED_GATES * PAGE / flight / 200e9, rel=1e-9)
    assert 10 < share < 100


def test_a_transfer_is_a_start_and_its_done(ctx):
    found = ctx["trace"].transfers("pager_exchange")
    assert sorted(found) == [f"/device:TPU:{c}" for c in range(PAGES)]
    for plane in found.values():
        assert len(plane) == 2 * PAGED_GATES
        assert all(sent == PAGE // 2 and 20e6 < end - begin < 30e6
                   for begin, end, sent in plane)
    # an operand that names a collective's result is no collective
    fusion = ("%f.6 = (f32[2,8]{1,0}) fusion(%collective-permute-done.3, %p), "
              "kind=kLoop")
    assert not ctx["trace"].is_kernel("pager_exchange", fusion)
    assert tracing.result_bytes(fusion) == 64


def test_the_exchange_is_exposed_and_in_no_other_layers_time(ctx):
    exposed = _read("pager.exposed_share", ctx)
    assert 95 < exposed <= 100
    flight = _read("pager.collective_ms_per_circuit", ctx)
    kernel = _read("kernel.ms_per_circuit", ctx)
    other = _read("xla.ms_per_circuit", ctx)
    chain = _read("xla.chain_ms_per_circuit", ctx)
    assert 0 < chain < other
    busy = ctx["trace"].busy_s() * 1e3 / ctx["attempted"]
    # the layers add up to the chip's busy time, a little over: the chain's
    # ``while`` (48 ms) is on the line beside the operations of its body
    assert busy < kernel + other + flight < 1.05 * busy
    classes = ctx["program_spans"].device_classes(
        ctx["trace"].kernels["window_kernel"])
    moved = sum(v for k, v in classes.items() if "collective-permute" in k)
    assert moved / 1e6 == pytest.approx(flight, rel=0.02)
    chain_all = sum(v for k, v in classes.items()
                    if k.startswith("jit_qrack_sharded_xla_window:"))
    assert chain < chain_all / 1e6  # the chain's exchange is not the chain's


def test_idle_time_is_a_chips(ctx):
    spans = ctx["program_spans"]
    assert spans.planes == [f"/device:TPU:{c}" for c in range(PAGES)]
    idle = sum(spans.idle_by_span().values()) / 1e9
    window = (spans.end - spans.start) / 1e9
    assert idle + ctx["trace"].busy_s() == pytest.approx(window, rel=1e-6)
    gaps = sum(s for _, s in ctx["trace"].idle_gaps(limit=100))
    assert gaps == pytest.approx(idle, rel=1e-6)
