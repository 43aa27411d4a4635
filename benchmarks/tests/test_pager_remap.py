"""The pager's own placement, cell ``tfim_w30.pager4``: the driver that
settles the table before the window, the comparisons that have to see a
table ignored or a step forgotten, the bytes a chip sends reckoned from
the planner's prologues, and the ``remap.*`` readers on events small
enough to read."""

import argparse
import importlib
import json

import pytest

import harness
import roofline
import roofline_remap
import tracing
from families import PARAMS, engine, family, issue

CELL = "tfim_w30.pager4"
PAGES = 4


def _args(trace=0):
    return argparse.Namespace(workload=CELL, seed=2147485999, seconds=0.5,
                              trace=trace, rehearse_cpu=True)


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    return importlib.import_module("run")


def _said(capsys):
    lines = [json.loads(t) for t in capsys.readouterr().out.splitlines()]
    return lines, {k: v for t in lines for k, v in t.items()}


# -- the cell, rehearsed whole ------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_settles_its_table_and_passes_every_comparison(run, capsys,
                                                                trace):
    code, line, checks = run.execute(_args(trace))
    assert code == 3 and checks.correct, checks.failures
    lines, said = _said(capsys)
    cell = harness.Cell(CELL)
    settle, width = (cell.traffic["settle_applications"],
                     cell.config["rehearse_qubit_count"])
    tables = [t["placement"] for t in lines if "placement" in t]
    # the engine's first use, then the window's start and every settled step
    assert lines[[("placement" in t) for t in lines].index(True)]["before"] \
        == "warmup"
    assert len(tables) == 2 + settle
    assert tables[0] == tables[1] == list(range(width))
    assert tables[-1] == tables[-3] != list(range(width))
    assert said["window_compiles"] == 0 and said["identity"] is False
    assert said["evolved_ket_bit_positions"] in tables[-2:]
    names = {t["check"] for t in lines if "check" in t}
    assert {"engine_is_QPager", "planes_on_device_float32",
            "placement_is_periodic", "evolved_ket.bond_zz",
            "evolved_ket.norm_drift_per_step"} <= names
    if trace:
        assert "remap.prologues_per_circuit" in said["rehearsed_metrics"]
        assert said["gates_left_on_paged_qubits"] == 0


def test_a_reduction_that_ignores_the_table_is_not_correct(run, monkeypatch):
    monkeypatch.setattr(harness, "bit_positions",
                        lambda q: list(range(q.qubit_count)))
    code, line, checks = run.execute(_args())
    assert checks.failures == ["evolved_ket.bond_zz"]


def test_a_settled_step_left_out_of_the_count_is_not_correct(run, monkeypatch):
    """The closed forms are of every step the ket took: with one of the
    settled steps forgotten the bond correlations are another step's."""
    class Forgetful(harness.Cell):
        def __init__(self, name):
            super().__init__(name)

            def final_check(settled, q, plan, last_i, spans, checks):
                settled.family.final_check(
                    q, plan, last_i + settled.settle - 1, spans, checks)

            self.driver.Settled.final_check = final_check

    monkeypatch.setattr(harness, "Cell", Forgetful)
    code, line, checks = run.execute(_args())
    assert "evolved_ket.bond_zz" in checks.failures
    assert not checks.correct


def test_the_window_opens_on_a_table_that_does_not_recur(run, monkeypatch):
    """Too few settled steps for the table to have come round: the
    driver says so, by a comparison of its own."""
    class Hasty(harness.Cell):
        def __init__(self, name):
            super().__init__(name)
            self.traffic = dict(self.traffic, settle_applications=2)

    monkeypatch.setattr(harness, "Cell", Hasty)
    code, line, checks = run.execute(_args())
    assert "placement_is_periodic" in checks.failures


# -- bytes between chips ------------------------------------------------------------

def _counters_of_settled_steps(steps=4):
    from qrack_tpu import telemetry

    cfg = harness.Cell(CELL).config
    fam, width = family("tfim"), cfg["rehearse_qubit_count"]
    q = engine("pager", width, **cfg["engine"]["kwargs"])
    gates = fam.gates(width, PARAMS["tfim"])
    q.SetPermutation(77)
    telemetry.enable()
    try:
        for i in range(steps):
            issue(q, gates)
            q.GetAmplitude(i)
        return width, dict(
            telemetry.snapshot(include_events=False)["counters"])
    finally:
        telemetry.disable()
        telemetry.reset()


def test_the_prologues_count_is_the_programs_bytes():
    width, counters = _counters_of_settled_steps()
    page = roofline.ket_bytes(width) // PAGES
    by_k = roofline_remap.prologues_by_k(counters)
    assert by_k and sum(by_k.values()) == counters["remap.pager.windows"]
    assert roofline_remap.sent_bytes(counters, page) \
        == counters["exchange.pager.bytes"] / PAGES > 0
    assert counters.get("exchange.pager.global_2x2", 0) == 0


def test_sent_bytes_by_hand():
    page = 1 << 20
    assert roofline_remap.sent_bytes({}, page) == 0
    assert roofline_remap.sent_bytes(
        {"remap.pager.prologues.k1": 2, "remap.pager.prologues.k2": 2}, page) \
        == 2.5 * page
    assert roofline_remap.sent_bytes(
        {"remap.pager.prologues.k2": 4, "remap.pager.page_perms": 1,
         "exchange.pager.global_2x2": 2, "remap.pager.pairs": 8}, page) \
        == 6 * page


# -- the readers, on events small enough to read -----------------------------------------

W, L = 8, 6
PAGE = roofline.ket_bytes(W) // PAGES  # (2, 64) float32: 512 bytes


def _ctx(prologues=2):
    """Four chips; an application is ``prologues`` prologues of two
    pairs: three quarter pages sent, 30 ns each, 10 ns of the first
    under a launch."""
    start = ("%collective-permute-start.{i} = (f32[2,16]{{1,0}}, f32[2,16]{{1,0}}) "
             "collective-permute-start(%x)")
    done = ("%collective-permute-done.{i} = f32[2,16]{{1,0}} "
            "collective-permute-done(%collective-permute-start.{i})")
    launch = ('%tpu_custom_call.1 = f32[2,64]{1,0} custom-call(%p), '
              'custom_call_target="tpu_custom_call"')
    events = []
    for p in range(prologues):
        t = 1000 * p
        events.append([launch, t, 110])
        for i in range(3):
            events.append([start.format(i=i), t + 100 + 40 * i, 5])
            events.append([done.format(i=i), t + 125 + 40 * i, 5])
    trace = tracing.Trace.from_events(
        {"devices": {f"/device:TPU:{c}": events for c in range(PAGES)},
         "spans": [["window", 0, 1000 * prologues]]})
    return {"trace": trace, "attempted": 1, "width": W, "pages": PAGES,
            "peaks": {"ici_bits_per_s": 8 * 100e9},
            "window_counters": {
                "remap.pager.prologues.k2": prologues,
                "remap.pager.windows": prologues,
                "exchange.pager.remap": prologues,
                "exchange.pager.bytes": 0.75 * PAGE * PAGES * prologues}}


def _read(metric, ctx):
    return harness.load_module("per_layer", metric).read(ctx)


def test_the_readers_by_hand(capsys):
    ctx = _ctx()
    assert _read("remap.prologues_per_circuit", ctx) == 2
    assert _read("remap.pages_sent_per_circuit", ctx) == 1.5
    assert _read("remap.collective_ms_per_circuit", ctx) == pytest.approx(
        2 * 3 * 30e-6)
    # 10 of each prologue's 90 ns in flight are under the launch
    assert _read("remap.exposed_share", ctx) == pytest.approx(100 * 80 / 90)
    capsys.readouterr()
    share = _read("remap_exchange_roofline", ctx)
    said = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert said["equal"] is True
    assert said["sent_bytes_a_chip_an_application"] == 1.5 * PAGE
    assert said["transfers_a_chip_an_application"] == 6
    # 768 bytes in 180 ns of a link that takes 100 GB/s
    assert share == pytest.approx(100 * (1.5 * PAGE / 100e9) / 180e-9)
    assert 0 < share < 100


def test_a_program_without_the_counters_or_the_transfers_reads_nothing():
    """What the parent's program gives a traced run of another cell laid
    under these files: no counter of prologues, and on one chip no
    transfer; a reader then returns None and the line leaves it out."""
    ctx = _ctx()
    ctx["window_counters"] = {}
    assert _read("remap.prologues_per_circuit", ctx) is None
    one = tracing.Trace.from_events(
        {"devices": {"/device:TPU:0": [["%fusion.1 = f32[2,64]{1,0} fusion(%p)",
                                        10, 50]]},
         "spans": [["window", 0, 100]]})
    ctx = dict(ctx, trace=one)
    for metric in ("remap.pages_sent_per_circuit", "remap_exchange_roofline",
                   "remap.collective_ms_per_circuit", "remap.exposed_share"):
        assert _read(metric, ctx) is None, metric
    ctx = dict(ctx, trace=None)  # an untraced run or a rehearsal
    for metric in ("remap.pages_sent_per_circuit", "remap_exchange_roofline",
                   "remap.collective_ms_per_circuit", "remap.exposed_share"):
        assert _read(metric, ctx) is None, metric
