"""The cell ``qft_w31.pager4`` without a chip: what the pager plans for
one application at w31 (the counts its ``why`` rests on), that the bytes
its two prologues send are what ``roofline_remap.sent_bytes`` reckons,
that the configuration is ``dense_qft_w30``'s circuit under
``paged_tfim_w30``'s guarantees, the cell rehearsed whole on four host
devices, and the readers PR 45 brought on synthetic events whose numbers
a hand can check."""

import argparse
import importlib
import json
import os
import sys

import pytest

import harness
import program_spans
import roofline
import roofline_remap
from conftest import ROOT
from families import CONFIGS
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import sharded as shb

sys.path.append(os.path.join(ROOT, "tests"))  # tier-1's plan-only pager
from helpers import plan_only_pager  # noqa: E402

W, PAGES, L = 31, 4, 29
CELL = "qft_w31.pager4"
FILL = "jit_qrack_page_fill"
PAGE_BYTES = roofline.ket_bytes(W) // PAGES
MS = 1_000_000  # ns
NEW_METRICS = ("page_fill.ms_per_circuit", "page_fill_roofline",
               "page_fill.in_place_per_circuit", "hbm.pages_at_peak",
               "paged_qft.prologues_per_circuit",
               "paged_qft.collective_ms_per_circuit",
               "paged_qft_exchange_roofline")


@pytest.fixture(scope="module")
def applications():
    """Two applications on one pager, as the cell's loop issues them."""
    q = plan_only_pager(W, n_pages=PAGES)
    out = []
    for x in (12345, (1 << W) - 7):
        q.windows.clear()
        q.SetPermutation(x)
        q.QFT(0, W)
        q.GetAmplitude(3)
        out.append((list(q.windows), q.placement()))
    return out


def test_qft_w31_plans_31_windows_and_two_prologues(applications):
    windows, table = applications[0]
    assert len(windows) == 31 and sum(len(w.tops) for w in windows) == 496
    assert [len(w.tops) for w in windows] == [16] * 31
    kinds = [k for w in windows for k, _, _ in w.structure]
    assert kinds.count("gen") == 31 and kinds.count("cphase") == 465
    assert [len(w.swaps) for w in windows] == [2, 2] + [0] * 29
    assert all(w.batched for w in windows)
    # no gate that is not diagonal is left on a page bit
    assert not [t for w in windows for k, t, _ in w.structure
                if k == "gen" and t >= L]
    assert table != tuple(range(W)) and sorted(table) == list(range(W))


def test_every_application_plans_what_the_first_planned(applications):
    first, second = applications
    assert [(w.structure, w.swaps, w.batched) for w in first[0]] \
        == [(w.structure, w.swaps, w.batched) for w in second[0]]
    assert first[1] == second[1]


def test_every_window_is_a_kernel_window_of_a_4_gib_page(applications):
    plans = [fu.sharded_kernel_lowering(L, w.structure, backend="tpu")
             for w in applications[0][0]]
    assert all(why is None for _, why in plans)
    sweeps = [p["sweeps"] for p, _ in plans]
    assert sweeps[:8] == [5, 3, 3, 2, 3, 2, 2, 2] and set(sweeps[8:]) == {1}
    assert sum(sweeps) == 45
    # a led launch for every H whose qubit sits above the 2^16 tile when
    # its window runs: the 13 page bits 16 to 28 and, through the
    # prologues, the two qubits that began on page bits
    assert sum(p["cross"] for p, _ in plans) == 15
    assert roofline.launch_bytes(W, PAGES) == 2 * PAGE_BYTES == 8 * 2 ** 30


def test_the_prologues_send_a_page_and_a_half(applications):
    """Two batches of two pairs: (1 - 2^-2) of a page each, by the
    program's own accounting and by the benchmark's."""
    windows = applications[0][0]
    plans = [shb.plan_exchange(L, 2, w.swaps) for w in windows if w.swaps]
    assert [(p.k, p.page_dest) for p in plans] == [(2, None), (2, None)]
    # the first prologue's victims sit below the carrier bits: a shuffle
    # of the page before and after; the second rides the carriers
    assert bool(plans[0].pre) and not plans[1].pre and not plans[1].post
    program = sum(shb.exchange_cost(L, 2, w.swaps) for w in windows) \
        * roofline.ket_bytes(W) / PAGES
    counted = roofline_remap.sent_bytes(
        {"remap.pager.prologues.k2": 2}, PAGE_BYTES)
    assert program == counted == 1.5 * PAGE_BYTES == 6 * 2 ** 30


def test_configuration_is_the_w30_circuit_under_the_paged_guarantees():
    w31, dense, paged = (CONFIGS["paged_qft_w31"], CONFIGS["dense_qft_w30"],
                         CONFIGS["paged_tfim_w30"])
    assert (w31["qubit_count"], w31["rehearse_qubit_count"], w31["pages"]) \
        == (W, 14, PAGES)
    for key in ("family", "circuit", "assumed"):
        assert w31[key] == dense[key], key
    for key in ("stack", "engine", "pages", "guarantees", "reduced"):
        assert w31[key] == paged[key], key
    assert w31["limits"]["amplitude_rel_err"] <= dense["limits"][
        "amplitude_rel_err"]
    assert len(w31["source"]) <= 200 and "PLACEHOLDER" not in json.dumps(w31)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("paged_qft_w31", "pager4_library", 4)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
    traffic = harness.Cell(CELL).traffic
    assert (traffic["kind"], traffic["loop"], traffic["callers"],
            traffic["warmup_applications"], traffic["traced_seconds"]) \
        == ("library", "closed", 1, 1, 8.0)
    # nothing the benchmark had lists the new cell: its own metrics do
    assert [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])] == list(NEW_METRICS)


# -- the cell, rehearsed whole ------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_four_host_devices(monkeypatch, capsys, trace):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    run = importlib.import_module("run")
    code, line, checks = run.execute(argparse.Namespace(
        workload=CELL, seed=3000000019, seconds=0.5, trace=trace,
        rehearse_cpu=True))
    assert code == 3 and checks.correct, checks.failures
    lines = [json.loads(t) for t in capsys.readouterr().out.splitlines()]
    said = {k: v for t in lines for k, v in t.items()}
    assert said["window_compiles"] == 0
    assert {"engine_is_QPager", "planes_on_device_float32",
            "window_read_amplitudes", "post_window_amplitudes"} \
        <= {t["check"] for t in lines if "check" in t}
    if trace:
        assert {"page_fill.in_place_per_circuit",
                "paged_qft.prologues_per_circuit"} \
            <= set(said["rehearsed_metrics"])
        assert said["prologues_an_application_by_pairs"] == {"k2": 2.0}
        assert said["gates_left_on_paged_qubits"] == 0


# -- the readers, on synthetic events ----------------------------------------

def _events(fills, module=FILL, planes=2):
    """``planes`` device planes, each: for every application a fill of
    two operations (the zeros, ``fills[i]`` ms, and the update, 2 us) in
    ``module`` and a launch of 20 ms; one window around them."""
    devices, spans = {}, []
    for plane in range(planes):
        device, t = [], 1000
        for ms in fills:
            start = t
            device.append(("%broadcast_fusion = f32[2,536870912]{1,0} fusion()",
                           t, int(ms * MS), module))
            t += int(ms * MS)
            device.append(("%dynamic-update-slice.1 = f32[2,536870912]{1,0} "
                           "dynamic-update-slice(f32[2,536870912], f32[2,1])",
                           t, 2000, module))
            t += 2000
            device.append(('%call = f32[2,536870912] custom-call(), '
                           'custom_call_target="tpu_custom_call"', t, 20 * MS,
                           "jit_qrack_sharded_kernel_window"))
            t += 20 * MS
            if plane == 0:
                spans.append(("bench.application", start, t - start, "main"))
                spans.append(("qrack.engine.set_permutation", start, 50_000,
                              "main"))
        devices[f"/device:TPU:{plane}"] = device
    spans.append(("bench.window", 0, t + 1000, "main"))
    return {"devices": devices, "spans": spans}


def _ctx(fills, module=FILL, counters=None, peak=None):
    n = len(fills)
    if counters is None:
        counters = {"pager.fill.in_place": n}
    return {
        "program_spans": program_spans.ProgramSpans.from_events(
            _events(fills, module)),
        "attempted": n, "width": W, "pages": PAGES,
        "peaks": harness.load_json("peaks.json")["TPU v5 lite"],
        "window_counters": counters, "peak_bytes_after_window": peak,
    }


def _read(metric, ctx):
    return harness.load_module("per_layer", metric).read(ctx)


def test_page_fill_ms_is_a_chips_time_in_the_module_per_application():
    """Two planes with the same events: a chip's time, not their sum."""
    assert _read("page_fill.ms_per_circuit", _ctx([6.0, 7.0])) \
        == pytest.approx((6.0 + 7.0 + 2 * 0.002) / 2)


def test_page_fill_roofline_by_hand():
    """Two fills of a 4 GiB page in 6 and 7 ms (and 2 us each for the
    update): 2 x 4 GiB written at 819 GB/s is 10.49 ms of 13.004."""
    assert PAGE_BYTES == 4 * 2 ** 30
    least_ms = 2 * PAGE_BYTES / 819e9 * 1e3
    assert least_ms == pytest.approx(10.488, abs=1e-3)
    assert _read("page_fill_roofline", _ctx([6.0, 7.0])) == pytest.approx(
        100 * least_ms / 13.004, rel=1e-9)
    at_peak = PAGE_BYTES / 819e9 * 1e3 - 0.002
    assert _read("page_fill_roofline", _ctx([at_peak])) \
        == pytest.approx(100, abs=1e-2)


def test_page_fills_in_place_per_application():
    assert _read("page_fill.in_place_per_circuit", _ctx([1.0] * 4)) == 1.0
    mixed = _ctx([1.0] * 4, counters={"pager.fill.in_place": 3,
                                      "pager.fill.fresh": 1})
    assert _read("page_fill.in_place_per_circuit", mixed) == 0.75
    assert _read("page_fill_roofline", mixed) is not None


@pytest.mark.parametrize("peak_gib,pages", [(4.0, 1.0), (8.0, 2.0),
                                            (15.0, 3.75)])
def test_pages_at_peak(peak_gib, pages):
    ctx = _ctx([1.0], peak=int(peak_gib * 2 ** 30))
    assert _read("hbm.pages_at_peak", ctx) == pytest.approx(pages)
    assert _read("hbm.pages_at_peak", dict(ctx, width=30)) \
        == pytest.approx(2 * pages)


def test_the_new_readers_find_nothing_on_a_parent():
    """A parent of PR 45 fills with one program over the global axis
    (no module of that name) and keeps no ``pager.fill.*`` counter; an
    untraced run has no trace: None, no raise."""
    parent = _ctx([6.0], module="jit_f", counters={})
    for name in ("page_fill.ms_per_circuit", "page_fill_roofline",
                 "page_fill.in_place_per_circuit"):
        assert _read(name, parent) is None
    no_trace = {"trace": None, "attempted": 1, "width": W, "pages": PAGES,
                "window_counters": {}, "peak_bytes_after_window": 0}
    for name in NEW_METRICS:
        assert _read(name, no_trace) is None
