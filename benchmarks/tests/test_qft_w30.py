"""The cell ``qft_w30.library`` without a chip: what the fuser plans for
one application at w30 (the counts its ``why`` rests on), that the
configuration is ``dense_qft_w28``'s but for the width and the guarantee
it adds, and the four readers PR 43 brought, on synthetic events whose
numbers a hand can check."""

import json
import os

import pytest

import harness
import program_spans
import roofline
import structure
from conftest import ROOT
from families import CONFIGS, PARAMS, family
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk

W = 30
CELL = "qft_w30.library"
FILL = "jit_qrack_fill"
MS = 1_000_000  # ns


class PlanEveryWindow(structure.PlanOnlyEngine):
    """``structure.PlanOnlyEngine`` keeps a window of one op away from
    the planner (the rule ``single_op``, which went with PR 43): here it
    is asked like any other window."""

    def _fuse_flush(self, gates):
        ops = fu.lower_gates(gates)
        if len(ops) != 1:
            return super()._fuse_flush(gates)
        lone = fu.structure_of(ops)
        plan, why = fu.kernel_lowering(self.qubit_count, lone, backend="tpu")
        segments = pk.plan_window(lone, plan["block_pow"]) if plan else ()
        self.windows.append({
            "structure": lone, "path": "kernel" if plan else why,
            "sweeps": plan["sweeps"] if plan else 1,
            "cross_tile": sum(1 for s in segments if s["xgen"] is not None)})
        return 1


@pytest.fixture(scope="module")
def windows():
    planner, structure.PlanOnlyEngine = structure.PlanOnlyEngine, PlanEveryWindow
    try:
        return structure.plan_application(family("qft"), W, PARAMS["qft"])
    finally:
        structure.PlanOnlyEngine = planner


def test_qft_w30_structure(windows):
    s = structure.summary(windows)
    assert s["ops"] == 465
    assert s["windows"] == s["programs"] == s["kernel_windows"] == 30
    assert [len(w["structure"]) for w in windows] == [16] * 29 + [1]
    assert s["kernel_sweeps_by_window"] == [5, 3, 3, 2, 3, 2, 2] + [1] * 23
    assert s["kernel_sweeps"] == 43
    assert s["cross_tile_segments"] == 14
    assert s["fallbacks"] == []


def test_the_last_window_is_the_lone_h_on_qubit_zero(windows):
    assert windows[-1]["structure"] == (("gen", 0, False),)
    assert (windows[-1]["sweeps"], windows[-1]["cross_tile"]) == (1, 0)


def test_cross_tile_segments_lead_on_targets_16_to_29(windows):
    """Fourteen cross-tile ``H`` where w28 has twelve: the led segments
    of the first seven windows, one for each target above the tile."""
    led = [seg["xgen"][2] for w in windows
           for seg in pk.plan_window(w["structure"], pk.DEFAULT_BLOCK_POW)
           if seg["xgen"] is not None]
    assert led == list(range(29, 15, -1))


def test_configuration_is_w28s_but_for_the_width_and_one_guarantee():
    w28, w30 = CONFIGS["dense_qft_w28"], CONFIGS["dense_qft_w30"]
    assert w30["qubit_count"] == W and w30["rehearse_qubit_count"] == 12
    for key in ("stack", "engine", "entry", "family", "circuit", "assumed",
                "limits", "reduced"):
        assert w30[key] == w28[key], key
    assert w30["guarantees"][:4] == w28["guarantees"]
    assert len(w30["guarantees"]) == 5 and "one ket" in w30["guarantees"][4]
    assert len(w30["source"]) <= 200 and "PLACEHOLDER" not in json.dumps(w30)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("dense_qft_w30", "library", 1)


# -- the readers, on synthetic events ----------------------------------------

def _events(fills, module=FILL):
    """One device plane: for each application a fill of two operations
    (the zeros, ``fills[i]`` ms, and the update, 2 us) in ``module``, a
    launch of 40 ms and a read; one window around them."""
    device, spans, t = [], [], 1000
    for ms in fills:
        start = t
        device.append(("%broadcast_fusion = f32[2,1073741824]{1,0} fusion()",
                       t, int(ms * MS), module))
        t += int(ms * MS)
        device.append(("%dynamic-update-slice.1 = f32[2,1073741824]{1,0} "
                       "dynamic-update-slice(f32[2,1073741824], f32[2,1])",
                       t, 2000, module))
        t += 2000
        device.append(('%call = f32[2,1073741824] custom-call(), '
                       'custom_call_target="tpu_custom_call"', t, 40 * MS,
                       "jit_qrack_kernel_window"))
        t += 40 * MS
        device.append(("%dynamic-slice = f32[2,1]{1,0} dynamic-slice()",
                       t, 1500, "jit_dynamic_slice"))
        t += 1500
        spans.append(("bench.application", start, t - start, "main"))
        spans.append(("qrack.engine.set_permutation", start, 50_000, "main"))
    spans.append(("bench.window", 0, t + 1000, "main"))
    return {"devices": {"/device:TPU:0": device}, "spans": spans}


def _ctx(fills, module=FILL, counters=None, peak=None):
    n = len(fills)
    if counters is None:
        counters = {"engine.fill.in_place": n}
    return {
        "program_spans": program_spans.ProgramSpans.from_events(
            _events(fills, module)),
        "attempted": n, "width": W, "pages": 1,
        "peaks": harness.load_json("peaks.json")["TPU v5 lite"],
        "window_counters": counters, "peak_bytes_after_window": peak,
    }


def _read(metric, ctx):
    return harness.load_module("per_layer", metric).read(ctx)


def test_the_new_readers_are_listed_for_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("fill.ms_per_circuit", "fill_roofline",
                 "fill.in_place_per_circuit"):
        assert per_layer[name]["workloads"] == [CELL]
    assert per_layer["hbm.kets_at_peak"]["workloads"] == [
        "qft_w28.library", "tfim_w28.library", "rcs_w28.library", CELL]
    assert per_layer["hbm.kets_at_peak"]["moves"] == "peak_hbm_gib"
    # no descriptor under kernels/: it would take the fill's events out
    # of xla.ms_per_circuit in every cell
    assert not [f for f in os.listdir(os.path.join(harness.HERE, "kernels"))
                if "fill" in f]


def test_fill_ms_is_the_modules_device_time_per_application():
    ctx = _ctx([12.0, 13.0])
    assert _read("fill.ms_per_circuit", ctx) == pytest.approx(
        (12.0 + 13.0 + 2 * 0.002) / 2)


def test_fill_roofline_by_hand():
    """Two fills of a w30 ket in 12 and 13 ms (and 2 us each for the
    update): 2 x 8 GiB written at 819 GB/s is 20.98 ms of 25.004."""
    ctx = _ctx([12.0, 13.0])
    assert roofline.ket_bytes(W) == 8 * 2 ** 30
    least_ms = 2 * 8 * 2 ** 30 / 819e9 * 1e3
    assert least_ms == pytest.approx(20.976, abs=1e-3)
    assert _read("fill_roofline", ctx) == pytest.approx(
        100 * least_ms / 25.004, rel=1e-9)
    # at the peak itself the share is 100 %, never more
    at_peak = 8 * 2 ** 30 / 819e9 * 1e3 - 0.002
    assert _read("fill_roofline", _ctx([at_peak])) == pytest.approx(100, abs=1e-2)


def test_fill_readers_find_nothing_on_a_parent():
    """A parent of PR 43 fills with eager operations (no module of the
    program's) and keeps no ``engine.fill.*`` counter: None, no raise."""
    parent = _ctx([12.0], module="jit_broadcast_in_dim", counters={})
    for name in ("fill.ms_per_circuit", "fill_roofline",
                 "fill.in_place_per_circuit"):
        assert _read(name, parent) is None
    no_trace = {"trace": None, "attempted": 1, "width": W, "pages": 1,
                "window_counters": {}, "peak_bytes_after_window": 0}
    for name in ("fill.ms_per_circuit", "fill_roofline",
                 "fill.in_place_per_circuit", "hbm.kets_at_peak"):
        assert _read(name, no_trace) is None


def test_fills_in_place_per_application():
    assert _read("fill.in_place_per_circuit", _ctx([1.0] * 4)) == 1.0
    mixed = _ctx([1.0] * 4, counters={"engine.fill.in_place": 3,
                                      "engine.fill.fresh": 1})
    assert _read("fill.in_place_per_circuit", mixed) == 0.75
    assert _read("fill_roofline", mixed) is not None  # a fresh fill is a fill
    fresh = _ctx([1.0] * 2, counters={"engine.fill.fresh": 2})
    assert _read("fill.in_place_per_circuit", fresh) == 0.0


@pytest.mark.parametrize("width,peak_gib,kets", [
    (30, 8.006854057312012, 1.000857), (28, 2.0061888694763184, 1.003094),
    (28, 8.000247, 4.000124)], ids=["w30", "w28", "w28-parent"])
def test_kets_at_peak(width, peak_gib, kets):
    ctx = dict(_ctx([1.0], peak=int(peak_gib * 2 ** 30)), width=width)
    assert _read("hbm.kets_at_peak", ctx) == pytest.approx(kets, abs=1e-6)
