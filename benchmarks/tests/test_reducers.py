"""The reduction from a trace to numbers, on recorded traces: the first
two applications of a traced run of each cell on a TPU v5e (PR 27),
cut by ``tools/describe_trace.py --cut``."""

import json
import os

import pytest

import harness
import tracing
from conftest import ROOT, TESTS

W = 28
# PR 27's recordings hold no name, span or module of a later PR: they are
# asked for the metrics every cell reports.  A metric that lists its cells
# is held on a recording of one of them (tests/test_program_spans.py,
# tests/test_pager.py).
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    PER_LAYER = [m["name"] for m in json.load(_f)["per_layer"]
                 if "workloads" not in m]
# what the structure of one application says the trace must hold
EXPECT = {"qft": {"launches": 37}, "tfim": {"launches": 28}}


def _recorded(name):
    with open(os.path.join(TESTS, "data", f"trace_{name}_w28.json")) as f:
        return json.load(f)


def _context(name):
    rec = _recorded(name)
    trace = tracing.Trace.from_events(rec)
    n = rec["applications"]
    return {
        "trace": trace, "attempted": n, "width": W, "pages": 1,
        "peaks": harness.load_json("peaks.json")["TPU v5 lite"],
        "window_counters": {"fuse.kernel.sweeps": EXPECT[name]["launches"] * n},
        "host_spans": {"gate_calls": [0.05, 0.07, 0.06]},
        "compiles_before_window": (26, 1.5), "window_compiles": 0,
    }


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_launches_in_the_trace_are_the_planned_sweeps(name):
    ctx = _context(name)
    launches = ctx["trace"].kernel_events("window_kernel")
    assert len(launches) == EXPECT[name]["launches"] * ctx["attempted"]


@pytest.mark.parametrize("name", sorted(EXPECT))
@pytest.mark.parametrize("metric", PER_LAYER)
def test_every_reader_reads_the_recorded_trace(name, metric):
    value = harness.load_module("per_layer", metric).read(_context(name))
    assert value is not None and value >= 0
    if metric.endswith("_roofline") or metric == "device.idle_share":
        assert value <= 100


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_busy_and_gaps_add_up_to_the_window(name):
    trace = _context(name)["trace"]
    gaps = sum(s for _, s in trace.idle_gaps(limit=100))
    assert trace.busy_s() + gaps == pytest.approx(trace.window_s(), rel=1e-6)
    assert 0 < trace.busy_s() <= trace.window_s()
    assert len(trace.top_ops()) <= 10 and len(trace.idle_gaps()) <= 10


def test_union_counts_an_overlap_once():
    assert tracing._union_ns([(0, 10), (5, 20), (30, 40)]) == 30


def test_an_unknown_device_kind_has_no_peaks():
    assert "cpu" not in harness.load_json("peaks.json")
