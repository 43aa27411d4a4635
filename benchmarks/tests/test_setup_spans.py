"""``setup_spans`` on a ring and host events written by hand: which ring
entries are the set-up, whose they are, what their self seconds add up
to; and the seven ``setup.*_s`` readers on a run with no trace."""

import json
import os

import pytest

import harness
import setup_spans
from conftest import ROOT

ME, OTHER = 11, 22   # thread ids
OFFSET = 1000.0      # the trace's clock minus the ring's, seconds
WINDOW = 10.0        # the window opens here, on the ring's clock

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SETUP = [m["name"] for m in json.load(_f)["per_layer"]
             if m["moves"] == "setup_s" and m["source"] == "program_span"]


def _e(span_id, name, ts, dur, parent=None, tid=ME):
    return {"id": span_id, "name": name, "ts_s": ts, "dur_s": dur,
            "parent": parent, "tid": tid, "depth": 0}


RING = [
    # the stack's construction: a fill inside it, whose first call compiles
    _e(1, "factory.create_interface", 1.0, 2.0),
    _e(2, "engine.set_permutation", 1.5, 1.0, parent=1),
    _e(3, "compile.trace", 1.6, 0.1, parent=2),
    _e(4, "compile.lower", 1.7, 0.2, parent=2),
    _e(5, "compile.backend", 1.9, 0.4, parent=2),
    _e(6, "compile.cache_load", 2.0, 0.25, parent=5),
    # a window program's first call: an eager operation compiles inside
    # its trace, and counts there
    _e(7, "fuse.flush", 4.0, 3.0),
    _e(8, "fuse.dispatch", 4.5, 2.0, parent=7),
    _e(9, "compile.trace", 4.5, 1.0, parent=8),
    _e(10, "compile.lower", 4.6, 0.125, parent=9),
    _e(11, "compile.backend", 4.75, 0.5, parent=9),
    _e(12, "compile.backend", 5.5, 0.5, parent=8),
    # a jit of the benchmark's own, under no span of the program
    _e(13, "compile.backend", 8.0, 0.75),
    # another thread's work before the window
    _e(14, "engine.read", 3.0, 0.5, tid=OTHER),
    # the window's own spans, and one after it
    _e(15, "fuse.flush", 10.5, 1.0),
    _e(16, "engine.read", 11.5, 0.25),
    _e(17, "engine.read", 30.0, 0.25),
]
# what the trace holds: the window's two spans, on its own clock; one
# event whose ring entry is gone, one whose name is another's
EVENTS = [("qrack.fuse.flush", 15, int((10.5 + OFFSET) * 1e9)),
          ("qrack.engine.read", 16, int((11.5 + OFFSET + 2e-6) * 1e9)),
          ("qrack.engine.read", 99, int((12.0 + OFFSET) * 1e9)),
          ("qrack.fuse.lower", 17, int((30.0 + OFFSET) * 1e9))]


@pytest.fixture(scope="module")
def found():
    return setup_spans.SetupSpans(RING, EVENTS, int((WINDOW + OFFSET) * 1e9),
                                  ME)


def test_the_clocks_are_tied_by_the_matched_ids(found):
    assert (found.host_events, found.matched) == (4, 2)
    assert found.offset_s == pytest.approx(OFFSET + 1e-6, abs=1e-9)
    assert found.offset_spread_s == pytest.approx(2e-6, abs=1e-9)
    assert found.window_opened_s == pytest.approx(WINDOW, abs=1e-5)


def test_the_set_up_is_the_callers_entries_before_the_window(found):
    assert [e["id"] for e in found.entries] == list(range(1, 14))
    # the program's: not the compile under no span
    assert [e["id"] for e in found.program] == list(range(1, 13))
    assert found.outside_s == 0.75
    assert found.program_s == pytest.approx(2.0 + 3.0)
    # the first span began at 1.0, the window opened at 10.0
    assert found.since_first_span_s == pytest.approx(9.0, abs=1e-5)


def test_whole_spans_and_outermost_stages(found):
    assert found.named_s("factory.create_interface") == 2.0
    assert found.named_s("engine.set_permutation") == 1.0
    assert found.named_s("compile.cache_load") == 0.25
    assert found.stage_s("compile.trace") == pytest.approx(0.1 + 1.0)
    # the lowering and the compile inside the trace are the trace's
    assert found.stage_s("compile.lower") == 0.2
    assert found.stage_s("compile.backend") == pytest.approx(0.4 + 0.5)
    stages = sum(found.stage_s("compile." + s)
                 for s in ("trace", "lower", "backend"))
    assert stages <= found.program_s
    assert found.named_s("compile.cache_load") \
        <= found.stage_s("compile.backend")


def test_self_seconds_by_name_add_up_to_the_programs_seconds(found):
    table = found.self_seconds_by_name()
    assert sum(table.values()) == pytest.approx(found.program_s)
    assert table["factory.create_interface"] == pytest.approx(1.0)
    assert table["engine.set_permutation"] == pytest.approx(0.3)
    assert table["compile.backend"] == pytest.approx(0.15 + 0.5 + 0.5)
    assert table["compile.trace"] == pytest.approx(0.1 + 0.375)
    assert table["fuse.dispatch"] == pytest.approx(0.5)
    assert table["fuse.flush"] == pytest.approx(1.0)


def test_no_matched_id_is_no_set_up():
    none = setup_spans.SetupSpans(RING, [("qrack.fuse.flush", 99, 5)], 10, ME)
    assert none.matched == 0 and none.entries == []


def test_another_threads_set_up_is_its_own():
    theirs = setup_spans.SetupSpans(RING, EVENTS,
                                    int((WINDOW + OFFSET) * 1e9), OTHER)
    assert [e["id"] for e in theirs.entries] == [14]
    assert theirs.program_s == 0.5


def test_the_seven_readers_are_in_the_benchmark():
    assert sorted(SETUP) == sorted(
        "setup." + n + "_s" for n in ("program", "create", "fill", "trace",
                                      "lower", "backend", "cache_load"))


@pytest.mark.parametrize("metric", SETUP)
def test_a_reader_reads_nothing_without_a_trace(metric):
    read = harness.load_module("per_layer", metric).read
    assert read({"trace": None, "setup_seconds": 17.0}) is None
    assert read({"setup_seconds": 17.0}) is None


@pytest.mark.parametrize("metric,want", [
    ("setup.program_s", 5.0), ("setup.create_s", 2.0), ("setup.fill_s", 1.0),
    ("setup.trace_s", 1.1), ("setup.lower_s", 0.2), ("setup.backend_s", 0.9),
    ("setup.cache_load_s", 0.25)])
def test_a_reader_reads_its_spans(found, metric, want):
    read = harness.load_module("per_layer", metric).read
    assert read({"setup_spans": found}) == pytest.approx(want)
