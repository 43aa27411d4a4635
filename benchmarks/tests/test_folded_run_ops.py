"""``kernel.folded_run_ops_per_circuit`` (PR 54): the reader, and the
program's counter behind it on the families that fold."""

import json
import os

import pytest

import harness
from conftest import ROOT

NAME = "kernel.folded_run_ops_per_circuit"


def test_the_reader_reads_the_count_over_the_applications():
    read = harness.load_module("per_layer", NAME).read
    counters = {"fuse.kernel.diag_run.folded_ops": 256 * 7,
                "fuse.kernel.diag_run.ops": 373 * 7}
    assert read({"window_counters": counters, "attempted": 7}) == 256.0
    # a run that folds nothing counts nothing, and a parent of PR 54
    # does not count: the line leaves the metric out
    assert read({"window_counters": {"fuse.kernel.diag_run.ops": 12},
                 "attempted": 7}) is None
    assert read({"window_counters": {}, "attempted": 7}) is None


def test_the_metric_lists_the_cells_that_fold():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        contract = json.load(f)
    metric, = (m for m in contract["per_layer"] if m["name"] == NAME)
    assert metric == contract["per_layer"][-1]
    assert (metric["layer"], metric["moves"], metric["better"]) \
        == ("window kernel", "circuit_ms.p50", "higher")
    folding = {w["name"] for w in contract["workloads"]
               if w["name"].split("_")[0] in ("qft", "tfim")}
    assert set(metric["workloads"]) == folding and len(folding) == 6
    for name in metric["workloads"]:
        assert metric in harness.Cell(name).metrics("per_layer")


@pytest.mark.parametrize("family,folded", [("qft", 256), ("tfim", 24),
                                           ("rcs", 0), ("grover", 0)])
def test_the_program_counts_what_the_plan_folds(family, folded):
    """One application at w28 through the fuser's own windows
    (``tests/helpers.benchmark_plans``): what the counter adds up to an
    application, from the host's plan and no device."""
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from helpers import benchmark_plans
    from qrack_tpu.ops import fusion as fu

    total = 0
    with benchmark_plans() as windows:
        for w in windows(family):
            if w["path"] == "kernel":
                plan, _ = fu.kernel_lowering(28, w["structure"], backend="tpu")
                total += fu.count_kernel_window(
                    w["ops"], plan["block_pow"])["diag_run.folded_ops"]
    assert total == folded
