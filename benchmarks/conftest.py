"""PR 27's recordings (``tests/data/trace_*_w28.json``) predate the names
PR 28 gave the program's kernels, scopes and spans, and
``tests/test_reducers.py`` asks every per-layer reader of BENCHMARK.json
for a value on them.  The readers that read those names find nothing
there, as they find nothing in the trace of a parent that lacks them, and
return None.  They are held on a recording of their own
(``tests/test_program_spans.py``); here their cases on the older
recordings are skipped, by name, so that the rest still runs."""

import pytest

READ_NAMES_OF_PR_28 = {
    "fuser.queue_ms", "fuser.lower_ms", "fuser.operands_ms",
    "fuser.dispatch_ms", "fuser.programs_per_circuit",
    "kernel.intile_ms_per_circuit", "kernel.cross_ms_per_circuit",
    "kernel.ms_per_op", "xla.chain_ms_per_circuit",
    "xla.operand_ms_per_circuit", "device.idle_unattributed_share",
}


def pytest_collection_modifyitems(items):
    for item in items:
        params = getattr(getattr(item, "callspec", None), "params", {})
        if (item.name.startswith("test_every_reader_reads_the_recorded_trace")
                and params.get("metric") in READ_NAMES_OF_PR_28):
            item.add_marker(pytest.mark.skip(
                reason="a recording of PR 27 holds no name of PR 28"))
