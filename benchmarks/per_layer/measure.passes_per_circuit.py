"""Whole-ket programs the measurements of one application dispatched:
the program's counter ``measure.tpu.passes`` over the window, over its
applications.  2 where a register is measured by one reduction and one
collapse; twice the register's length where it is measured a qubit at a
time.  None where the program counts no measurement (an untraced run, a
parent of PR 53)."""

import roofline_measure


def read(ctx):
    counters = ctx["window_counters"]
    if not roofline_measure.counts_measure(counters):
        return None
    return counters.get(roofline_measure.PASSES_COUNTER, 0) / ctx["attempted"]
