"""A chip's device time in a register's measurement for one application:
the operations of the modules ``jit_qrack_prob_reg`` (the reduction to
the register's probabilities, ``engines/tpu.qrack_prob_reg``) and
``jit_qrack_collapse`` (the collapse, ``engines/tpu.qrack_collapse``).
None where the program counts no measurement (a parent of PR 53, whose
``jit_prob_mask_sum`` and ``jit_collapse`` ran a qubit at a time)."""

import program_spans
import roofline_measure


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None or not roofline_measure.counts_measure(
            ctx["window_counters"]):
        return None
    ns = roofline_measure.chip_ns(spans, roofline_measure.MEASURE)
    return ns / 1e6 / ctx["attempted"]
