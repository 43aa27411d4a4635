"""A chip's device time in the ALU's table write for one application: the
operations of the module ``jit_qrack_alu_modn`` (``engines/tpu.qrack_alu_modn``:
the ket written from a slice and a table) and of the program that takes
the slice first (``jit_qrack_alu_modn_slice``).  None where the program
has no table write (a parent of PR 53, whose out-of-place calls are eager
operations that carry no name of the program's)."""

import program_spans
import roofline_measure


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None or not roofline_measure.counts_modn(ctx["window_counters"]):
        return None
    ns = roofline_measure.chip_ns(spans, (roofline_measure.MODN,))
    return ns / 1e6 / ctx["attempted"]
