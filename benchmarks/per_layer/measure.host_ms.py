"""Host time of one application in ``qrack.engine.measure.sample``: the
host's part of a register's measurement, from the probabilities on the
host to the value drawn (a running sum, one draw, one search); the wait
for the reduction and the collapse's dispatch are outside it.  Median
over the traced applications; None where the program has no such span."""

import program_spans
import roofline_measure


def read(ctx):
    return program_spans.span_ms_per_application(
        ctx, roofline_measure.SAMPLE_SPAN)
