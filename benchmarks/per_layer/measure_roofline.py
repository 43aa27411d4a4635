"""A register measurement's share of its HBM roofline.  Bound: HBM.

The least a register's measurement can move is three times the planes'
bytes: one read for the reduction, one read and one write for the
collapse (``roofline_measure.measure_bytes``: 6 GiB at w28, 7.9 ms at the
published peak).  The registers are the program's own count over the
window (``measure.tpu.reg``); their time is a chip's device time in the
modules ``jit_qrack_prob_reg`` and ``jit_qrack_collapse``
(``measure.ms_per_circuit``).  The program's own ledger of the bytes
(``roofline.tpu.measure.planned_bytes``) is printed beside the
benchmark's arithmetic.  It cannot pass 100 %: a measurement a qubit at a
time moves more than is counted here."""

import harness
import program_spans
import roofline
import roofline_measure


def read(ctx):
    spans = program_spans.load(ctx)
    counters = ctx["window_counters"]
    registers = counters.get(roofline_measure.REG_COUNTER, 0)
    if spans is None or not registers:
        return None
    ns = roofline_measure.chip_ns(spans, roofline_measure.MEASURE)
    if not ns:
        return None
    least_bytes = roofline_measure.measure_bytes(ctx["width"], registers)
    planned = counters.get(roofline_measure.MEASURE_PLANNED)
    least = roofline.least_seconds(hbm_bytes=least_bytes, peaks=ctx["peaks"])
    harness.say(registers_measured=registers,
                measure_device_seconds=ns / 1e9, measure_least_seconds=least,
                measure_bytes=least_bytes, measure_bytes_counted=planned,
                equal=planned == least_bytes)
    return 100.0 * least / (ns / 1e9)
