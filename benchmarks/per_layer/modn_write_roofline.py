"""The table write's share of its HBM roofline.  Bound: HBM.

The least a table write can move is one write of the planes and no read
(``roofline_measure.modn_write_bytes``: 2 GiB at w28, 2.6 ms at the
published peak).  The writes are the program's own count over the window
(``alu.tpu.modn``); their time is a chip's device time in the module
``jit_qrack_alu_modn`` and the slice's program (``modn.ms_per_circuit``).
The program's own ledger of the bytes
(``roofline.tpu.alu.modn.planned_bytes``) is printed beside the
benchmark's arithmetic.  It cannot pass 100 %: a write that read the ket
too, or wrote it twice, moves more than is counted here."""

import harness
import program_spans
import roofline
import roofline_measure


def read(ctx):
    spans = program_spans.load(ctx)
    counters = ctx["window_counters"]
    writes = counters.get(roofline_measure.MODN_COUNTER, 0)
    if spans is None or not writes:
        return None
    ns = roofline_measure.chip_ns(spans, (roofline_measure.MODN,))
    if not ns:
        return None
    least_bytes = roofline_measure.modn_write_bytes(ctx["width"], writes)
    planned = counters.get(roofline_measure.MODN_PLANNED)
    least = roofline.least_seconds(hbm_bytes=least_bytes, peaks=ctx["peaks"])
    harness.say(modn_writes_counted=writes, modn_device_seconds=ns / 1e9,
                modn_least_seconds=least, modn_bytes=least_bytes,
                modn_bytes_counted=planned, equal=planned == least_bytes)
    return 100.0 * least / (ns / 1e9)
