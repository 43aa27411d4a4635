"""The rotation's share of its HBM roofline.  Bound: HBM.

The least a rotation can move is one read and one write of the planes
(``roofline_alu.rotate_bytes``: 4 GiB at w28, 5.2 ms at the published
peak).  The rotations are the program's own count over the window
(``alu.tpu.rotate``); their time is a chip's device time in the module
``jit_qrack_alu_rotate``.  The program's own ledger of the bytes
(``roofline.tpu.alu.rotate.planned_bytes``) is printed beside the
benchmark's arithmetic.  It cannot pass 100 %: a rotation that wrote its
ket twice moves more than is counted here."""

import harness
import program_spans
import roofline
import roofline_alu


def read(ctx):
    spans = program_spans.load(ctx)
    counters = ctx["window_counters"]
    rotations = counters.get("alu.tpu.rotate", 0)
    if spans is None or not rotations:
        return None
    ns = roofline_alu.chip_ns(spans, (roofline_alu.ROTATE,))
    if not ns:
        return None
    least_bytes = roofline_alu.rotate_bytes(ctx["width"], rotations)
    planned = counters.get(roofline_alu.PLANNED)
    least = roofline.least_seconds(hbm_bytes=least_bytes, peaks=ctx["peaks"])
    harness.say(rotations_counted=rotations, rotate_device_seconds=ns / 1e9,
                rotate_least_seconds=least, rotate_bytes=least_bytes,
                rotate_bytes_counted=planned, equal=planned == least_bytes)
    return 100.0 * least / (ns / 1e9)
