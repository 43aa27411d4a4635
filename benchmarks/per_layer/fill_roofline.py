"""The fill's share of its HBM roofline.  Bound: HBM.

The least a fill can move is one write of the planes and no read:
``roofline.ket_bytes`` (8 GiB at w30: 10.5 ms at the published peak).
The fills are the program's own count over the window
(``engine.fill.in_place`` + ``engine.fill.fresh``); their time is a
chip's device time in the module ``jit_qrack_fill``
(``fill.ms_per_circuit``).  It cannot pass 100 %: a fill that read the
ket too, or wrote it twice, moves more than is counted here."""

import harness
import roofline

fill = harness.load_module("per_layer", "fill.ms_per_circuit")


def read(ctx):
    ns = fill.chip_ns(ctx)
    counters = ctx["window_counters"]
    fills = (counters.get("engine.fill.in_place", 0)
             + counters.get("engine.fill.fresh", 0))
    if ns is None or not fills:
        return None
    least = roofline.least_seconds(
        hbm_bytes=fills * roofline.ket_bytes(ctx["width"]),
        peaks=ctx["peaks"])
    harness.say(fills_counted=fills, fill_device_seconds=ns / 1e9,
                fill_least_seconds=least)
    return 100.0 * least / (ns / 1e9)
