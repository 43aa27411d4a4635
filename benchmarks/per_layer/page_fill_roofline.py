"""The per-page fill's share of its HBM roofline.  Bound: HBM.

The least a chip's fill can move is one write of its page and no read:
``roofline.ket_bytes`` over the pages (4 GiB at w31 on four: 5.2 ms at
the published peak).  The fills are the program's own count over the
window (``pager.fill.in_place`` + ``pager.fill.fresh``); their time is a
chip's device time in the module ``jit_qrack_page_fill``
(``page_fill.ms_per_circuit``).  It cannot pass 100 %: a fill that read
the page too, or wrote it twice, moves more than is counted here."""

import harness
import roofline

fill = harness.load_module("per_layer", "page_fill.ms_per_circuit")


def read(ctx):
    ns = fill.chip_ns(ctx)
    counters = ctx["window_counters"]
    fills = (counters.get("pager.fill.in_place", 0)
             + counters.get("pager.fill.fresh", 0))
    if ns is None or not fills:
        return None
    page = roofline.ket_bytes(ctx["width"]) // ctx["pages"]
    least = roofline.least_seconds(hbm_bytes=fills * page, peaks=ctx["peaks"])
    harness.say(page_fills_counted=fills, page_bytes=page,
                page_fill_device_seconds=ns / 1e9,
                page_fill_least_seconds=least)
    return 100.0 * least / (ns / 1e9)
