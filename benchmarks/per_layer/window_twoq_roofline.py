"""The share of their HBM roofline that the sweeps carrying a two-target
op reach.  Bound: HBM.

The least a chip's launch can move is one read and one write of the
planes that chip holds (``roofline.launch_bytes``); the launches and
their time are counted in the trace (``kernels/window_twoq.json``), the
peak is the table's.  It cannot pass 100 %: a launch on the pair grid
reads a second tile and one on the quad grid three more, which is more
than is counted here, never less."""

import roofline


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    events = trace.kernel_events("window_twoq")
    if not events:
        return None
    least = roofline.least_seconds(
        hbm_bytes=trace.chip_count(events)
        * roofline.launch_bytes(ctx["width"], ctx["pages"]),
        peaks=ctx["peaks"])
    return 100.0 * least / (trace.chip_ns(events) / 1e9)
