"""The window kernel's share of its HBM roofline.  Bound: HBM.

The least a chip's launch can move is one read and one write of the
planes that chip holds: ``2 * ket bytes / pages``
(``roofline.launch_bytes``).  Launches and their time are a chip's,
counted in the trace; the peak is the table's.  It cannot pass 100 %: a
launch that reads its partner tile too moves more than is counted here,
never less."""

import harness
import roofline


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    events = trace.kernel_events("window_kernel")
    if not events:
        return None
    planned = ctx["window_counters"].get("fuse.kernel.sweeps")
    launches = trace.chip_count(events)
    harness.say(kernel_launches_in_trace=launches,
                fuse_kernel_sweeps_counted=planned,
                equal=launches == planned)
    seconds = trace.chip_ns(events) / 1e9
    least = roofline.least_seconds(
        hbm_bytes=launches * roofline.launch_bytes(ctx["width"], ctx["pages"]),
        peaks=ctx["peaks"])
    return 100.0 * least / seconds
