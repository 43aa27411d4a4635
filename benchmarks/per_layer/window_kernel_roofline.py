"""The window kernel's share of its HBM roofline.  Bound: HBM.

The least a launch can move is one read and one write of the planes:
``2 * ket bytes``.  Launches are counted in the trace; the peak is the
table's.  It cannot pass 100 %: a launch that reads its partner tile too
moves more than is counted here, never less."""

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
    harness.say(kernel_launches_in_trace=len(events),
                fuse_kernel_sweeps_counted=planned,
                equal=len(events) == planned)
    seconds = sum(d for _, _, d in events) / 1e9
    least = roofline.least_seconds(
        hbm_bytes=len(events) * roofline.sweep_bytes(ctx["width"]),
        peaks=ctx["peaks"])
    return 100.0 * least / seconds
