"""A chip's device time in the cross-tile pair sweeps of one
application: the launches the program names ``qrack_window_cross``
(``kernels/window_cross.json``).  Their count has to be the program's
``fuse.kernel.sweeps.cross``; both are printed on an earlier line."""

import harness


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    events = trace.kernel_events("window_cross")
    if not events:
        return None
    planned = ctx["window_counters"].get("fuse.kernel.sweeps.cross")
    launches = trace.chip_count(events)
    harness.say(cross_launches_in_trace=launches,
                fuse_kernel_sweeps_cross_counted=planned,
                equal=launches == planned)
    return trace.chip_ns(events) / 1e6 / ctx["attempted"]
