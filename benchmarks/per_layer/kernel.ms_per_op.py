"""A chip's device time in the window kernel per fused op it carried:
its launches in the window over the program's ``fuse.kernel.ops``."""


def read(ctx):
    trace = ctx["trace"]
    ops = ctx["window_counters"].get("fuse.kernel.ops")
    if trace is None or not ops:
        return None
    events = trace.kernel_events("window_kernel")
    if not events:
        return None
    return trace.chip_ns(events) / 1e6 / ops
