"""Device time of the window kernel per fused op it carried: all its
launches in the window over the program's ``fuse.kernel.ops``."""


def read(ctx):
    trace = ctx["trace"]
    ops = ctx["window_counters"].get("fuse.kernel.ops")
    if trace is None or not ops:
        return None
    events = trace.kernel_events("window_kernel")
    if not events:
        return None
    return sum(d for _, _, d in events) / 1e6 / ops
