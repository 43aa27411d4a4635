"""A chip's device time in the window kernel's launches for one
application: summed durations of its events in the device trace, a
chip, over the applications traced."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    events = trace.kernel_events("window_kernel")
    if not events:
        return None
    return trace.chip_ns(events) / 1e6 / ctx["attempted"]
