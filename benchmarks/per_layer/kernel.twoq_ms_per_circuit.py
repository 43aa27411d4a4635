"""A chip's device time in the sweeps of one application that carry a
two-target op: the launches the program names ``qrack_window_twoq_*``
(``kernels/window_twoq.json``)."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    events = trace.kernel_events("window_twoq")
    if not events:
        return None
    return trace.chip_ns(events) / 1e6 / ctx["attempted"]
