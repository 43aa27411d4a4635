"""A chip's device time in the in-tile sweeps of one application: the
launches the program names ``qrack_window_intile``
(``kernels/window_intile.json``)."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    events = trace.kernel_events("window_intile")
    if not events:
        return None
    return trace.chip_ns(events) / 1e6 / ctx["attempted"]
