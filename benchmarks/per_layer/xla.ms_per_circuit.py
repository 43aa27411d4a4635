"""A chip's device time in everything that is no named kernel's launch
(``kernels/*.json``), for one application: the XLA chain's fusions, the
SetPermutation fill, the read."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return trace.chip_ns(trace.other_events()) / 1e6 / ctx["attempted"]
