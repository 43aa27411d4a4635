"""Passes over the ket the fuser planned for one application: the
program's counters ``fuse.kernel.sweeps`` + ``fuse.xla.sweeps`` over the
window, over its applications."""


def read(ctx):
    counters = ctx["window_counters"]
    sweeps = counters.get("fuse.kernel.sweeps", 0) + counters.get("fuse.xla.sweeps", 0)
    return sweeps / ctx["attempted"] if sweeps else None
