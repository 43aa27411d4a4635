"""Kernel sweeps of one application that carry a two-target op: the
program's counters ``fuse.kernel.twoq.sweeps.intile`` + ``.pair`` +
``.quad`` over the window, over its applications.  Their sum has to be
the launches ``kernels/window_twoq.json`` finds in the trace; both are
printed on an earlier line, beside the ops they carried."""

import harness

PREFIX = "fuse.kernel.twoq.sweeps."


def read(ctx):
    counters = ctx["window_counters"]
    by_placement = {k[len(PREFIX):]: v for k, v in counters.items()
                    if k.startswith(PREFIX)}
    if not by_placement:  # a program without the op counts none
        return None
    planned = sum(by_placement.values())
    trace = ctx["trace"]
    launches = (trace.chip_count(trace.kernel_events("window_twoq"))
                if trace is not None else None)
    harness.say(twoq_sweeps_counted=by_placement,
                twoq_ops_counted=counters.get("fuse.kernel.twoq.ops"),
                twoq_launches_in_trace=launches, equal=launches == planned)
    return planned / ctx["attempted"]
