"""Whole-ket programs the ALU ran for one application: the program's
counters ``alu.tpu.rotate`` + ``alu.tpu.gather`` + ``alu.tpu.out_of_place``
+ ``alu.tpu.phase_fn`` over the window, over its applications.  Each is a
barrier: the pending window flushes before it.  A comparator's flip that
was queued as a gate of the window (``alu.tpu.phase_queued``) runs no
program and is not counted.  Read only where the program counts its ALU:
none of the ``alu.tpu.*`` means an untraced run or a parent of PR 49,
not 0."""

import roofline_alu


def read(ctx):
    counters = ctx["window_counters"]
    if not roofline_alu.counted(counters):
        return None
    return sum(counters.get(k, 0) for k in roofline_alu.COUNTERS) \
        / ctx["attempted"]
