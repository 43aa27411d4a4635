"""Out-of-place and gathered ALU calls of one application that took the
lowering the chip has no form for: ``alu.tpu.out_of_place`` (eager: a
second ket, index arrays, an XLA scatter) + ``alu.tpu.gather``, over the
window's applications.  What ``alu.gather_programs_per_circuit`` is to
Grover: 0 where every modular call of the deployment is a table write.
None where the program counts no ALU call (an untraced run, a parent of
PR 49)."""

import roofline_alu
import roofline_measure


def read(ctx):
    counters = ctx["window_counters"]
    if not roofline_alu.counted(counters):
        return None
    return sum(counters.get(k, 0) for k in roofline_measure.SCATTER_COUNTERS) \
        / ctx["attempted"]
