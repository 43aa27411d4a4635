"""A chip's device time in the ALU's own whole-ket programs for one
application: the operations of the modules ``jit_qrack_alu_*`` (the
rotation an add of a constant is, ``engines/tpu.qrack_alu_rotate``) and
of the lowerings the rest of the ALU still runs (``jit_gather``,
``jit_phase_factor_apply``; their eager index arithmetic carries no name
of the program's and is not in this number).  A comparator's phase flip
that rides the fused window is the window kernel's time, not this.  None
where the program counts no ALU call (a parent of PR 49)."""

import program_spans
import roofline_alu


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None or not roofline_alu.counted(ctx["window_counters"]):
        return None
    ns = roofline_alu.chip_ns(spans, roofline_alu.MODULES)
    return ns / 1e6 / ctx["attempted"]
