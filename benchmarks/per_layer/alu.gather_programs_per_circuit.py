"""ALU calls of one application that took the lowering the chip has no
form for: ``alu.tpu.gather`` (eager whole-ket index arrays and a general
gather of the ket through them) + ``alu.tpu.phase_fn`` (eager whole-ket
factor arrays and a multiply), over the window's applications.  What
``twoq.eager_programs_per_circuit`` is to a coupler: 0 where every ALU
call of the deployment has its form.  None where the program counts no
ALU call (an untraced run, a parent of PR 49)."""

import roofline_alu


def read(ctx):
    counters = ctx["window_counters"]
    if not roofline_alu.counted(counters):
        return None
    return (counters.get("alu.tpu.gather", 0)
            + counters.get("alu.tpu.phase_fn", 0)) / ctx["attempted"]
