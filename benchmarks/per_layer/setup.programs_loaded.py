"""Programs taken from the program store before the window: the
``warmstart.program.load`` spans among the program's set-up spans
(``setup_spans``), one a window program that a process read back from
``<compile cache>/qrack_programs`` where an earlier one traced and
exported it.  0 on a cold machine; nothing where the program keeps no
such store (a parent of PR 52)."""

import setup_spans

LOAD = "warmstart.program.load"


def read(ctx):
    found = setup_spans.load(ctx)
    if found is None:
        return None
    from qrack_tpu.checkpoint import warmstart

    if not hasattr(warmstart, "stored_program"):
        return None
    return sum(e["name"] == LOAD for e in found.program)
