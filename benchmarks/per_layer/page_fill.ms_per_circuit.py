"""A chip's device time in the pager's ``SetPermutation`` for one
application: the operations of the program's module
``jit_qrack_page_fill`` (``parallel/pager.QPager._p_page_fill``: every
page writes zeros over itself and one page the amplitude at its offset).
None where the program has no such module (a parent of PR 45 fills with
one undonated program over the global axis, ``jit_f``, and at w31 does
not construct at all)."""

import program_spans

MODULE = "jit_qrack_page_fill"


def chip_ns(ctx):
    """A chip's device time (ns: every plane's, averaged over the
    planes) in the fill's module inside the window; None where there is
    no trace, or no operation of that module in it."""
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    total = sum(dur for events in spans.device.values()
                for _, _, dur, module in events if module == MODULE)
    return total / len(spans.device) if total else None


def read(ctx):
    ns = chip_ns(ctx)
    return None if ns is None else ns / 1e6 / ctx["attempted"]
