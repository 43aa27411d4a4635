"""A chip's device time in ``SetPermutation``'s fill for one application:
the operations of the program's module ``jit_qrack_fill``
(``engines/tpu.qrack_fill``: zeros and one amplitude written over the
ket the engine owns).  None where the program has no such module (a
parent of PR 43 fills with eager operations, which carry no name of the
program's)."""

import program_spans

MODULE = "jit_qrack_fill"


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
