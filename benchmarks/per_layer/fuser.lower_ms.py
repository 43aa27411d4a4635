"""Host time of one application in ``qrack.fuse.lower``: lowering the
gates, the window's structure and plan, the program lookup.  Summed over
the application's flushes wherever they happen (the last one is inside
the completion read); median over the traced applications."""

import program_spans


def read(ctx):
    return program_spans.span_ms_per_application(ctx, "qrack.fuse.lower")
