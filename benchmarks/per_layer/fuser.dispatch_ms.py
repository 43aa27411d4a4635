"""Host time of one application in ``qrack.fuse.dispatch``: the call of
the window program, enqueue plus whatever the caller waits behind the
device.  Summed over the application's flushes; median over the traced
applications."""

import program_spans


def read(ctx):
    return program_spans.span_ms_per_application(ctx, "qrack.fuse.dispatch")
