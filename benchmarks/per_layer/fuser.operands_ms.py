"""Host time of one application in ``qrack.fuse.operands``: every small
host-to-device put and conversion that builds a window's operands, and
whatever the caller waits there behind the device.  Summed over the
application's flushes; median over the traced applications."""

import program_spans


def read(ctx):
    return program_spans.span_ms_per_application(ctx, "qrack.fuse.operands")
