"""Pages a chip sends in one application: the bytes of the trace's
``collective-permute`` operations (the first array of each one's
result, ``tracing.Trace.transfers``), a chip, over a page's bytes.  The
fixed placement sends 6."""

import roofline
import roofline_remap


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    sent = roofline_remap.traced_bytes(trace)
    if not sent:
        return None
    page = roofline.ket_bytes(ctx["width"]) // ctx["pages"]
    return sent / ctx["attempted"] / page
