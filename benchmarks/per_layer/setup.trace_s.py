"""Seconds of ``compile.trace`` under the program's spans before the
window: JAX tracing functions to jaxprs.  Counted where the stage is
outermost (``setup_spans``)."""

import setup_spans


def read(ctx):
    found = setup_spans.load(ctx)
    return None if found is None else found.stage_s("compile.trace")
