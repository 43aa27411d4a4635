"""Seconds of ``compile.cache_load`` under the program's spans before the
window: the persistent cache's retrievals, each inside a
``compile.backend``.  0 in a cold run."""

import setup_spans


def read(ctx):
    found = setup_spans.load(ctx)
    return None if found is None else found.named_s("compile.cache_load")
