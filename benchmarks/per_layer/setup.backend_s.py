"""Seconds of ``compile.backend`` under the program's spans before the
window: the backend's compiles, or against a warm persistent cache the
loads that stand for them; the program's own (``setup.compile_s`` counts
every jit of the process, the benchmark's too).  Counted where the stage
is outermost (``setup_spans``)."""

import setup_spans


def read(ctx):
    found = setup_spans.load(ctx)
    return None if found is None else found.stage_s("compile.backend")
