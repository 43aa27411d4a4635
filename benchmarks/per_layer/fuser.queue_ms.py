"""Host time of one application's gate calls that is in no flush:
queueing and merging gates between the windows.  ``bench.gate_calls``
minus the program's ``qrack.fuse.flush`` spans inside it, median over
the traced applications."""

import program_spans


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    calls = spans.per_application("bench.gate_calls")
    flushes = spans.per_application("qrack.fuse.flush",
                                    inside="bench.gate_calls")
    return program_spans.median_ms([c - f for c, f in zip(calls, flushes)])
