"""Set-up seconds spent inside the program: the union of its top-level
spans on the caller's thread before the window opened (``setup_s`` minus
it is the process's start, the imports, the runtime's start and the
benchmark's own work).  It is a traced run's set-up: one warm
application more than an untraced one's (the driver's barrier probe),
and the reads of the comparisons before the window, which ``setup_s``
leaves out (``compare_seconds_before_window`` on an earlier line)."""

import setup_spans


def read(ctx):
    found = setup_spans.load(ctx)
    return None if found is None else found.program_s
