"""Device programs the flushes of one application issue: the program's
counters ``fuse.<engine>.programs`` over the window (one put per operand
and the window program, or one eager program for a one-op flush), over
its applications."""

import re


def read(ctx):
    programs = sum(v for k, v in ctx["window_counters"].items()
                   if re.fullmatch(r"fuse\.[^.]+\.programs", k))
    return programs / ctx["attempted"] if programs else None
