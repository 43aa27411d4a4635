"""Ops of runs of diagonal ops that the window kernel folded in one
application: the program's counter ``fuse.kernel.diag_run.folded_ops``
over the window, over its applications.  An op of a run that reads a bit
above the tile and shares its in-tile signature with others of the run
is one complex multiply on one vreg a tile, and the run one pass over
the tile, where each such op was a pass of its own
(``kernel.intile_ms_per_circuit`` falls with it).  A
program that does not count them (a parent of PR 54, an untraced run)
reads nothing."""


def read(ctx):
    folded = ctx["window_counters"].get("fuse.kernel.diag_run.folded_ops")
    if folded is None:
        return None
    return folded / ctx["attempted"]
