"""Second leads that joined a kernel segment in one application: the
program's counter ``fuse.kernel.leads.paired`` over the window, over its
applications.  A cross-tile 2 x 2 that directly follows a bare one on
another qubit shares its launch, so each is a sweep the application did
not pay (``fuser.sweeps_per_circuit`` fell by as many).  A program that
does not count them (a parent of PR 50, an untraced run) reads nothing."""


def read(ctx):
    paired = ctx["window_counters"].get("fuse.kernel.leads.paired")
    if paired is None:
        return None
    return paired / ctx["attempted"]
