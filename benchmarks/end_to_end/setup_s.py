"""Process start to the first timed application: imports, engine,
compile or cache load, warm-up.  Comparison work is left out."""


def read(ctx):
    return ctx["setup_seconds"]
