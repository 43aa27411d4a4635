"""Device time of everything that is not a window kernel launch, for
one application: the XLA chain's fusions, the SetPermutation fill, the
read."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return sum(d for _, _, d in trace.other_events()) / 1e6 / ctx["attempted"]
