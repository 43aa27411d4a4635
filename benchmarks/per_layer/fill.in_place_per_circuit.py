"""Fills that wrote over the ket they were given, for one application:
the program's counter ``engine.fill.in_place`` over the window, over
its applications.  1.0 when every ``SetPermutation`` of the window
donated the engine's ket to the fill and none allocated a second
(``engine.fill.fresh`` counts those).  None where the program keeps no
such counter."""


def read(ctx):
    counters = ctx["window_counters"]
    if not any(k.startswith("engine.fill.") for k in counters):
        return None
    return counters.get("engine.fill.in_place", 0) / ctx["attempted"]
