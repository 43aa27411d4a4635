"""Per-page fills that wrote over the ket the pager held, for one
application: the program's counter ``pager.fill.in_place`` over the
window, over its applications.  1.0 when every ``SetPermutation`` of the
window donated the pager's ket to the fill and none allocated a second
(``pager.fill.fresh`` counts those).  None where the program keeps no
such counter."""


def read(ctx):
    counters = ctx["window_counters"]
    if not any(k.startswith("pager.fill.") for k in counters):
        return None
    return counters.get("pager.fill.in_place", 0) / ctx["attempted"]
