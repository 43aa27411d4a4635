"""Seconds in ``engine.set_permutation`` before the window, whole: its
``compile.*`` children included (an eager fill's first call compiles).
The constructor's fill is inside ``factory.create_interface`` too."""

import setup_spans


def read(ctx):
    found = setup_spans.load(ctx)
    return None if found is None else found.named_s("engine.set_permutation")
