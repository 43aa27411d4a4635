"""Seconds in ``factory.create_interface`` before the window, whole: the
stack's construction, the constructor's fill and its compiles included."""

import setup_spans


def read(ctx):
    found = setup_spans.load(ctx)
    return None if found is None else found.named_s("factory.create_interface")
