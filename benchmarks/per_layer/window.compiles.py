"""Compile events inside the window; expected 0."""


def read(ctx):
    return ctx["window_compiles"]
