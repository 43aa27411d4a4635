"""Seconds of those events: compile in a cold run, cache loads in a warm one."""


def read(ctx):
    return ctx["compiles_before_window"][1]
