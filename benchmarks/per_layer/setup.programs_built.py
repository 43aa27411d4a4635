"""Programs JAX built or loaded before the window: its
``backend_compile_duration`` events.  Against a warm persistent cache
the event is a load."""


def read(ctx):
    return ctx["compiles_before_window"][0]
