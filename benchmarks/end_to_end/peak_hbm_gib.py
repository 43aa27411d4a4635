"""The allocator's high-water mark on the fullest chip once the window
has closed, set-up included: what decides the widest ket that fits."""


def read(ctx):
    return ctx["peak_bytes_after_window"] / 2 ** 30
