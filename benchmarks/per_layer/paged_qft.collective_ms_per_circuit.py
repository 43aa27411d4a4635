"""A chip's time with a transfer between chips under way, for one
application of the paged QFT: the reading of
``pager.collective_ms_per_circuit``, which asks nothing of the circuit
or the placement."""

import harness


def read(ctx):
    return harness.load_module(
        "per_layer", "pager.collective_ms_per_circuit").read(ctx)
