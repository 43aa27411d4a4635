"""A chip's time with a transfer between chips under way, for one
application, under the pager's own placement: the reading of
``pager.collective_ms_per_circuit``, which asks nothing of the
placement."""

import harness


def read(ctx):
    return harness.load_module(
        "per_layer", "pager.collective_ms_per_circuit").read(ctx)
