"""Share of a chip's exchange time in which nothing else ran on that
chip, under the pager's own placement: the reading of
``pager.exposed_share``, which asks nothing of the placement."""

import harness


def read(ctx):
    return harness.load_module("per_layer", "pager.exposed_share").read(ctx)
