"""Remap prologues one application of the paged QFT runs: the reading
of ``remap.prologues_per_circuit`` (the program's counters
``remap.pager.prologues.k<k>`` over the window), which asks nothing of
the circuit.  Two of two pairs each, the same in every application:
``SetPermutation`` resets the placement table."""

import harness


def read(ctx):
    return harness.load_module(
        "per_layer", "remap.prologues_per_circuit").read(ctx)
