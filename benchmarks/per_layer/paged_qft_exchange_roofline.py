"""The paged QFT's exchange as a share of the chip-to-chip
interconnect's peak.  Bound: ICI.  The reading of
``remap_exchange_roofline``: the bytes of the trace's
``collective-permute`` operations over the time a transfer was under
way, beside the benchmark's own count from the prologues the planner
emitted (``roofline_remap.sent_bytes``, which holds for any placement)
and the program's counter ``exchange.pager.bytes``, which have to be
``equal`` (1.5 pages a chip an application: 6 GiB at w31)."""

import harness


def read(ctx):
    return harness.load_module(
        "per_layer", "remap_exchange_roofline").read(ctx)
