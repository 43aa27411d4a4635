"""Bytes a chip sends under the pager's own placement: the arithmetic
``remap_exchange_roofline`` rests on, from the program's counters of
what the planner emitted and by nothing of ``ops/sharded.exchange_cost``.

A prologue of ``k`` pairs across the page boundary splits the page into
``2^k`` sub-blocks, keeps the one whose carrier bits equal the page's
own bits and sends each of the others to another chip: ``(1 - 2^-k)``
of a page.  A permutation of the page bits left over after it sends the
whole page of every chip it moves, reckoned here as every chip's (the
Trotter step leaves none).  A gate the planner left on a paged qubit
sends half a page to the partner and half a page of results back, as
under a fixed placement (``roofline.paged_gate_bytes``).
"""

PROLOGUES = "remap.pager.prologues.k"


def prologues_by_k(counters):
    """``{k: prologues}`` from the program's counters."""
    return {int(name[len(PROLOGUES):]): count
            for name, count in counters.items() if name.startswith(PROLOGUES)}


def sent_bytes(counters, page_bytes):
    """What one chip sends in everything ``counters`` counted."""
    pages = sum(n * (1.0 - 2.0 ** -k)
                for k, n in prologues_by_k(counters).items())
    pages += counters.get("remap.pager.page_perms", 0)
    pages += counters.get("exchange.pager.global_2x2", 0)
    return pages * page_bytes


def traced_bytes(trace, kernel="pager_exchange"):
    """What one chip sends in the trace's transfers of ``kernel``: the
    first array of each one's result, every plane's, over the planes."""
    found = trace.transfers(kernel)
    return sum(b for plane in found.values() for _, _, b in plane) / trace.chips
