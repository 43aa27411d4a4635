"""The allocator's high-water mark in kets: ``peak_hbm_gib``'s bytes
(the fullest chip, after the window, set-up included) over the bytes of
the planes this cell's ket has (``roofline.ket_bytes``).  1.0 and a
little: the process never held a second array of the ket's size; 4.0:
two eager fills back to back, each with its copy (the dense cells until
PR 43).  One chip, one ket: the paged cells have their own reckoning."""

import roofline


def read(ctx):
    peak = ctx.get("peak_bytes_after_window")
    if not peak:
        return None
    return peak / roofline.ket_bytes(ctx["width"])
