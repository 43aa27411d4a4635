"""The allocator's high-water mark in pages: ``peak_hbm_gib``'s bytes
(the fullest chip, after the window, set-up included) over the bytes of
one page of this cell's ket (``roofline.ket_bytes`` over the pages).
The paged cells' reckoning that ``hbm.kets_at_peak`` leaves to them: 1.0
and a little where a chip never held a second array of a page's size,
2.0 where a fill, a copy or an exchange stood a second page beside the
first.  At w32 on four chips a page is 8 GiB: under 1.97 or no run."""

import roofline


def read(ctx):
    peak = ctx.get("peak_bytes_after_window")
    if not peak:
        return None
    return peak / (roofline.ket_bytes(ctx["width"]) // ctx["pages"])
