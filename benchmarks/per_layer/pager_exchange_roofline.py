"""The pager exchange's share of the chip-to-chip interconnect's peak.
Bound: ICI.

The bytes are what a chip sends in the trace's ``collective-permute``
operations (the first array of each one's result), the time is the time
a transfer was under way (``pager.collective_ms_per_circuit``), the peak
is the table's ``ici_bits_per_s``, all of a chip's links together.  It
cannot pass 100 %.  On an earlier line the bytes stand beside two counts
that have to equal them where the placement is held at the identity: the
benchmark's own, from the gate list (``roofline.paged_gate_bytes``), and
the program's counter ``exchange.pager.bytes``, a chip's share."""

import harness
import roofline


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    found = trace.transfers("pager_exchange")
    sent = sum(b for plane in found.values() for _, _, b in plane) / trace.chips
    flight = sum(f for f, _ in trace.exposed_ns("pager_exchange").values())
    if not sent or not flight:
        return None
    seconds = flight / trace.chips / 1e9
    n, pages, cell = ctx["attempted"], ctx["pages"], ctx["cell"]
    counted = roofline.paged_gate_bytes(
        cell.family.gates(ctx["width"], cell.config["circuit"]),
        ctx["width"] - (pages.bit_length() - 1),
        roofline.ket_bytes(ctx["width"]) // pages)
    program = ctx["window_counters"].get("exchange.pager.bytes", 0) / pages / n
    harness.say(sent_bytes_a_chip_an_application=sent / n,
                counted_from_the_gate_list=counted,
                exchange_pager_bytes_a_chip=program,
                equal=sent / n == counted == program,
                transfers_a_chip_an_application=sum(
                    len(p) for p in found.values()) / trace.chips / n,
                bytes_per_s_a_chip=sent / seconds)
    return 100.0 * roofline.ici_seconds(sent, ctx["peaks"]) / seconds
