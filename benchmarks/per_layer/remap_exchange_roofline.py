"""The remap prologues' exchange as a share of the chip-to-chip
interconnect's peak.  Bound: ICI.

The bytes are what a chip sends in the trace's ``collective-permute``
operations, the time is the time a transfer was under way
(``remap.collective_ms_per_circuit``), the peak is the table's
``ici_bits_per_s``, all of a chip's links together.  It cannot pass
100 %.  On an earlier line the bytes stand beside two counts that have
to equal them: the benchmark's own, from the prologues the planner
emitted (``roofline_remap.sent_bytes``), and the program's counter
``exchange.pager.bytes``, a chip's share."""

import harness
import roofline
import roofline_remap


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    sent = roofline_remap.traced_bytes(trace)
    flight = sum(f for f, _ in trace.exposed_ns("pager_exchange").values())
    if not sent or not flight:
        return None
    seconds = flight / trace.chips / 1e9
    n, pages, counters = ctx["attempted"], ctx["pages"], ctx["window_counters"]
    counted = roofline_remap.sent_bytes(
        counters, roofline.ket_bytes(ctx["width"]) // pages) / n
    program = counters.get("exchange.pager.bytes", 0) / pages / n
    harness.say(sent_bytes_a_chip_an_application=sent / n,
                counted_from_the_prologues=counted,
                exchange_pager_bytes_a_chip=program,
                equal=sent / n == counted == program,
                transfers_a_chip_an_application=sum(
                    len(p) for p in trace.transfers("pager_exchange").values())
                / trace.chips / n,
                bytes_per_s_a_chip=sent / seconds)
    return 100.0 * roofline.ici_seconds(sent, ctx["peaks"]) / seconds
