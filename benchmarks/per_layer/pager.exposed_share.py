"""Share of a chip's exchange time (``pager.collective_ms_per_circuit``)
in which no other operation ran on that chip: what the exchange adds to
the application, the rest being hidden behind the chip's own work.
Every plane's, summed over the planes."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    found = trace.exposed_ns("pager_exchange").values()
    flight = sum(f for f, _ in found)
    if not flight:
        return None
    return 100.0 * sum(e for _, e in found) / flight
