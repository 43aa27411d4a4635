"""A chip's time with an exchange between chips under way, for one
application: the ``collective-permute`` operations of the trace
(``kernels/pager_exchange.json``), each from its start's begin to its
done's end (``tracing.Trace.transfers``); every plane's, averaged over
the planes.  None where the trace holds none (a ket on one chip)."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    flight = sum(f for f, _ in trace.exposed_ns("pager_exchange").values())
    if not flight:
        return None
    return flight / trace.chips / 1e6 / ctx["attempted"]
