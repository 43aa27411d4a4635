"""A chip's device time in the XLA chain's windows for one application:
the operations of the program's modules ``jit_qrack_xla_window``
(``ops/fusion.window_fn``) and, where the ket is paged,
``jit_qrack_sharded_xla_window``, but for the exchange between chips that
such a window carries (``kernels/pager_exchange.json``): that is the
pager exchange's (``pager.collective_ms_per_circuit``).  None where no
window takes the chain."""

import program_spans

MODULES = ("jit_qrack_xla_window", "jit_qrack_sharded_xla_window")


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    kernels = ctx["trace"].kernels
    classes = spans.device_classes(
        kernels["window_kernel"] + kernels["pager_exchange"])
    chain = sum(ns for label, ns in classes.items()
                if label.split(":", 1)[0] in MODULES)
    return chain / 1e6 / ctx["attempted"] if chain else None
