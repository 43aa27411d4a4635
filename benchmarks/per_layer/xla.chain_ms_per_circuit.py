"""Device time of the XLA chain's windows for one application: the
operations of the program's module ``jit_qrack_xla_window``
(``ops/fusion.window_fn``).  None where no window takes the chain."""

import program_spans


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    classes = spans.device_classes(ctx["trace"].kernels["window_kernel"])
    chain = sum(v for k, v in classes.items()
                if k.startswith("jit_qrack_xla_window:"))
    return chain / 1e6 / ctx["attempted"] if chain else None
