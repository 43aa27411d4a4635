"""Device time one application spends building operands: the small
operations of the kernel windows' module ``jit_qrack_kernel_window``
(packing the operands into the kernel's scalar columns) and of the eager
programs (the puts and conversions of ``dense_operands``), small meaning
that they touch no ket-sized array.  The whole table of device time that
is no launch, by module and operation, is printed on an earlier line."""

import harness
import program_spans


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    classes = spans.device_classes(ctx["trace"].kernels["window_kernel"])
    n = ctx["attempted"]
    harness.say(device_ms_per_circuit_no_launch={
        k: v / 1e6 / n for k, v in sorted(classes.items(), key=lambda kv: -kv[1])})
    ns = (classes.get("jit_qrack_kernel_window:small", 0)
          + classes.get("eager:small", 0))
    return ns / 1e6 / n
