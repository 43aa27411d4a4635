"""A chip's device time in building one application's operands: the small
operations of the kernel windows' module ``jit_qrack_kernel_window``
(``jit_qrack_sharded_kernel_window`` where the ket is paged: packing the
operands into the kernel's scalar columns) and of the eager
programs (the puts and conversions of ``dense_operands``), small meaning
that they touch no ket-sized array.  The whole table of device time that
is no launch, by module and operation, is printed on an earlier line."""

import harness
import program_spans

MODULES = ("jit_qrack_kernel_window", "jit_qrack_sharded_kernel_window",
           "eager")


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    classes = spans.device_classes(ctx["trace"].kernels["window_kernel"])
    n = ctx["attempted"]
    harness.say(device_ms_per_circuit_no_launch={
        k: v / 1e6 / n for k, v in sorted(classes.items(), key=lambda kv: -kv[1])})
    ns = sum(classes.get(module + ":small", 0) for module in MODULES)
    return ns / 1e6 / n
