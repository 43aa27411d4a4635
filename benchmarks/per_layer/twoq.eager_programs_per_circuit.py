"""Eager whole-ket two-qubit programs of one application: the program's
counters ``gate.tpu.swap.w<n>`` + ``gate.tpu.4x4.w<n>`` over the window,
over its applications.  A coupler that enters the fused window as one
op runs no such program; one that cannot flushes the pending window and
runs one over the whole ket.  Read only where the engine's gate counters
are on (its ``gate.tpu.*`` count the single-qubit gates too): none of
them means an untraced run or another engine, not 0."""


def read(ctx):
    counters = ctx["window_counters"]
    if not any(k.startswith("gate.tpu.") for k in counters):
        return None
    eager = sum(v for k, v in counters.items()
                if k.startswith(("gate.tpu.swap.", "gate.tpu.4x4.")))
    return eager / ctx["attempted"]
