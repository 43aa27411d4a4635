"""Counts of structure without a ket: what the fuser plans for one
application of a family at the cell's own width.

A stub stands where the engine's planes would be: the real gate funnel,
``GateStreamFuser``, ``lower_gates``, ``structure_of`` and
``kernel_lowering(..., backend="tpu")`` decide, nothing is allocated and
nothing runs.  These are counts, never times.
"""

from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk


class PlanOnlyEngine(QEngineTPU):
    """QEngineTPU with no planes: records each flushed window's plan."""

    def __init__(self, qubit_count):
        self.windows = []
        super().__init__(qubit_count, rand_global_phase=False)

    def SetPermutation(self, perm, phase=None):
        self._state = None  # drops a pending window, as the engine's does

    def GetAmplitude(self, perm):
        if self._fuser.gates:
            self._fuser.flush("read")
        return 0j

    def _fuse_flush(self, gates):
        ops = fu.lower_gates(gates)
        structure = fu.structure_of(ops)
        if len(ops) == 1:
            self.windows.append({"structure": structure, "path": "single_op",
                                 "sweeps": 1, "cross_tile": 0})
            return 1
        plan, why = fu.kernel_lowering(self.qubit_count, structure,
                                       backend="tpu")
        if plan is None:
            self.windows.append({"structure": structure, "path": why,
                                 "sweeps": len(ops), "cross_tile": 0})
            return 1
        segments = pk.plan_window(structure, plan["block_pow"])
        self.windows.append({
            "structure": structure, "path": "kernel", "sweeps": plan["sweeps"],
            "cross_tile": sum(1 for s in segments if s["xgen"] is not None)})
        return 1


def _no_spans(name):
    import contextlib

    return contextlib.nullcontext()


def plan_application(family, width, params, seed=1):
    """The windows of one application, as the fuser flushes them."""
    plan = family.Plan(width, params, seed)
    q = PlanOnlyEngine(width)
    family.start(q, plan, _no_spans)
    q.windows.clear()
    family.enqueue(q, plan, 0, _no_spans)
    q.GetAmplitude(family.read_index(plan, 0))
    return q.windows


def summary(windows):
    kernel = [w for w in windows if w["path"] == "kernel"]
    return {
        "ops": sum(len(w["structure"]) for w in windows),
        "windows": len(windows),
        "programs": len({w["structure"] for w in windows}),
        "kernel_windows": len(kernel),
        "kernel_sweeps": sum(w["sweeps"] for w in kernel),
        "kernel_sweeps_by_window": [w["sweeps"] for w in kernel],
        "cross_tile_segments": sum(w["cross_tile"] for w in kernel),
        "fallbacks": [(w["path"], len(w["structure"])) for w in windows
                      if w["path"] != "kernel"],
    }
