"""Bytes of the ALU's rotation: the arithmetic ``alu_rotate_roofline``
rests on, of the benchmark's own.

An add of a constant on a contiguous register (``INC``, ``DEC``) moves
every amplitude to another place of the ket: the least it can move is one
read and one write of the planes (``roofline.sweep_bytes``), 4 GiB at
w28, whatever the constant.  A rotation that laid its ket out twice, or
copied its result back over its planes, moves more than is counted here,
never less.  The program counts the same under
``roofline.tpu.alu.rotate.planned_bytes`` (``engines/tpu.py _k_rotate``);
the reader holds the two equal.
"""

import roofline

ROTATE = "jit_qrack_alu_rotate"
# every whole-ket program of the ALU: the rotation, and the lowerings it
# took the add family off (an index gather, a factor multiply), which
# the rest of the ALU still runs
MODULES = ("jit_qrack_alu_", "jit_gather", "jit_phase_factor_apply")
COUNTERS = ("alu.tpu.rotate", "alu.tpu.gather", "alu.tpu.out_of_place",
            "alu.tpu.phase_fn")
PLANNED = "roofline.tpu.alu.rotate.planned_bytes"


def rotate_bytes(width, rotations, itemsize=4):
    """The least ``rotations`` rotations of a ket of ``width`` move."""
    return rotations * roofline.sweep_bytes(width, itemsize)


def chip_ns(spans, modules):
    """A chip's device time (ns: every plane's, averaged over the
    planes) in the modules whose names begin with one of ``modules``;
    0 where the trace holds no operation of theirs."""
    if not spans.device:
        return 0
    total = sum(dur for events in spans.device.values()
                for _, _, dur, module in events if module.startswith(modules))
    return total / len(spans.device)


def counted(counters):
    """Whether the program counts its ALU at all (a parent of PR 49 has
    none of these counters: its readers then read nothing)."""
    return any(k.startswith("alu.tpu.") for k in counters)
