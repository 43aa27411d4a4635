"""Bytes of the ALU's table write and of a register's measurement: the
arithmetic ``modn_write_roofline`` and ``measure_roofline`` rest on, of
the benchmark's own.

An out-of-place modular call (``POWModNOut``, ``MULModNOut``,
``IMULModNOut``) on a ket whose out register reads 0 leaves, for every
other bit of the index, one amplitude in a place the table names: the
least it can move is one write of the planes and no read
(``roofline.ket_bytes``: 2 GiB at w28, 2.6 ms at the published peak; the
slice it reads is 2^-14 of that).  A register's measurement reads the
planes once for the register's probabilities and reads and writes them
once for the collapse: three times the planes' bytes.  A write that laid
its ket out twice, or a measurement a qubit at a time, moves more than
is counted here, never less.  The program counts the same under
``roofline.tpu.alu.modn.planned_bytes`` and
``roofline.tpu.measure.planned_bytes`` (``engines/tpu.py`` ``_k_modn``,
``_k_prob_reg_all``, ``_k_collapse``); the readers hold each pair equal.
"""

import roofline
from roofline_alu import chip_ns  # noqa: F401  (the readers' device time)

# the table write's module, and the program that takes its slice first
MODN = "jit_qrack_alu_modn"
# the register's reduction and the collapse
MEASURE = ("jit_qrack_prob_reg", "jit_qrack_collapse")
MODN_COUNTER = "alu.tpu.modn"
# the lowerings the table write took the out-of-place family off
SCATTER_COUNTERS = ("alu.tpu.out_of_place", "alu.tpu.gather")
REG_COUNTER = "measure.tpu.reg"
PASSES_COUNTER = "measure.tpu.passes"
MODN_PLANNED = "roofline.tpu.alu.modn.planned_bytes"
MEASURE_PLANNED = "roofline.tpu.measure.planned_bytes"
SAMPLE_SPAN = "qrack.engine.measure.sample"


def modn_write_bytes(width, writes, itemsize=4):
    """The least ``writes`` table writes of a ket of ``width`` move."""
    return writes * roofline.ket_bytes(width, itemsize)


def measure_bytes(width, registers, itemsize=4):
    """The least ``registers`` register measurements move: one read for
    the reduction, one read and one write for the collapse."""
    return registers * 3 * roofline.ket_bytes(width, itemsize)


def counts_modn(counters):
    """Whether the program has a table write to count (a parent of
    PR 53 has no ``alu.tpu.modn``: its readers then read nothing)."""
    return MODN_COUNTER in counters


def counts_measure(counters):
    """Whether the program counts its measurements at all."""
    return any(k.startswith("measure.tpu.") for k in counters)
