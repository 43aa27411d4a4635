"""Bytes and least times: the arithmetic a roofline share rests on.

Copied in substance from ``qrack_tpu/telemetry/roofline.plane_pass_bytes``
(one pass over split planes reads and writes both), with the peak taken
from ``peaks.json`` and never defaulted.  The exchange between chips is
reckoned from the gate list (``paged_gate_bytes``), by arithmetic of the
benchmark's own: it shares nothing with ``ops/sharded.exchange_cost``.
"""


def ket_bytes(width, itemsize=4):
    """The split planes (2, 2^width) of a ket."""
    return 2 * itemsize << width


def sweep_bytes(width, itemsize=4):
    """One read and one write of the planes: the least a pass moves."""
    return 2 * ket_bytes(width, itemsize)


def launch_bytes(width, pages=1, itemsize=4):
    """The least one chip's launch of a sweep moves: one read and one
    write of the planes that chip holds, a page of the ket."""
    return sweep_bytes(width, itemsize) // pages


def least_seconds(hbm_bytes, peaks):
    """The least time the chip could take to move that many bytes."""
    return hbm_bytes / peaks["hbm_bytes_per_s"]


def paged_gate_bytes(gates, local_bits, page_bytes):
    """What one chip sends in one application of ``gates`` when every
    logical qubit sits at its own bit position (a placement table held
    at the identity): a gate whose matrix is not diagonal, on a qubit at
    or above ``local_bits``, mixes each amplitude with one of the partner
    chip's.  The chip sends the half of its page it cannot pair and sends
    the partner's results back: a page a gate.  ``gates`` are
    ``(controls, matrix, target)``."""
    return page_bytes * sum(
        1 for _, matrix, target in gates
        if target >= local_bits and (matrix[0][1] != 0 or matrix[1][0] != 0))


def ici_seconds(sent_bytes, peaks):
    """The least time one chip's links take to send that many bytes."""
    return sent_bytes / (peaks["ici_bits_per_s"] / 8)
