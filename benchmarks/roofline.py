"""Bytes and least times: the arithmetic a roofline share rests on.

Copied in substance from ``qrack_tpu/telemetry/roofline.plane_pass_bytes``
(one pass over split planes reads and writes both), with the peak taken
from ``peaks.json`` and never defaulted.
"""


def ket_bytes(width, itemsize=4):
    """The split planes (2, 2^width) of a ket."""
    return 2 * itemsize << width


def sweep_bytes(width, itemsize=4):
    """One read and one write of the planes: the least a pass moves."""
    return 2 * ket_bytes(width, itemsize)


def least_seconds(hbm_bytes, peaks):
    """The least time the chip could take to move that many bytes."""
    return hbm_bytes / peaks["hbm_bytes_per_s"]
