"""The QFT family: its circuit builder and the basis-state planes the
tests and the driver's entry point start from.

Gate order matches QInterface::QFT (reference:
src/qinterface/qinterface.cpp:114) so results are bit-for-bit
comparable with the gate-at-a-time path.  The circuit runs where every
other circuit runs: gate calls or ``QCircuit.RunFused`` into an engine,
whose fuser windows it (ops/fusion.py).
"""

from __future__ import annotations

import cmath
import math

import jax
import jax.numpy as jnp


def qft_qcircuit(n: int, inverse: bool = False):
    """The QFT as a QCircuit gate-IR object — the form ``RunFused``
    lowers and the serving layer batches (QCircuit.shape_key /
    compile_batched_fn).  Gate order matches QInterface::QFT exactly
    (reference: src/qinterface/qinterface.cpp:114), so states are
    bit-for-bit comparable with an engine's own ``QFT``."""
    from ..layers.qcircuit import QCircuit
    from .. import matrices as mat

    circ = QCircuit(n)
    end = n - 1
    for i in range(n):
        h_bit = i if inverse else end - i
        if i:
            for j in range(i):
                other = h_bit - 1 - j if inverse else h_bit + 1 + j
                ang = (-1.0 if inverse else 1.0) * math.pi / (1 << (j + 1))
                circ.append_ctrl((other,), h_bit,
                                 mat.phase_mtrx(1.0, cmath.exp(1j * ang)), 1)
        circ.append_1q(h_bit, mat.H2)
    return circ


def basis_planes(n: int, perm: int, sharding=None, dtype=jnp.float32):
    """|perm> as (2, 2^n) planes, optionally sharded."""
    st = jnp.zeros((2, 1 << n), dtype=dtype).at[0, perm].set(1.0)
    if sharding is not None:
        st = jax.device_put(st, sharding)
    return st
