"""The plain reference on the device: a gate-by-gate ``jax.numpy``
state-vector simulator in float32, for kets the host cannot hold.

No fusion, no kernels, nothing imported from the program under test.  A
gate is ``(matrix, qubits)``: a 2x2 on ``(q,)`` or a 4x4 on ``(lo, hi)``
with ``hi == lo + 1``, row and column ``(bit hi << 1) | bit lo``.  Qubit 0
is the least significant bit of a basis state's index, as in Qrack.

The ket is two float32 arrays (real and imaginary part).  A gate is a
matrix on one axis of seven qubits of the view ``(high, 2^7, low)``
(``matmul`` at precision ``highest``, the matrix a run-time operand, so
there is one compiled program for each such axis, whatever the gate).
The axes start at qubits 0, 7, 14 ...; a pair that lies across two of
them takes an axis of its own that starts three qubits under its low
one.  No view has a minor axis under 128 (``low`` is 1, where the axis
itself is the minor one, or at least 2^7), so nothing pads on a TPU, and
``(2,) * n`` never appears.  Only the pair (6, 7) has no such axis: its
4x4 is applied as the sum of its four blocks,
``U = sum_ij |i><j|_7 (x) B_ij``, each factor a gate on one axis.

``dtype`` holds the ket in a narrower type instead (the control of
"How correct is decided"): it is rounded to it after every gate.
"""

import numpy as np

GROUP = 7
RUN_AHEAD = 4  # gates enqueued before the host waits: each holds a ket


def _embed(matrix, qubits, base, size):
    """``matrix`` on ``qubits`` as a 2^size matrix on the group that
    starts at qubit ``base``; complex128, on the host."""
    k = len(qubits)
    m = np.asarray(matrix, dtype=np.complex128).reshape(1 << k, 1 << k)
    dim = 1 << size
    out = np.zeros((dim, dim), dtype=np.complex128)
    bits = [q - base for q in qubits]  # qubits[0] is the matrix's low bit
    mask = sum(1 << b for b in bits)
    for col in range(dim):
        c = sum(((col >> b) & 1) << j for j, b in enumerate(bits))
        for r in range(1 << k):
            row = (col & ~mask) | sum(((r >> j) & 1) << b
                                      for j, b in enumerate(bits))
            out[row, col] = m[r, c]
    return out


class Simulator:
    """A ket of ``width`` qubits on the default device."""

    def __init__(self, width, x=0, dtype=None):
        import jax
        import jax.numpy as jnp

        self.jax, self.jnp = jax, jnp
        self.width = width
        self.dtype = jnp.dtype(dtype or jnp.float32)
        self.re = jnp.zeros(1 << width, self.dtype).at[x].set(1.0)
        self.im = jnp.zeros(1 << width, self.dtype)
        self._programs = {}
        self._embedded = {}
        self._pending = 0

    def _group(self, qubits):
        """``(first qubit, qubits)`` of an axis that holds ``qubits``, or
        None for a pair across qubit 7, where no axis may start."""
        lo, hi = qubits[0], qubits[-1]
        base = (lo // GROUP) * GROUP
        if hi >= base + GROUP:  # across two: an axis of the pair's own
            base = max(lo - 3, 0)
            if base < GROUP:
                return None
        return base, min(GROUP, self.width - base)

    def _program(self, base, size):
        """One compiled program a group: the ket times a run-time matrix
        on that group's axis, real and imaginary parts apart."""
        key = (base, size)
        if key not in self._programs:
            jax, jnp = self.jax, self.jnp
            shape = (1 << (self.width - base - size), 1 << size)
            if base:
                shape += (1 << base,)
            dtype = self.dtype

            def apply(re, im, m_re, m_im):
                with jax.default_matmul_precision("highest"):
                    a = re.reshape(shape).astype(jnp.float32)
                    b = im.reshape(shape).astype(jnp.float32)
                    if base == 0:  # the group's axis is the minor one
                        out_re = a @ m_re.T - b @ m_im.T
                        out_im = a @ m_im.T + b @ m_re.T
                    else:
                        out_re = m_re @ a - m_im @ b
                        out_im = m_im @ a + m_re @ b
                return (out_re.reshape(-1).astype(dtype),
                        out_im.reshape(-1).astype(dtype))

            self._programs[key] = jax.jit(apply, donate_argnums=(0, 1))
        return self._programs[key]

    def _in_group(self, re, im, matrix, qubits):
        base, size = self._group(qubits)
        key = (np.asarray(matrix, dtype=np.complex128).tobytes(), qubits)
        if key not in self._embedded:
            self._embedded[key] = _embed(matrix, qubits, base, size)
        m = self._embedded[key]
        return self._program(base, size)(
            re, im, self.jnp.asarray(m.real, self.jnp.float32),
            self.jnp.asarray(m.imag, self.jnp.float32))

    def apply(self, matrix, qubits):
        qubits = tuple(qubits)
        if self._group(qubits) is not None:
            self.re, self.im = self._in_group(self.re, self.im, matrix, qubits)
        else:
            lo, hi = qubits
            m = np.asarray(matrix, dtype=np.complex128).reshape(4, 4)
            total = None
            for i in (0, 1):
                for j in (0, 1):
                    e = np.zeros((2, 2))
                    e[i, j] = 1.0
                    # the programs donate what they are handed: copies
                    part = self._in_group(self.re + 0, self.im + 0,
                                          m[2 * i:2 * i + 2, 2 * j:2 * j + 2],
                                          (lo,))
                    part = self._in_group(*part, e, (hi,))
                    total = part if total is None else (
                        total[0] + part[0], total[1] + part[1])
                    self.jax.block_until_ready(total)
            self.re, self.im = total
        self._pending += 1
        if self._pending >= RUN_AHEAD:  # every gate in flight holds a ket
            self.jax.block_until_ready((self.re, self.im))
            self._pending = 0

    def run(self, gates):
        for matrix, qubits in gates:
            self.apply(matrix, qubits)
        self.jax.block_until_ready((self.re, self.im))
        return self.re, self.im
