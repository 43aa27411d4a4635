"""Circuit family ``qft``: the quantum Fourier transform of a random
basis state, in Qrack's gate order (QInterface::QFT,
src/qinterface/qinterface.cpp:114: no final swaps).

One application is what upstream's ``test_qft_permutation_init`` times:
``SetPermutation(x)``, ``QFT(0, n)``, and here one amplitude read, which
is the completion barrier a library user has.  ``(x_i, y_i)`` come from
the seed; the read is checked against the closed form once the window
has closed.
"""

import cmath
import math

import numpy as np

H2 = np.array([[1, 1], [1, -1]], dtype=np.complex128) / math.sqrt(2)


def gates(width, params):
    """The gate list of one application, for the plain reference."""
    out = []
    end = width - 1
    for i in range(width):
        h_bit = end - i
        for j in range(i):
            phase = cmath.exp(1j * math.pi / (1 << (j + 1)))
            out.append(((h_bit,), np.diag([1.0, phase]), h_bit + 1 + j))
        out.append(((), H2, h_bit))
    return out


def _bitrev(y, n):
    return int(format(y, f"0{n}b")[::-1], 2)


def amplitude(width, params, x, y):
    """<y| QFT |x>: the textbook transform, output register bit-reversed."""
    return (cmath.exp(2j * math.pi * x * _bitrev(y, width) / (1 << width))
            / math.sqrt(1 << width))


class Plan:
    """What the seed decides: the basis state and the amplitude read of
    every application, and the amplitudes sampled after the last."""

    def __init__(self, width, params, seed):
        self.width = width
        self.params = params
        self._rng = np.random.default_rng(seed)
        self.sample = [int(y) for y in self._rng.integers(
            0, 1 << width, params["checked_amplitudes"])]
        self.warm = [self._pair() for _ in range(params["warmup_applications"])]
        self._xy = []

    def _pair(self):
        return (int(self._rng.integers(1, 1 << self.width)),
                int(self._rng.integers(0, 1 << self.width)))

    def draw(self, i):
        while len(self._xy) <= i:
            self._xy.append(self._pair())
        return self._xy[i]


def _qft_of(q, plan, x, spans):
    with spans("set_permutation"):
        q.SetPermutation(x)
    with spans("gate_calls"):
        q.QFT(0, plan.width)


def warmup(q, plan, k, spans, checks):
    """A whole application on a pair of its own, checked."""
    x, y = plan.warm[k]
    _qft_of(q, plan, x, spans)
    got = q.GetAmplitude(y)
    with checks.untimed():
        checks.amplitudes(f"warmup_{k}_amplitude", [got],
                          [amplitude(plan.width, plan.params, x, y)])


def start(q, plan, spans):
    """Nothing carries over: every application sets its own |x_i>."""


def enqueue(q, plan, i, spans):
    """The user's calls of application i, up to its read."""
    _qft_of(q, plan, plan.draw(i)[0], spans)


def read_index(plan, i):
    return plan.draw(i)[1]


def expected(plan, i):
    x, y = plan.draw(i)
    return amplitude(plan.width, plan.params, x, y)


def final_check(q, plan, last_i, spans, checks):
    """After the window: more amplitudes of the last application's ket."""
    x, _ = plan.draw(last_i)
    got = [q.GetAmplitude(y) for y in plan.sample]
    want = [amplitude(plan.width, plan.params, x, y) for y in plan.sample]
    checks.amplitudes("post_window_amplitudes", got, want)
