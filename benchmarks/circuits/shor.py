"""Circuit family ``shor``: the order finding of Shor's algorithm
(upstream ``examples/shors_factoring.cpp:98-160``; in this repo
``qrack_tpu/models/algorithms.shor_period_measure``).  For a number ``N``
of ``n`` bits on ``2n`` qubits, per attempt with a fresh base ``a``
coprime to ``N``:

    SetPermutation(0); H(0..n-1)          uniform over the input register x
    POWModNOut(a, N, 0, n, n)             |x>|0> -> |x>|a^x mod N>
    IQFT(0, n)                            on the input register
    y = MReg(0, n)                        measured: the ket collapses

One application is one attempt, issued through the engine's own calls,
and one amplitude read of the **collapsed** ket: index ``(v, y)`` with
``v = a^x* mod N`` for a seeded ``x*``.  From ``|0...0>`` the ket before
the measurement is ``A(v, y) = 2^-n sum over {x: a^x mod N = v} of
exp(-2 pi i y bitrev(x) / 2^n)`` (Qrack's IQFT has no final swaps), the
register's distribution ``P(y) = sum over v of |A(v, y)|^2``, and the
read ``A(v, y) / sqrt(P(y))``: wrong if the table, the IQFT, the
reduction's ``P(y)`` or the collapse's scale is.

The plain reference shares nothing with the engine's ALU, its table
builder or its measurement: ``f`` comes from Python's ``pow``; ``gates``
lists the circuit as controlled 2x2s for ``reference.py``, the modular
power as a permutation that is unitary on the whole space and the
engine's call wherever the out register starts at 0, which is the
call's domain (``gates``' own note says which, and why not the plain
``out ^= f(x)``); ``amplitude`` is the circuit's matrix element as a
direct sum.  The harness holds one against the other over all
amplitudes at w12 in every run.

The mean of ``2^n P(y_i)`` over the window's samples is printed beside
a uniform sampler's 1 and the expectation ``2^n sum P^2`` of the
post-window base alone, and not judged: every application has a base of
its own, so its own distribution (``2^n sum P^2`` is near 2 for a long
period and ``2^n / r`` for a short one; the whole distribution of every
base is a second of host arithmetic each), and some 300 samples a window
leave no limit the room PERF.md section 2 asks between a sound sampler
and a uniform one for every ``N``.  What is judged of the sampler: every
``y_i`` has ``P(y_i)`` above a floor, the lowest bit of the ``y_i`` is a
fair coin (exactly, for every base: ``final_check``), each read of the
collapsed ket carries ``1 / sqrt(P(y_i))``, and after the window the
register's whole distribution as the device reduces it, every value.
"""

import functools
import math

import numpy as np

import harness

H2 = np.array([[1, 1], [1, -1]], dtype=np.complex128) / math.sqrt(2)
X2 = np.array([[0, 1], [1, 0]], dtype=np.complex128)
# a measured value has to be possible: the mean probability is 2^-n, and
# a value this far under it is one the ket's own distribution gives once
# in a million draws at most (the values under the floor sum to less
# than 2^n * 1e-6 * 2^-n)
P_FLOOR_OF_MEAN = 1e-6
W12_MODULI = (33, 35, 39, 51, 55, 57)


@functools.lru_cache(maxsize=4)
def _bitrev_all(n):
    x = np.arange(1 << n)
    out = np.zeros_like(x)
    for k in range(n):
        out |= ((x >> k) & 1) << (n - 1 - k)
    return out


@functools.lru_cache(maxsize=8)
def table(a, N, n):
    """``a^x mod N`` for every ``x`` of ``n`` bits, by Python's ``pow``."""
    return np.array([pow(a, x, N) for x in range(1 << n)], dtype=np.int64)


@functools.lru_cache(maxsize=8)
def semiprimes(bits):
    """Every odd ``p * q``, ``p != q`` prime, of exactly ``bits`` bits."""
    top = 1 << bits
    sieve = np.ones(top // 3 + 1, dtype=bool)
    sieve[:2] = False
    for i in range(2, int(len(sieve) ** 0.5) + 1):
        if sieve[i]:
            sieve[i * i::i] = False
    primes = [int(p) for p in np.flatnonzero(sieve) if p > 2]
    return sorted(p * q for i, p in enumerate(primes) for q in primes[i + 1:]
                  if top // 2 <= p * q < top)


def input_bits(width):
    """The input register of the reference's circuit: half the width, as
    the source's, at the widths the harness holds every amplitude at (12
    and below; 13 in the tests).  The gate list grows as ``n 2^n``
    (the permutation is a block of gates for every ``x``), so above them,
    where the tests hold the engine's window kernel under the interpreter
    to the closed form at a handful of amplitudes, it keeps three bits
    and the qubits above the two registers look on."""
    return width // 2 if width < 14 else 3


def _moduli(n):
    """The numbers a plan of ``n`` input qubits draws from (below four
    bits there is no such semiprime: the largest odd number)."""
    return W12_MODULI if n == 6 else semiprimes(n) or [(1 << n) - 1]


def problem_of(width, params):
    """``(N, a)`` at ``width``: the plan's own where its ``N`` has
    ``width / 2`` bits, else (the harness's closed-form check at w12 with
    a w28 cell's parameters) a modulus of that many bits and the first
    base at or above the plan's, reduced, that is coprime to it."""
    n = input_bits(width)
    N, a = params.get("N", 55), params.get("a", 7)
    if N.bit_length() == n and math.gcd(a, N) == 1:
        return N, a
    pool = _moduli(n)
    N = pool[N % len(pool)]
    a = 2 + a % (N - 2)
    while math.gcd(a, N) != 1:
        a = 2 + (a - 1) % (N - 2)
    return N, a


def _swap_zero_with(c, n, controls):
    """The out register's ``|0>`` and ``|c>`` exchanged where
    ``controls`` are all 1: ``c`` folded onto its lowest set bit, that
    bit flipped where every other out qubit is 0, and unfolded."""
    bits = [b for b in range(n) if (c >> b) & 1]
    if not bits:
        return []
    fold = [(controls + (n + bits[0],), X2, n + b) for b in bits[1:]]
    others = tuple(n + b for b in range(n) if b != bits[0])
    zeros = [((), X2, q) for q in others]
    return (fold + zeros + [(controls + others, X2, n + bits[0])] + zeros
            + fold[::-1])


def gates(width, params):
    """The circuit as controlled 2x2s, for ``reference.py``: unitary on
    the whole space.  The modular power is the permutation ``(v0, x) ->
    (s_x(v0) xor x, x)`` with ``s_x`` the exchange of ``0`` and ``x xor
    f(x)``: on the call's domain, the out register at 0, it is the
    engine's ``|x>|0> -> |x>|f(x)>``; off it, where the call drops what
    it finds and any permutation would do, ``x -> v0 xor x`` is a
    bijection for (nearly) every ``v0``, so that a basis state's column
    has amplitude at every ``v`` and a relative error has a denominator
    (``out ^= f(x)`` alone leaves <y|circuit|x> exactly 0 wherever ``v
    xor v0`` is no value of ``f``: at ``y = x`` for every ``x``)."""
    n = input_bits(width)
    N, a = problem_of(width, params)
    out = [((), H2, q) for q in range(n)]
    controls = tuple(range(n))
    for x in range(1 << n):
        zeros = [((), X2, q) for q in range(n) if not (x >> q) & 1]
        out += zeros + _swap_zero_with(x ^ pow(a, x, N), n, controls) + zeros
    out += [((q,), X2, n + q) for q in range(n)]
    for i in range(n):
        for j in range(i):
            phase = np.exp(-1j * math.pi / (1 << (j + 1)))
            out.append(((i - (j + 1),), np.diag([1.0, phase]), i))
        out.append(((), H2, i))
    return out


def amplitude(width, params, x, y):
    """<y| circuit |x>: a direct sum in complex128 over the ``x'`` that
    the permutation sends from out register ``v0`` to ``v``, signs from
    the H layer on ``|x0>``, phases from the IQFT (Qrack's: no final
    swaps, so ``bitrev(x')`` meets ``y``)."""
    n = input_bits(width)
    N, a = problem_of(width, params)
    mask = (1 << n) - 1
    if x >> 2 * n != y >> 2 * n:  # the qubits above the registers look on
        return 0j
    v0, v = (x >> n) & mask, (y >> n) & mask
    xs = np.arange(1 << n)
    c = xs ^ table(a, N, n)
    swapped = np.where(v0 == 0, c, np.where(v0 == c, 0, v0))
    orbit = np.flatnonzero(swapped ^ xs == v)
    overlap = orbit & (x & mask)
    parity = np.zeros_like(overlap)
    for k in range(n):
        parity ^= (overlap >> k) & 1
    phases = np.exp(-2j * math.pi * (y & mask) * _bitrev_all(n)[orbit]
                    / (1 << n))
    return complex(np.sum((1.0 - 2.0 * parity) * phases)) / (1 << n)


def column(a, N, n, y):
    """``A(v, y)`` for every ``v`` (complex128, length ``2^n``) of the
    ket before the measurement, from ``|0...0>``."""
    f = table(a, N, n)
    phases = np.exp(-2j * math.pi * y * _bitrev_all(n) / (1 << n))
    size = 1 << n
    return (np.bincount(f, weights=phases.real, minlength=size)
            + 1j * np.bincount(f, weights=phases.imag, minlength=size)) / size


def register_distribution(a, N, n, chunk=256):
    """``P(y)`` for every ``y``: each value's column is the discrete
    Fourier transform of its orbit's indicator over ``bitrev(x)``."""
    f = table(a, N, n)
    size = 1 << n
    at = _bitrev_all(n)
    values = np.unique(f)
    total = np.zeros(size)
    for lo in range(0, len(values), chunk):
        vs = values[lo:lo + chunk]
        row_of = np.full(size, -1)
        row_of[vs] = np.arange(len(vs))
        ind = np.zeros((len(vs), size))
        keep = row_of[f] >= 0
        ind[row_of[f][keep], at[keep]] = 1.0
        total += np.sum(np.abs(np.fft.fft(ind, axis=1)) ** 2, axis=0)
    return total / size ** 2


class Plan:
    """What the seed decides: once a run the number ``N``, an odd
    semiprime of exactly ``width / 2`` bits; for every application a base
    coprime to it and the ``x*`` whose value's amplitude is read (each
    from the seed and the application's number, whatever ran before).
    It keeps each application's measured ``y``."""

    WARM, POST = -1000, -1  # applications outside the window

    def __init__(self, width, params, seed):
        self.width, self.n, self.seed = width, width // 2, seed
        pool = _moduli(self.n)
        self.N = int(pool[int(self._rng(0).integers(0, len(pool)))])
        self._draws = {}
        self.y = {}
        self._closed = {}
        self.params = dict(params, N=self.N, a=self.draw(self.WARM)[0])

    def _rng(self, *tag):
        return np.random.default_rng((self.seed, 7) + tuple(t + 2000 for t in tag))

    def draw(self, i):
        """``(a_i, x*_i)`` of application ``i``."""
        if i not in self._draws:
            rng = self._rng(1, i)
            a = int(rng.integers(2, self.N))
            while math.gcd(a, self.N) != 1:
                a = int(rng.integers(2, self.N))
            self._draws[i] = a, int(rng.integers(0, 1 << self.n))
        return self._draws[i]

    def indices(self, i, count):
        """Of application ``i``: ``count`` seeded ``(v, y)`` of the ket
        before the measurement, every other one on a row that has
        amplitude, and ``count`` values ``v`` of such rows for the
        collapsed ket."""
        rng = self._rng(2, i)
        f = table(self.draw(i)[0], self.N, self.n)
        xs, ys, vs, after = rng.integers(0, 1 << self.n, (4, count))
        vs = np.where(np.arange(count) % 2 == 0, f[xs], vs)
        return ([(int(v), int(y)) for v, y in zip(vs, ys)],
                [int(v) for v in f[after]])

    def outcome(self, i):
        """``(index read, its closed form, P(y_i))`` of application
        ``i``'s read of the collapsed ket."""
        if i not in self._closed:
            a, x = self.draw(i)
            v, y = pow(a, x, self.N), self.y[i]
            want, p = _collapsed(self, a, y, [v])
            self._closed[i] = (v << self.n) | y, complex(want[0]), p
        return self._closed[i]


def _attempt(q, plan, a, spans, measure=True):
    """The source's attempt, ``SetPermutation(0)`` first, by the engine's
    own calls; the measured value, or None where it stops ahead of the
    measurement."""
    from qrack_tpu.models import algorithms

    with spans("gate_calls"):
        if measure:
            return algorithms.shor_period_measure(q, a, plan.N, plan.n)
        algorithms.shor_period_state(q, a, plan.N, plan.n)
    return None


def _read(q, plan, pairs):
    return np.array([q.GetAmplitude((v << plan.n) | y) for v, y in pairs])


def _compare(checks, name, got, want, scale):
    """Amplitudes against their closed form: by their relative error,
    but for one whose closed form is under ``scale`` (an interference
    that cancels), which is held by its share of ``scale``."""
    want = np.asarray(want)
    checks.compare(name, float(np.max(
        np.abs(got - want) / np.maximum(np.abs(want), scale))),
        "amplitude_rel_err")


def _collapsed(plan, a, y, values):
    """``(closed form of the collapsed ket at (v, y) for v in values,
    P(y))``."""
    col = column(a, plan.N, plan.n, y)
    p = float(np.sum(np.abs(col) ** 2))
    return col[values] / math.sqrt(p), p


def _checked_attempt(q, plan, i, spans, checks, name):
    """Application ``i`` with its ket held to the closed form at 64
    seeded indices before the measurement and at 64 of the collapsed
    ket after it; returns the measured value."""
    n, count = plan.n, plan.params["checked_amplitudes"]
    a = plan.draw(i)[0]
    rms = 2.0 ** (-n)  # sqrt(mean |A|^2) over the 2^2n amplitudes
    _attempt(q, plan, a, spans, measure=False)
    pairs, values = plan.indices(i, count)
    got = _read(q, plan, pairs)
    with checks.untimed():
        cols = {y: column(a, plan.N, n, y) for _, y in pairs}
        _compare(checks, name + ".before_measurement", got,
                 [cols[y][v] for v, y in pairs], rms)
    with spans("gate_calls"):
        y = q.MReg(0, n)
    got = _read(q, plan, [(v, y) for v in values])
    with checks.untimed():
        want, p = _collapsed(plan, a, y, values)
        checks.require(name + ".measured_value_is_possible",
                       p >= P_FLOOR_OF_MEAN * 2.0 ** (-n), f"y={y} P={p}")
        _compare(checks, name + ".collapsed", got, want, rms / math.sqrt(p))
    return y


def warmup(q, plan, k, spans, checks):
    """A whole application, checked: every program of the window has
    run (the read between the IQFT and the measurement flushes the same
    windows the measurement's reduction does).  ``shor_period_state`` is
    a function only this change has: a parent fails here, in set-up."""
    _checked_attempt(q, plan, plan.WARM + k, spans, checks, f"warmup_{k}")


def start(q, plan, spans):
    """Nothing carries over: every application sets its own |0...0>."""


def enqueue(q, plan, i, spans):
    plan._closed.pop(i, None)
    plan.y[i] = _attempt(q, plan, plan.draw(i)[0], spans)


def read_index(plan, i):
    a, x = plan.draw(i)
    return (pow(a, x, plan.N) << plan.n) | plan.y[i]


def expected(plan, i):
    # None: the application raised ahead of its measurement
    return plan.outcome(i)[1] if i in plan.y else None


def measured_ket(planes, n, f, y=None):
    """Of split planes ``(2, 2^2n)``, reduced on the device by a program
    of the benchmark's own: the norm, the mass on rows that are no value
    of ``f`` (before a measurement) or off the column ``y`` (after one),
    and the register's distribution."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reduce(planes, is_value, y):
        size = 1 << n
        # the squares first, flat: the planes lie interleaved on the chip
        # and a two-dimensional view of them is a copy of the ket
        p = (planes[0].astype(jnp.float32) ** 2
             + planes[1].astype(jnp.float32) ** 2).reshape(size, size)
        x = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
        outside = jnp.where(y < 0, ~is_value[:, None], x != y)
        return jnp.sum(p), jnp.sum(jnp.where(outside, p, 0.0)), jnp.sum(p, axis=0)

    is_value = np.zeros(1 << n, dtype=bool)
    is_value[f] = True
    norm, outside, dist = reduce(planes, is_value,
                                 np.int32(-1 if y is None else y))
    return float(norm), float(outside), np.asarray(dist, dtype=np.float64)


def final_check(q, plan, last_i, spans, checks):
    """Over the window's samples: every measured value is possible, and
    their mean probability is printed beside its expectation.  Then one
    more application through the window's own programs: before the
    measurement the norm, the mass outside the orbit's rows and the whole
    register distribution against ``P(y)`` (each value's error over the
    larger of its ``P(y)`` and the mean ``2^-n``: a short period's peaks
    stand thousands of times over the mean); after it the norm, the mass
    off the measured column and 64 amplitudes."""
    n, size = plan.n, 1 << plan.n
    floor = P_FLOOR_OF_MEAN / size
    seen = [plan.outcome(i)[2] for i in sorted(plan.y) if i >= 0]
    impossible = sum(p < floor for p in seen)
    checks.require("window_measured_values_are_possible", impossible == 0,
                   f"{impossible} of {len(seen)} under {floor}")
    # the measured value's lowest bit is a fair coin, exactly, whatever N
    # and the base: the IQFT's first gate is the H on qubit 0, where
    # |2k>|f(2k)> and |2k+1>|f(2k+1)> do not interfere.  Five standard
    # deviations: a sampler that ignores the ket (always one value) fails
    # it, a sound one once in a million runs
    evens = sum(1 for i, y in plan.y.items() if i >= 0 and y % 2 == 0)
    checks.require("window_low_bit_is_a_fair_coin",
                   abs(evens - len(seen) / 2) <= 2.5 * math.sqrt(len(seen)) + 1,
                   f"{evens} even of {len(seen)}")

    a = plan.draw(plan.POST)[0]
    f = table(a, plan.N, n)
    want = register_distribution(a, plan.N, n)
    harness.say(N=plan.N, base=a, samples=len(seen),
                mean_relative_probability=float(np.mean(seen)) * size
                if seen else None,
                of_the_kets_own_sampler_at_the_last_base=float(np.sum(want ** 2)) * size,
                of_a_uniform_sampler=1.0,
                closed_form_distribution_sums_to=float(np.sum(want)))
    _attempt(q, plan, a, spans, measure=False)
    norm, outside, got = measured_ket(q._state, n, f)
    checks.compare("post_window.before_measurement.norm_drift_per_step",
                   abs(norm - 1.0), "norm_drift_per_step")
    checks.require("post_window.before_measurement.no_mass_outside_the_orbit",
                   outside == 0.0, outside)
    checks.compare("post_window.register_distribution", float(np.max(
        np.abs(got - want) / np.maximum(want, 1.0 / size))),
        "register_prob_rel_err")
    y = _checked_attempt(q, plan, plan.POST, spans, checks, "post_window")
    norm, off, _ = measured_ket(q._state, n, f, y)
    checks.compare("post_window.collapsed.norm_drift_per_step",
                   abs(norm - 1.0), "norm_drift_per_step")
    checks.require("post_window.collapsed.no_mass_off_the_column", off == 0.0,
                   off)
