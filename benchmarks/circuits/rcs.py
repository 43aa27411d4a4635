"""Circuit family ``rcs``: nearest-neighbour random circuit sampling
(upstream ``test/benchmarks.cpp`` ``test_random_circuit_sampling_nn``,
with the gate set of Arute et al., Nature 574, 505 (2019)).

Every cycle puts one random root of {sqrt X, sqrt Y, sqrt W} on every
qubit, never the root that qubit had the cycle before (Arute et al.'s
rule), then ISwap couplers on the open chain's brick wall: pairs
``(0,1), (2,3) ...`` in even cycles, ``(1,2), (3,4) ...`` in odd ones.

One application is one sample of the source's loop: ``SetPermutation(0)``,
a circuit **drawn anew from the seed**, one amplitude read.  There is no
closed form.  The plain references are two routes that share nothing but
the draw: ``gates`` lists the circuit as controlled 2x2s for
``reference.py`` (an ISwap as CNOT CNOT CNOT, CZ, S, S) and ``amplitude``
applies every coupler as one 4x4 block in complex128; the harness holds
one against the other over all amplitudes at w12 in every run.  At the
cell's own width the ket the timed path left is held, after the window,
to ``reference_jnp.Simulator`` run once on the last application's circuit.
"""

import math
import time

import numpy as np

import harness
import reference_jnp

X2 = np.array([[0, 1], [1, 0]], dtype=np.complex128)
Z2 = np.diag([1.0, -1.0]).astype(np.complex128)
S2 = np.diag([1.0, 1j])
Y2 = np.array([[0, -1j], [1j, 0]])
# principal square roots of X, Y and W = (X + Y) / sqrt 2: the
# eigenvalue +1 to 1, -1 to i
ROOTS = tuple(0.5 * ((1 + 1j) * np.eye(2) + (1 - 1j) * g)
              for g in (X2, Y2, (X2 + Y2) / math.sqrt(2.0)))
ISWAP = np.array([[1, 0, 0, 0], [0, 0, 1j, 0], [0, 1j, 0, 0], [0, 0, 0, 1]],
                 dtype=np.complex128)


def draw(width, cycles, rng):
    """One circuit: per cycle the root of every qubit (0, 1 or 2, never
    the one it had the cycle before) and the cycle's pairs."""
    roots = np.empty((cycles, width), dtype=np.int64)
    roots[0] = rng.integers(0, 3, width)
    steps = rng.integers(1, 3, (cycles - 1, width))  # to one of the other two
    for c in range(1, cycles):
        roots[c] = (roots[c - 1] + steps[c - 1]) % 3
    return [([int(g) for g in roots[c]],
             [(q, q + 1) for q in range(c & 1, width - 1, 2)])
            for c in range(cycles)]


def _circuit_of(width, params):
    """The circuit that ``gates`` and ``amplitude`` speak of: drawn from
    ``params["circuit_seed"]`` (a Plan sets it to the run's seed)."""
    rng = np.random.default_rng((params.get("circuit_seed", 0), 2))
    return draw(width, params["cycles"], rng)


def blocks(circuit):
    """The circuit as ``(matrix, qubits)``: roots as 2x2, couplers 4x4."""
    out = []
    for roots, pairs in circuit:
        out += [(ROOTS[g], (q,)) for q, g in enumerate(roots)]
        out += [(ISWAP, pair) for pair in pairs]
    return out


def gates(width, params):
    """One application as controlled 2x2s, for ``reference.py``."""
    out = []
    for roots, pairs in _circuit_of(width, params):
        out += [((), ROOTS[g], q) for q, g in enumerate(roots)]
        for a, b in pairs:
            out += [((a,), X2, b), ((b,), X2, a), ((a,), X2, b),
                    ((a,), Z2, b), ((), S2, a), ((), S2, b)]
    return out


def evolve_blocks(width, circuit, x):
    """The second plain route: the ket after ``circuit`` on ``|x>``, every
    coupler one 4x4 block, complex128, numpy."""
    state = np.zeros(1 << width, dtype=np.complex128)
    state[x] = 1.0
    for matrix, qubits in blocks(circuit):
        lo = qubits[0]
        k = len(qubits)  # the pairs are neighbours: one axis of 2^k
        view = state.reshape(1 << (width - lo - k), 1 << k, 1 << lo)
        state = np.einsum("ab,hbl->hal", matrix, view).reshape(-1)
    return state


_KETS = {}


def amplitude(width, params, x, y):
    """<y| circuit |x> by the second route; the ket is kept, the harness
    asks for every y of one x."""
    key = (width, params.get("circuit_seed", 0), params["cycles"], x)
    if key not in _KETS:
        _KETS.clear()
        _KETS[key] = evolve_blocks(width, _circuit_of(width, params), x)
    return _KETS[key][y]


class Plan:
    """What the seed decides: every application's circuit and read, and
    the amplitudes compared after the window."""

    def __init__(self, width, params, seed):
        self.width = width
        self.params = dict(params, circuit_seed=seed)
        self.seed = seed
        rng = np.random.default_rng((seed, 3))
        self.sample = [int(y) for y in rng.integers(
            0, 1 << width, params["checked_amplitudes"])]
        self._drawn = {}

    def application(self, i, warmup=False):
        """``(circuit, read)`` of application ``i`` (of warm-up ``i``):
        a stream of its own from the seed, so any one can be drawn again."""
        key = (int(warmup), i)
        if key not in self._drawn:
            rng = np.random.default_rng((self.seed, *key))
            self._drawn[key] = (draw(self.width, self.params["cycles"], rng),
                                int(rng.integers(0, 1 << self.width)))
        return self._drawn[key]


def _apply(q, plan, circuit, spans):
    with spans("set_permutation"):
        q.SetPermutation(0)
    with spans("gate_calls"):
        for roots, pairs in circuit:
            for target, g in enumerate(roots):
                q.Mtrx(ROOTS[g], target)
            for a, b in pairs:
                q.ISwap(a, b)


def warmup(q, plan, k, spans, checks):
    """A whole application on a circuit of its own: every draw has the
    window's programs.  Its read has to be an amplitude."""
    circuit, y = plan.application(k, warmup=True)
    _apply(q, plan, circuit, spans)
    got = q.GetAmplitude(y)
    with checks.untimed():
        checks.require(f"warmup_{k}_read_is_an_amplitude",
                       math.isfinite(abs(got)) and abs(got) <= 1.0 + 1e-3, got)


def start(q, plan, spans):
    """Nothing carries over: every application starts from |0...0>."""


def enqueue(q, plan, i, spans):
    """The user's calls of sample i: a new circuit, up to its read."""
    _apply(q, plan, plan.application(i)[0], spans)


def read_index(plan, i):
    return plan.application(i)[1]


def expected(plan, i):
    """No closed form."""
    return None


def final_check(q, plan, last_i, spans, checks):
    """The ket the last application left against the device reference
    run on the same circuit: the norm, the whole ket, and seeded
    amplitudes through the engine's own read."""
    import jax
    import jax.numpy as jnp

    checks.norm_drift("last_ket", q, 1)
    planes = q._state
    t0 = time.perf_counter()
    sim = reference_jnp.Simulator(plan.width)
    re, im = sim.run(blocks(plan.application(last_i)[0]))
    harness.say(reference_seconds=time.perf_counter() - t0)

    @jax.jit
    def distance(planes, re, im):
        d = jnp.sum((planes[0].astype(jnp.float32) - re) ** 2
                    + (planes[1].astype(jnp.float32) - im) ** 2)
        return jnp.sqrt(d / jnp.sum(re * re + im * im))

    checks.compare("last_ket.rel_err", float(distance(planes, re, im)),
                   "ket_rel_err")
    at = jnp.asarray(plan.sample)
    want = np.asarray(re[at], np.float64) + 1j * np.asarray(im[at], np.float64)
    got = np.array([q.GetAmplitude(y) for y in plan.sample])
    # over the rms amplitude: a sample of a random circuit's ket holds
    # amplitudes far under it, and an error is not smaller for them
    rms = 2.0 ** (-plan.width / 2)
    checks.compare("last_ket.amplitudes",
                   float(np.max(np.abs(got - want))) / rms,
                   "amplitude_rel_err")
