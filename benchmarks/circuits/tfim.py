"""Circuit family ``tfim``: brute-force time evolution of the open 1-D
transverse-field Ising chain by first-order Trotter steps
(arXiv:2111.10466; gate order of the repo's
``models/algorithms.trotter_qcircuit``: on every bond CNOT, RZ(2 J dt),
CNOT, then RX(2 h dt) on every qubit).

One application is one Trotter step and one amplitude read; the ket
evolves on from step to step.  From a basis state ``|x>`` one step is a
product state with a closed form: the bond layer is a phase on ``|x>``,
then every qubit is rotated by RX.  Both warm-up applications and one
more application after the window are held to it, through the same
programs the window runs.  The evolved ket's amplitudes have no closed form, but the chain is a
free-fermion model and every gate of a step is a rotation of two
Majorana operators, so its bond correlations ``<Z_j Z_j+1>`` after any
number of steps follow exactly from a 2n x 2n orthogonal matrix
(``bond_zz``).  After the window the ket the engine holds is reduced to
those width - 1 numbers and held to them, and to the norm.
"""

import cmath
import math

import numpy as np

import harness

X2 = np.array([[0, 1], [1, 0]], dtype=np.complex128)


def _angles(params):
    return 2.0 * params["J"] * params["dt"], 2.0 * params["h"] * params["dt"]


def _rz(theta):
    return np.diag([cmath.exp(-0.5j * theta), cmath.exp(0.5j * theta)])


def _rx(theta):
    c, s = math.cos(theta / 2), math.sin(theta / 2)
    return np.array([[c, -1j * s], [-1j * s, c]], dtype=np.complex128)


def gates(width, params):
    """The gate list of one Trotter step."""
    tz, tx = _angles(params)
    out = []
    for i in range(width - 1):
        out.append(((i,), X2, i + 1))
        out.append(((), _rz(tz), i + 1))
        out.append(((i,), X2, i + 1))
    for q in range(width):
        out.append(((), _rx(tx), q))
    return out


def amplitude(width, params, x, y):
    """<y| step |x> for a basis state |x>."""
    tz, tx = _angles(params)
    spins = [1 - 2 * ((x >> q) & 1) for q in range(width)]
    bonds = sum(spins[q] * spins[q + 1] for q in range(width - 1))
    d = bin(x ^ y).count("1")
    c, s = math.cos(tx / 2), math.sin(tx / 2)
    return cmath.exp(-0.5j * tz * bonds) * c ** (width - d) * (-1j * s) ** d


def bond_zz(width, params, x, steps):
    """<Z_j Z_j+1> for every bond after ``steps`` Trotter steps from |x>.

    Jordan-Wigner with X as the local parity: g[2j] = (prod_{k<j} X_k) Z_j,
    g[2j+1] = (prod_{k<j} X_k) Y_j, so X_j = i g[2j] g[2j+1] and
    Z_j Z_j+1 = i g[2j+1] g[2j+2].  RX(a) on j is exp(a/2 g[2j] g[2j+1])
    and the bond gate exp(-i t/2 Z_j Z_j+1) is exp(t/2 g[2j+1] g[2j+2]):
    each turns its two Majoranas into each other by its angle and leaves
    the rest.  In |x> the only pairs with an expectation are the bonds'.
    """
    tz, tx = _angles(params)
    n2 = 2 * width

    def turn(a, b, angle):
        r = np.eye(n2)
        r[a, a] = r[b, b] = math.cos(angle)
        r[a, b] = math.sin(angle)
        r[b, a] = -math.sin(angle)
        return r

    step = np.eye(n2)
    for j in range(width - 1):
        step = turn(2 * j + 1, 2 * j + 2, tz) @ step
    for j in range(width):
        step = turn(2 * j, 2 * j + 1, tx) @ step
    m = np.linalg.matrix_power(step, steps)
    spins = [1 - 2 * ((x >> q) & 1) for q in range(width)]
    g = np.zeros((n2, n2))  # <x| i g_a g_b |x> for a != b
    for j in range(width - 1):
        g[2 * j + 1, 2 * j + 2] = spins[j] * spins[j + 1]
        g[2 * j + 2, 2 * j + 1] = -spins[j] * spins[j + 1]
    corr = m @ g @ m.T
    return [corr[2 * j + 1, 2 * j + 2] for j in range(width - 1)]


def measured_bond_zz(planes, positions):
    """The same numbers from split planes (2, 2^width), by a reduction of
    the benchmark's own.  ``positions[j]`` is the bit of the planes'
    index that holds logical qubit ``j`` (``harness.bit_positions``)."""
    import jax
    import jax.numpy as jnp

    width = len(positions)

    @jax.jit
    def reduce(planes):
        p = jnp.sum(planes * planes, axis=0)
        idx = jax.lax.iota(jnp.int32, 1 << width)
        return jnp.stack([
            jnp.sum(jnp.where(((idx >> a) ^ (idx >> b)) & 1 == 1, -p, p))
            for a, b in zip(positions, positions[1:])])

    return [float(v) for v in reduce(planes)]


class Plan:
    """What the seed decides: the basis states of the checked steps with
    the amplitudes read near each, the window's own start, and the
    amplitude read after every step."""

    def __init__(self, width, params, seed):
        self.width = width
        self.params = params
        self.gates = gates(width, params)
        rng = np.random.default_rng(seed)
        n_checked = params["warmup_applications"] + 1
        starts = [int(v) for v in rng.integers(1, 1 << width, n_checked + 1)]
        self.checked = [(x, self._near(rng, x, params["checked_amplitudes"]))
                        for x in starts[:n_checked]]
        self.window_start = starts[-1]
        self._rng = rng
        self._reads = []

    def _near(self, rng, x, count):
        """``count`` basis states within Hamming distance 0-2 of x, the
        flipped positions drawn over the whole register."""
        out = [x]
        while len(out) < count:
            flips = rng.choice(self.width, size=int(rng.integers(1, 3)),
                               replace=False)
            out.append(x ^ sum(1 << int(f) for f in flips))
        return out

    def read(self, i):
        while len(self._reads) <= i:
            self._reads.append(int(self._rng.integers(0, 1 << self.width)))
        return self._reads[i]


def _step(q, plan):
    for controls, matrix, target in plan.gates:
        if controls:
            q.MCMtrx(controls, matrix, target)
        else:
            q.Mtrx(matrix, target)


def _checked_step(q, plan, k, spans, checks, name):
    """One step from a basis state, held to the closed form."""
    x, ys = plan.checked[k]
    with spans("set_permutation"):
        q.SetPermutation(x)
    with spans("gate_calls"):
        _step(q, plan)
    got0 = q.GetAmplitude(ys[0])
    with checks.untimed():
        got = [got0] + [q.GetAmplitude(y) for y in ys[1:]]
        want = [amplitude(plan.width, plan.params, x, y) for y in ys]
        checks.amplitudes(name, got, want)


def warmup(q, plan, k, spans, checks):
    _checked_step(q, plan, k, spans, checks, f"warmup_step_{k}_amplitudes")


def start(q, plan, spans):
    """The window's evolution starts from a basis state of its own."""
    with spans("set_permutation"):
        q.SetPermutation(plan.window_start)


def enqueue(q, plan, i, spans):
    with spans("gate_calls"):
        _step(q, plan)


def read_index(plan, i):
    return plan.read(i)


def expected(plan, i):
    """No closed form past the first step."""
    return None


def final_check(q, plan, last_i, spans, checks):
    """The evolved ket's norm and bond correlations, then one step from
    a fresh basis state through the programs the window has just used."""
    steps = last_i + 1
    checks.norm_drift("evolved_ket", q, steps)
    planes = q._state  # before the table: the read flushes what is queued
    positions = harness.bit_positions(q)
    harness.say(evolved_ket_bit_positions=positions,
                identity=positions == sorted(positions))
    got = measured_bond_zz(planes, positions)
    want = bond_zz(plan.width, plan.params, plan.window_start, steps)
    checks.compare("evolved_ket.bond_zz",
                   max(abs(g - w) for g, w in zip(got, want)), "bond_zz_abs_err")
    _checked_step(q, plan, len(plan.checked) - 1, spans, checks,
                  "post_window_step_amplitudes")
