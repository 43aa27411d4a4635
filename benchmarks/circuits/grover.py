"""Circuit family ``grover``: Grover's search with an arithmetic oracle
(upstream ``test/benchmarks.cpp:542`` ``test_grover``, the loop of
``examples/grovers.cpp``).  ``SetPermutation(0)`` and ``H`` on every
qubit, then per iteration

    DEC(t, 0, n); ZeroPhaseFlip(0, n); INC(t, 0, n)        the oracle: true for |t>
    H(0..n-1);    ZeroPhaseFlip(0, n); H(0..n-1); PhaseFlip()   the diffusion

One application is one iteration, issued through the engine's own calls,
and one amplitude read; **the ket evolves on** from the uniform state.
After ``k`` iterations it is ``sin((2k+1) theta) |t> + cos((2k+1) theta)
|rest>``, ``theta = asin(2^(-n/2))`` and ``|rest>`` the uniform state of
the other ``2^n - 1``: every application's read has a closed form, and
so has the whole ket the window leaves (one value for every amplitude
but the target's).

The plain reference shares nothing with the engine's ALU: ``gates``
lists one iteration as controlled 2x2s for ``reference.py`` (``INC`` and
``DEC`` as their MCX carry cascade, ``ZeroPhaseFlip`` as an X-conjugated
multi-controlled Z, since ``reference.apply_gate`` takes controls at 1)
and ``amplitude`` is the iteration's matrix element in closed form; the
harness holds one against the other over all amplitudes at w12 in every
run.
"""

import math

import numpy as np

import harness

H2 = np.array([[1, 1], [1, -1]], dtype=np.complex128) / math.sqrt(2)
X2 = np.array([[0, 1], [1, 0]], dtype=np.complex128)
Z2 = np.diag([1.0, -1.0]).astype(np.complex128)
MINUS_I2 = -np.eye(2, dtype=np.complex128)
# a closed form this near a zero of its sine or cosine is not compared
# by its relative error (never the case in a w28 window: 130 iterations
# turn the ket by 0.016 rad; a w12 rehearsal passes its optimum at 50)
NEAR_ZERO = 0.1


def target_of(width, params):
    """The searched state: the plan's draw, cut to ``width`` bits (the
    harness holds ``gates`` to ``amplitude`` at w12 with the cell's own
    parameters); the source's 3 where no plan drew one."""
    return params.get("target", 3) & ((1 << width) - 1)


def _add(width, constant):
    """``INC(constant, 0, width)`` as its MCX carry cascade: for every
    set bit of the constant, from the top of the register down to that
    bit, flip a qubit where all the qubits between the bit and it are 1,
    then flip the bit itself."""
    out = []
    for k in range(width):
        if (constant >> k) & 1:
            out += [(tuple(range(k, i)), X2, i)
                    for i in range(width - 1, k, -1)]
            out.append(((), X2, k))
    return out


def _zero_phase_flip(width):
    """-1 on ``|0...0>``: Z on the top qubit where all the others are 1,
    between two layers of X."""
    flips = [((), X2, q) for q in range(width)]
    return flips + [(tuple(range(width - 1)), Z2, width - 1)] + flips


def gates(width, params):
    """One iteration as controlled 2x2s, for ``reference.py``."""
    t = target_of(width, params)
    layer = [((), H2, q) for q in range(width)]
    return (_add(width, ((1 << width) - t) & ((1 << width) - 1))
            + _zero_phase_flip(width) + _add(width, t)
            + layer + _zero_phase_flip(width) + layer
            + [((), MINUS_I2, 0)])


def amplitude(width, params, x, y):
    """<y| iteration |x>: the oracle is a sign on ``|t>``, the diffusion
    ``2 |s><s| - 1`` with ``|s>`` the uniform state."""
    sign = -1.0 if x == target_of(width, params) else 1.0
    return sign * (2.0 / (1 << width) - (1.0 if x == y else 0.0))


def closed_form(width, k):
    """``(target's amplitude, every other amplitude)`` after ``k``
    iterations from the uniform state."""
    angle = (2 * k + 1) * math.asin(2.0 ** (-width / 2))
    return math.sin(angle), math.cos(angle) / math.sqrt((1 << width) - 1)


def reads_the_target(width, k):
    """Whether the read after ``k`` iterations is the target's amplitude:
    not where it is within ``NEAR_ZERO`` rad of a zero past the start,
    where a seeded other amplitude, then at its largest, is read."""
    angle = (2 * k + 1) * math.asin(2.0 ** (-width / 2))
    return angle < math.pi / 2 or abs(math.sin(angle)) >= NEAR_ZERO


class Plan:
    """What the seed decides: the target, over the whole register, and
    the other amplitudes compared.  It also counts the iterations applied
    since ``start``, so that a read knows its closed form whatever ran
    before the window."""

    def __init__(self, width, params, seed):
        self.width = width
        rng = np.random.default_rng((seed, 5))
        self.target = int(rng.integers(0, 1 << width))
        self.params = dict(params, target=self.target)
        self.others = []
        while len(self.others) < params["checked_amplitudes"] - 1:
            y = int(rng.integers(0, 1 << width))
            if y != self.target:
                self.others.append(y)
        self.iterations = params.get("iterations", 1)  # an application
        self.applied = 0   # iterations since the uniform state was set
        self.after = {}    # application -> iterations applied when it read

    def read(self, i):
        """``(index read, its closed form)`` of application ``i``."""
        k = self.after[i]
        at_target, elsewhere = closed_form(self.width, k)
        if reads_the_target(self.width, k):
            return self.target, at_target
        return self.others[i % len(self.others)], elsewhere


def _uniform(q, plan, spans):
    """The uniform state, there when this returns: the read leaves
    nothing of the layer in the queue for the first iteration's ``DEC``
    to flush (in the window no iteration finds a layer pending)."""
    with spans("set_permutation"):
        q.SetPermutation(0)
    with spans("uniform_layer"):
        for i in range(plan.width):
            q.H(i)
        q.GetAmplitude(0)
    plan.applied = 0
    plan.after = {}


def _iteration(q, plan):
    """The source's loop body, by the engine's own calls."""
    n, t = plan.width, plan.target
    for _ in range(plan.iterations):
        q.DEC(t, 0, n)
        q.ZeroPhaseFlip(0, n)
        q.INC(t, 0, n)
        for i in range(n):
            q.H(i)
        q.ZeroPhaseFlip(0, n)
        for i in range(n):
            q.H(i)
        q.PhaseFlip()
    plan.applied += plan.iterations


def _checked_iteration(q, plan, spans, checks, name):
    """One more iteration on the ket the engine holds, its target and the
    seeded others held to the closed form: each by its relative error,
    but for one whose closed form is near a zero (a rehearsal's), which
    is held by its share of the rms amplitude."""
    with spans("gate_calls"):
        _iteration(q, plan)
    first = q.GetAmplitude(plan.target)
    with checks.untimed():
        at_target, elsewhere = closed_form(plan.width, plan.applied)
        rms = 2.0 ** (-plan.width / 2)
        got = np.array([first] + [q.GetAmplitude(y) for y in plan.others])
        want = np.array([at_target] + [elsewhere] * len(plan.others))
        scale = np.maximum(np.abs(want), NEAR_ZERO * rms)
        if not reads_the_target(plan.width, plan.applied):
            scale[0] = NEAR_ZERO
        checks.compare(name, float(np.max(np.abs(got - want) / scale)),
                       "amplitude_rel_err")


def warmup(q, plan, k, spans, checks):
    """A whole application from the uniform state, checked: every
    program of the window has run."""
    _uniform(q, plan, spans)
    _checked_iteration(q, plan, spans, checks, f"warmup_{k}_amplitudes")


def start(q, plan, spans):
    """The window's evolution starts from the uniform state (set-up)."""
    _uniform(q, plan, spans)


def enqueue(q, plan, i, spans):
    with spans("gate_calls"):
        _iteration(q, plan)
    plan.after[i] = plan.applied


def read_index(plan, i):
    return plan.read(i)[0]


def expected(plan, i):
    return plan.read(i)[1]


def measured_ket(planes, target, elsewhere):
    """``(target's amplitude, largest |amplitude - elsewhere| over all
    the others)`` of split planes, reduced on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reduce(planes, target, elsewhere):
        re, im = planes[0].astype(jnp.float32), planes[1].astype(jnp.float32)
        idx = jax.lax.iota(jnp.int32, planes.shape[-1])
        off = jnp.sqrt((re - elsewhere) ** 2 + im * im)
        worst = jnp.max(jnp.where(idx == target, 0.0, off))
        return re[target], im[target], worst

    re, im, worst = reduce(planes, np.int32(target), np.float32(elsewhere))
    return complex(float(re), float(im)), float(worst)


def final_check(q, plan, last_i, spans, checks):
    """The ket the window left: its norm, the target's amplitude and
    every other amplitude against their one closed form (over the rms
    amplitude: the value itself passes through 0 at the optimum), then
    one more checked iteration through the window's own programs."""
    k = plan.applied
    checks.norm_drift("evolved_ket", q, k)
    at_target, elsewhere = closed_form(plan.width, k)
    rms = 2.0 ** (-plan.width / 2)
    got, worst = measured_ket(q._state, plan.target, elsewhere)
    harness.say(evolved_ket_iterations=k, target=plan.target,
                target_amplitude=[got.real, got.imag],
                target_closed_form=at_target, elsewhere_closed_form=elsewhere)
    checks.compare("evolved_ket.target_amplitude", abs(got - at_target) / (
        abs(at_target) if reads_the_target(plan.width, k) else NEAR_ZERO),
        "amplitude_rel_err")
    checks.compare("evolved_ket.rest", worst / rms, "rest_rel_err")
    _checked_iteration(q, plan, spans, checks, "post_window_amplitudes")
