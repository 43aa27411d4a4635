"""Whole-circuit QFT programs: the flagship fused workload.

The reference dispatches one GPU kernel per gate (reference:
test/benchmarks.cpp test_qft_* drive QInterface::QFT gate by gate).
TPU-native, the entire circuit is traced into ONE XLA program — the
n H-gates and n(n-1)/2 controlled phases unroll at trace time into a
single fused executable (the reference's QueueItem chain becomes jit
tracing, SURVEY.md §7 step 4), and the sharded variant runs the same
program per page with ppermute pair exchanges over ICI for paged-qubit
targets (reference: src/qpager.cpp:400-447 host-staged ShuffleBuffers).

Gate order matches QInterface::QFT (reference:
src/qinterface/qinterface.cpp:114) so results are bit-for-bit
comparable with the gate-at-a-time path.
"""

from __future__ import annotations

import cmath
import math
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import gatekernels as gk


def _h_mp(dtype):
    s = 1.0 / math.sqrt(2.0)
    re = jnp.asarray([[s, s], [s, -s]], dtype=dtype)
    return jnp.stack([re, jnp.zeros_like(re)])


def _stage_phase(planes, pairs):
    """ONE fused elementwise pass applying a whole stage's controlled
    phases: diagonal gates commute, so their product is a single
    exp(i*theta(idx)) with theta = sum over (c, t, ang) of
    ang * bit_c(idx) * bit_t(idx).  Collapsing the reference's
    kernel-per-gate chain (test/benchmarks.cpp test_qft_*) to one HBM
    pass per stage bounds both traffic and XLA temp pressure at
    O(n) passes for the whole QFT instead of O(n^2)."""
    acc = jnp.float64 if planes.dtype == jnp.float64 else jnp.float32
    idx = gk.iota_for(planes)
    theta = jnp.zeros(planes.shape[-1], dtype=acc)
    for c, t, ang in pairs:
        on = ((idx >> c) & (idx >> t) & 1).astype(acc)
        theta = theta + on * acc(ang)
    fre = jnp.cos(theta).astype(planes.dtype)
    fim = jnp.sin(theta).astype(planes.dtype)
    return gk.cmul(fre, fim, planes)


def qft_planes(planes, n: int):
    """Single-shard QFT over all n qubits (pure, trace-safe)."""
    hm = _h_mp(planes.dtype)
    end = n - 1
    for i in range(n):
        h_bit = end - i
        if i:
            planes = _stage_phase(planes, [
                (h_bit, h_bit + 1 + j, math.pi / (1 << (j + 1)))
                for j in range(i)])
        planes = gk.apply_2x2(planes, hm, n, h_bit)
    return planes


def iqft_planes(planes, n: int):
    hm = _h_mp(planes.dtype)
    for i in range(n):
        if i:
            planes = _stage_phase(planes, [
                (i - (j + 1), i, -math.pi / (1 << (j + 1)))
                for j in range(i)])
        planes = gk.apply_2x2(planes, hm, n, i)
    return planes


def _carried_phase(planes, frac, h_bit: int, sign: float):
    """One stage's controlled phases from the carried fraction:
    theta(idx) = sign * pi * bit_h(idx) * frac(idx)."""
    acc = frac.dtype
    idx = gk.iota_for(planes)
    on = ((idx >> h_bit) & 1).astype(acc)
    theta = jnp.asarray(sign * math.pi, dtype=acc) * on * frac
    return gk.cmul(jnp.cos(theta).astype(planes.dtype),
                   jnp.sin(theta).astype(planes.dtype), planes)


def qft_planes_fast(planes, n: int, inverse: bool = False):
    """O(n)-op QFT: stage i's angle sum  sum_j bit_{h+1+j} * pi/2^(j+1)
    obeys the exact recurrence  frac_h = (frac_{h+1} + bit_{h+1}) / 2,
    so one carried (2^n,) fraction array replaces the per-stage O(i)
    term sums of `_stage_phase` — the traced HLO shrinks from O(n^2) to
    O(n) ops (an ~n-fold cut of the traced program) at the cost of one extra array's HBM traffic per stage.
    Bit-for-bit the same gate order as qft_planes/iqft_planes
    (reference: QInterface::QFT, src/qinterface/qinterface.cpp:114);
    f32 carried fractions add <= 2^-24 relative angle error."""
    hm = _h_mp(planes.dtype)
    acc = jnp.float64 if planes.dtype == jnp.float64 else jnp.float32
    idx = gk.iota_for(planes)
    frac = jnp.zeros(planes.shape[-1], dtype=acc)
    end = n - 1
    for i in range(n):
        h_bit = i if inverse else end - i
        if i:
            prev = h_bit - 1 if inverse else h_bit + 1
            pb = ((idx >> prev) & 1).astype(acc)
            frac = (frac + pb) * acc(0.5)
            planes = _carried_phase(planes, frac, h_bit,
                                    -1.0 if inverse else 1.0)
        planes = gk.apply_2x2(planes, hm, n, h_bit)
    return planes


# Above this width the O(n)-op carried-fraction form is the default off
# the CPU; exact-same gate order either way.
FAST_COMPILE_QB = int(os.environ.get("QRACK_QFT_FAST_QB", "23"))


def default_fast(n: int) -> bool:
    """Platform-aware default: the carried-fraction form trades ~14%
    runtime (one extra array's HBM traffic per stage, measured at w24
    on CPU-XLA) for an ~n-fold smaller HLO.  Compiled for a described
    v5e (PR 25, a compile here and no chip run) a whole-QFT program is
    the expensive thing to build either way: w20 fast 55 s, w24 fast
    99 s against 111 s unrolled, where one 16-gate engine window takes
    about 10 s.  So the smaller program stays the default wherever the
    backend is not the CPU, and CPU backends keep the unrolled form
    UNLESS the operator set QRACK_QFT_FAST_QB explicitly (an explicit
    threshold wins on every backend; otherwise the knob would be dead
    on CPU).  The env var is re-read here so a threshold set after
    import is honored."""
    env = os.environ.get("QRACK_QFT_FAST_QB")
    threshold = int(env) if env is not None else FAST_COMPILE_QB
    if n < threshold:
        return False
    if env is not None:
        return True
    return jax.default_backend() != "cpu"


def make_qft_fn(n: int, inverse: bool = False, fast: bool | None = None):
    """Jittable single-chip whole-QFT program over (2, 2^n) planes."""
    if fast is None:
        fast = default_fast(n)
    if fast:
        return lambda planes: qft_planes_fast(planes, n, inverse)
    body = iqft_planes if inverse else qft_planes

    def fn(planes):
        return body(planes, n)

    return fn


def qft_qcircuit(n: int, inverse: bool = False):
    """The same QFT as :func:`qft_planes` but as a QCircuit gate-IR
    object — the form the serving layer batches (QCircuit.shape_key /
    compile_batched_fn).  Gate order matches QInterface::QFT exactly
    (reference: src/qinterface/qinterface.cpp:114), so states are
    bit-for-bit comparable with every other QFT path here."""
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


# ---------------------------------------------------------------------------
# sharded whole-circuit program (pages mesh axis)
# ---------------------------------------------------------------------------

def _sharded_h(local, hm, L, npg, target):
    """H inside the shard_map body: local target applies per page; paged
    target rides the pager's half-buffer pair exchange (each ppermute
    payload is half a page — never ship a whole page; reference
    discipline: ShuffleBuffers, src/qpager.cpp:400-447)."""
    if target < L:
        return gk.apply_2x2(local, hm, L, target)
    from ..ops import sharded as shb

    return shb.apply_global_2x2(local, hm, npg, target - L, 0, 0, 0, 0)


def _sharded_stage_phase(local, L, pairs):
    """Whole stage of controlled phases as ONE collective-free
    elementwise pass (split local/page bit reads; see _stage_phase)."""
    pid = jax.lax.axis_index("pages")
    idx = gk.iota_for(local)

    def gbit(b):
        return ((idx >> b) & 1) if b < L else ((pid >> (b - L)) & 1)

    acc = jnp.float64 if local.dtype == jnp.float64 else jnp.float32
    theta = jnp.zeros(local.shape[-1], dtype=acc)
    for c, t, ang in pairs:
        on = (gbit(c) & gbit(t)).astype(acc)
        theta = theta + on * acc(ang)
    fre = jnp.cos(theta).astype(local.dtype)
    fim = jnp.sin(theta).astype(local.dtype)
    return gk.cmul(fre, fim, local)


def make_sharded_qft_fn(mesh: Mesh, n: int, inverse: bool = False,
                        fast: bool | None = None):
    """One jitted program: full QFT over a ket sharded across the 'pages'
    mesh axis — in-page math per device, ppermute over ICI for paged
    targets. Returns (fn, sharding).  `fast` selects the O(n)-op
    carried-fraction form (see qft_planes_fast); the recurrence reads
    each stage's previous bit from the local index or the page id, so it
    is mesh-shape agnostic like the unrolled form."""
    npg = mesh.devices.size
    g = npg.bit_length() - 1
    L = n - g
    assert (1 << g) == npg, "page count must be a power of two"
    if fast is None:
        fast = default_fast(n)
    sharding = NamedSharding(mesh, P(None, "pages"))

    def _gbit(local, b: int):
        if b < L:
            return (gk.iota_for(local) >> b) & 1
        return (jax.lax.axis_index("pages") >> (b - L)) & 1

    def body(local):
        hm = _h_mp(local.dtype)
        end = n - 1
        if fast:
            acc = jnp.float64 if local.dtype == jnp.float64 else jnp.float32
            frac = jnp.zeros(local.shape[-1], dtype=acc)
            for i in range(n):
                h_bit = i if inverse else end - i
                if i:
                    prev = h_bit - 1 if inverse else h_bit + 1
                    frac = (frac + _gbit(local, prev).astype(acc)) * acc(0.5)
                    on = _gbit(local, h_bit).astype(acc)
                    theta = (jnp.asarray(-math.pi if inverse else math.pi,
                                         dtype=acc) * on * frac)
                    local = gk.cmul(jnp.cos(theta).astype(local.dtype),
                                    jnp.sin(theta).astype(local.dtype), local)
                local = _sharded_h(local, hm, L, npg, h_bit)
            return local
        if not inverse:
            for i in range(n):
                h_bit = end - i
                if i:
                    local = _sharded_stage_phase(local, L, [
                        (h_bit, h_bit + 1 + j, math.pi / (1 << (j + 1)))
                        for j in range(i)])
                local = _sharded_h(local, hm, L, npg, h_bit)
        else:
            for i in range(n):
                if i:
                    local = _sharded_stage_phase(local, L, [
                        (i - (j + 1), i, -math.pi / (1 << (j + 1)))
                        for j in range(i)])
                local = _sharded_h(local, hm, L, npg, i)
        return local

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(None, "pages"), out_specs=P(None, "pages")),
        donate_argnums=(0,),
    )
    return fn, sharding


def basis_planes(n: int, perm: int, sharding=None, dtype=jnp.float32):
    """|perm> as (2, 2^n) planes, optionally sharded."""
    st = jnp.zeros((2, 1 << n), dtype=dtype).at[0, perm].set(1.0)
    if sharding is not None:
        st = jax.device_put(st, sharding)
    return st
