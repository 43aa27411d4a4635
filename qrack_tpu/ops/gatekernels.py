"""Pure-function XLA gate kernels over a dense state vector.

TPU-native replacement for the reference GPU kernel set (reference:
src/common/qengine.cl:144-1085 apply2x2*/x/z/phase/invert/compose/
decompose/prob*/nrmlze/applym; enumerated include/common/oclapi.hpp).

Representation: **split real/imag planes** — the ket is a real array of
shape (2, 2^n), plane 0 = Re, plane 1 = Im. TPUs have no complex ALU,
so complex arithmetic is written out as plane algebra. This also makes
bf16 amplitude storage a dtype switch rather than a redesign.

Design rules (see SURVEY.md §7):
  * A 1- or 2-qubit gate is elementwise over the FLAT (2, 2^n) planes:
    each amplitude is mixed with its pair partner at index distance
    2^target, fetched by a shifted read (`pair_partner`).  The planes
    are never viewed as (…, 2, 2^target): the TPU tiles the minor
    dimension to 128 lanes, so for target < 7 that view is padded
    128/2^target-fold (refused outright at w28) and its einsum takes
    minutes to compile at any target.  No gathers in the hot path.
  * Controls are dynamic (cmask, cval) scalar operands folded in with a
    `where` select, so the jit cache is keyed only on (n, target axis) —
    the reference's 8 apply2x2 kernel variants (opencl.cpp:810-1016)
    collapse into three XLA program families.
  * Every function is pure and trace-safe: usable eagerly, under
    per-gate jit, inside a whole-circuit jit, and inside shard_map.

Index convention: qubit q is bit q of the flat index.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

# Flat indices are int32: a single dense shard beyond 2^31 amplitudes
# (31 qubits, 16 GiB at float32 planes) exceeds one chip's HBM; wider
# registers live above the pager/QUnit layers, where index math is
# host-side Python int (arbitrary precision).
IDX_DTYPE = jnp.int32

# Gate contractions are 2-4 wide: full-precision multiplies cost nothing,
# while TPU DEFAULT precision truncates f32 operands to bf16 and visibly
# decays the norm over deep circuits (measured: w22 QFT x18 -> |psi|^2 =
# 0.918).  Explicit here as defense in depth — the package also sets
# jax_default_matmul_precision at import — with the per-einsum value
# derived from the SAME env parse so the two layers cannot disagree.
from .._precision import matmul_precision

PREC = matmul_precision()


# ---------------------------------------------------------------------------
# plane representation helpers
# ---------------------------------------------------------------------------

def to_planes(state_complex, dtype=jnp.float32):
    """Host complex vector -> (2, N) real planes."""
    arr = np.asarray(state_complex)
    return jnp.stack([jnp.asarray(arr.real, dtype=dtype), jnp.asarray(arr.imag, dtype=dtype)])

def from_planes(planes) -> np.ndarray:
    """(2, N) real planes -> host complex128 vector."""
    host = np.asarray(planes, dtype=np.float64)
    return host[0] + 1j * host[1]

def mtrx_planes(m, dtype=jnp.float32):
    """Host complex (d, d) matrix -> (2, d, d) real planes."""
    m = np.asarray(m)
    return jnp.stack([jnp.asarray(m.real, dtype=dtype), jnp.asarray(m.imag, dtype=dtype)])

def iota_for(planes):
    return jax.lax.iota(IDX_DTYPE, planes.shape[-1])

def join_planes(re, im):
    """(N,) real and imaginary parts -> (2, N) planes, as a select on
    the plane index.  Not jnp.stack: the TPU compiler lowers that
    concatenate as an update of the output in place, and aborts (a
    check failure in its fusion emitter, not an error) when the parts
    are computed from shifted reads alone, as in apply_invert."""
    plane = jax.lax.broadcasted_iota(IDX_DTYPE, (2,) + re.shape, 0)
    return jnp.where(plane == 0, re[None], im[None])

def cmul(fre, fim, v):
    """Multiply planes v=(2,N) by a complex factor given as (re, im)
    arrays/scalars broadcastable over N."""
    return join_planes(v[0] * fre - v[1] * fim, v[0] * fim + v[1] * fre)


# ---------------------------------------------------------------------------
# gate kernels
# ---------------------------------------------------------------------------

def _ctrl_select(new, old, cmask, cval):
    idx = iota_for(new)
    keep = (idx & cmask) == cval
    return jnp.where(keep, new, old)


def _shifted(v, d: int):
    """``out[..., i] = v[..., i + d]``, zero past either end: a negative
    edge pad, which XLA fuses into its consumer."""
    cfg = [(0, 0, 0)] * (v.ndim - 1) + [(-d, d, 0)]
    return jax.lax.pad(v, jnp.zeros((), v.dtype), cfg)


def pair_partner(planes, target: int):
    """``planes[..., i ^ (1 << target)]``: the other amplitude of each
    target-bit pair, as two shifted reads and a select on the bit.
    The barrier makes the producer of `planes` one pass of its own:
    without it XLA fuses a chain of k gates by recomputing each input
    at all three read offsets, 3^k-fold."""
    planes = jax.lax.optimization_barrier(planes)
    dist = 1 << target
    bit = (iota_for(planes) & dist) != 0
    return jnp.where(bit, _shifted(planes, -dist), _shifted(planes, dist))


def apply_2x2(planes, mp, n: int, target: int, cmask=0, cval=0):
    """Generic (optionally controlled) single-qubit gate
    (reference kernels apply2x2/apply2x2single/..., qengine.cl:144-244).
    Each amplitude takes its own row of the matrix: the diagonal entry
    times itself plus the off-diagonal entry times its partner."""
    bit = (iota_for(planes) & (1 << target)) != 0
    o = pair_partner(planes, target)
    re, im = mp[0], mp[1]
    dre = jnp.where(bit, re[1, 1], re[0, 0])
    dim = jnp.where(bit, im[1, 1], im[0, 0])
    ore = jnp.where(bit, re[1, 0], re[0, 1])
    oim = jnp.where(bit, im[1, 0], im[0, 1])
    out = join_planes(
        planes[0] * dre - planes[1] * dim + o[0] * ore - o[1] * oim,
        planes[0] * dim + planes[1] * dre + o[0] * oim + o[1] * ore)
    if isinstance(cmask, int) and cmask == 0:
        return out
    return _ctrl_select(out, planes, cmask, cval)


def apply_diag(planes, d0re, d0im, d1re, d1im, n: int, tmask, cmask=0, cval=0):
    """Diagonal (phase) gate with dynamic target/control masks — one XLA
    program per width n (reference kernels phasesingle/zsingle/...,
    qengine.cl:247-340)."""
    idx = iota_for(planes)
    bit = (idx & tmask) != 0
    fre = jnp.where(bit, d1re, d0re)
    fim = jnp.where(bit, d1im, d0im)
    active = (idx & cmask) == cval
    one = jnp.ones((), planes.dtype)
    zero = jnp.zeros((), planes.dtype)
    fre = jnp.where(active, fre, one)
    fim = jnp.where(active, fim, zero)
    return cmul(fre, fim, planes)


def apply_invert(planes, tr_re, tr_im, bl_re, bl_im, n: int, target: int, cmask=0, cval=0):
    """Anti-diagonal gate: bit-flip + per-half phases (reference kernels
    xsingle/invertsingle, qengine.cl:247-290)."""
    bit = (iota_for(planes) & (1 << target)) != 0
    fre = jnp.where(bit, bl_re, tr_re)
    fim = jnp.where(bit, bl_im, tr_im)
    out = cmul(fre, fim, pair_partner(planes, target))
    if isinstance(cmask, int) and cmask == 0:
        return out
    return _ctrl_select(out, planes, cmask, cval)


def apply_4x4(planes, mp4, n: int, q1: int, q2: int):
    """Arbitrary two-qubit gate (the reference decomposes instead).
    Matrix index = (bit q2 << 1) | bit q1.  Each amplitude reads the
    four members of its (q1, q2) quad — itself and its partners across
    q1, q2 and both — weighted by its own row of the matrix."""
    idx = iota_for(planes)
    b1 = (idx & (1 << q1)) != 0
    b2 = (idx & (1 << q2)) != 0

    def own_row(m, x2, x1):
        """m[row, row ^ (x2, x1)] with row = this amplitude's (b2, b1)."""
        at = [m[r, r ^ ((x2 << 1) | x1)] for r in range(4)]
        return jnp.where(b2, jnp.where(b1, at[3], at[2]),
                         jnp.where(b1, at[1], at[0]))

    p1 = pair_partner(planes, q1)
    quad = ((planes, p1), (pair_partner(planes, q2), pair_partner(p1, q2)))
    re = im = 0.0
    for x2 in (0, 1):
        for x1 in (0, 1):
            v = quad[x2][x1]  # the member across (x2, x1)
            cre, cim = own_row(mp4[0], x2, x1), own_row(mp4[1], x2, x1)
            re = re + v[0] * cre - v[1] * cim
            im = im + v[0] * cim + v[1] * cre
    return join_planes(re, im)


def uc_2x2(planes, mps, n: int, target: int, controls):
    """Uniformly-controlled gate: per-control-permutation payloads
    (reference kernel uniformlycontrolled, qengine.cl:409).
    mps: (2, 2^k, 2, 2) matrix planes.

    Expressed as a batched 2x2 matmul over the control-key axis
    (reshape/transpose bit->axis form) — no per-element gathers, so XLA
    keeps it on the MXU instead of scatter/gather units."""
    k = len(controls)
    t = planes.reshape((2,) + (2,) * n)
    # qubit q lives on tensor axis 1 + (n - 1 - q)
    caxes = [1 + n - 1 - c for c in list(controls)[::-1]]
    tax = 1 + n - 1 - target
    rest = [a for a in range(1, n + 1) if a not in caxes and a != tax]
    perm = [0] + caxes + [tax] + rest
    v = jnp.transpose(t, perm).reshape(2, 1 << k, 2, -1)
    re, im = mps[0], mps[1]  # [2^k, 2, 2]
    vr, vi = v[0], v[1]
    outr = (jnp.einsum("kab,kbr->kar", re, vr, precision=PREC)
            - jnp.einsum("kab,kbr->kar", im, vi, precision=PREC))
    outi = (jnp.einsum("kab,kbr->kar", re, vi, precision=PREC)
            + jnp.einsum("kab,kbr->kar", im, vr, precision=PREC))
    out = jnp.stack([outr, outi]).reshape((2,) + (2,) * n)
    inv = np.argsort(np.asarray(perm))
    return jnp.transpose(out, list(inv)).reshape(2, -1)


def phase_factor_apply(planes, fre, fim):
    """Multiply by an arbitrary per-index complex factor (diagonal ops:
    parity rz, phase flips — reference kernels uniformparityrz/
    phaseparity/phaseflipifless)."""
    return cmul(fre, fim, planes)


def swap_bits(planes, n: int, q1: int, q2: int):
    """Swap two qubits as an index relabel — zero FLOPs (the reference
    pays 3 CNOT kernels).  Amplitudes whose two bits differ trade
    places with the index 2^hi - 2^lo away; the rest stay."""
    lo, hi = (q1, q2) if q1 < q2 else (q2, q1)
    dist = (1 << hi) - (1 << lo)
    idx = iota_for(planes)
    blo = (idx & (1 << lo)) != 0
    bhi = (idx & (1 << hi)) != 0
    planes = jax.lax.optimization_barrier(planes)  # as in pair_partner
    moved = jnp.where(blo, _shifted(planes, dist), _shifted(planes, -dist))
    return jnp.where(blo == bhi, planes, moved)


def gather(planes, src_idx):
    """Basis permutation (ALU family, reference qheader_alu.cl)."""
    return planes[:, src_idx]


def prob_mask_sum(planes, mask, val):
    """Masked probability reduction (reference kernels probmask/probreg,
    qengine.cl:704-948)."""
    idx = iota_for(planes)
    p = planes[0] ** 2 + planes[1] ** 2
    return jnp.sum(jnp.where((idx & mask) == val, p, 0.0))


def normalize(planes, nrm_sq):
    return planes * (1.0 / jnp.sqrt(nrm_sq)).astype(planes.dtype)


def probs(planes):
    return planes[0] ** 2 + planes[1] ** 2


def sum_sqr_diff(a, b):
    """1 - |<a|b>|^2 from planes (reference: approxcompare kernel)."""
    re = jnp.sum(a[0] * b[0] + a[1] * b[1])
    im = jnp.sum(a[0] * b[1] - a[1] * b[0])
    return jnp.maximum(0.0, 1.0 - (re * re + im * im))


def expectation_bits(planes, bits, offset: int = 0):
    """<integer value of bits> via per-bit marginal reductions (reference:
    expperm kernel, qengine.cl:930). Summing 2^j * P(bit_j) keeps each
    accumulation O(1)-magnitude, which matters because plane dtype may be
    float32 (a direct sum of p*value over 2^n terms loses integer
    precision for wide registers)."""
    idx = iota_for(planes)
    p = planes[0] ** 2 + planes[1] ** 2
    total = jnp.asarray(float(offset), dtype=p.dtype)
    for j, b in enumerate(bits):
        bit_set = ((idx >> b) & 1) == 1
        total = total + float(1 << j) * jnp.sum(jnp.where(bit_set, p, 0.0))
    return total


def sample(planes, u):
    """Device-side categorical draw for MAll (no 2^n host transfer)."""
    p = planes[0] ** 2 + planes[1] ** 2
    cdf = jnp.cumsum(p)
    idx = jnp.searchsorted(cdf, u * cdf[-1], side="right")
    return jnp.minimum(idx, p.shape[0] - 1)


def multishot_mask_keys(planes, u, bits):
    """Batched categorical draws + masked-bit compaction, all on device
    (reference: the bulk MultiShotMeasureMask op,
    src/qinterface/qinterface.cpp:807).  `u` is (shots,) uniforms,
    `bits` a (k,) int array of qubit indices; returns (shots,) ints
    whose bit j is drawn-index bit bits[j] — only the k-bit keys cross
    to the host, never the 2^n probability vector."""
    p = planes[0] ** 2 + planes[1] ** 2
    cdf = jnp.cumsum(p)
    draws = jnp.searchsorted(cdf, u * cdf[-1], side="right")
    draws = jnp.minimum(draws, p.shape[0] - 1)
    hit = (draws[:, None] >> bits[None, :]) & 1
    return jnp.sum(hit << jnp.arange(bits.shape[0], dtype=draws.dtype), axis=1)


def allocate(planes, n: int, start: int, length: int):
    """Insert |0> qubits at `start` as zero-pad + reshape."""
    high = 1 << (n - start)
    low = 1 << start
    v = planes.reshape(2, high, 1, low)
    z = jnp.zeros((2, high, (1 << length) - 1, low), dtype=planes.dtype)
    return jnp.concatenate([v, z], axis=2).reshape(2, -1)


def compose(planes_self, planes_other, n: int, m: int, start: int):
    """Tensor product with other's qubits inserted at `start`
    (reference kernel compose, qengine.cl:521)."""
    # complex outer product in planes
    re = jnp.outer(planes_other[0], planes_self[0]) - jnp.outer(planes_other[1], planes_self[1])
    im = jnp.outer(planes_other[0], planes_self[1]) + jnp.outer(planes_other[1], planes_self[0])
    from ..utils.states import insertion_axes

    t = jnp.stack([re, im]).reshape((2,) + (2,) * (m + n))
    return jnp.transpose(t, insertion_axes(n, m, start, lead=1)).reshape(2, -1)


def split_matrix(planes, n: int, start: int, length: int):
    """Reshape ket planes to (2, remainder, dest) for dest = [start,
    start+length) (reference kernels decomposeprob/decomposeamp,
    qengine.cl:569-702)."""
    t = planes.reshape((2,) + (2,) * n)
    dest_axes = [1 + n - 1 - q for q in range(start + length - 1, start - 1, -1)]
    rem_axes = [a for a in range(1, n + 1) if a not in dest_axes]
    tt = jnp.transpose(t, [0] + rem_axes + dest_axes)
    return tt.reshape(2, 1 << (n - length), 1 << length)
