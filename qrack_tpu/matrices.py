"""Canonical 2x2 gate matrices and small matrix utilities.

Replaces the reference's inline constant tables and the 2x2
exp/log/sqrt helpers (reference: src/common/functions.cpp:1-328).
All host-side matrices are complex128 for accuracy; engines down-cast
to their storage dtype at dispatch time.
"""

from __future__ import annotations

import cmath
import math

import numpy as np

SQRT1_2 = 1.0 / math.sqrt(2.0)

I2 = np.eye(2, dtype=np.complex128)
X2 = np.array([[0, 1], [1, 0]], dtype=np.complex128)
Y2 = np.array([[0, -1j], [1j, 0]], dtype=np.complex128)
Z2 = np.array([[1, 0], [0, -1]], dtype=np.complex128)
H2 = np.array([[SQRT1_2, SQRT1_2], [SQRT1_2, -SQRT1_2]], dtype=np.complex128)
S2 = np.array([[1, 0], [0, 1j]], dtype=np.complex128)
IS2 = np.array([[1, 0], [0, -1j]], dtype=np.complex128)
T2 = np.array([[1, 0], [0, cmath.exp(0.25j * math.pi)]], dtype=np.complex128)
IT2 = np.array([[1, 0], [0, cmath.exp(-0.25j * math.pi)]], dtype=np.complex128)
# sqrt(X) and its inverse (reference: SqrtX include/qinterface.hpp:1010)
SQRTX2 = 0.5 * np.array([[1 + 1j, 1 - 1j], [1 - 1j, 1 + 1j]], dtype=np.complex128)
ISQRTX2 = 0.5 * np.array([[1 - 1j, 1 + 1j], [1 + 1j, 1 - 1j]], dtype=np.complex128)
SQRTY2 = 0.5 * np.array([[1 + 1j, -1 - 1j], [1 + 1j, 1 + 1j]], dtype=np.complex128)
ISQRTY2 = 0.5 * np.array([[1 - 1j, 1 - 1j], [-1 + 1j, 1 - 1j]], dtype=np.complex128)
# sqrt(W), W = (X+Y)/sqrt(2) — Sycamore gate set (reference: SqrtW usage
# in test/benchmarks.cpp supremacy circuits). W is Hermitian-unitary with
# eigenvalues ±1, so the principal square root below is unitary.
_W2 = (X2 + Y2) / math.sqrt(2.0)
_w_vals, _w_vecs = np.linalg.eigh(_W2)
SQRTW2 = (_w_vecs * np.sqrt(_w_vals.astype(np.complex128))) @ _w_vecs.conj().T

# the swap family as 4x4s, row and column (bit q2 << 1) | bit q1 (all
# three are symmetric in their qubits): what an engine that holds a
# two-qubit gate as one op applies in place of the 2x2 syntheses of
# interface/gates.py (reference: src/qinterface/gates.cpp:166-247)
SWAP4 = np.eye(4, dtype=np.complex128)[[0, 2, 1, 3]]
ISWAP4 = np.array([[1, 0, 0, 0], [0, 0, 1j, 0], [0, 1j, 0, 0], [0, 0, 0, 1]],
                  dtype=np.complex128)
IISWAP4 = ISWAP4.conj().T


def phase_mtrx(top_left: complex, bottom_right: complex) -> np.ndarray:
    return np.array([[top_left, 0], [0, bottom_right]], dtype=np.complex128)


def invert_mtrx(top_right: complex, bottom_left: complex) -> np.ndarray:
    return np.array([[0, top_right], [bottom_left, 0]], dtype=np.complex128)


def u3_mtrx(theta: float, phi: float, lambd: float) -> np.ndarray:
    """General single-qubit rotation (reference: U, src/qinterface/rotational.cpp:18)."""
    cos = math.cos(theta / 2)
    sin = math.sin(theta / 2)
    return np.array(
        [
            [cos, -cmath.exp(1j * lambd) * sin],
            [cmath.exp(1j * phi) * sin, cmath.exp(1j * (phi + lambd)) * cos],
        ],
        dtype=np.complex128,
    )


def ai_mtrx(azimuth: float, inclination: float) -> np.ndarray:
    """Bloch-vector azimuth/inclination prep (reference: AI,
    src/qinterface/rotational.cpp:55-129)."""
    cosine = math.cos(inclination / 2)
    sine = math.sin(inclination / 2)
    e_az = cmath.exp(1j * azimuth)
    return np.array([[cosine, -sine / e_az], [sine * e_az, cosine]], dtype=np.complex128)


def exp_mtrx(m: np.ndarray) -> np.ndarray:
    """2x2 matrix exponential via eigendecomposition (reference: exp2x2,
    src/common/functions.cpp)."""
    w, v = np.linalg.eig(m)
    return (v * np.exp(w)) @ np.linalg.inv(v)


def sqrt_mtrx(m: np.ndarray) -> np.ndarray:
    w, v = np.linalg.eig(m)
    return (v * np.sqrt(w.astype(np.complex128))) @ np.linalg.inv(v)


def is_phase(m: np.ndarray, tol: float = 1e-12) -> bool:
    """True if the matrix is diagonal (phase-only fast path,
    reference: IS_NORM_0 checks in src/qengine/opencl.cpp:810-900)."""
    return abs(m[0, 1]) <= tol and abs(m[1, 0]) <= tol


def is_invert(m: np.ndarray, tol: float = 1e-12) -> bool:
    """True if the matrix is anti-diagonal (X-like fast path)."""
    return abs(m[0, 0]) <= tol and abs(m[1, 1]) <= tol


def is_identity(m: np.ndarray, tol: float = 1e-12) -> bool:
    ph = m[0, 0]
    return (
        abs(m[0, 1]) <= tol
        and abs(m[1, 0]) <= tol
        and abs(m[1, 1] - ph) <= tol
        and abs(abs(ph) - 1.0) <= tol
    )


def is_clifford_mtrx(m: np.ndarray, tol: float = 1e-6) -> bool:
    """Heuristic single-qubit Clifford membership test, used by the
    stabilizer-hybrid layer (reference: QStabilizerHybrid gate triage,
    src/qstabilizerhybrid.cpp:206-239)."""
    from itertools import product

    cliffords = _clifford_cache()
    for c in cliffords:
        # compare up to global phase
        inner = np.trace(c.conj().T @ m) / 2.0
        if abs(abs(inner) - 1.0) <= tol:
            return True
    return False


_CLIFFORD_CACHE = None


def _clifford_cache():
    global _CLIFFORD_CACHE
    if _CLIFFORD_CACHE is None:
        gens = [I2, H2, S2]
        group = [I2]
        frontier = [I2]
        while frontier:
            nxt = []
            for g in frontier:
                for h in gens:
                    cand = h @ g
                    # normalize global phase: make first nonzero entry real positive
                    flat = cand.flatten()
                    nz = flat[np.argmax(np.abs(flat) > 1e-9)]
                    cand_n = cand * (abs(nz) / nz)
                    if not any(np.allclose(cand_n, m, atol=1e-9) for m in group):
                        group.append(cand_n)
                        nxt.append(cand_n)
            frontier = nxt
        _CLIFFORD_CACHE = group  # 24 elements
    return _CLIFFORD_CACHE
