"""Fused random-circuit-sampling programs — the RCS headline benchmark.

The reference's RCS benchmarks dispatch one kernel per gate (reference:
test/benchmarks.cpp:4141 test_random_circuit_sampling_nn — random
sqrt-root layers + brick-wall ISwap couplers). TPU-native, a whole
depth-d circuit traces into one XLA executable: single-qubit roots are
plane-mixing 2x2 contractions, couplers are one 4x4 contraction each,
and XLA fuses across layers.
"""

from __future__ import annotations

import numpy as np

import jax

from .. import matrices as mat
from ..ops import gatekernels as gk
from ..utils.rng import QrackRandom

_ISWAP4 = np.array(
    [[1, 0, 0, 0], [0, 0, 1j, 0], [0, 1j, 0, 0], [0, 0, 0, 1]],
    dtype=np.complex128,
)
_ROOTS = (mat.SQRTX2, mat.SQRTY2, mat.SQRTW2)


def rcs_layers(n: int, depth: int, seed: int):
    """Deterministic gate plan: per layer, a random root per qubit and the
    brick-wall ISwap pairing (matches models.algorithms.random_circuit_sampling)."""
    rng = QrackRandom(seed)
    plan = []
    for d in range(depth):
        roots = [rng.randint(0, 3) for _ in range(n)]
        off = d & 1
        pairs = [(q, q + 1) for q in range(off, n - 1, 2)]
        plan.append((roots, pairs))
    return plan


def rcs_qcircuit(n: int, depth: int, seed: int):
    """The RCS gate plan as a ``QCircuit`` gate list — the form the
    noisy trajectory engine lowers (qrack_tpu/noise/trajectories.py).
    ``QCircuitGate`` is a controlled-1q payload model, so the brick-wall
    couplers are CZ instead of ISwap: same entangling topology,
    payload-representable."""
    from ..layers.qcircuit import QCircuit

    cz = mat.phase_mtrx(1.0, -1.0)
    c = QCircuit(n)
    for roots, pairs in rcs_layers(n, depth, seed):
        for q, g in enumerate(roots):
            c.append_1q(q, _ROOTS[g])
        for a, b in pairs:
            c.append_ctrl((a,), b, cz, 1)
    return c


def _iswap_layer(planes, n: int, pairs):
    """A whole brick-wall ISwap layer as ONE transpose + ONE phase pass.

    ISwap = SWAP . diag(1, i, i, 1): disjoint pairs make the layer's
    permutation part a product of adjacent bit-axis swaps (a single
    jnp.transpose) and its phase part i^(number of pairs whose bits
    differ) — one fused elementwise multiply.  Collapses the
    reference's kernel-per-coupler chain (test/benchmarks.cpp:4141) to
    2 HBM passes per layer instead of n/2 4x4 contractions, and shrinks
    the traced program accordingly (compile time scales with op
    count)."""
    import jax.numpy as jnp

    shape = (2,) + (2,) * n
    perm = list(range(n + 1))
    for (a, b) in pairs:
        pa, pb = n - a, n - b  # C-order: axis k holds bit n - k
        perm[pa], perm[pb] = perm[pb], perm[pa]
    out = planes.reshape(shape).transpose(perm).reshape(2, -1)
    idx = gk.iota_for(out)
    k = None
    for (a, b) in pairs:
        t = ((idx >> a) ^ (idx >> b)) & 1
        k = t if k is None else k + t
    k = k & 3
    re = jnp.asarray([1.0, 0.0, -1.0, 0.0], dtype=planes.dtype)[k]
    im = jnp.asarray([0.0, 1.0, 0.0, -1.0], dtype=planes.dtype)[k]
    return gk.cmul(re, im, out)


def _cluster_mats(roots, k: int):
    """Kron the layer's single-qubit roots into per-cluster 2^k x 2^k
    matrices over CONTIGUOUS qubit spans (all roots in a layer act on
    disjoint qubits, so grouping is exact).  np.kron(next, acc) keeps
    the earlier qubit least significant, matching the index convention."""
    out = []
    for c0 in range(0, len(roots), k):
        ms = [_ROOTS[g] for g in roots[c0:c0 + k]]
        acc = ms[0]
        for m in ms[1:]:
            acc = np.kron(m, acc)
        out.append((c0, len(ms), acc))
    return out


def resolve_fuse_qb(n: int, fuse_qb: int | None = None) -> int:
    """Single source of truth for the root-cluster width (also used by
    bench.py's HBM-pass model, so the two can never drift)."""
    import os

    if fuse_qb is None:
        fuse_qb = int(os.environ.get("QRACK_RCS_FUSE_QB", "6"))
    return max(1, min(fuse_qb, n))


def make_rcs_fn(n: int, depth: int, seed: int, fuse_qb: int | None = None):
    """Jittable single-chip whole-RCS program over (2, 2^n) planes.

    Root layers fuse into 2^k-wide cluster contractions (one HBM pass
    per cluster instead of per qubit; the reference dispatches one
    kernel per gate, test/benchmarks.cpp:4141).  k defaults to
    QRACK_RCS_FUSE_QB (6 -> 64-wide MXU matmuls); k=1 recovers the
    per-gate program."""
    fuse_qb = resolve_fuse_qb(n, fuse_qb)
    plan = rcs_layers(n, depth, seed)
    baked = [(_cluster_mats(roots, fuse_qb), pairs)
             for (roots, pairs) in plan]

    def fn(planes):
        for (clusters, pairs) in baked:
            for (c0, w, m) in clusters:
                mp = gk.mtrx_planes(m, planes.dtype)
                planes = gk.apply_kxk(planes, mp, n, c0, w)
            if pairs:
                planes = _iswap_layer(planes, n, pairs)
        return planes

    return fn


def make_sharded_rcs_fn(mesh, n: int, depth: int, seed: int,
                        fuse_qb: int | None = None):
    """Whole-RCS program over a ket sharded across the 'pages' mesh axis
    (BASELINE target 4's RCS counterpart to make_sharded_qft_fn).

    Per brick-wall layer, the coupler set splits by geometry:
      * pairs fully below the page boundary: in-page transpose + phase
        (no communication, same as single-chip);
      * the one pair straddling bit L-1/L: one `lax.ppermute` partner
        exchange + an axis flip + select (the SWAP part) with the ISwap
        i-phase on the moved half;
      * pairs fully in page bits: a pure page permutation (ppermute)
        plus a per-page scalar phase.
    Root clusters apply per page on local axes; clusters are capped at
    the local width so they never straddle the boundary."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    npg = mesh.devices.size
    g = npg.bit_length() - 1
    L = n - g
    assert (1 << g) == npg, "page count must be a power of two"
    assert L >= 1, "at least one local qubit per page"
    k = min(resolve_fuse_qb(n, fuse_qb), L)
    plan = rcs_layers(n, depth, seed)
    sharding = NamedSharding(mesh, P(None, "pages"))

    def body(local):
        from ..ops import sharded as shb

        pid = jax.lax.axis_index("pages")
        dt = local.dtype
        for (roots, pairs) in plan:
            # roots: local spans cluster per page; a paged qubit's root
            # rides the existing half-buffer pair exchange
            for (c0, w, m) in _cluster_mats(roots[:L], k):
                local = gk.apply_kxk(local, gk.mtrx_planes(m, dt), L, c0, w)
            for q in range(L, n):
                mp = gk.mtrx_planes(_ROOTS[roots[q]], dt)
                local = shb.apply_global_2x2(local, mp, npg, q - L,
                                             0, 0, 0, 0)
            if not pairs:
                continue
            idx = gk.iota_for(local)
            loc_pairs = [(a, b) for (a, b) in pairs if b < L]
            straddle = [(a, b) for (a, b) in pairs if a < L <= b]
            page_pairs = [(a, b) for (a, b) in pairs if a >= L]
            if loc_pairs:
                local = _iswap_layer(local, L, loc_pairs)
            for (a, b) in straddle:   # a == L-1, b == L by construction
                gpos = b - L
                perm = [(j, j ^ (1 << gpos)) for j in range(npg)]
                partner = jax.lax.ppermute(local, "pages", perm)
                pb = (pid >> gpos) & 1
                bl = (idx >> a) & 1
                flipped = jnp.flip(
                    partner.reshape(2, 1 << (L - 1 - a), 2, 1 << a),
                    axis=2).reshape(2, -1)
                moved = gk.cmul(jnp.zeros((), dt), jnp.ones((), dt), flipped)
                local = jnp.where(bl == pb, local, moved)
            for (a, b) in page_pairs:
                ga, gb = a - L, b - L
                swap_map = []
                for j in range(npg):
                    ba, bb = (j >> ga) & 1, (j >> gb) & 1
                    t = j & ~((1 << ga) | (1 << gb))
                    swap_map.append((j, t | (bb << ga) | (ba << gb)))
                local = jax.lax.ppermute(local, "pages", swap_map)
                diff = ((pid >> ga) ^ (pid >> gb)) & 1
                local = jnp.where(diff == 1,
                                  gk.cmul(jnp.zeros((), dt), jnp.ones((), dt),
                                          local),
                                  local)
        return local

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(None, "pages"),
                      out_specs=P(None, "pages")),
        donate_argnums=(0,),
    )
    return fn, sharding


def reference_rcs_state(n: int, depth: int, seed: int, engine) -> np.ndarray:
    """Same plan through a gate-at-a-time engine (parity checking)."""
    plan = rcs_layers(n, depth, seed)
    for (roots, pairs) in plan:
        for q, g in enumerate(roots):
            engine.Mtrx(_ROOTS[g], q)
        for (a, b) in pairs:
            engine.Apply4x4(_ISWAP4, a, b)
    return np.asarray(engine.GetQuantumState())
