"""Fused Grover-search program — the loop-structured headline workload.

The reference benchmarks Grover as gate-at-a-time engine calls
(reference: test/benchmarks.cpp test_grover_search; examples/
grovers.cpp drives QInterface H/PhaseFlip per iteration).  TPU-native,
one Grover ITERATION traces into a handful of fused passes (oracle
phase flip + H-ladder + |0> phase flip + H-ladder) and the O(sqrt(N))
iteration count rides `jax.lax.fori_loop` — the compiled HLO is
constant-size no matter how many iterations run, the loop stays on
device, and XLA fuses the phase flips into the neighbouring H
contractions.  H-ladders use 2^k-wide cluster contractions
(H^(x)k kron blocks on the MXU) like models.rcs.

This is the repo's canonical example of XLA-semantics design: a
data-independent loop belongs in `lax.fori_loop`, not unrolled trace
(contrast the QFT, whose per-stage angles differ and therefore unroll).
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from .. import matrices as mat
from ..ops import gatekernels as gk


def grover_iterations(n: int) -> int:
    """floor(pi/4 * sqrt(N)) — the optimal rotation count."""
    return int(math.floor(math.pi / 4.0 * math.sqrt(float(1 << n))))


# H-ladder cluster width (single source of truth — bench.py's HBM-pass
# model imports this so the two cannot drift)
FUSE_QB = 6


def _h_clusters(n: int, k: int, dtype):
    """H^(x)w kron blocks covering [0, n) in spans of width <= k."""
    out = []
    for c0 in range(0, n, k):
        w = min(k, n - c0)
        acc = np.asarray(mat.H2)
        for _ in range(w - 1):
            acc = np.kron(np.asarray(mat.H2), acc)
        out.append((c0, w, gk.mtrx_planes(acc, dtype)))
    return out


def make_grover_fn(n: int, target: int, iters: int | None = None,
                   fuse_qb: int = FUSE_QB):
    """Jittable whole-search program over (2, 2^n) planes: prepare the
    uniform superposition, then fori_loop the Grover iteration.  Returns
    (fn, iters)."""
    if iters is None:
        iters = grover_iterations(n)
    target &= (1 << n) - 1
    k = max(1, min(fuse_qb, n))

    def fn(planes):
        clusters = _h_clusters(n, k, planes.dtype)
        idx = gk.iota_for(planes)
        oracle = jnp.where(idx == target, -1.0, 1.0).astype(planes.dtype)
        zflip = jnp.where(idx == 0, -1.0, 1.0).astype(planes.dtype)

        def h_all(p):
            for (c0, w, mp) in clusters:
                p = gk.apply_kxk(p, mp, n, c0, w)
            return p

        def iteration(_, p):
            p = p * oracle              # phase oracle on |target>
            p = h_all(p)
            p = p * zflip               # diffusion = H ladder . flip|0> . H ladder
            return h_all(p)

        planes = h_all(planes)          # uniform superposition from |0>
        return jax.lax.fori_loop(0, iters, iteration, planes)

    return fn, iters


def success_probability(planes, target: int) -> float:
    p = planes[0] ** 2 + planes[1] ** 2
    return float(p[target] / p.sum())


def make_sharded_grover_fn(mesh, n: int, target: int,
                           iters: int | None = None, fuse_qb: int = FUSE_QB):
    """Grover over a ket sharded across the 'pages' mesh axis: local
    H-clusters per page, paged H bits via the half-buffer pair exchange,
    phase flips from split (local, page) index reads — all inside the
    same `lax.fori_loop` body, so the HLO stays constant-size and the
    per-iteration collectives ride ICI.  Returns (fn, sharding, iters)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..ops import sharded as shb

    npg = mesh.devices.size
    g = npg.bit_length() - 1
    L = n - g
    assert (1 << g) == npg, "page count must be a power of two"
    assert L >= 1
    if iters is None:
        iters = grover_iterations(n)
    target &= (1 << n) - 1
    t_lo, t_hi = target & ((1 << L) - 1), target >> L
    k = max(1, min(fuse_qb, L))

    def body(local):
        pid = jax.lax.axis_index("pages")
        dt = local.dtype
        hmp2 = gk.mtrx_planes(np.asarray(mat.H2), dt)
        clusters = _h_clusters(L, k, dt)
        idx = gk.iota_for(local)
        is_t = (idx == t_lo) & (pid == t_hi)
        oracle = jnp.where(is_t, -1.0, 1.0).astype(dt)
        is_0 = (idx == 0) & (pid == 0)
        zflip = jnp.where(is_0, -1.0, 1.0).astype(dt)

        def h_all(p):
            for (c0, w, mp) in clusters:
                p = gk.apply_kxk(p, mp, L, c0, w)
            for q in range(L, n):
                p = shb.apply_global_2x2(p, hmp2, npg, q - L, 0, 0, 0, 0)
            return p

        def iteration(_, p):
            p = p * oracle
            p = h_all(p)
            p = p * zflip
            return h_all(p)

        return jax.lax.fori_loop(0, iters, iteration, h_all(local))

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(None, "pages"),
                      out_specs=P(None, "pages")),
        donate_argnums=(0,),
    )
    return fn, NamedSharding(mesh, P(None, "pages")), iters
