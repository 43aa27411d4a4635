"""Pallas TPU kernels: fused dequantize -> gate -> requantize over the
block-compressed resident ket.

The XLA chunk programs (engines/turboquant.py) express a gate as
dequant-matmul -> gate contraction -> requant-matmul; XLA schedules
those as separate matmul ops, so the decompressed f32 chunk usually
round-trips HBM between them.  These kernels fuse the whole pipeline
per VMEM tile: a (TB, 2D) slab of int codes and its scales are read
ONCE, dequantized against the resident rotation (a 2Dx2D MXU matmul),
run through the gate in-register, re-rotated, re-scaled, and written
back ONCE — HBM traffic per gate is exactly one read+write of the
b-bit codes, the compressed engine's information-theoretic floor
(4x below the dense f32 per-gate floor at int8).

Gate parameters (matrix planes, control masks) are RUNTIME operands,
so the compile cache stays keyed on (layout, target) exactly like the
XLA chunk programs — a million distinct rotation angles share one
binary.  Tiles whose high-control test fails (or whose diagonal factor
is identically 1) write their ORIGINAL codes back bit-for-bit, matching
the XLA path's untouched-chunk exactness contract.

Compatibility: diagonal payloads at ANY target/controls; non-diagonal
payloads with target < log2(tile amplitudes) (pairs live inside a
tile); controls anywhere.  The engine routes the rest to the XLA
programs.

:func:`make_tq_window` extends the same fusion to a WHOLE gate window:
one dequant, every window op through the shared tile primitives
(ops/pallas_kernels.py), one requant — so a W-op window costs a single
read+write of the codes instead of W (the single-pass sweep the
`fuse.tq.sweeps_saved` counter measures).  Gate payloads and control
masks stay runtime operands; the compile cache is keyed on the window
STRUCTURE (per-op kind/target/controlled), so every QFT sweep at one
width shares one binary.  Tiles no window op dirtied keep their codes
bit-for-bit, same as the per-gate kernels.

Opt-in via QRACK_USE_PALLAS=1 (read by engines/turboquant.py alone;
off by default until validated on a healthy chip); `interpret=True`
runs the identical kernels on CPU for the conformance tests.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dequant_to_planes(c_ref, s_ref, rott_ref, qmax, TB, D):
    y = c_ref[...].astype(jnp.float32) * (s_ref[...] / qmax)[:, None]
    rows = y @ rott_ref[...]
    return rows.reshape(TB, 2, D).transpose(1, 0, 2).reshape(2, TB * D)


def _requant_select(v, active, c_ref, s_ref, rot_ref, oc_ref, os_ref,
                    qmax, cdt, TB, D):
    """Re-rotate + requantize the tile; untouched tiles keep their
    exact codes (bit-for-bit, like the XLA chunk programs)."""
    back = v.reshape(2, TB, D).transpose(1, 0, 2).reshape(TB, 2 * D)
    y2 = back @ rot_ref[...]
    sc = jnp.max(jnp.abs(y2), axis=1)
    safe = jnp.where(sc > 0, sc, 1.0)
    nc = jnp.round(y2 / safe[:, None] * qmax).astype(cdt)
    oc_ref[...] = jnp.where(active, nc, c_ref[...])
    os_ref[...] = jnp.where(active, sc.astype(jnp.float32), s_ref[...])


def _mk_call(kernel, B, D, TB, nblk, cdt, n_scalars, interpret):
    def fn(codes, scales, rot, rot_t, mp, *scalars):
        call = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((B, 2 * D), cdt),
                       jax.ShapeDtypeStruct((B,), jnp.float32)),
            grid=(nblk,),
            in_specs=[
                pl.BlockSpec((TB, 2 * D), lambda i: (i, 0)),
                pl.BlockSpec((TB,), lambda i: (i,)),
                pl.BlockSpec((2 * D, 2 * D), lambda i: (0, 0)),
                pl.BlockSpec((2 * D, 2 * D), lambda i: (0, 0)),
                pl.BlockSpec((2, 2, 2), lambda i: (0, 0, 0)),
            ] + [pl.BlockSpec((1,), lambda i: (0,))] * n_scalars,
            out_specs=(pl.BlockSpec((TB, 2 * D), lambda i: (i, 0)),
                       pl.BlockSpec((TB,), lambda i: (i,))),
            interpret=interpret,
        )
        sc_ops = [jnp.asarray(s, jnp.int32).reshape(1) for s in scalars]
        return call(codes, scales, rot, rot_t,
                    jnp.asarray(mp, jnp.float32), *sc_ops)

    return fn


def make_tq_gate_low(n: int, block_pow: int, bits: int, target: int,
                     tile_pow: int = 18, interpret: bool = False):
    """fn(codes, scales, rot, rot_t, mp, hm, hv, lm, lv) applying one
    generic 2x2 with target < tile_pow; mp is (2, 2, 2) matrix planes,
    masks are runtime scalars split at the TILE boundary."""
    D = 1 << block_pow
    tp = min(tile_pow, n)
    if target >= tp:
        raise ValueError("target above the tile: use the XLA pair path")
    T = 1 << tp
    TB = max(1, T // D)
    B = (1 << n) // D
    nblk = max(1, B // TB)
    qmax = float((1 << (bits - 1)) - 1)
    cdt = jnp.int8 if bits <= 8 else jnp.int16

    def kernel(c_ref, s_ref, rot_ref, rott_ref, mp_ref,
               hm_ref, hv_ref, lm_ref, lv_ref, oc_ref, os_ref):
        blk = pl.program_id(0)
        active = (blk & hm_ref[0]) == hv_ref[0]
        v = _dequant_to_planes(c_ref, s_ref, rott_ref, qmax, TB, D)
        lidx = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)[0]
        sel = (lidx & lm_ref[0]) == lv_ref[0]
        high = T >> (target + 1)
        low = 1 << target
        vv = v.reshape(2, high, 2, low)
        a0r, a1r = vv[0, :, 0, :], vv[0, :, 1, :]
        a0i, a1i = vv[1, :, 0, :], vv[1, :, 1, :]
        mr, mi = mp_ref[0], mp_ref[1]
        n0r = mr[0, 0] * a0r - mi[0, 0] * a0i + mr[0, 1] * a1r - mi[0, 1] * a1i
        n0i = mr[0, 0] * a0i + mi[0, 0] * a0r + mr[0, 1] * a1i + mi[0, 1] * a1r
        n1r = mr[1, 0] * a0r - mi[1, 0] * a0i + mr[1, 1] * a1r - mi[1, 1] * a1i
        n1i = mr[1, 0] * a0i + mi[1, 0] * a0r + mr[1, 1] * a1i + mi[1, 1] * a1r
        new = jnp.stack([
            jnp.stack([n0r, n1r], axis=1),
            jnp.stack([n0i, n1i], axis=1),
        ]).reshape(2, T)
        v = jnp.where(sel, new, v)
        _requant_select(v, active, c_ref, s_ref, rot_ref, oc_ref, os_ref,
                        qmax, cdt, TB, D)

    return _mk_call(kernel, B, D, TB, nblk, cdt, 4, interpret)


def make_tq_diag(n: int, block_pow: int, bits: int,
                 tile_pow: int = 18, interpret: bool = False):
    """fn(codes, scales, rot, rot_t, dp, tm_lo, tb_hi, lm, lv, hm, hv)
    applying a diagonal gate at any target; dp is (2, 2, 2) planes
    holding [[d0, d1], [d0, d1]] factors (reusing the matrix slot:
    dp[0,0,0]=d0.re, dp[0,0,1]=d1.re, dp[1,0,0]=d0.im, dp[1,0,1]=d1.im)."""
    D = 1 << block_pow
    tp = min(tile_pow, n)
    T = 1 << tp
    TB = max(1, T // D)
    B = (1 << n) // D
    nblk = max(1, B // TB)
    qmax = float((1 << (bits - 1)) - 1)
    cdt = jnp.int8 if bits <= 8 else jnp.int16

    def kernel(c_ref, s_ref, rot_ref, rott_ref, dp_ref,
               tml_ref, tbh_ref, lm_ref, lv_ref, hm_ref, hv_ref,
               oc_ref, os_ref):
        blk = pl.program_id(0)
        ok_hi = (blk & hm_ref[0]) == hv_ref[0]
        d0re, d1re = dp_ref[0, 0, 0], dp_ref[0, 0, 1]
        d0im, d1im = dp_ref[1, 0, 0], dp_ref[1, 0, 1]
        v = _dequant_to_planes(c_ref, s_ref, rott_ref, qmax, TB, D)
        lidx = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)[0]
        hi_bit = (blk & tbh_ref[0]) != 0
        bit = ((lidx & tml_ref[0]) != 0) | hi_bit
        fre = jnp.where(bit, d1re, d0re)
        fim = jnp.where(bit, d1im, d0im)
        sel = (lidx & lm_ref[0]) == lv_ref[0]
        one = jnp.ones((), v.dtype)
        zero = jnp.zeros((), v.dtype)
        fre = jnp.where(sel, fre, one)
        fim = jnp.where(sel, fim, zero)
        v = jnp.stack([v[0] * fre - v[1] * fim,
                       v[0] * fim + v[1] * fre])
        # exactness: a tile whose factor is constant 1 keeps its codes
        cf_re = jnp.where(hi_bit, d1re, d0re)
        cf_im = jnp.where(hi_bit, d1im, d0im)
        ident = ((tml_ref[0] == 0) & (lm_ref[0] == 0)
                 & (cf_re == 1.0) & (cf_im == 0.0))
        active = ok_hi & ~ident
        _requant_select(v, active, c_ref, s_ref, rot_ref, oc_ref, os_ref,
                        qmax, cdt, TB, D)

    return _mk_call(kernel, B, D, TB, nblk, cdt, 6, interpret)


def make_tq_window(n: int, block_pow: int, bits: int, structure,
                   tile_pow: int = 18, interpret: bool = False):
    """fn(codes, scales, rot, rot_t, *operands) running a whole fused
    window — ONE dequant, every op, ONE requant — per VMEM tile.

    `structure` is fusion.sharded_structure_of's (kind, target,
    controlled?) tuple and `operands` fusion.per_op_operands' views of
    the sharded layout, split at THIS kernel's tile boundary: cphase
    ops carry a (2,) phase payload (+2 combined-mask scalars when
    controlled), diag a (2, 2) factor table (+4 split-mask scalars),
    gen a (2, 2, 2) matrix-planes payload (+4).  Per-op tile math is
    the shared pallas_kernels primitives, f32 throughout; the dirty
    accumulator mirrors engines/turboquant.py _mk_fuse_window so tiles
    no op acted on (failed high-control tests, identically-1 diagonal
    factors) keep their exact codes."""
    from . import pallas_kernels as pk

    D = 1 << block_pow
    tp = min(tile_pow, n)
    T = 1 << tp
    TB = max(1, T // D)
    B = (1 << n) // D
    nblk = max(1, B // TB)
    qmax = float((1 << (bits - 1)) - 1)
    cdt = jnp.int8 if bits <= 8 else jnp.int16
    lbits = T - 1

    # one ref per view of fusion.per_op_operands(split_at=tile): "f" slots
    # are small float payload arrays, "i" slots int32 mask scalars
    slots = []
    for kind, _target, has_ctrl in structure:
        if kind == "cphase":
            slots.append(("f", (2,)))
            if has_ctrl:
                slots += [("i", (1,))] * 2
        else:
            slots.append(("f", (2, 2) if kind == "diag" else (2, 2, 2)))
            if has_ctrl:
                slots += [("i", (1,))] * 4

    def kernel(*refs):
        c_ref, s_ref, rot_ref, rott_ref = refs[:4]
        op_refs = refs[4:4 + len(slots)]
        oc_ref, os_ref = refs[4 + len(slots):]
        blk = pl.program_id(0)
        v = _dequant_to_planes(c_ref, s_ref, rott_ref, qmax, TB, D)
        lidx = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)[0]
        dirty = jnp.zeros((), jnp.bool_)
        i = 0
        for kind, target, has_ctrl in structure:
            p = op_refs[i][...]
            i += 1
            if kind == "cphase":
                if has_ctrl:
                    clo, chi = op_refs[i][0], op_refs[i + 1][0]
                    i += 2
                else:
                    comb = 1 << target
                    clo, chi = comb & lbits, comb >> tp
                v, hi_ok = pk.tile_cphase(v, lidx, blk, clo, chi,
                                          p[0], p[1])
                dirty = dirty | hi_ok
                continue
            if has_ctrl:
                lo_cm, lo_cv = op_refs[i][0], op_refs[i + 1][0]
                hi_cm, hi_cv = op_refs[i + 2][0], op_refs[i + 3][0]
                i += 4
            else:
                lo_cm = lo_cv = hi_cm = hi_cv = 0
            if kind == "diag":
                v, hi_ok = pk.tile_diag(
                    v, lidx, blk, target, tp,
                    p[0, 0], p[0, 1], p[1, 0], p[1, 1],
                    lo_cm, lo_cv, hi_cm, hi_cv)
                if target >= tp:
                    # whole-tile constant factor: exact-keep tiles whose
                    # factor is identically 1 (make_tq_diag's ident)
                    hi_bit = (blk & (1 << (target - tp))) != 0
                    cf_re = jnp.where(hi_bit, p[1, 0], p[0, 0])
                    cf_im = jnp.where(hi_bit, p[1, 1], p[0, 1])
                    ident = ((lo_cm == 0) & (cf_re == 1.0)
                             & (cf_im == 0.0))
                    dirty = dirty | (hi_ok & ~ident)
                else:
                    dirty = dirty | hi_ok
            else:  # gen: target < tile pow guaranteed by _fuse_admit
                if interpret:  # XLA lowers the body: see pk.tile_partner
                    v = jax.lax.optimization_barrier(v)
                v, hi_ok = pk.tile_local_2x2(v, lidx, blk, target, p,
                                             lo_cm, lo_cv, hi_cm, hi_cv)
                dirty = dirty | hi_ok
        _requant_select(v, dirty, c_ref, s_ref, rot_ref, oc_ref, os_ref,
                        qmax, cdt, TB, D)

    _MAPS = {1: lambda i: (0,), 2: lambda i: (0, 0),
             3: lambda i: (0, 0, 0)}

    def fn(codes, scales, rot, rot_t, *operands):
        in_specs = [
            pl.BlockSpec((TB, 2 * D), lambda i: (i, 0)),
            pl.BlockSpec((TB,), lambda i: (i,)),
            pl.BlockSpec((2 * D, 2 * D), lambda i: (0, 0)),
            pl.BlockSpec((2 * D, 2 * D), lambda i: (0, 0)),
        ]
        packed = []
        for (tag, shape), val in zip(slots, operands):
            in_specs.append(pl.BlockSpec(shape, _MAPS[len(shape)]))
            packed.append(jnp.asarray(val, jnp.float32) if tag == "f"
                          else jnp.asarray(val, jnp.int32).reshape(1))
        call = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((B, 2 * D), cdt),
                       jax.ShapeDtypeStruct((B,), jnp.float32)),
            grid=(nblk,),
            in_specs=in_specs,
            out_specs=(pl.BlockSpec((TB, 2 * D), lambda i: (i, 0)),
                       pl.BlockSpec((TB,), lambda i: (i,))),
            interpret=interpret,
        )
        return call(codes, scales, rot, rot_t, *packed)

    return fn
