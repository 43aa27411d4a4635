"""QEngineTurboQuant: block-compressed dense ket as the RESIDENT form.

Live-runtime counterpart of the reference's StateVectorTurboQuant
(reference: include/statevector_turboquant.hpp — each 2^p-amplitude
block is rotated by a random orthogonal matrix and quantized at b bits;
read/write decompress one block, operate, recompress; get_probs
decompresses block-by-block; serialization stores the seed, not the
matrices).  There it is a storage class under QEngineCPU; here it is an
engine whose amplitudes live in HBM as b-bit integer codes, giving a
4x (int8) or 2x (int16) wider single-device ket than float32 planes.
The sharded composition (parallel/turboquant_pager.py QPagerTurboQuant)
distributes the chunk axis over a pages mesh, so the beyond-HBM width
story multiplies with the beyond-single-chip one.

TPU-first mapping:

* codes (B, 2D) int8/int16 + scales (B,) f32 are the state.  The
  rotation is one shared seed-derived (2D, 2D) matrix, so
  decompress/compress is a batched matmul (128-wide at the default
  p=6) — MXU work, not scalar loops (storage/turboquant.py).
* Gates run CHUNK-WISE: a chunk of blocks is decompressed to f32
  planes, the existing XLA gate kernel applied, and the chunk
  recompressed — the float32 working set is bounded by the chunk size
  no matter the register width (the reference's per-block
  decompress-operate-recompress, scaled to batches the MXU likes).
  Targets above the chunk boundary pair chunks the way QPager pairs
  pages (parallel/pager.py), mixing two decompressed chunks.
* The chunk axis is a `lax.map` dimension INSIDE one cached jitted
  program per gate family: a gate is O(1) dispatches and one in-place
  donated update of the resident code array regardless of chunk count,
  while the loop body keeps the decompressed working set at one (or
  one pair of) chunk(s).  Index math inside the loop is split
  (chunk_id, local_index) int32 pairs — exact past 31 qubits without
  int64, the same scheme as QPager's (page, local) masks.
* Normalization never touches codes: dequantization is linear in the
  per-block scales, so _k_normalize is a pure scale multiply.
* Untouched chunks (failed high-bit control tests) keep their exact
  codes — requantization error accrues only where a gate acted.

Everything the chunked hot path does not cover (ALU permutations,
compose/decompose, amplitude pages) falls back through the `_state`
property, which materializes f32 planes transiently — the analogue of
the reference QPager's CombineAndOp escape hatch.  `peak_transient_amps`
records the largest f32 materialization for memory-honesty tests.
"""

from __future__ import annotations

import math
import os

import numpy as np

import jax
import jax.numpy as jnp

from ..ops import gatekernels as gk
from ..storage import turboquant as tq
from .. import matrices as mat
from .. import telemetry as _tele
from ..telemetry import roofline as _roofline
from .qengine import QEngine
from .tpu import QEngineTPU


# ---------------------------------------------------------------------------
# module-level jitted programs (shape-polymorphic via jit cache)
# ---------------------------------------------------------------------------

# compiled chunked-gate programs, keyed on (kind, layout, gate statics) —
# the same cached-builder discipline as parallel/pager.py's _PROGRAMS,
# but BOUNDED: an LRU with a cap (QRACK_TQ_PROGRAM_CACHE_CAP) so a
# long-lived process stops accumulating compiled programs forever, and
# mesh-derived key parts (QPagerTurboQuant._layout_key) are weakly tied
# to their mesh — entries die with it instead of pinning it.  Hit/miss/
# eviction stats surface as compile.turboquant.* telemetry counters and
# via _PROGRAMS.stats().
_PROGRAMS = _tele.ProgramCache(
    "turboquant", cap_env="QRACK_TQ_PROGRAM_CACHE_CAP", default_cap=256)


def _program(key, builder, site: str = "turboquant.dispatch"):
    # cached-with-the-program resilience wrapper — same discipline as
    # parallel/pager.py's _program (disabled cost: one boolean test)
    from .. import resilience as _res

    return _PROGRAMS.get_or_build(
        key, lambda: _res.instrument_dispatch(site, builder()))


def _dec_rows_f(codes, scales, rot_t, qmax):
    """Decompress codes (B, 2D) -> original-space rows (trace-safe:
    composes inside lax.map bodies as well as under plain jit)."""
    y = codes.astype(jnp.float32) * (scales / qmax)[:, None]
    return y @ rot_t


def _comp_rows_f(rows, rot, qmax, code_dtype):
    """Recompress original-space rows (B, 2D) -> (codes, scales)."""
    y = rows @ rot
    scales = jnp.max(jnp.abs(y), axis=1)
    safe = jnp.where(scales > 0, scales, 1.0)
    codes = jnp.round(y / safe[:, None] * qmax).astype(code_dtype)
    return codes, scales


_j_dec_rows = jax.jit(_dec_rows_f)


from functools import partial


@partial(jax.jit, static_argnums=(3,))
def _j_comp_full(rows, rot, qmax, code_dtype_name):
    return _comp_rows_f(rows, rot, qmax, jnp.dtype(code_dtype_name))


def _rows_to_planes(rows, block: int):
    b = rows.shape[0]
    return rows.reshape(b, 2, block).transpose(1, 0, 2).reshape(2, -1)


def _planes_to_rows(planes, block: int):
    b = planes.shape[-1] // block
    return planes.reshape(2, b, block).transpose(1, 0, 2).reshape(b, 2 * block)


def _pair_mix_f(a, b, mp, lo_cmask, lo_cval):
    """2x2 mix of two decompressed chunks (the cross-chunk gate pair,
    like QPager's half-buffer exchange): new_a = m00*a + m01*b,
    new_b = m10*a + m11*b, applied only where the low control test
    passes."""
    mre, mim = mp[0], mp[1]

    def cm(re_f, im_f, v):
        return jnp.stack([v[0] * re_f - v[1] * im_f,
                          v[0] * im_f + v[1] * re_f])

    na = cm(mre[0, 0], mim[0, 0], a) + cm(mre[0, 1], mim[0, 1], b)
    nb = cm(mre[1, 0], mim[1, 0], a) + cm(mre[1, 1], mim[1, 1], b)
    idx = gk.iota_for(a)
    keep = (idx & lo_cmask) == lo_cval
    return jnp.where(keep, na, a), jnp.where(keep, nb, b)


@jax.jit
def _j_chunk_masses(codes3, scales2, qmax):
    """Per-chunk probability masses WITHOUT decompressing: the block
    rotation is orthogonal, so row norms are invariant and each chunk's
    mass is sum((codes * scale/qmax)^2) — one elementwise reduction
    over the resident int codes, no matmul, no f32 ket."""
    y = codes3.astype(jnp.float32) * (scales2 / qmax)[..., None]
    return jnp.sum(y * y, axis=(1, 2))


# ---------------------------------------------------------------------------
# chunked-gate run bodies, shared by the single-device engine (plain jit,
# cid0=0) and the sharded QPagerTurboQuant (shard_map, cid0=page offset).
# Each returns a pure fn over chunk-major views (C, cb, 2D)/(C, cb); the
# trailing cid0 operand is the GLOBAL id of local chunk 0.
# ---------------------------------------------------------------------------


def _mk_gate_low(ca, block, cdt, qmax, target):
    def run(codes3, scales2, rot, rot_t, mp,
            hi_cmask, hi_cval, lo_cmask, lo_cval, cid0):
        def body(args):
            cid, cc, ss = args
            pl = _rows_to_planes(_dec_rows_f(cc, ss, rot_t, qmax), block)
            out = gk.apply_2x2(pl, mp, ca, target, lo_cmask, lo_cval)
            nc, ns = _comp_rows_f(_planes_to_rows(out, block), rot, qmax, cdt)
            sel = (cid & hi_cmask) == hi_cval
            return jnp.where(sel, nc, cc), jnp.where(sel, ns, ss)

        cids = cid0 + jnp.arange(codes3.shape[0], dtype=gk.IDX_DTYPE)
        return jax.lax.map(body, (cids, codes3, scales2))

    return run


def _mk_gate_pair(ca, block, cdt, qmax, tb_pos):
    """Pair mixing for a target whose chunk bit is LOCAL to the shard
    (tb_pos below the sharded page bits; cid0 is a multiple of the local
    chunk count, so local pair structure equals global)."""

    def run(codes3, scales2, rot, rot_t, mp,
            hi_cmask, hi_cval, lo_cmask, lo_cval, cid0):
        C, cb, twoD = codes3.shape
        lo_n = 1 << tb_pos
        hi_n = C // (2 * lo_n)
        # chunk id bits [hi | pair-bit | lo]: expose the pair axis,
        # map over (hi, lo) pairs
        c5 = (codes3.reshape(hi_n, 2, lo_n, cb, twoD)
              .transpose(1, 0, 2, 3, 4).reshape(2, C // 2, cb, twoD))
        s4 = (scales2.reshape(hi_n, 2, lo_n, cb)
              .transpose(1, 0, 2, 3).reshape(2, C // 2, cb))

        def body(args):
            pid, cca, ccb, ssa, ssb = args
            lpart = pid & (lo_n - 1)
            cid_a = cid0 + (((pid >> tb_pos) << (tb_pos + 1)) | lpart)
            a = _rows_to_planes(_dec_rows_f(cca, ssa, rot_t, qmax), block)
            b = _rows_to_planes(_dec_rows_f(ccb, ssb, rot_t, qmax), block)
            na, nb = _pair_mix_f(a, b, mp, lo_cmask, lo_cval)
            nca, nsa = _comp_rows_f(_planes_to_rows(na, block), rot,
                                    qmax, cdt)
            ncb, nsb = _comp_rows_f(_planes_to_rows(nb, block), rot,
                                    qmax, cdt)
            # controls never sit on the target bit, so the hi test is
            # identical for both pair halves
            sel = (cid_a & hi_cmask) == hi_cval
            return (jnp.where(sel, nca, cca), jnp.where(sel, ncb, ccb),
                    jnp.where(sel, nsa, ssa), jnp.where(sel, nsb, ssb))

        pids = jnp.arange(C // 2, dtype=gk.IDX_DTYPE)
        nca, ncb, nsa, nsb = jax.lax.map(
            body, (pids, c5[0], c5[1], s4[0], s4[1]))
        nc = (jnp.stack([nca, ncb]).reshape(2, hi_n, lo_n, cb, twoD)
              .transpose(1, 0, 2, 3, 4).reshape(C, cb, twoD))
        ns = (jnp.stack([nsa, nsb]).reshape(2, hi_n, lo_n, cb)
              .transpose(1, 0, 2, 3).reshape(C, cb))
        return nc, ns

    return run


def _mk_diag(ca, block, cdt, qmax):
    def run(codes3, scales2, rot, rot_t, d0re, d0im, d1re, d1im,
            tmask_lo, tb_hi, lo_cmask, lo_cval, hi_cmask, hi_cval, cid0):
        def body(args):
            cid, cc, ss = args
            pl = _rows_to_planes(_dec_rows_f(cc, ss, rot_t, qmax), block)
            lidx = gk.iota_for(pl)
            hi_bit = (cid & tb_hi) != 0
            bit = ((lidx & tmask_lo) != 0) | hi_bit
            fre = jnp.where(bit, d1re, d0re)
            fim = jnp.where(bit, d1im, d0im)
            active = (lidx & lo_cmask) == lo_cval
            fre = jnp.where(active, fre, 1.0)
            fim = jnp.where(active, fim, 0.0)
            out = gk.cmul(fre, fim, pl)
            nc, ns = _comp_rows_f(_planes_to_rows(out, block), rot,
                                  qmax, cdt)
            # exactness: a chunk whose factor is constant 1 (target
            # above the chunk selecting a unit diagonal, no low
            # controls) must keep its codes bit-for-bit
            cf_re = jnp.where(hi_bit, d1re, d0re)
            cf_im = jnp.where(hi_bit, d1im, d0im)
            ident = ((tmask_lo == 0) & (lo_cmask == 0)
                     & (cf_re == 1.0) & (cf_im == 0.0))
            sel = ((cid & hi_cmask) == hi_cval) & ~ident
            return jnp.where(sel, nc, cc), jnp.where(sel, ns, ss)

        cids = cid0 + jnp.arange(codes3.shape[0], dtype=gk.IDX_DTYPE)
        return jax.lax.map(body, (cids, codes3, scales2))

    return run


def _mk_fuse_window(ca, block, cdt, qmax, structure):
    """Fused gate window on the compressed ket: ONE decompress -> every
    window op -> ONE recompress per chunk, inside one lax.map program.
    This is where fusion pays double on this engine — each eager gate
    costs a full decompress/recompress round trip AND a requantization;
    a W-op window amortizes both to 1/W.  Payloads/masks are runtime
    operands in the ops/fusion.py sharded layout with the chunk axis
    standing in for the page axis (lo = in-chunk index, hi = chunk id).
    Non-diagonal targets at/above the chunk axis never reach here
    (_fuse_admit routes them to the eager pair-mixing program).  A chunk
    no window op acted on keeps its codes bit-for-bit — same exactness
    contract as the per-gate kernels.

    The per-op tile math lives in ops/pallas_kernels.py's shared tile
    primitives (one implementation for the dense Pallas kernel, the
    pager's per-page kernel body and this decompress->window->recompress
    sweep); only the dirty/ident exact-keep accounting is local."""
    from ..ops import pallas_kernels as pk

    lbits = (1 << ca) - 1

    def run(codes3, scales2, rot, rot_t, cid0, *operands):
        def body(args):
            cid, cc, ss = args
            pl = _rows_to_planes(_dec_rows_f(cc, ss, rot_t, qmax), block)
            lidx = gk.iota_for(pl)
            dirty = jnp.zeros((), jnp.bool_)
            i = 0
            for kind, target, has_ctrl in structure:
                p = operands[i]
                i += 1
                if kind == "cphase":
                    if has_ctrl:
                        clo, chi = operands[i], operands[i + 1]
                        i += 2
                    else:
                        comb = 1 << target
                        clo, chi = comb & lbits, comb >> ca
                    # chi carries the target's high bit too, so hi_ok
                    # is already exact per chunk (factor-1 chunks stay)
                    pl, hi_ok = pk.tile_cphase(pl, lidx, cid, clo, chi,
                                               p[0], p[1])
                    dirty = dirty | hi_ok
                    continue
                if has_ctrl:
                    lo_cm, lo_cv, hi_cm, hi_cv = operands[i:i + 4]
                    i += 4
                else:
                    lo_cm = lo_cv = hi_cm = hi_cv = 0
                if kind == "diag":
                    pl, hi_ok = pk.tile_diag(
                        pl, lidx, cid, target, ca,
                        p[0, 0], p[0, 1], p[1, 0], p[1, 1],
                        lo_cm, lo_cv, hi_cm, hi_cv)
                    if target >= ca:
                        # whole-chunk constant factor: exact-keep chunks
                        # whose factor is identically 1 (_mk_diag ident)
                        hi_bit = (cid & (1 << (target - ca))) != 0
                        cf_re = jnp.where(hi_bit, p[1, 0], p[0, 0])
                        cf_im = jnp.where(hi_bit, p[1, 1], p[0, 1])
                        ident = ((lo_cm == 0) & (cf_re == 1.0)
                                 & (cf_im == 0.0))
                        dirty = dirty | (hi_ok & ~ident)
                    else:
                        dirty = dirty | hi_ok
                else:  # gen: target < ca guaranteed by _fuse_admit
                    # XLA lowers this body: see pk.tile_partner
                    pl, hi_ok = pk.tile_local_2x2(
                        jax.lax.optimization_barrier(pl), lidx, cid, target,
                        p, lo_cm, lo_cv, hi_cm, hi_cv)
                    dirty = dirty | hi_ok
            nc, ns = _comp_rows_f(_planes_to_rows(pl, block), rot,
                                  qmax, cdt)
            return jnp.where(dirty, nc, cc), jnp.where(dirty, ns, ss)

        cids = cid0 + jnp.arange(codes3.shape[0], dtype=gk.IDX_DTYPE)
        return jax.lax.map(body, (cids, codes3, scales2))

    return run


def _mk_phase_split(ca, block, cdt, qmax, body_fn):
    def run(codes3, scales2, rot, rot_t, cid0, *targs):
        def body(args):
            cid, cc, ss = args
            pl = _rows_to_planes(_dec_rows_f(cc, ss, rot_t, qmax), block)
            lidx = gk.iota_for(pl)
            fre, fim = body_fn(jnp, cid, lidx, ca, *targs)
            out = gk.cmul(fre, fim, pl)
            return _comp_rows_f(_planes_to_rows(out, block), rot, qmax, cdt)

        cids = cid0 + jnp.arange(codes3.shape[0], dtype=gk.IDX_DTYPE)
        return jax.lax.map(body, (cids, codes3, scales2))

    return run


def _mk_prob_mask(ca, block, qmax):
    def run(codes3, scales2, rot_t, mask_lo, val_lo, mask_hi, val_hi, cid0):
        def body(args):
            cid, cc, ss = args
            pl = _rows_to_planes(_dec_rows_f(cc, ss, rot_t, qmax), block)
            lidx = gk.iota_for(pl)
            ok = (((lidx & mask_lo) == val_lo)
                  & ((cid & mask_hi) == val_hi))
            p = pl[0] ** 2 + pl[1] ** 2
            return jnp.sum(jnp.where(ok, p, 0.0))

        cids = cid0 + jnp.arange(codes3.shape[0], dtype=gk.IDX_DTYPE)
        return jnp.sum(jax.lax.map(body, (cids, codes3, scales2)))

    return run


def _mk_collapse(ca, block, cdt, qmax):
    def run(codes3, scales2, rot, rot_t, mask_lo, val_lo,
            mask_hi, val_hi, scale, cid0):
        def body(args):
            cid, cc, ss = args
            pl = _rows_to_planes(_dec_rows_f(cc, ss, rot_t, qmax), block)
            lidx = gk.iota_for(pl)
            keep = (((lidx & mask_lo) == val_lo)
                    & ((cid & mask_hi) == val_hi))
            pl = jnp.where(keep, pl * scale, jnp.zeros((), pl.dtype))
            return _comp_rows_f(_planes_to_rows(pl, block), rot, qmax, cdt)

        cids = cid0 + jnp.arange(codes3.shape[0], dtype=gk.IDX_DTYPE)
        return jax.lax.map(body, (cids, codes3, scales2))

    return run


def _mk_collapse_scales():
    def run(scales2, mask_hi, val_hi, scale, cid0):
        cids = cid0 + jnp.arange(scales2.shape[0], dtype=gk.IDX_DTYPE)
        sel = (cids & mask_hi) == val_hi
        return jnp.where(sel[:, None], scales2 * scale,
                         jnp.zeros((), scales2.dtype))

    return run


_ZERO = 0  # cid0 for the single-device engine (weak-typed int32 operand)


class QEngineTurboQuant(QEngineTPU):
    """Dense ket resident as rotated b-bit block codes (lossy)."""

    _tele_name = "turboquant"
    # codes, not planes: the add family keeps the gather over the
    # decompressed ket and the comparator flips their chunked phase pass
    _alu_on_planes = False

    # the chunk and tile window bodies hold no two-target op: the
    # two-qubit gates keep the base engine's routes, not QEngineTPU's
    # funnel into the window
    Swap = QEngine.Swap
    ISwap = QEngine.ISwap
    IISwap = QEngine.IISwap
    Apply4x4 = QEngine.Apply4x4

    def __init__(self, qubit_count: int, init_state: int = 0,
                 bits: int = None, block_pow: int = None,
                 chunk_qb: int = None, seed_rot: int = tq.DEFAULT_SEED,
                 **kwargs):
        self._tq_bits = int(bits if bits is not None
                            else os.environ.get("QRACK_TURBO_BITS",
                                                tq.DEFAULT_BITS))
        bp = int(block_pow if block_pow is not None
                 else os.environ.get("QRACK_TURBO_BLOCK_POW",
                                     tq.DEFAULT_BLOCK_POW))
        self._tq_block_pow = min(bp, self._max_chunk_pow(qubit_count))
        cq = int(chunk_qb if chunk_qb is not None
                 else os.environ.get("QRACK_TURBOQUANT_CHUNK_QB", "20"))
        self._tq_chunk_pow = max(self._tq_block_pow,
                                 min(cq, self._max_chunk_pow(qubit_count)))
        self._tq_seed = seed_rot
        d = 1 << self._tq_block_pow
        self._rot = jnp.asarray(tq.rotation_matrix(2 * d, seed_rot))
        self._rot_t = self._rot.T
        self._qmax = float(tq.qmax(self._tq_bits))
        self._code_np = tq.code_dtype(self._tq_bits)
        self._codes = None
        self._scales = None
        self.peak_transient_amps = 0
        super().__init__(qubit_count, init_state=init_state, **kwargs)

    # ------------------------------------------------------------------
    # compressed <-> planes
    # ------------------------------------------------------------------

    def _max_chunk_pow(self, qubit_count: int) -> int:
        """Largest legal chunk power at this width (the sharded subclass
        subtracts its page bits so every page owns >= 1 chunk)."""
        return qubit_count

    def _compressed_cap(self) -> int:
        """Per-device width ceiling: codes store 4x (int8) / 2x (int16)
        more amplitudes per HBM byte than f32 planes — +2 / +1 qubits
        over the dense cap.  The CHUNKED kernels index with split
        (chunk, local) int32 pairs, so they are not int32-bound past
        the dense limit (ADVICE r4 fix); the dense `_state` fallback IS
        still bound, and its property guard enforces that separately."""
        from .tpu import MAX_DENSE_QB

        return MAX_DENSE_QB + (2 if self._tq_bits <= 8 else 1)

    def _check_capacity(self, qubit_count: int) -> None:
        cap = self._compressed_cap()
        if qubit_count > cap:
            raise MemoryError(
                f"QEngineTurboQuant width {qubit_count} exceeds the "
                f"compressed single-device cap ({cap} at "
                f"{self._tq_bits}-bit codes); use QPagerTurboQuant or "
                "the pager/QUnit layers above this engine")
        # GROWTH (Compose/Allocate on a live engine) routes through the
        # dense f32 fallback plane, which is only sound to MAX_DENSE_QB;
        # fresh construction is codes-native and may use the full cap
        from .tpu import MAX_DENSE_QB

        if (qubit_count > MAX_DENSE_QB
                and getattr(self, "_codes", None) is not None):
            raise MemoryError(
                f"growing a compressed engine past {MAX_DENSE_QB} qubits "
                "requires the dense fallback plane (unsound at that "
                "width); construct at the target width instead")

    @property
    def _block(self) -> int:
        return 1 << self._tq_block_pow

    @property
    def _chunk_amps(self) -> int:
        return 1 << self._tq_chunk_pow

    @property
    def _chunk_blocks(self) -> int:
        return self._chunk_amps // self._block

    def resident_bytes(self) -> int:
        """HBM bytes of the resident representation."""
        if self._codes is None:
            return 0
        return self._codes.nbytes + self._scales.nbytes

    # resident-form access: every read of the code/scale arrays (gate
    # kernels, prob/collapse, Dump, checkpoint capture) flushes the
    # pending gate window first, and a blind write drops it — the same
    # laziness boundary the dense engines put on `_state`
    # (ops/fusion.py).  The `_state` fallback plane inherits the
    # discipline for free: its getter/setter go through these.
    @property
    def _codes(self):
        f = self._fuser
        if f is not None and f.gates and not f._flushing:
            f.flush("read")
        return self._codes_raw

    @_codes.setter
    def _codes(self, v) -> None:
        f = self._fuser
        if f is not None and f.gates and not f._flushing:
            f.drop("overwritten")
        self._codes_raw = v

    @property
    def _scales(self):
        f = self._fuser
        if f is not None and f.gates and not f._flushing:
            f.flush("read")
        return self._scales_raw

    @_scales.setter
    def _scales(self, v) -> None:
        f = self._fuser
        if f is not None and f.gates and not f._flushing:
            f.drop("overwritten")
        self._scales_raw = v

    def _compress_planes(self, planes):
        rows = _planes_to_rows(jnp.asarray(planes, jnp.float32), self._block)
        codes, scales = _j_comp_full(rows, self._rot, self._qmax,
                                     jnp.dtype(self._code_np).name)
        self._codes = codes
        self._scales = scales
        self._note_resident()

    def _note_resident(self) -> None:
        """Resident-footprint gauges: codes+scales bytes vs what the
        same ket would cost as two f32 planes (the compression-ratio
        numerator/denominator telemetry_report's == compression ==
        section reads).  Reads the raw arrays — the public properties
        flush the fuser, which must not fire from bookkeeping."""
        if not _tele._ENABLED:
            return
        codes = getattr(self, "_codes_raw", None)
        if codes is None:
            return
        _tele.gauge("tq.resident.bytes",
                    float(codes.nbytes + self._scales_raw.nbytes))
        _tele.gauge("tq.resident.dense_equiv_bytes",
                    float(8 * (1 << self.qubit_count)))

    def _note_sweeps(self, n: int = 2) -> None:
        """Counted decompress/recompress passes over the resident codes
        (one of each per dispatched program) — the denominator of the
        single-pass fused-window win.  Each pass reads or writes the
        full compressed residency, so the planned bytes also enter the
        roofline ledger (`roofline.tq.sweep.*`) — raw arrays again, the
        public properties would flush the fuser from bookkeeping."""
        if _tele._ENABLED:
            _tele.inc("tq.sweeps", n)
            codes = getattr(self, "_codes_raw", None)
            if codes is not None:
                _roofline.note_bytes(
                    "tq.sweep",
                    float(n) * (codes.nbytes + self._scales_raw.nbytes))

    def _decompress_planes(self):
        rows = _j_dec_rows(self._codes, self._scales, self._rot_t, self._qmax)
        return _rows_to_planes(rows, self._block)

    # the fallback data plane: any inherited kernel that reads/writes
    # `_state` transparently decompresses/recompresses the whole ket
    @property
    def _state(self):
        if self._codes is None:
            return None
        from .tpu import MAX_DENSE_QB

        if self.qubit_count > MAX_DENSE_QB:
            # beyond the dense cap, full f32 planes exceed HBM AND the
            # dense kernels' int32 flat indices — the chunked op set
            # (gates, prob, collapse, measurement, SetPermutation) is
            # the only sound surface at these widths
            raise MemoryError(
                f"this operation needs the dense f32 fallback plane, "
                f"which is unsound past {MAX_DENSE_QB} qubits (width "
                f"{self.qubit_count}): flat int32 indices overflow and "
                "the planes exceed HBM.  At this width the chunked op "
                "set (gates, prob, collapse, measurement, "
                "SetPermutation, amplitude/page reads) is the "
                "supported surface")
        self.peak_transient_amps = max(self.peak_transient_amps,
                                       1 << self.qubit_count)
        return self._decompress_planes()

    @_state.setter
    def _state(self, planes) -> None:
        if planes is None:
            self._codes = None
            self._scales = None
            return
        # width may have changed (compose/decompose/allocate funnel
        # through the fallback): re-derive the block layout from the
        # planes WITHOUT touching qubit_count — QEngine's structure ops
        # adjust it themselves after the kernel, so mutating it here
        # double-counted the width change (round-4 defect caught by the
        # sharded Dispose regression test)
        n_amps = planes.shape[-1]
        n_new = int(round(math.log2(n_amps)))
        from .tpu import MAX_DENSE_QB

        if n_new > MAX_DENSE_QB:
            # belt to the growth guard in _check_capacity: full-width
            # f32 planes past the dense cap are unsound (HBM + int32)
            raise MemoryError(
                f"dense fallback write at width {n_new} is unsound past "
                f"{MAX_DENSE_QB} qubits on the compressed engine")
        max_cp = self._max_chunk_pow(n_new)
        if self._tq_block_pow > max_cp:
            self._tq_block_pow = max_cp
            d = 1 << self._tq_block_pow
            self._rot = jnp.asarray(tq.rotation_matrix(2 * d, self._tq_seed))
            self._rot_t = self._rot.T
        self._tq_chunk_pow = max(self._tq_block_pow,
                                 min(self._tq_chunk_pow, max_cp))
        self._compress_planes(planes)

    # ------------------------------------------------------------------
    # chunk helpers
    # ------------------------------------------------------------------

    def _n_chunks(self) -> int:
        return max(1, (1 << self.qubit_count) // self._chunk_amps)

    def _chunk_slice(self, c: int) -> slice:
        cb = self._chunk_blocks
        return slice(c * cb, (c + 1) * cb)

    def _dec_chunk(self, c: int):
        sl = self._chunk_slice(c)
        rows = _j_dec_rows(self._codes[sl], self._scales[sl],
                           self._rot_t, self._qmax)
        return _rows_to_planes(rows, self._block)

    def _chunk3(self):
        """Chunk-major views of the resident arrays: (C, cb, 2D), (C, cb)."""
        C, cb = self._n_chunks(), self._chunk_blocks
        return (self._codes.reshape(C, cb, -1), self._scales.reshape(C, cb))

    def _store3(self, codes3, scales2) -> None:
        self._codes = codes3.reshape(-1, codes3.shape[-1])
        self._scales = scales2.reshape(-1)
        self._note_resident()

    def _layout_key(self):
        return (self.qubit_count, self._tq_chunk_pow, self._tq_block_pow,
                self._tq_bits)

    def _note_transient(self, n_chunks_live: int) -> None:
        self.peak_transient_amps = max(
            self.peak_transient_amps, n_chunks_live * self._chunk_amps)

    # ------------------------------------------------------------------
    # chunked kernel overrides (the hot path).  Each gate is ONE cached
    # jitted program whose chunk axis is a lax.map dimension: O(1)
    # dispatches and an in-place donated update of the code array, with
    # the decompressed f32 working set still bounded by one (or a pair
    # of) chunk(s).  Chunks whose high-control test fails — or whose
    # diagonal factor is identically 1 — keep their EXACT codes via a
    # per-chunk select, so requantization error accrues only where a
    # gate acted (same exactness contract as the old host loop).
    # ------------------------------------------------------------------

    def _p_gate_low(self, target: int):
        run = _mk_gate_low(self._tq_chunk_pow, self._block, self._code_np,
                           self._qmax, target)

        def build():
            return jax.jit(
                lambda c3, s2, rot, rot_t, mp, hm, hv, lm, lv:
                run(c3, s2, rot, rot_t, mp, hm, hv, lm, lv, _ZERO),
                donate_argnums=(0, 1))

        return _program(("tq_low", self._layout_key(), target), build)

    def _p_gate_pair(self, tb_pos: int):
        run = _mk_gate_pair(self._tq_chunk_pow, self._block, self._code_np,
                            self._qmax, tb_pos)

        def build():
            return jax.jit(
                lambda c3, s2, rot, rot_t, mp, hm, hv, lm, lv:
                run(c3, s2, rot, rot_t, mp, hm, hv, lm, lv, _ZERO),
                donate_argnums=(0, 1))

        return _program(("tq_pair", self._layout_key(), tb_pos), build)

    # opt-in fused Pallas path (ops/pallas_turboquant.py): one HBM
    # read+write of the b-bit CODES per gate.  Single-device only (the
    # sharded subclass keeps the shard_map XLA programs); QRACK_USE_PALLAS
    # is read here and nowhere else.
    _pallas_capable = True
    _PALLAS_TILE_POW = int(os.environ.get("QRACK_PALLAS_TQ_TILE_QB", "18"))

    def _use_pallas(self) -> bool:
        return (self._pallas_capable
                and os.environ.get("QRACK_USE_PALLAS") == "1")

    def _pallas_interpret(self) -> bool:
        return jax.default_backend() != "tpu"

    def _pallas_tile_pow(self) -> int:
        # tile must cover whole blocks (a tile smaller than one code
        # row breaks the kernel's reshapes) and fit the register
        return max(min(self._PALLAS_TILE_POW, self.qubit_count),
                   self._tq_block_pow)

    def _p_pallas_low(self, target: int, tp: int):
        from ..ops import pallas_turboquant as ptq

        def build():
            # donated like every sibling chunk program: without it each
            # gate holds TWO full code arrays in HBM
            return jax.jit(ptq.make_tq_gate_low(
                self.qubit_count, self._tq_block_pow, self._tq_bits,
                target, tile_pow=tp, interpret=self._pallas_interpret()),
                donate_argnums=(0, 1))

        return _program(("tq_pl_low", self._layout_key(), target, tp),
                        build)

    def _p_pallas_diag(self, tp: int):
        from ..ops import pallas_turboquant as ptq

        def build():
            return jax.jit(ptq.make_tq_diag(
                self.qubit_count, self._tq_block_pow, self._tq_bits,
                tile_pow=tp, interpret=self._pallas_interpret()),
                donate_argnums=(0, 1))

        return _program(("tq_pl_diag", self._layout_key(), tp), build)

    def _k_apply_2x2(self, m2, target, controls, perm) -> None:
        self._note_sweeps()
        cmask, cval = self._cmask_cval(controls, perm)
        mp = gk.mtrx_planes(np.asarray(m2, dtype=np.complex128), jnp.float32)
        ca = self._tq_chunk_pow
        cs = self._chunk_amps
        tp = self._pallas_tile_pow()
        if self._use_pallas() and target < tp:
            self._note_transient(1)
            T = 1 << tp
            self._codes, self._scales = self._p_pallas_low(target, tp)(
                self._codes, self._scales, self._rot, self._rot_t, mp,
                cmask >> tp, cval >> tp, cmask & (T - 1), cval & (T - 1))
            return
        if target < ca:
            self._note_transient(1)
            prog = self._p_gate_low(target)
        else:
            self._note_transient(2)
            prog = self._p_gate_pair(target - ca)
        c3, s2 = self._chunk3()
        nc, ns = prog(c3, s2, self._rot, self._rot_t, mp,
                      cmask >> ca, cval >> ca, cmask & (cs - 1),
                      cval & (cs - 1))
        self._store3(nc, ns)

    def _p_diag(self):
        run = _mk_diag(self._tq_chunk_pow, self._block, self._code_np,
                       self._qmax)

        def build():
            return jax.jit(
                lambda c3, s2, rot, rot_t, *sc:
                run(c3, s2, rot, rot_t, *sc, _ZERO),
                donate_argnums=(0, 1))

        return _program(("tq_diag", self._layout_key()), build)

    def _k_apply_diag(self, d0, d1, target, controls, perm) -> None:
        self._note_sweeps()
        cmask, cval = self._cmask_cval(controls, perm)
        ca = self._tq_chunk_pow
        cs = self._chunk_amps
        d0, d1 = complex(d0), complex(d1)
        if self._use_pallas():
            self._note_transient(1)
            tp = self._pallas_tile_pow()
            T = 1 << tp
            dp = np.zeros((2, 2, 2), np.float32)
            dp[0, 0, 0], dp[0, 0, 1] = d0.real, d1.real
            dp[1, 0, 0], dp[1, 0, 1] = d0.imag, d1.imag
            tm_lo = (1 << target) if target < tp else 0
            tb_hi = 0 if target < tp else (1 << (target - tp))
            self._codes, self._scales = self._p_pallas_diag(tp)(
                self._codes, self._scales, self._rot, self._rot_t, dp,
                tm_lo, tb_hi, cmask & (T - 1), cval & (T - 1),
                cmask >> tp, cval >> tp)
            return
        tmask_lo = (1 << target) if target < ca else 0
        tb_hi = 0 if target < ca else (1 << (target - ca))
        self._note_transient(1)
        c3, s2 = self._chunk3()
        nc, ns = self._p_diag()(c3, s2, self._rot, self._rot_t,
                                d0.real, d0.imag, d1.real, d1.imag,
                                tmask_lo, tb_hi, cmask & (cs - 1),
                                cval & (cs - 1), cmask >> ca, cval >> ca)
        self._store3(nc, ns)

    # ------------------------------------------------------------------
    # gate-stream fusion hooks (ops/fusion.py GateStreamFuser)
    # ------------------------------------------------------------------

    def _fuse_admit(self, m, target, controls) -> bool:
        # both backends fuse whole windows into ONE decompress -> ops ->
        # recompress pass now; only cross-boundary non-diagonal targets
        # (pair mixing above the chunk/tile axis) stay per-gate
        if self._use_pallas():
            return mat.is_phase(m) or target < self._pallas_tile_pow()
        return mat.is_phase(m) or target < self._tq_chunk_pow

    def _fuse_tick(self) -> None:
        # the chunked kernels never ticked drift accounting (norm checks
        # would force a full decompress); keep that contract under fusion
        pass

    def _p_fuse_window(self, structure):
        run = _mk_fuse_window(self._tq_chunk_pow, self._block,
                              self._code_np, self._qmax, structure)

        def build():
            return _tele.instrument_jit("fuse.window", jax.jit(
                lambda c3, s2, rot, rot_t, *ops:
                run(c3, s2, rot, rot_t, _ZERO, *ops),
                donate_argnums=(0, 1)))

        return _program(("tq_fusewin", self._layout_key(), structure),
                        build, site="tpu.fuse.flush")

    def _p_pallas_window(self, structure, tp: int):
        from ..ops import pallas_turboquant as ptq

        def build():
            return _tele.instrument_jit("fuse.window", jax.jit(
                ptq.make_tq_window(
                    self.qubit_count, self._tq_block_pow, self._tq_bits,
                    structure, tile_pow=tp,
                    interpret=self._pallas_interpret()),
                donate_argnums=(0, 1)))

        return _program(("tq_pl_fusewin", self._layout_key(), tp,
                         structure), build, site="tpu.fuse.flush")

    def _note_window(self, n_ops: int) -> None:
        """Single-pass window accounting: one decompress + one
        recompress sweep total, where the per-gate path would have paid
        a pair per op — `fuse.tq.sweeps_saved` is the difference."""
        self._note_sweeps()
        if _tele._ENABLED:
            _tele.inc("fuse.tq.windows")
            _tele.inc("fuse.tq.ops", n_ops)
            _tele.inc("fuse.tq.sweeps_saved", 2 * (n_ops - 1))

    def _fuse_flush(self, gates) -> int:
        from ..ops import fusion as fu

        ops = fu.lower_gates(gates)
        if len(ops) == 1:
            # merged down to one op: the per-gate chunk programs already
            # exist and skip the recompress of untouched chunk pairs
            op = ops[0]
            controls, perm = fu.controls_perm(op)
            m = np.asarray(op.m)
            if op.kind in ("cphase", "diag"):
                self._k_apply_diag(m[0, 0], m[1, 1], op.target,
                                   controls, perm)
            else:
                self._k_apply_2x2(m, op.target, controls, perm)
            return 1
        structure = fu.sharded_structure_of(ops)
        if self._use_pallas():
            # single-pass per VMEM tile: masks split at the tile
            # boundary, whole window in-register between dequant/requant
            tp = self._pallas_tile_pow()
            operands = fu.per_op_operands(ops, jnp.float32, split_at=tp)
            self._note_transient(1)
            self._note_window(len(ops))
            prog = self._p_pallas_window(structure, tp)
            self._codes, self._scales = prog(
                self._codes, self._scales, self._rot, self._rot_t,
                *operands)
            self._note_resident()
            return 1
        operands = fu.per_op_operands(ops, jnp.float32,
                                      split_at=self._tq_chunk_pow)
        self._note_transient(1)
        self._note_window(len(ops))
        prog = self._p_fuse_window(structure)
        c3, s2 = self._chunk3()
        nc, ns = prog(c3, s2, self._rot, self._rot_t, *operands)
        self._store3(nc, ns)
        return 1

    def _p_phase_split(self, key, body_fn, n_targs: int):
        run = _mk_phase_split(self._tq_chunk_pow, self._block, self._code_np,
                              self._qmax, body_fn)

        def build():
            return jax.jit(
                lambda c3, s2, rot, rot_t, *targs:
                run(c3, s2, rot, rot_t, _ZERO, *targs),
                donate_argnums=(0, 1))

        if key is None:  # unkeyed generic fn: trace per call
            return build()
        return _program(("tq_phase", self._layout_key(), tuple(key)), build)

    def _k_phase_fn(self, fn, split=None) -> None:
        self._note_sweeps()
        self._note_transient(1)
        if split is not None:
            # split (chunk_id, local_idx) form: exact past 31 qubits,
            # program cached on the op's split key
            key, body, targs = split
            prog = self._p_phase_split(key, body, len(targs))
            c3, s2 = self._chunk3()
            nc, ns = prog(c3, s2, self._rot, self._rot_t,
                          *[jnp.asarray(t) for t in targs])
        else:
            if self.qubit_count > 31:
                raise NotImplementedError(
                    "this diagonal op lacks a split-index form for "
                    ">31-qubit compressed kets (see the `split=` forms "
                    "in engines/qengine.py)")
            cs = self._chunk_amps

            def body(xp, cid, lidx, L):
                return fn(xp, cid * cs + lidx)

            prog = self._p_phase_split(None, body, 0)
            c3, s2 = self._chunk3()
            nc, ns = prog(c3, s2, self._rot, self._rot_t)
        self._store3(nc, ns)

    def _p_prob_mask(self):
        run = _mk_prob_mask(self._tq_chunk_pow, self._block, self._qmax)

        def build():
            return jax.jit(lambda c3, s2, rot_t, ml, vl, mh, vh:
                           run(c3, s2, rot_t, ml, vl, mh, vh, _ZERO))

        return _program(("tq_probmask", self._layout_key()), build)

    @staticmethod
    def _host_scalar(x) -> float:
        """Host value of a (possibly replicated, possibly not fully
        addressable) device scalar — the multi-host-legal read pattern
        (parallel/pager.py _host_read)."""
        if getattr(x, "is_fully_addressable", True):
            return float(np.asarray(x))
        return float(np.asarray(x.addressable_shards[0].data))

    def _k_prob_mask(self, mask, perm) -> float:
        ca, cs = self._tq_chunk_pow, self._chunk_amps
        c3, s2 = self._chunk3()
        total = self._host_scalar(self._p_prob_mask()(
            c3, s2, self._rot_t, mask & (cs - 1), perm & (cs - 1),
            mask >> ca, perm >> ca))
        return min(max(total, 0.0), 1.0)

    def _p_collapse(self):
        run = _mk_collapse(self._tq_chunk_pow, self._block, self._code_np,
                           self._qmax)

        def build():
            return jax.jit(
                lambda c3, s2, rot, rot_t, ml, vl, mh, vh, sc:
                run(c3, s2, rot, rot_t, ml, vl, mh, vh, sc, _ZERO),
                donate_argnums=(0, 1))

        return _program(("tq_collapse", self._layout_key()), build)

    def _p_collapse_scales(self):
        run = _mk_collapse_scales()

        def build():
            return jax.jit(lambda s2, mh, vh, sc: run(s2, mh, vh, sc, _ZERO),
                           donate_argnums=(0,))

        return _program(("tq_collapse_s", self._layout_key()), build)

    def _k_collapse(self, mask, val, nrm_sq) -> None:
        ca, cs = self._tq_chunk_pow, self._chunk_amps
        scale = 1.0 / math.sqrt(nrm_sq)
        c3, s2 = self._chunk3()
        if (mask & (cs - 1)) == 0:
            # chunk-aligned mask: collapse is a pure per-chunk scale
            # update (match -> *scale, else -> 0); codes stay exact and
            # nothing decompresses (the linear-in-scales property again)
            nc, ns = c3, self._p_collapse_scales()(s2, mask >> ca,
                                                   val >> ca, scale)
        else:
            self._note_transient(1)
            nc, ns = self._p_collapse()(c3, s2, self._rot, self._rot_t,
                                        mask & (cs - 1), val & (cs - 1),
                                        mask >> ca, val >> ca, scale)
        self._store3(nc, ns)

    def _k_normalize(self, nrm_sq) -> None:
        # dequantization is linear in scales: normalization never
        # decompresses (see module docstring)
        self._scales = self._scales * (1.0 / math.sqrt(nrm_sq))

    def MAll(self) -> int:
        """Two-stage chunked sampling: categorical over per-chunk
        probability masses (computed WITHOUT decompressing — rotation
        orthogonality preserves norms), then within the drawn chunk —
        never materializes more than one chunk."""
        n_ch = self._n_chunks()
        c3, s2 = self._chunk3()
        masses = self._chunk_masses(c3, s2)
        tot = masses.sum()
        u = self.Rand() * tot
        acc = 0.0
        chosen = n_ch - 1
        for c in range(n_ch):
            acc += masses[c]
            if u <= acc:
                chosen = c
                break
        self._note_transient(1)
        pl = self._dec_chunk(chosen)
        local = int(self._host_scalar(_j_sample_chunk(
            pl, float(self.Rand()))))
        result = chosen * self._chunk_amps + local
        self.SetPermutation(result)
        return result

    def _chunk_masses(self, c3, s2) -> np.ndarray:
        """Host copy of per-chunk masses (sharded subclass overrides
        with an all-gather program so the read is multi-host legal)."""
        return np.asarray(_j_chunk_masses(c3, s2, self._qmax),
                          dtype=np.float64)

    # ------------------------------------------------------------------
    # codes-native initialization: a basis state occupies ONE block, so
    # SetPermutation writes that block's rotated one-hot row directly —
    # no full-width f32 materialization (the inherited dense path would
    # transiently allocate 2^n f32 planes, capping the engine at f32
    # widths and defeating the 4x-wider-ket point; reference: the
    # compressed storage is written in place, statevector_turboquant.hpp)
    # ------------------------------------------------------------------

    def _perm_out_shardings(self):
        """Output placement for the SetPermutation program (sharded
        subclass returns its mesh shardings)."""
        if self._device is not None:
            from jax.sharding import SingleDeviceSharding

            return (SingleDeviceSharding(self._device),) * 2
        return None

    def _p_setperm(self, n_chunks: int, cb: int, twoD: int):
        cdt = self._code_np
        sh = self._perm_out_shardings()

        def build():
            def run(row_codes, scale, cid, bid):
                # two-level (chunk, block-in-chunk) scatter: both
                # indices stay int32 at ANY width (a flat block index
                # would overflow int32 at max pager widths)
                codes = (jnp.zeros((n_chunks, cb, twoD), dtype=cdt)
                         .at[cid, bid].set(row_codes))
                scales = (jnp.zeros((n_chunks, cb), dtype=jnp.float32)
                          .at[cid, bid].set(scale.astype(jnp.float32)))
                return codes.reshape(n_chunks * cb, twoD), scales.reshape(-1)

            kw = {"out_shardings": sh} if sh is not None else {}
            return jax.jit(run, **kw)

        return _program(("tq_setperm", self._layout_key(),
                         getattr(self, "_device_id", -1), n_chunks, cb),
                        build)

    def SetPermutation(self, perm: int, phase=None) -> None:
        ph = self._rand_phase() if phase is None else complex(phase)
        D = self._block
        cs = self._chunk_amps
        cb = self._chunk_blocks
        cid, bid, d = perm // cs, (perm % cs) // D, perm % D
        # rotated one-hot row (re at row-slot d, im at slot D+d), built
        # DEVICE-side from the resident rotation.  The zero-fill +
        # scatter runs inside a jitted program with explicit output
        # shardings, so the codes materialize directly where they live
        # (per-shard on the pager's mesh) — no full-size default-device
        # transient, which at w32+ would alone exceed one chip's HBM.
        row = ph.real * self._rot[d] + ph.imag * self._rot[D + d]
        scale = jnp.max(jnp.abs(row))
        safe = jnp.where(scale > 0, scale, 1.0)
        q = tq.qmax(self._tq_bits)
        row_codes = jnp.round(row / safe * q).astype(self._code_np)
        self._codes, self._scales = self._p_setperm(
            self._n_chunks(), cb, 2 * D)(
            row_codes, scale, jnp.asarray(cid, gk.IDX_DTYPE),
            jnp.asarray(bid, gk.IDX_DTYPE))
        self.running_norm = 1.0

    # ------------------------------------------------------------------
    # block-local reads: one amplitude needs only its own block decoded
    # (the reference's decompress-per-block read access,
    # statevector_turboquant.hpp) — no dense fallback, sound at ANY
    # width, ~2D bytes over the wire
    # ------------------------------------------------------------------

    def _rot_host_np(self) -> np.ndarray:
        cached = getattr(self, "_rot_host", None)
        if cached is None or cached.shape[0] != 2 * self._block:
            cached = np.asarray(self._rot, dtype=np.float32)
            self._rot_host = cached
        return cached

    def _fetch_blocks(self, b0: int, nb: int):
        """Host (codes, scales) for blocks [b0, b0+nb) — the sharded
        subclass overrides with a replicated collective fetch so the
        read stays multi-host legal."""
        return (np.asarray(self._codes[b0:b0 + nb], dtype=np.float32),
                np.asarray(self._scales[b0:b0 + nb], dtype=np.float32))

    def GetAmplitude(self, perm: int) -> complex:
        D = self._block
        b, d = perm // D, perm % D
        codes, scales = self._fetch_blocks(b, 1)
        scale = float(scales[0])
        if scale == 0.0:
            return 0j
        rot = self._rot_host_np()
        y = codes[0] * (scale / self._qmax)
        # decompress just the two needed coordinates: row @ rot.T at
        # columns d (re) and D+d (im) = dot with rot's rows d / D+d
        re = float(y @ rot[d])
        im = float(y @ rot[D + d])
        return complex(re, im)

    def _p_setamp(self):
        sh = self._perm_out_shardings()

        def build():
            def run(codes3, scales2, row, scale, cid, bid):
                # two-level (chunk, block-in-chunk) scatter, like
                # _p_setperm: a flat block index silently wraps int32
                # at max pager widths; output shardings keep the write
                # on-mesh for the sharded subclass
                C, cb, twoD = codes3.shape
                codes3 = codes3.at[cid, bid].set(row)
                scales2 = scales2.at[cid, bid].set(
                    scale.astype(jnp.float32))
                return codes3.reshape(C * cb, twoD), scales2.reshape(-1)

            kw = {"out_shardings": sh} if sh is not None else {}
            return jax.jit(run, donate_argnums=(0, 1), **kw)

        return _program(("tq_setamp", self._layout_key(),
                         getattr(self, "_device_id", -1)), build)

    def SetAmplitude(self, perm: int, amp: complex) -> None:
        """Block-local write: decode the one covered block, poke the
        amplitude, requantize that block only."""
        amp = complex(amp)
        D = self._block
        cs = self._chunk_amps
        b, d = perm // D, perm % D
        cid, bid = perm // cs, (perm % cs) // D
        codes, scales = self._fetch_blocks(b, 1)
        rot = self._rot_host_np()
        vec = (codes[0] * (float(scales[0]) / self._qmax)) @ rot.T
        vec[d] = amp.real
        vec[D + d] = amp.imag
        y = vec @ rot
        scale = float(np.max(np.abs(y)))
        safe = scale if scale > 0 else 1.0
        row = np.round(y / safe * self._qmax).astype(self._code_np)
        c3, s2 = self._chunk3()
        self._codes, self._scales = self._p_setamp()(
            c3, s2, jnp.asarray(row), jnp.float32(scale),
            jnp.asarray(cid, gk.IDX_DTYPE), jnp.asarray(bid, gk.IDX_DTYPE))

    def GetAmplitudePage(self, offset: int, length: int) -> np.ndarray:
        """Block-aligned page read: decode only the covered blocks."""
        D = self._block
        b0 = offset // D
        b1 = (offset + length - 1) // D + 1
        codes, scales = self._fetch_blocks(b0, b1 - b0)
        rot = self._rot_host_np()
        rows = (codes * (scales / self._qmax)[:, None]) @ rot.T
        flat_re = rows[:, :D].reshape(-1)
        flat_im = rows[:, D:].reshape(-1)
        lo = offset - b0 * D
        return (flat_re[lo:lo + length]
                + 1j * flat_im[lo:lo + length]).astype(np.complex128)

    # ------------------------------------------------------------------
    # serialization: seed + scales + codes (reference stores the seed,
    # never the matrices — statevector_turboquant.hpp serialization)
    # ------------------------------------------------------------------

    def SaveTurboQuant(self, path: str) -> None:
        from ..checkpoint.container import save_container

        p = path if str(path).endswith(".npz") else str(path) + ".npz"
        # scalar members mirror the pre-container layout so older
        # readers still load these archives as bare npz
        save_container(p, {"codes": np.asarray(self._codes),
                           "scales": np.asarray(self._scales),
                           "n": np.asarray(self.qubit_count),
                           "bits": np.asarray(self._tq_bits),
                           "block_pow": np.asarray(self._tq_block_pow),
                           "seed": np.asarray(self._tq_seed)},
                       meta={"n": self.qubit_count, "bits": self._tq_bits,
                             "block_pow": self._tq_block_pow,
                             "seed": self._tq_seed},
                       kind="turboquant-codes")

    @classmethod
    def LoadTurboQuant(cls, path: str, **kwargs):
        from ..checkpoint.container import load_container

        p = path if str(path).endswith(".npz") else str(path) + ".npz"
        kind, meta, z = load_container(p, legacy_ok=True)
        if kind is None:  # legacy bare-npz archive (pre-container)
            meta = {k: int(z[k]) for k in ("n", "bits", "block_pow", "seed")}
        eng = cls(int(meta["n"]), bits=int(meta["bits"]),
                  block_pow=int(meta["block_pow"]), seed_rot=int(meta["seed"]),
                  **kwargs)
        eng._ckpt_place(np.asarray(z["codes"], dtype=eng._code_np),
                        np.asarray(z["scales"], dtype=np.float32))
        return eng

    # ------------------------------------------------------------------
    # checkpoint protocol (checkpoint/registry.py)
    # ------------------------------------------------------------------

    _ckpt_kind = "turboquant"

    def _ckpt_place(self, codes: np.ndarray, scales: np.ndarray) -> None:
        """Land host (codes, scales) where this engine keeps them (the
        sharded subclass overrides with its mesh placement)."""
        self._codes = jnp.asarray(codes)
        self._scales = jnp.asarray(scales)

    def _ckpt_capture(self, capture_child):
        return {"kind": self._ckpt_kind,
                "meta": {"n": self.qubit_count, "bits": self._tq_bits,
                         "block_pow": self._tq_block_pow,
                         "chunk_pow": self._tq_chunk_pow,
                         "seed": self._tq_seed,
                         "running_norm": float(self.running_norm)},
                "arrays": {"codes": np.asarray(self._codes),
                           "scales": np.asarray(self._scales)}}

    def _ckpt_restore(self, arrays, meta, children, restore_child):
        if int(meta["n"]) != self.qubit_count:
            raise ValueError("checkpoint width mismatch")
        if (int(meta["bits"]) != self._tq_bits
                or int(meta["block_pow"]) != self._tq_block_pow
                or int(meta["seed"]) != self._tq_seed):
            raise ValueError(
                "turboquant layout mismatch (bits/block_pow/seed)")
        codes = np.asarray(arrays["codes"], dtype=self._code_np)
        if self._codes is not None and codes.shape != tuple(self._codes.shape):
            raise ValueError(
                "turboquant chunk layout mismatch (QRACK_TURBOQUANT_CHUNK_QB "
                "differs from the saving process)")
        self._ckpt_place(codes, np.asarray(arrays["scales"],
                                           dtype=np.float32))
        self.running_norm = float(meta.get("running_norm", 1.0))


@jax.jit
def _j_sample_chunk(planes, u):
    p = planes[0] ** 2 + planes[1] ** 2
    cdf = jnp.cumsum(p)
    idx = jnp.searchsorted(cdf, u * cdf[-1], side="right")
    return jnp.minimum(idx, p.shape[0] - 1)
