"""Parametric single-sweep Pallas window kernels.

A fused gate window (ops/fusion.py) lowers here to ONE Pallas sweep per
*segment*: the ket streams through VMEM tile by tile and every in-tile
window op is applied while the tile is resident, instead of the one
full HBM read+write per gate the XLA op-chain pays.  Matrices, control
masks and phase operands enter as RUNTIME arguments, the two packed
columns of fusion.pack_operands that fusion.window_fn reads too — the
compiled program is keyed by the window's *structure* tuple alone, so
same-structure windows with different rotation angles never retrace
(the property the XLA window path already had).

Vocabulary (everything the fuser emits):

* cphase / diag — ANY target and controls.  The combined/control mask
  splits at runtime inside the kernel into a tile-local part tested
  against the in-tile index and a high part tested against the grid
  block id, so high targets cost one scalar compare per tile.  Two or
  more of them in a row are a RUN, applied as the one diagonal operator
  they are (_apply_run) in ONE pass over the tile.  The ops with no
  high part are multiplied together once a launch into a phase tile in
  VMEM.  An op with a high part is ``1 + 0i`` on a tile whose id does
  not admit it, and on one that does a complex scalar (two, by the
  target's bit, for a diag with its target in the tile) wherever the
  in-tile index matches a mask: its SIGNATURE (fold_signature).  Ops of
  one signature multiply scalar by scalar, so a step folds the ops its
  tile admits into a FOLD SLOT a signature (a sublane of one vreg a
  plane, from operands laid out for the vector unit at the launch's
  first step: a step does no scalar work an op), and its one pass
  multiplies the value by the phase tile and by each used slot's
  factor where the index matches; a run none of whose ops folds
  branches to the one multiply by the phase tile.  Which ops share a
  signature depends on where their controls lie, which the program key
  leaves out: the host plans (run_planner: the first FOLD_SLOTS
  signatures of a run get a slot, an op of a later one keeps a pass of
  its own over the value, on the tiles that admit it) and the kernel
  reads the plan as runtime rows at the tail of ``iv``.  Table, folded
  or passed follows from the runtime masks and the plan, so the program
  key stays the structure.  One that stands alone is an op of a stretch
  (below) like any other.
* inv / gen with target < block_pow — in-tile pair mix: each element
  reads its partner 2^target amplitudes away through two rotations
  (tile_partner); controls anywhere (runtime mask split).
* inv / gen with target >= block_pow — CROSS-TILE: the planner starts a
  new segment led by the op, unless the op joins a bare lead (below),
  and the segment's grid walks an ORBIT at a time.  An orbit is the set
  of tiles the lead mixes, here the two tiles ``{base, base | 1 <<
  (target - block_pow)}`` (orbit_tile); the grid is ``(orbits + 1,
  members)``, the member innermost.  A step moves
  one tile in and one tile out, as an unled step does: step ``(o, j)``
  is handed tile ``j`` of orbit ``o``, casts it to the dense tile
  (below) into one half of a VMEM scratch, and computes tile ``j`` of
  orbit ``o - 1`` from the other half, which that orbit's steps filled:
  its own row of the 2x2 mix over the "bit 0" and "bit 1" tiles.  So a
  led sweep reads the ket once, writes it once and casts one tile in
  and one out a step, evenly, whatever the lead mixes (the read is
  clamped on the added last orbit; the first orbit's steps write blocks
  that the second's then write with what belongs there).  The tile id
  that high-bit masks and the riding in-tile ops read is
  ``orbit_tile(lead bits, o - 1, j)``, not a grid index.
  TWO LEADS A LAUNCH: a cross-tile inv/gen that directly follows a
  cross-tile inv/gen on another qubit with no op behind it yet joins
  that segment as its second lead (plan_window; nothing is reordered,
  a u4 lead neither joins nor is joined, and a third lead opens the
  next segment).  The segment's orbits are the four tiles over both
  targets, its grid and scratch those of a u4 above the tile, and
  member ``j`` computes its tile of ``second . first`` in their order:
  the first lead's row on tile ``j`` and on the tile across the
  second's bit, the second's row over those two (six complex
  multiply-adds an amplitude where two launches make four, and one
  pass over HBM where they make two).  Each lead keeps its own control,
  its high half tested on the id of the tile the lead computes, so a
  lead controlled by the other's target pairs like any other; the
  arithmetic and its order are two launches', the result theirs bit
  for bit but for the sign of a zero (the value between the leads
  stays in VMEM and meets no ``+ 0.0`` cast).  The ops behind the
  second lead ride as behind a single one.
* u4 — the two-target op, a 4x4 on ``target = (lo, hi)``, never
  controlled: each amplitude reads the four members of its (lo, hi)
  quad and applies its own row of the matrix (tile_quad_mix).  Three
  placements.  ``hi < block_pow``: in the tile, both partners by
  tile_partner, in any segment.  ``lo < block_pow <= hi``: it leads a
  segment over the two-tile orbits above, the ``lo`` partner taken
  inside each of the two tiles.  ``lo >= block_pow``: it leads a segment
  whose orbits are the four tiles over both bits.  tile_quad_mix sums a
  quad's members in the order the amplitude meets them, itself first:
  member ``r`` of an orbit reads the scratch's tile ``r ^ x`` for ``x``
  across.

What is NOT the way to one read: a view of the ket that puts the lead's
bit on an axis of its own, ``(2, A, 2, B * 2^block_pow)``, so that one
block holds both tiles.  The planes are ``f32[2, 2^n]`` tiled
``(2, 128)`` on the chip, plane-interleaved every 128 amplitudes; the
view's physical order differs, so XLA pays a copy of the ket (6.5 ms at
w28) per launch for it, and a view with a short minor axis is padded
(32 GiB at w28: PERF.md section 6, PR 25).

``sweeps == len(segments)``: a window with no cross-tile non-diagonal
op is exactly one sweep; each cross-tile op opens one more unless it
joins a bare lead (telemetry counts those as
``fuse.kernel.leads.paired``: sweeps the window does not pay).

The dense tile.  The refs hold a block as ``(2, 2^block_pow)``: two
rows in a vreg's eight sublanes, every vreg a quarter full.  From
``block_pow`` 10 on (dense_tile) the body casts what it loads to
``(2, rows, 128)`` with ``rows = 2^(block_pow - 7)`` and casts back
before the store, so its arithmetic runs on full vregs; the BlockSpecs,
the grid and the custom call's operand shapes are those of the flat
tile.  The in-tile index is ``row * 128 + lane`` and every mask test
reads it unchanged; the pair partner ``i ^ 2^target`` is a lane roll by
``2^target`` for ``target < 7`` and a sublane roll by ``2^(target - 7)``
rows above that (by whole vregs from target 10).  The arithmetic of
each amplitude, and its order, are those of the flat tile: the results
are bit-identical.  Under ``block_pow`` 10 (kets under 1024 amplitudes
a shard; Mosaic wants rows a multiple of 8) the body keeps the flat
tile, the one-axis case of the same tile_* functions.  Telemetry counts
the sweeps that computed dense as ``fuse.kernel.sweeps.dense``.

Stretch, pass, chunk.  A segment's in-tile ops are its runs of
diagonal ops and the STRETCHES between them (segment_pieces): a stretch
is a maximal sequence of ops that are in no run.  A body written on the
whole ``(2, 512, 128)`` tile is emitted operation by operation over the
tile's 64 vreg pairs, the TPU's scheduler keeps that order, and the 128
live vregs go through the one vector-store slot between any two
operations (PERF.md section 6, PR 42).  So on a dense tile of more than
64 rows a stretch is applied in PASSES (stretch_passes), of two kinds.

An op that takes its partner by lane rotation (a non-diagonal op with a
target on bits 0 to 6: rolls_lanes) stays on the whole tile's value,
with the diagonal ops that stand behind it: a lane rotation goes
through the XLU and comes back some hundred cycles later, which the
tile's 64 vreg pairs hide and a chunk's eight cannot (a lane ``gen``
costs 1.6 ms a sweep at w28 on the whole tile and 3.4 ms chunk by
chunk: PERF.md section 6, PR 44).

Every other op, whose partner is a sublane away or in another vreg, is
applied chunk by chunk: the segment's value lives in a VMEM scratch
tile (the one a run's passes work on, 512 KiB at ``block_pow`` 16), a
pass is one rolled loop over the tile's CHUNKS (_for_tile_chunks), and
in its body every op of the pass is applied to the chunk while registers
hold it, one after the other, by the tile_* function it has on a whole
tile, and the chunk is stored once.  A chunk is eight vregs a plane (64
rows; four, 32 rows, in a pass with a u4, whose quad keeps four members
live).  It holds the seven lane bits and the three sublane bits of the
in-tile index and three (two) of the six bits above them, any three: the
targets from bit 10 on of the pass's non-diagonal ops, whose partners
are other vregs, which the chunk takes from wherever those bits put
them; the loop's counter runs over the other bits.  A stretch whose ops
ask for more such bits than a chunk holds is split, in order, into
passes that do not; diagonal ops and controls read the index (``lidx``,
the element's own in the tile whatever rows a chunk holds) and go with
any pass.  The value goes to the scratch ahead of the first run or
chunked pass and comes back to registers where an op wants the whole
tile.

On a dense tile a target's own bit is a matter of which lane, sublane
or vreg (own_bit): a bit below the vreg is read once for all vregs, a
bit from the vreg on picks vregs and costs nothing, and the partner
across it is the other vregs (tile_partner).  Every amplitude's
arithmetic and its order are the same on the whole tile and in a chunk:
only the order in which amplitudes are visited differs.  The flat tile,
and a dense one of at most 64 rows, is one chunk: its stretches are
their ops on the tile's value.  What a loop costs to trace and lower is
part of every set-up, and a line traced inside a loop's body costs
several times what it costs outside one in the benchmark's process: an
op's scalars are read ahead of its pass's loop (_slot_operands), and a
diagonal op that is a segment's only op stays on the whole tile.
Telemetry: ``fuse.kernel.stretches``, ``.stretch.ops``,
``.stretch.passes``, ``fuse.kernel.whole_tile_ops`` (stretch_counts).

Scalar operands ride in two packed SMEM refs (floats and int32 masks),
a (K, 1) column each — TPU SMEM wants 2-D refs — packed on the host
(fusion.pack_operands) at the offsets of _operand_slots.
``interpret=True`` runs the same kernel under the Pallas interpreter
for CPU parity tests; the interpreter re-materializes full buffers per
grid step, so it is a CORRECTNESS harness, not a fast path
(docs/PERFORMANCE.md, "interpret caveat").
"""

from __future__ import annotations

import functools
import itertools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_POW = 16
_LANE_POW = 7  # a vreg is 8 sublanes of 128 lanes
_VREG_POW = _LANE_POW + 3  # the dense tile's index: lane, sublane, vreg

# A segment holds one input block and one output block, each
# double-buffered (2 MiB at block_pow 16), beside the body's
# temporaries and, where an op leads it, two orbits of cast tiles (2 or
# 4 MiB).  On the flat tile a controlled cross-tile gen with five
# cphases behind it asked for 17.04 MiB against the compiler's default
# 16 MiB scoped limit (v5e, w28 over 4 pages, the grid that read a
# partner block a step); on the dense tile the same body, and a 16-op
# one, compile inside 13 MiB (described v5e, PR 29).  The limit stays
# as headroom: the v5e has 128 MiB of VMEM.
_VMEM_LIMIT_BYTES = 32 << 20

# floats each op contributes to the packed scalar vector (dense layout
# order: cphase [f.re,f.im]; diag [d0.re,d0.im,d1.re,d1.im];
# inv [tr.re,tr.im,bl.re,bl.im]; gen mtrx_planes (2,2,2) row-major)
_NFLOATS = {"cphase": 2, "diag": 4, "inv": 4, "gen": 8, "u4": 32}


def segment_compatible(kind: str, target, block_pow: int) -> bool:
    """Can this op join an in-tile segment?  diag/cphase always can
    (high bits resolve against the grid block id); non-diagonal ops
    need their pair partner inside the tile, a u4 both of its partners.
    An incompatible op is NOT an error any more — the planner opens a
    cross-tile segment for it (plan_window), so callers never see the
    old mid-plan ValueError."""
    if kind == "u4":
        return target[1] < block_pow
    return kind in ("cphase", "diag") or target < block_pow


def _pairs_with(cur: dict, slot) -> bool:
    """Does the cross-tile ``slot`` join the segment ``cur`` as its
    second lead?  Where ``cur`` is led by one cross-tile inv/gen on
    another qubit and has no op behind that lead yet: the two mix one
    orbit of four tiles, in their order, in one sweep.  A u4 neither
    joins nor is joined, and the rule stops at two leads.  From the
    structure alone: the program key does not know it."""
    leads = cur["leads"]
    return (slot[1] in ("inv", "gen") and len(leads) == 1 and not cur["ops"]
            and leads[0][1] in ("inv", "gen") and leads[0][2] != slot[2])


def plan_window(structure: Tuple, block_pow: int) -> List[dict]:
    """Split a window structure into single-sweep segments.

    Returns a list of ``{"xgen": slot | None, "leads": (slot, ...),
    "ops": [slot, ...]}`` where each slot is ``(op_index, kind, target,
    has_ctrl)``.  A cross-tile inv/gen (target >= block_pow) or u4
    (hi >= block_pow) leads a segment: the grid walks the lead's orbits
    of two or four tiles and mixes them, then the rest of the segment
    applies in-tile.  ``leads`` are the ops that lead the segment, in
    their order, and ``xgen`` is the first of them: one, or the two
    cross-tile inv/gen of a PAIR, where the second directly follows a
    bare first on another qubit (_pairs_with) and the segment's orbits
    are the four tiles over both targets."""
    segs: List[dict] = []
    cur = {"xgen": None, "leads": (), "ops": []}
    for idx, (kind, target, has_ctrl) in enumerate(structure):
        slot = (idx, kind, target, has_ctrl)
        if segment_compatible(kind, target, block_pow):
            cur["ops"].append(slot)
        elif _pairs_with(cur, slot):
            cur["leads"] += (slot,)
        else:
            if cur["ops"] or cur["leads"]:
                segs.append(cur)
            cur = {"xgen": slot, "leads": (slot,), "ops": []}
    segs.append(cur)
    return segs


def dense_tile(block_pow: int) -> Optional[Tuple[int, int]]:
    """The ``(rows, 128)`` view in which a kernel body sees its tile, or
    None where it keeps the flat one: Mosaic takes the cast of the
    loaded ``(2, block)`` value when rows is a multiple of the eight
    sublanes."""
    if block_pow < _VREG_POW:
        return None
    return (1 << (block_pow - _LANE_POW), 1 << _LANE_POW)


def plan_counts(structure: Tuple, block_pow: int) -> Tuple[int, int, int, int]:
    """``(sweeps, cross, dense, paired)``: the HBM sweeps the kernel
    lowering pays for this window (the XLA window chain pays
    ~len(structure)), how many of them are cross-tile segments led by
    2x2s (a pair of leads is one), how many compute on the dense tile
    (all, where the block has one), and the second leads that joined a
    segment (plan_window): each is a sweep the window does not pay."""
    segs = plan_window(structure, block_pow)
    return (len(segs),
            sum(segment_kernel_name(seg, block_pow) == CROSS_KERNEL_NAME
                for seg in segs),
            len(segs) if dense_tile(block_pow) else 0,
            sum(len(seg["leads"]) - 1 for seg in segs if seg["leads"]))


def segment_kernel_name(seg: dict, block_pow: int) -> str:
    """The name a segment's launch has in a device trace: by what leads
    it, and for an unled one by whether a two-target op rides in it."""
    lead = seg["xgen"]
    if lead is None:
        return (TWOQ_INTILE_KERNEL_NAME
                if any(slot[1] == "u4" for slot in seg["ops"])
                else INTILE_KERNEL_NAME)
    if lead[1] != "u4":
        return CROSS_KERNEL_NAME
    return (TWOQ_QUAD_KERNEL_NAME if lead[2][0] >= block_pow
            else TWOQ_PAIR_KERNEL_NAME)


def twoq_counts(structure: Tuple, block_pow: int) -> dict:
    """A window's two-target ops and the sweeps that carry them:
    ``ops``, and the launches named ``qrack_window_twoq_intile`` /
    ``_pair`` / ``_quad`` as ``sweeps.intile`` / ``.pair`` / ``.quad``
    (the telemetry counters ``fuse.kernel.twoq.*``)."""
    out = {"ops": sum(kind == "u4" for kind, _, _ in structure),
           "sweeps.intile": 0, "sweeps.pair": 0, "sweeps.quad": 0}
    if out["ops"]:
        for seg in plan_window(structure, block_pow):
            name = segment_kernel_name(seg, block_pow)
            if name in _TWOQ_SWEEP_KEY:
                out[_TWOQ_SWEEP_KEY[name]] += 1
    return out


def _nints(kind: str, has_ctrl: bool, split: bool = False) -> int:
    """int32 slots of one op: ``[cmask, cval]`` where it is controlled;
    with ``split`` (fusion's sharded layout, masks split at a shard
    boundary) a cphase holds its combined mask's two halves and a
    diag/gen the four of ``sharded.split_masks``."""
    if not has_ctrl:
        return 0
    return 4 if split and kind != "cphase" else 2


def _operand_slots(structure: Tuple, split: bool = False):
    """``(slots, F, I)``: per-op (float, int) offsets into the packed
    scalar columns ``fv (F, 1)`` / ``iv (I, 1)`` and the columns'
    lengths (``_NFLOATS``, ``_nints``).  ``iv`` keeps one dead slot
    where no op is controlled: a Pallas ref cannot be empty."""
    slots = []
    f = i = 0
    for kind, target, has_ctrl in structure:
        slots.append((f, i))
        f += _NFLOATS[kind]
        i += _nints(kind, has_ctrl, split)
    return slots, f, max(i, 1)


# ---------------------------------------------------------------------------
# shared tile math — pure jnp on VALUES, used by the Pallas kernel body
# below AND by the per-chunk / per-page window bodies (engines/
# turboquant.py _mk_fuse_window, fusion.sharded_window_body) so every
# stack applies window ops through one implementation
# ---------------------------------------------------------------------------

def tile_cphase(v, lidx, hi_id, clo, chi, fre, fim):
    """Combined-mask phase on one tile; returns (planes, hi_ok)."""
    hi_ok = (hi_id & chi) == chi
    hit = ((lidx & clo) == clo) & hi_ok
    one = jnp.ones((), v.dtype)
    zero = jnp.zeros((), v.dtype)
    f_re = jnp.where(hit, fre, one)
    f_im = jnp.where(hit, fim, zero)
    return jnp.stack([v[0] * f_re - v[1] * f_im,
                      v[0] * f_im + v[1] * f_re]), hi_ok


def tile_diag(v, lidx, hi_id, target, L,
              d0re, d0im, d1re, d1im, lm, lv, gm, gv):
    """Diagonal on one (2, 2^L) tile, target anywhere: in-tile targets
    select per element, higher targets per tile via hi_id's bit.  The
    target is a Python int or, for the ops of a run's group, which are
    one traced body (_apply_run), a traced int32."""
    if isinstance(target, int):
        tmask_lo = (1 << target) if target < L else 0
        tb_hi = 0 if target < L else (1 << (target - L))
    else:
        tbit = jnp.int32(1) << target
        tmask_lo, tb_hi = tbit & ((1 << L) - 1), tbit >> L
    hi_bit = (hi_id & tb_hi) != 0
    bit = ((lidx & tmask_lo) != 0) | hi_bit
    fre = jnp.where(bit, d1re, d0re)
    fim = jnp.where(bit, d1im, d0im)
    hi_ok = (hi_id & gm) == gv
    active = ((lidx & lm) == lv) & hi_ok
    one = jnp.ones((), v.dtype)
    zero = jnp.zeros((), v.dtype)
    f_re = jnp.where(active, fre, one)
    f_im = jnp.where(active, fim, zero)
    return jnp.stack([v[0] * f_re - v[1] * f_im,
                      v[0] * f_im + v[1] * f_re]), hi_ok


_TILE_DIAGONAL = {"cphase": tile_cphase, "diag": tile_diag}


def own_bit(lidx, target, at=None):
    """``pick(one, zero)``: for every element of a tile ``one`` where
    the ``target`` bit of its in-tile index ``lidx`` is set and ``zero``
    where it is not; each a scalar or a value of the tile's shape.  It
    is ``jnp.where`` on that bit, written so that the TPU's compiler
    sees what the dense tile makes of it: ``at`` is the bit of the
    value's own index that holds the target (tile_partner's), and a
    value of ``(rows, lanes)`` is whole vregs of eight rows.  A bit
    below the vreg reads the same in every vreg, so it is read from the
    first one's index, repeated: the compare, and every select among
    scalars on it, is then one vreg's work for the whole value and not
    one a vreg (the compiler merges equal operations on equal
    operands).  A bit from the vreg on is the same all through a vreg,
    and which vregs have it set is known here: the pick is those vregs
    of ``one`` and the others of ``zero``, no select at all."""
    at = target if at is None else at
    rows = lidx.shape[0] if lidx.ndim == 2 else 0
    if rows > 8 and at >= _VREG_POW:
        half = 1 << (at - _LANE_POW)  # rows that share the bit

        def pick(one, zero):
            # a scalar fills its vregs, a value gives its own
            sides = [side if jnp.ndim(side) else
                     jnp.full((half, lidx.shape[1]), side) for side in (zero, one)]
            return jnp.concatenate([
                side if side.shape[0] == half else
                jax.lax.slice_in_dim(side, r, r + half)
                for r in range(0, rows, half)
                for side in [sides[(r // half) & 1]]])

        return pick
    if rows > 8:
        lidx = jnp.concatenate([jax.lax.slice_in_dim(lidx, 0, 8)] * (rows // 8))
    bit = (lidx & (1 << target)) != 0
    return lambda one, zero: jnp.where(bit, one, zero)


def tile_partner(v, lidx, target, at=None):
    """``v[:, i ^ (1 << target)]`` on one tile, as two rotations and a
    select on the target bit.  The tile is ``v.shape[1:]``: flat
    ``(block,)``, or ``(rows, lanes)`` with index ``row * lanes + lane``.
    A target below the lane power is a lane roll by ``2^target``; one
    above it a sublane roll by ``2^(target - lane power)`` rows; from
    eight rows on the partner is another vreg, and the value's vregs are
    taken in the partners' order, which costs nothing.  The flat tile is
    the case of one axis: every in-tile target is a lane roll.

    ``v`` may be a chunk of the tile whose rows are not consecutive
    there (_for_tile_chunks): ``at`` is then the bit of the chunk's own
    index that holds the target, which the rotations go by, while the
    select reads ``lidx``, the index in the tile, as every mask does.

    The flat tile stays flat: Mosaic refuses the (2, high, 2, low) view
    for low < 128 lanes, and XLA pads it 128/low-fold.

    Mosaic emits the rotations as written.  Where XLA lowers this code
    instead (the interpreter; a chunk body outside any kernel) the
    caller puts an optimization barrier between ops, or XLA fuses a run
    of k ops by recomputing each input at every read, 3^k-fold."""
    at = target if at is None else at
    lane_pow = (v.shape[-1] - 1).bit_length()
    if at < lane_pow:
        axis, dist = v.ndim - 1, 1 << at
    else:
        axis, dist = v.ndim - 2, 1 << (at - lane_pow)
    if v.ndim == 3 and dist >= 8 and axis == 1:
        return jnp.concatenate(
            [jax.lax.slice_in_dim(v, r ^ dist, (r ^ dist) + dist, axis=1)
             for r in range(0, v.shape[1], dist)], axis=1)
    down = pltpu.roll(v, dist, axis)                   # v[i - 2^target]
    up = pltpu.roll(v, v.shape[axis] - dist, axis)     # v[i + 2^target]
    return own_bit(lidx, target, at)(down, up)


def tile_local_2x2(v, lidx, hi_id, target, mp, lm, lv, gm, gv, at=None):
    """Generic 2x2 with the pair inside the tile (target < tile pow);
    mp indexes like mtrx_planes (2, 2, 2) [plane, row, col] but may be
    a nested list of traced scalars.  ``at``: tile_partner's."""
    o = tile_partner(v, lidx, target, at)
    pick = own_bit(lidx, target, at)
    # my own row of the matrix: (diagonal, off-diagonal) entry
    dre = pick(mp[0][1][1], mp[0][0][0])
    dim = pick(mp[1][1][1], mp[1][0][0])
    ore = pick(mp[0][1][0], mp[0][0][1])
    oim = pick(mp[1][1][0], mp[1][0][1])
    nv = jnp.stack([dre * v[0] - dim * v[1] + ore * o[0] - oim * o[1],
                    dre * v[1] + dim * v[0] + ore * o[1] + oim * o[0]])
    hi_ok = (hi_id & gm) == gv
    sel = ((lidx & lm) == lv) & hi_ok
    return jnp.where(sel, nv, v), hi_ok


def tile_quad_mix(members, b1, b2, mp):
    """Every amplitude's own row of a 4x4 over the four members of its
    quad.  ``members[x2][x1]`` is the value ``(2, *tile)`` across
    ``(x2, x1)`` from the amplitude (``[0][0]`` itself); ``b1`` / ``b2``
    pick by its own low / high target bit (own_bit's ``pick(one,
    zero)``; for a bit that is one scalar for the tile, ``jnp.where`` on
    it); ``mp[plane][row][col]`` are the matrix's scalars, row and
    column ``(bit hi << 1) | bit lo``.  The coefficient of the member
    across ``x`` is ``m[r, r ^ x]`` with ``r`` the amplitude's own row
    (gatekernels.apply_4x4's arithmetic, in its order)."""
    def own(plane, x):
        at = [mp[plane][r][r ^ x] for r in range(4)]
        return b2(b1(at[3], at[2]), b1(at[1], at[0]))

    re = im = None
    for x2 in (0, 1):
        for x1 in (0, 1):
            v = members[x2][x1]
            cre, cim = own(0, (x2 << 1) | x1), own(1, (x2 << 1) | x1)
            tre = v[0] * cre - v[1] * cim
            tim = v[0] * cim + v[1] * cre
            re = tre if re is None else re + tre
            im = tim if im is None else im + tim
    return jnp.stack([re, im])


def tile_local_4x4(v, lidx, lo, hi, mp, at=(None, None)):
    """The two-target op with both partners inside the tile; ``at``:
    tile_partner's, for ``lo`` and for ``hi``."""
    p1 = tile_partner(v, lidx, lo, at[0])
    members = ((v, p1), (tile_partner(v, lidx, hi, at[1]),
                         tile_partner(p1, lidx, hi, at[1])))
    return tile_quad_mix(members, own_bit(lidx, lo, at[0]),
                         own_bit(lidx, hi, at[1]), mp)


def tile_local_invert(v, lidx, hi_id, target,
                      trre, trim, blre, blim, lm, lv, gm, gv, at=None):
    """Anti-diagonal 2x2 (X/Y-like) with the pair inside the tile;
    ``at``: tile_partner's."""
    o = tile_partner(v, lidx, target, at)
    pick = own_bit(lidx, target, at)
    fre = pick(blre, trre)
    fim = pick(blim, trim)
    nv = jnp.stack([fre * o[0] - fim * o[1], fre * o[1] + fim * o[0]])
    hi_ok = (hi_id & gm) == gv
    sel = ((lidx & lm) == lv) & hi_ok
    return jnp.where(sel, nv, v), hi_ok


# ---------------------------------------------------------------------------
# the Pallas window program (dense single-shard layout)
# ---------------------------------------------------------------------------

# the names a device trace knows the two launches by (PERF.md section 3):
# name= is the HLO instruction's name where locations carry the name
# stack, metadata= rides the custom call's frontend attributes always
INTILE_KERNEL_NAME = "qrack_window_intile"
CROSS_KERNEL_NAME = "qrack_window_cross"
# the launches that carry a two-target op: an unled segment with one
# in its tile, and the segments one leads over orbits of two and four tiles
TWOQ_INTILE_KERNEL_NAME = "qrack_window_twoq_intile"
TWOQ_PAIR_KERNEL_NAME = "qrack_window_twoq_pair"
TWOQ_QUAD_KERNEL_NAME = "qrack_window_twoq_quad"
_TWOQ_SWEEP_KEY = {TWOQ_INTILE_KERNEL_NAME: "sweeps.intile",
                   TWOQ_PAIR_KERNEL_NAME: "sweeps.pair",
                   TWOQ_QUAD_KERNEL_NAME: "sweeps.quad"}

_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)
# packed scalar operands: SMEM on the TPU, honoured by the interpreter
_SCALAR_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _tile_index(tile: Tuple[int, ...]):
    """The in-tile amplitude index of every element of a ``(block,)`` or
    ``(rows, lanes)`` tile, int32: ``row * lanes + lane``.  Built from a
    2-D iota either way: the TPU has no 1-D one."""
    shape = (1,) * (2 - len(tile)) + tuple(tile)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if len(tile) == 1:
        return lane[0]
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1] + lane


def _static_pick(values, at):
    """``values[at]`` of a static list of ints under 256 at a traced
    index: four to a word, so that the pick is a select among the words
    and a shift, not a select an entry."""
    words = [sum(v << (8 * j) for j, v in enumerate(values[w:w + 4]))
             for w in range(0, len(values), 4)]
    word = jnp.int32(words[0])
    for later, value in enumerate(words[1:], 1):
        word = jnp.where(at >> 2 == later, jnp.int32(value), word)
    return (word >> ((at & 3) << 3)) & 255


def _slot_masks(slot, slots, iv_ref, at=0):
    """An op's runtime ``(cmask, cval)``, zeros where it is uncontrolled;
    with ``at``, those of the op that many alike slots further on."""
    idx, kind, _, has_ctrl = slot
    if not has_ctrl:
        return jnp.int32(0), jnp.int32(0)
    ioff = slots[idx][1] + at * _nints(kind, True)
    return iv_ref[ioff, 0], iv_ref[ioff + 1, 0]


def _diag_operands(group, at, slots, iv_ref, fv_ref, bp):
    """The arguments of tile_cphase / tile_diag after ``(v, lidx,
    hi_id)`` for the ``at``-th slot of ``group``: consecutive slots of
    one kind alike in having controls (_run_groups), whose operands lie
    a fixed stride apart in both columns, so that ``at`` may be a loop's
    traced index (_apply_run); the target is then picked at that index
    too (_static_pick), and is the slot's own Python int where ``at``
    is one.  Masks are runtime scalars; the lo/hi split happens here
    (dense widths are int32-safe: engines/tpu.py MAX_DENSE_QB)."""
    idx, kind, _, _ = group[0]
    foff = slots[idx][0] + at * _NFLOATS[kind]
    lbits = (1 << bp) - 1
    cm, cv = _slot_masks(group[0], slots, iv_ref, at)
    targets = [slot[2] for slot in group]
    target = (targets[at] if isinstance(at, int)
              else _static_pick(targets, at))
    if kind == "cphase":
        tbit = (jnp.int32(1 << target) if isinstance(at, int)
                else jnp.int32(1) << target)
        comb = tbit | cm
        return (comb & lbits, comb >> bp,
                fv_ref[foff, 0], fv_ref[foff + 1, 0])
    return (target, bp, fv_ref[foff, 0], fv_ref[foff + 1, 0],
            fv_ref[foff + 2, 0], fv_ref[foff + 3, 0],
            cm & lbits, cv & lbits, cm >> bp, cv >> bp)


def _slot_operands(slot, slots, iv_ref, fv_ref, bp):
    """What one in-tile op's tile_* function takes after ``(v, lidx,
    hi_id)``: its static target, its scalars read from the two columns
    and its masks split at the tile's edge.  Read outside any loop: a
    pass reads its ops' operands ahead of its loop over chunks (a read
    of a scalar ref traced inside a loop's body costs seven times what
    it costs outside one: PERF.md section 6, PR 44)."""
    idx, kind, target, _ = slot
    foff = slots[idx][0]
    lbits = (1 << bp) - 1
    if kind in _TILE_DIAGONAL:
        return _diag_operands([slot], 0, slots, iv_ref, fv_ref, bp)
    if kind == "u4":
        return (*target, _u4_scalars(fv_ref, foff))
    cm, cv = _slot_masks(slot, slots, iv_ref)
    masks = (cm & lbits, cv & lbits, cm >> bp, cv >> bp)
    if kind == "inv":
        return (target, fv_ref[foff, 0], fv_ref[foff + 1, 0],
                fv_ref[foff + 2, 0], fv_ref[foff + 3, 0], *masks)
    mp = [[[fv_ref[foff + 4 * plane + 2 * row + col, 0]
            for col in range(2)]
           for row in range(2)]
          for plane in range(2)]
    return (target, mp, *masks)


def _apply_slot(v, lidx, blk, slot, operands, held=()):
    """One in-tile window op on ``v``, from its _slot_operands: the
    tile's value, or a chunk of it that holds the bits ``held`` of the
    tile's index above the vreg (_for_tile_chunks) and with them the
    op's partners.  ``lidx`` is the in-tile index of every element of
    ``v``: the masks and the target's own bit are read there, whatever
    rows ``v`` holds."""
    _, kind, target, _ = slot
    if kind in _TILE_DIAGONAL:
        return _TILE_DIAGONAL[kind](v, lidx, blk, *operands)[0]

    def at(t):
        """tile_partner's ``at``: where a chunk holds the bit ``t``."""
        return _VREG_POW + held.index(t) if t in held else None

    if kind == "u4":
        return tile_local_4x4(v, lidx, *operands, at=tuple(map(at, target)))
    apply = tile_local_invert if kind == "inv" else tile_local_2x2
    return apply(v, lidx, blk, *operands, at=at(target))[0]


def diag_runs(ops) -> List[Tuple[int, int]]:
    """``[(start, stop), ...]``: the maximal runs of two or more
    consecutive diagonal ops (cphase, diag) among a segment's in-tile
    slots.  Diagonal ops commute, so a run is one diagonal operator and
    the body applies it as one (_apply_run); a diagonal op that stands
    alone is an op of a stretch (segment_pieces)."""
    runs = []
    start = None
    for at, slot in enumerate(list(ops) + [None]):
        if slot is not None and slot[1] in _TILE_DIAGONAL:
            if start is None:
                start = at
            continue
        if start is not None and at - start >= 2:
            runs.append((start, at))
        start = None
    return runs


def in_tile(kind: str, target: int, cmask: int, cval: int, bp: int) -> bool:
    """Do all the bits a diagonal op reads lie inside the tile?  Then
    its factor is the same on every tile of a launch, and a run folds it
    into its phase tile.  On Python ints, for the host's counters; the
    kernel decides the same from its runtime masks (_apply_run)."""
    return fold_signature(kind, target, cmask, cval, bp) is None


# the in-tile signatures a run folds its high-part ops by: a QFT run
# needs one (the cphase behind a gen share that gen's qubit, so its
# bit where it lies in the tile and the empty mask where it does not),
# the Trotter step's three (the bonds above the tile share the empty
# mask, the bond across the tile's edge is one for either value of its
# control).  An op of a fourth signature keeps a pass of its own
FOLD_SLOTS = 3
# a run's rows at the tail of ``iv``, _PLAN_HEAD of them and one an op:
# (mask, value, pick) a slot; how many of its ops fold, and how many
# with a high part fold into no slot and keep a pass of their own; then
# a row an op of the run: its slot id (FOLD_SLOTS: the op folds into
# none)
_SLOT_ROWS = 3
_PLAN_HEAD = _SLOT_ROWS * FOLD_SLOTS + 2
# the sublanes of the accumulators' vreg that hold an op's first entry,
# a slot each and the sink; as many more hold its second
_FOLD_ROWS = 1 << FOLD_SLOTS.bit_length()


def fold_signature(kind: str, target: int, cmask: int, cval: int, bp: int):
    """``(mask, value, pick)`` of a diagonal op that reads a bit above
    the tile, None for one that reads none (in_tile).  On one tile the
    op's factor is ``1 + 0i`` unless the tile id admits it, else a
    function of the in-tile index ``lidx`` alone: one complex scalar
    where ``(lidx & mask) == value`` (a cphase's phase; a diag's entry
    by the tile id's bit, its target above the tile: ``pick`` 0), or
    for a diag with its target in the tile and a control above, its
    two entries by the target's bit of ``lidx`` (``pick`` that bit's
    mask).  Ops of one signature multiply scalar by scalar (_apply_run).
    On Python ints, the masks as the kernel reads them."""
    lbits = (1 << bp) - 1
    if kind == "cphase":
        comb = (1 << target) | cmask
        return (comb & lbits, comb & lbits, 0) if comb >> bp else None
    if target >= bp:
        return (cmask & lbits, cval & lbits, 0)
    if (cmask | cval) >> bp:
        return (cmask & lbits, cval & lbits, 1 << target)
    return None


def _may_fold(run, bp: int) -> int:
    """How many of a run's slots may fold whatever their masks hold: all
    but a diag in the tile with no control, which has no high part."""
    return sum(kind == "cphase" or target >= bp or has_ctrl
               for _, kind, target, has_ctrl in run)


def window_runs(structure: Tuple, bp: int) -> List[list]:
    """The runs of diagonal ops of a window's segments (plan_window,
    diag_runs), each the list of its slots, in the window's order."""
    return [seg["ops"][start:stop] for seg in plan_window(structure, bp)
            for start, stop in diag_runs(seg["ops"])]


def run_planner(run, masks, bp: int) -> Tuple[List[int], int]:
    """``(rows, folded)``: one run's plan as the kernel reads it from
    the tail of ``iv``, and how many of its ops it folds.

    Which ops of a run share a signature depends on where their
    controls lie, which the structure, and so the program, does not
    know: the host plans from the masks it packs (``masks[idx]`` the
    ``(cmask, cval)`` of the slot with op index ``idx``, as the kernel
    reads them) and the kernel reads the plan as runtime rows.  The
    first FOLD_SLOTS signatures in op order get a FOLD SLOT each:
    ``(mask, value, pick)``, ``(0, -1, 0)`` for a slot no op uses (no
    index has the value -1).  An op that picks nothing goes with the
    ops of its mask and value that pick by some bit, and they with it:
    its scalar is both of their two (the pager's op on a page bit
    reaches its kernel as a diag on qubit 0 with two equal entries).
    Then how many ops fold and how many keep a pass (a step that has
    none of the second loops over the run's ops at the launch's first
    step alone), and a row an op: its slot, or FOLD_SLOTS for an op
    that folds into none, one with no high part (it goes to the phase
    tile) or of a later signature (it keeps its pass)."""
    folds: List[List[int]] = []
    ids, passed = [], 0
    for idx, kind, target, _ in run:
        sig = fold_signature(kind, target, *masks[idx], bp)
        at = FOLD_SLOTS
        if sig is not None:
            at = next((s for s, fold in enumerate(folds)
                       if fold[:2] == list(sig[:2])
                       and (fold[2] == sig[2] or not (fold[2] and sig[2]))),
                      len(folds))
            if at == len(folds) and at < FOLD_SLOTS:
                folds.append(list(sig))
            if at < FOLD_SLOTS:
                folds[at][2] |= sig[2]
            passed += at == FOLD_SLOTS
        ids.append(at)
    folded = sum(at < FOLD_SLOTS for at in ids)
    rows = [row for fold in folds for row in fold]
    rows += [0, -1, 0] * (FOLD_SLOTS - len(folds))
    return rows + [folded, passed] + ids, folded


def _run_plan_slots(structure: Tuple, bp: int) -> Tuple[dict, int]:
    """``({op index of a run's first slot: offset of its plan's rows in
    iv}, I)``: the runs' plans (run_planner) lie behind the ops' masks
    (_operand_slots), in the window's order, and ``I`` is the column's
    length with them."""
    _, _, at = _operand_slots(structure)
    offsets = {}
    for run in window_runs(structure, bp):
        offsets[run[0][0]] = at
        at += _PLAN_HEAD + len(run)
    return offsets, at


def run_plan_len(structure: Tuple, bp: int) -> int:
    """The rows a window's runs' plans take behind its masks in ``iv``."""
    return _run_plan_slots(structure, bp)[1] - _operand_slots(structure)[2]


def diag_run_counts(structure: Tuple, masks,
                    bp: int) -> Tuple[int, int, int, int]:
    """``(runs, ops, tile_ops, folded_ops)``: the runs of diagonal ops
    the kernel lowers to a phase tile for this window, the ops inside
    them, those of them whose factor goes into the tile (in_tile) and
    those with a high part that fold into a slot's scalars
    (run_planner); the rest keep a pass each.  ``masks`` are the ops'
    ``(cmask, cval)`` as the kernel reads them: the telemetry counters
    ``fuse.kernel.diag_runs``, ``.diag_run.ops``, ``.diag_run.tile_ops``
    and ``.diag_run.folded_ops``."""
    runs = window_runs(structure, bp)
    return (len(runs), sum(map(len, runs)),
            sum(in_tile(kind, target, *masks[idx], bp)
                for run in runs for idx, kind, target, _ in run),
            sum(run_planner(run, masks, bp)[1] for run in runs))


# rows of the dense tile a step of a pass takes: eight vreg pairs of the
# value (a run's passes, and a stretch's with nothing but 2x2 ops), and
# four where the pass holds a u4, which keeps its quad's four members
# live (on 64 rows they alone are the 64 vregs the core has)
_RUN_ROWS = 64
_U4_ROWS = 32


def chunked(tile: Tuple[int, ...]) -> bool:
    """Does a pass over this tile take it in more than one chunk?  A
    flat tile and a dense one of at most _RUN_ROWS rows are one chunk:
    their ops outside a run are applied on the tile's value, one after
    the other."""
    return len(tile) == 2 and tile[0] > _RUN_ROWS


def _partner_vregs(slot) -> Tuple[int, ...]:
    """The targets of a slot whose partner is another vreg of the dense
    tile (bits from _VREG_POW on): the bits a chunk has to hold for the
    op to find its partners in it.  A diagonal op has no partner."""
    _, kind, target, _ = slot
    if kind in _TILE_DIAGONAL:
        return ()
    return tuple(t for t in (target if kind == "u4" else (target,))
                 if t >= _VREG_POW)


def rolls_lanes(slot) -> bool:
    """Does the op take a partner by lane rotation (a non-diagonal op
    with a target below the lane power)?  A lane rotation goes through
    the XLU and comes back some hundred cycles later: a chunk's few vreg
    pairs cannot hide that, the tile's 64 can, so such an op is applied
    on the whole tile's value (3.4 ms a lane ``gen`` a sweep at w28 in a
    pass of 64-row chunks, the same in chunks of 32; 1.6 ms on the whole
    tile: PERF.md section 6, PR 44)."""
    _, kind, target, _ = slot
    return kind not in _TILE_DIAGONAL and any(
        t < _LANE_POW for t in (target if kind == "u4" else (target,)))


def stretch_passes(stretch, tile: Tuple[int, ...]) -> List[Tuple[list, tuple]]:
    """``[(slots, held), ...]``: a stretch of in-tile slots split, in
    their order, into the passes that apply it on a chunked dense tile.

    ``held`` None: the slots are applied on the whole tile's value, one
    after the other.  Those are the ops that roll lanes (rolls_lanes),
    with the diagonal ops that stand behind one of them (or ahead of the
    stretch's first), and a diagonal op that is a segment's only op.

    ``held`` a tuple: the slots are one loop over chunks; every op of
    the pass is applied to a chunk while registers hold it, so the chunk
    has to hold every op's partners.  A chunk of ``2^c`` vregs a plane
    holds the seven lane bits, the three sublane bits and ``c`` bits of
    the tile's index above them, any ``c``: its vregs are taken where
    those bits say (_for_tile_chunks).  ``held`` are those bits,
    ascending: the targets from _VREG_POW on of the pass's non-diagonal
    ops, filled up with the lowest bits left.  The split is greedy: a
    pass ends ahead of the op that would ask for more bits than its
    chunk has (three on _RUN_ROWS rows; two on the _U4_ROWS a pass with
    a u4 takes).  Diagonal ops, and any op's controls, read the index
    and go with any pass."""
    top = (tile[0] * tile[1] - 1).bit_length()

    def room(slots):
        rows = _U4_ROWS if any(s[1] == "u4" for s in slots) else _RUN_ROWS
        return (rows >> 3).bit_length() - 1

    def a_pass(slots, want):
        spare = [t for t in range(_VREG_POW, top) if t not in want]
        return slots, tuple(sorted(
            want | set(spare[:room(slots) - len(want)])))

    # whole or in chunks: a diagonal op goes as the op ahead of it does,
    # the stretch's first ones as the first op that is not diagonal; one
    # that is all the stretch (its segment's only op, or it would be in a
    # run) stays whole: in chunks it gains a tenth of a millisecond a
    # sweep and costs a loop to trace and lower
    whole = [rolls_lanes(s) if s[1] not in _TILE_DIAGONAL else None
             for s in stretch]
    known = [w for w in whole if w is not None]
    last = known[0] if known else True
    for at, w in enumerate(whole):
        whole[at] = last = last if w is None else w

    passes = []
    for wide, group in itertools.groupby(zip(whole, stretch), lambda p: p[0]):
        group = [slot for _, slot in group]
        if wide:
            passes.append((group, None))
            continue
        cur, want = [], set()
        for slot in group:
            more = want | set(_partner_vregs(slot))
            if cur and len(more) > room(cur + [slot]):
                passes.append(a_pass(cur, want))
                cur, more = [], set(_partner_vregs(slot))
            cur, want = cur + [slot], more
        passes.append(a_pass(cur, want))
    return passes


def segment_pieces(ops, tile: Tuple[int, ...]) -> List[Tuple[str, list, list]]:
    """A segment's in-tile slots as the body applies them, in order:
    ``("run", slots, [])`` for a run of diagonal ops (diag_runs) and
    ``("stretch", slots, passes)`` for a STRETCH, a maximal sequence of
    slots between runs, with its stretch_passes where the tile is
    chunked and one pass on the whole tile where it is one chunk."""
    pieces, at = [], 0
    for start, stop in diag_runs(ops) + [(len(ops), len(ops))]:
        if start > at:
            stretch = list(ops[at:start])
            pieces.append(("stretch", stretch, stretch_passes(stretch, tile)
                           if chunked(tile) else [(stretch, None)]))
        if stop > start:
            pieces.append(("run", list(ops[start:stop]), []))
        at = stop
    return pieces


def stretch_counts(structure: Tuple, bp: int) -> Tuple[int, int, int, int]:
    """``(stretches, ops, passes, whole_tile_ops)``: the stretches the
    kernel applies chunk by chunk for this window (in part, at least),
    the ops it applies so, the loops over chunks it lowers for them
    (stretch_passes), and the in-tile ops outside runs that it applies
    on the whole tile's value: those that roll lanes, and all of them
    where the tile is one chunk (the flat tile; a dense one of at most
    _RUN_ROWS rows).  The telemetry counters ``fuse.kernel.stretches``,
    ``.stretch.ops``, ``.stretch.passes`` and
    ``fuse.kernel.whole_tile_ops``."""
    tile = dense_tile(bp) or (1 << bp,)
    stretches = ops = passes = whole = 0
    for seg in plan_window(structure, bp):
        for kind, slots, split in segment_pieces(seg["ops"], tile):
            if kind != "stretch":
                continue
            chunks = [in_pass for in_pass, held in split if held is not None]
            stretches += bool(chunks)
            passes += len(chunks)
            ops += sum(map(len, chunks))
            whole += len(slots) - sum(map(len, chunks))
    return stretches, ops, passes, whole


def _for_tile_chunks(tile: Tuple[int, ...], body, held=None) -> None:
    """``body(pieces, lidx)`` for every chunk of a tile, in a loop.  A
    CHUNK is the part of the dense tile a pass holds in registers at a
    time: ``pieces`` are the slices of a tile-shaped ref's first axis
    that make it up, in the chunk's own order (_chunk_of, _chunk_to),
    and ``lidx`` is the in-tile index of its every element.  With no
    ``held`` a chunk is _RUN_ROWS consecutive rows, one piece.  With
    ``held``, the bits of the tile's index above the vreg that the
    chunk is to hold (stretch_passes; ascending), the chunk's vreg
    ``j`` is the tile's vreg whose index has bit ``k`` of ``j`` at
    ``held[k]`` and the loop's counter spread over the other bits
    (orbit_tile): where ``held`` are the lowest bits its rows are
    consecutive, else it is pieces of whole vregs, and a target
    ``held[k]`` sits on bit ``_VREG_POW + k`` of the chunk's own index
    (tile_partner's ``at``).

    Passes go chunk by chunk because the TPU's scheduler keeps the
    order it is given: a pass written on the whole tile is emitted
    operation by operation over its 64 vreg pairs, and the 128 live
    vregs go through the one vector-store slot between any two
    operations (635 bundles for one cphase whose arithmetic fills 176:
    PERF.md section 6, PR 42).  The loop is rolled: unrolled it
    schedules tighter (188 bundles a cphase against 284), and a window
    program takes seconds to trace and lower.  A flat tile is one
    chunk."""
    if len(tile) == 1:
        body([slice(None)], _tile_index(tile))
        return
    if held is None:
        rows = min(tile[0], _RUN_ROWS)
        held = tuple(range(_VREG_POW, _VREG_POW + (rows >> 3).bit_length() - 1))
    vregs = tuple(t - _VREG_POW for t in held)
    rows = 8 << len(vregs)
    # the lowest bits are one piece of consecutive rows; every bit above
    # them doubles the pieces
    joined = sum(v == k for k, v in enumerate(vregs))
    size = 8 << joined
    offsets = [8 * orbit_tile(vregs[joined:], 0, j)
               for j in range(1 << (len(vregs) - joined))]
    lidx = _tile_index((rows, tile[1]))
    if len(offsets) > 1:
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, tile[1]), 0)
        lidx = lidx & (size * tile[1] - 1)
        for k, v in enumerate(vregs[joined:], joined):
            lidx = lidx | (((row >> (3 + k)) & 1) << (_VREG_POW + v))

    def step(at, carry):
        first = at * rows if len(offsets) == 1 else 8 * orbit_tile(vregs, at, 0)
        start = pl.multiple_of(first, size)
        body([pl.ds(start + off if off else start, size) for off in offsets],
             lidx + start * tile[1])
        return carry

    if tile[0] == rows:
        body([pl.ds(off, size) for off in offsets], lidx)
    else:
        jax.lax.fori_loop(0, tile[0] // rows, step, 0)


def _chunk_of(ref, at, pieces):
    """The chunk ``pieces`` of the tile ``ref[at]``, ``(2, rows, lanes)``
    (a flat tile's whole ``(2, block)``)."""
    parts = [ref[(at, slice(None), rows)] for rows in pieces]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _chunk_to(ref, at, pieces, v) -> None:
    """Store ``v`` as the chunk ``pieces`` of the tile ``ref[at]``."""
    if len(pieces) == 1:
        ref[(at, slice(None), pieces[0])] = v
        return
    size = v.shape[1] // len(pieces)
    for j, rows in enumerate(pieces):
        ref[(at, slice(None), rows)] = jax.lax.slice_in_dim(
            v, j * size, (j + 1) * size, axis=1)


# the fewest consecutive diag ops that are traced as one loop.  The
# per-page QFT's cphase onto the two page bits are diag in pairs, so a
# loop from two on would leave none of its window programs the text they
# were; and a loop's op picks its target and reads its operands at a
# traced index, some two dozen cycles a tile more than a body of its own
# (one pair of runs of that cell at w31: +0.79 % as loops of two,
# PERF.md section 6, PR 47).  What a pair's two bodies cost to trace,
# every set-up has always paid
DIAG_GROUP_MIN = 3


def _run_groups(run) -> List[list]:
    """A run's slots as _diag_operands takes them: consecutive slots of
    one kind (cphase, diag) alike in having controls together; diag
    slots fewer than DIAG_GROUP_MIN each alone."""
    groups = []
    for slot in run:
        last = groups[-1][-1] if groups else None
        if last and slot[1] == last[1] and slot[3] == last[3]:
            groups[-1].append(slot)
        else:
            groups.append([slot])
    return [part for group in groups for part in (
        [[slot] for slot in group]
        if group[0][1] == "diag" and len(group) < DIAG_GROUP_MIN
        else [group])]


def _apply_run(v, blk, run, slots, plan_at, iv_ref, fv_ref, bp, first,
               run_ref, fold_ref, mask_ref, table, fold_at):
    """A run of diagonal ops on the tile value held in ``run_ref[0]``,
    in place, as one operator; ``v`` is the value where the scratch does
    not hold it yet (the segment begins with the run), else None.

    The run's factor at amplitude ``(blk, lidx)`` is ``T(lidx) * R(blk,
    lidx)``.  ``T`` is the product of the ops that read no bit above
    the tile: the same for every tile, so it is built once a launch, at
    ``first`` (the launch's first computing step), in ``run_ref[table]``
    from a tile of ``1 + 0i``.  ``R`` is the rest, the ops with a high
    part: on a tile whose id does not admit one its factor is exactly
    ``1 + 0i``, and on one that does it is a function of ``lidx`` with
    a signature of three ints (fold_signature), so ops of one signature
    multiply scalar by scalar.  The host's plan (run_planner; its rows
    in ``iv_ref`` from ``plan_at``) gives the first FOLD_SLOTS
    signatures a slot each and every op its slot.

    What a step pays an op is what counts: a scalar read, a float
    operation on the scalar core and a scalar handed to the vector unit
    each take tens of cycles, and a loop's iterations do not overlap
    (PERF.md section 6, PR 54).  So the ops of the run are walked, one
    traced body a group (_run_groups) in a loop over the operands'
    offsets, at the launch's first step alone, unless the plan says an
    op keeps a pass: there a table op is multiplied onto the table, and
    a folded op's operands are laid out for the vector unit, in
    ``fold_ref`` and ``mask_ref`` at the op's place among the folded
    (from ``fold_at`` on), a vreg each: sublane ``s`` is slot ``s``
    (the last of the first _FOLD_ROWS a sink), the sublanes from
    _FOLD_ROWS on the second entry of an op that picks by its target's
    bit of ``lidx``.  Every step then folds, in a loop of as many
    iterations as the plan says ops fold and no scalar work, the
    factors of the ops its tile admits into the slots' accumulators,
    one vreg a plane, in op order from ``1 + 0i``; and makes ONE pass
    over the tile: value times table times the first slot's factor
    where ``lidx`` matches its mask and ``1 + 0i`` elsewhere (the plan
    fills the slots in their order), then each further slot the plan
    uses (one it does not use is a scalar branch a chunk).  A run the
    plan folds no op of branches past all that, to the one multiply by
    the table it always was.  An op of a fourth signature keeps the pass
    it had, by the code it has alone over the value, under ``pl.when``
    on the tile id."""
    tile, dtype = run_ref.shape[2:], run_ref.dtype
    one, zero = jnp.ones((), dtype), jnp.zeros((), dtype)
    ids_at = plan_at + _PLAN_HEAD
    folded, passed = (iv_ref[ids_at - 2, 0], iv_ref[ids_at - 1, 0])
    vreg = fold_ref.shape[2:]
    row_of = jax.lax.broadcasted_iota(jnp.int32, vreg, 0)

    def a_pass(at, kind, args):
        """The op on ``run_ref[at]``, in place."""
        def chunk(pieces, lidx):
            _chunk_to(run_ref, at, pieces, _TILE_DIAGONAL[kind](
                _chunk_of(run_ref, at, pieces), lidx, blk, *args)[0])
        _for_tile_chunks(tile, chunk)

    def lay_out(at, kind, slot, args):
        """A folded op's operands as the fold's loop reads them, the
        ``at``-th: the tile id's mask and value that admit it (the
        value -1, which no id has, on the sublanes of other slots), its
        target's bit of the tile id, and its factor by that bit."""
        mine = (row_of & (_FOLD_ROWS - 1)) == slot
        if kind == "cphase":  # one entry, and no bit of the tile id picks
            gm = gv = args[1]
            entries, high = [args[2:], args[2:]], 0
        else:
            target, gm, gv = args[0], args[-2], args[-1]
            high = jnp.where(target >= bp,
                             jnp.int32(1) << jnp.maximum(target - bp, 0), 0)
            # the second entry's sublanes of an op with its target in
            # the tile hold its second entry whatever the tile id
            second = (row_of >= _FOLD_ROWS) & (high == 0)
            entries = [tuple(jnp.where(second, b, a)
                             for a, b in zip(args[2:4], args[4:6])),
                       args[4:6]]
        for k, value in enumerate((gm, jnp.where(mine, gv, -1), high)):
            mask_ref[at, k] = jnp.broadcast_to(value, vreg).astype(jnp.int32)
        for k, value in enumerate(entries[0] + entries[1]):
            fold_ref[at, k] = jnp.broadcast_to(value, vreg).astype(dtype)

    def for_ops(group, at_run, at_fold):
        """Each op of the group onto the table, laid out for the fold
        or onto the value; the group's ops are the ``at_run``-th on of
        the run, and ``at_fold`` of the run's ops ahead of them fold."""
        kind = group[0][1]

        def op(at, at_fold):
            slot = iv_ref[ids_at + at_run + at, 0]
            args = _diag_operands(group, at, slots, iv_ref, fv_ref, bp)
            if kind == "cphase":
                high, admits = args[1], (blk & args[1]) == args[1]   # chi
            else:
                high = jnp.int32(args[0] >= bp) | args[-2] | args[-1]
                admits = (blk & args[-2]) == args[-1]                # gm, gv
            folds = slot < FOLD_SLOTS
            # on the table an op has no high part: any tile id admits it
            pl.when(jnp.where(high == 0, first, admits & ~folds))(
                functools.partial(a_pass, jnp.where(high == 0, table, 0),
                                  kind, args))
            pl.when(first & folds)(functools.partial(
                lay_out, fold_at + at_fold, kind, slot, args))
            return at_fold + folds.astype(jnp.int32)

        if len(group) == 1:
            return op(0, at_fold)
        return jax.lax.fori_loop(0, len(group), op, at_fold)

    @pl.when(first)
    def _():
        run_ref[table] = jnp.stack([jnp.ones(tile, dtype),
                                    jnp.zeros(tile, dtype)])

    if v is not None:
        # + 0.0: a cast that feeds a store alone is made by strided stores
        # (led_kernel); it turns a -0.0 into 0.0 and nothing else
        run_ref[0] = v + 0.0

    @pl.when(first | (passed > 0))
    def _():
        at_run, at_fold = 0, jnp.int32(0)
        for group in _run_groups(run):
            at_fold = for_ops(group, at_run, at_fold)
            at_run += len(group)

    def times(rows, *factors):
        """The chunk's value times each factor in turn."""
        re, im = run_ref[0, 0, rows], run_ref[0, 1, rows]
        for f_re, f_im in factors:
            re, im = re * f_re - im * f_im, re * f_im + im * f_re
        run_ref[0, 0, rows], run_ref[0, 1, rows] = re, im

    def of_table(rows):
        return run_ref[table, 0, rows], run_ref[table, 1, rows]

    # a run that is all table pays the one multiply it always did
    @pl.when(folded == 0)
    def _():
        _for_tile_chunks(tile, lambda pieces, _: times(
            pieces[0], of_table(pieces[0])))

    # the accumulators go to the scratch's last place, whose sublanes
    # the pass reads a slot at a time
    acc_at = fold_ref.shape[0] - 1
    # may an op pick by its target's bit: a diag with its target in the
    # tile and a control (where above the tile, the plan says)
    picks = any(kind == "diag" and t < bp and c for _, kind, t, c in run)

    @pl.when(folded > 0)
    def _():
        blk_of = jnp.broadcast_to(blk, vreg).astype(jnp.int32)

        def fold(at, acc):
            at = fold_at + at
            admits = (blk_of & mask_ref[at, 0]) == mask_ref[at, 1]
            up = (blk_of & mask_ref[at, 2]) != 0
            f_re = jnp.where(up, fold_ref[at, 2], fold_ref[at, 0])
            f_im = jnp.where(up, fold_ref[at, 3], fold_ref[at, 1])
            f_re = jnp.where(admits, f_re, one)
            f_im = jnp.where(admits, f_im, zero)
            return (acc[0] * f_re - acc[1] * f_im,
                    acc[0] * f_im + acc[1] * f_re)

        fold_ref[acc_at, 0], fold_ref[acc_at, 1] = jax.lax.fori_loop(
            0, folded, fold, (jnp.ones(vreg, dtype), jnp.zeros(vreg, dtype)))
        folds = [tuple(iv_ref[plan_at + _SLOT_ROWS * s + row, 0]
                       for row in range(_SLOT_ROWS))
                 for s in range(FOLD_SLOTS)]

        def of_slot(s, lidx):
            """Slot ``s``'s scalar, a sublane of the accumulators, where
            the chunk's index matches its mask and ``1 + 0i`` elsewhere."""
            mask, value, pick = folds[s]

            def factor(plane):
                rows = [fold_ref[acc_at, plane, pl.ds(at, 1)] for at in
                        ((s, _FOLD_ROWS + s) if picks else (s,))]
                if len(tile) == 1:
                    rows = [row.reshape(tile) for row in rows]
                if picks:
                    return jnp.where((lidx & pick) != 0, rows[1], rows[0])
                return rows[0]
            hit = (lidx & mask) == value
            return jnp.where(hit, factor(0), one), jnp.where(hit, factor(1), zero)

        def by_table_and_slots(pieces, lidx):
            rows, = pieces
            # the plan fills the slots in their order: where an op folds
            # the first is used, and rides the table's multiply
            times(rows, of_table(rows), of_slot(0, lidx))
            for s in range(1, FOLD_SLOTS):
                # a branch that hands no value on: a chunk carried through
                # a cond is stored and loaded again whichever side runs
                # (3.3 ms a launch of an all-table run: PERF.md s.6, PR 54)
                pl.when(folds[s][1] >= 0)(
                    lambda s=s: times(rows, of_slot(s, lidx)))

        _for_tile_chunks(tile, by_table_and_slots)


def _u4_scalars(fv_ref, foff):
    """``mp[plane][row][col]`` of a u4's 32 floats (mtrx_planes flat)."""
    return [[[fv_ref[foff + 16 * plane + 4 * row + col, 0]
              for col in range(4)]
             for row in range(4)]
            for plane in range(2)]


def orbit_tile(lead_bits: Tuple[int, ...], orbit, member):
    """The tile id of a leading op's ``orbit``-th orbit's ``member``-th
    tile.  ``lead_bits`` are the positions, in the tile id and ascending,
    of the lead's targets above the tile: the orbit index is the tile id
    with those bits taken out (a zero is put back in at each), and bit
    ``p`` of the member index goes to position ``lead_bits[p]``.  Plain
    shifts and masks: it serves the BlockSpecs' index maps, the kernel
    bodies and, on Python ints, the tests."""
    for h in lead_bits:
        orbit = ((orbit >> h) << (h + 1)) | (orbit & ((1 << h) - 1))
    for p, h in enumerate(lead_bits):
        orbit = orbit | (((member >> p) & 1) << h)
    return orbit


def _segment_program(n: int, bp: int, seg: dict, slots, plans,
                     interpret: bool):
    """One pl.pallas_call for one segment: run(planes, iv, fv); ``slots``
    and ``plans`` the window's _operand_slots and _run_plan_slots."""
    block = 1 << bp
    nblk = 1 << (n - bp)
    lbits = block - 1
    xgen = seg["xgen"]

    # the tile the body computes on: (rows, 128) full vregs where the
    # block has them, else the (block,) row the refs hold.  A led
    # segment casts its inputs, ahead of the mix (fewer instructions
    # than casting the mixed value: PERF.md section 6, PR 29), each
    # tile once, into a scratch (led_kernel)
    tile = dense_tile(bp) or (block,)

    def load(ref):
        return ref[...].reshape((2,) + tile)

    # the segment's in-tile ops as the body applies them (segment_pieces:
    # runs of diagonal ops, and the stretches between them in their
    # passes), and their VMEM scratch after whatever the kernel has of
    # its own: tiles of the body's shape, the first for the value and
    # one phase tile a run (_apply_run).  A segment with no run and no
    # pass has none, and its body is its ops one after the other on the
    # tile's value
    pieces = segment_pieces(seg["ops"], tile)
    runs = sum(kind == "run" for kind, _, _ in pieces)
    run_scratch = ([(1 + runs, 2) + tile]
                   if any(kind == "run" or any(held is not None
                                               for _, held in passes)
                          for kind, _, passes in pieces) else [])
    # and what a run's fold reads (_apply_run): a place an op of the
    # segment's runs that may fold and one for the accumulators, four
    # float vregs a place, and three int32 ones an op
    may_fold = [_may_fold(slots_, bp) if kind == "run" else 0
                for kind, slots_, _ in pieces]
    fold_vreg = (2 * _FOLD_ROWS, tile[-1])

    def in_tile_ops(v, blk, iv_ref, fv_ref, first, run_refs):
        """The segment's in-tile ops on a loaded (or mixed) tile value,
        back in the refs' ``(2, block)`` shape.  A run, and a stretch in
        passes, work in place on the value held in VMEM, in the
        scratch's first tile: it is stored there ahead of the first of
        them and loaded behind the last.  A PASS is one rolled loop
        over the tile's chunks (_for_tile_chunks) in whose body every op
        of the pass is applied to the chunk in registers, one after the
        other, by the code it has on a whole tile (_apply_slot), and
        the chunk is stored once: the ops of a pass cost one load and
        one store of the tile together.  Where the tile is one chunk a
        stretch is its ops on the tile's value (the flat tile's body).
        ``first()``: is this the launch's first computing step (asked
        where a run builds its table); ``run_refs``: the refs of
        ``run_scratch``."""
        lidx = _tile_index(tile)
        held = False  # is the value in the scratch, or in ``v``
        table = 0

        def operands(slot):
            return _slot_operands(slot, slots, iv_ref, fv_ref, bp)

        def on_value(v, lidx, applied, bits=()):
            for slot, args in applied:
                v = _apply_slot(v, lidx, blk, slot, args, bits)
                if interpret:  # XLA lowers the body: see tile_partner
                    v = jax.lax.optimization_barrier(v)
            return v

        for (kind, in_piece, passes), fold_at in zip(
                pieces, itertools.accumulate(may_fold, initial=0)):
            if kind == "run":
                table += 1
                _apply_run(None if held else v, blk, in_piece, slots,
                           plans[in_piece[0][0]], iv_ref, fv_ref, bp, first(),
                           *run_refs, table, fold_at)
                held = True
            for in_pass, bits in passes:
                if bits is None:  # on the whole tile's value
                    if held:
                        v, held = run_refs[0][0], False
                    v = on_value(v, lidx, ((slot, operands(slot))
                                           for slot in in_pass))
                    continue
                if not held:
                    run_refs[0][0] = v + 0.0  # + 0.0: see _apply_run
                    held = True
                applied = [(slot, operands(slot)) for slot in in_pass]

                def chunk(rows, lidx, applied=applied, bits=bits):
                    _chunk_to(run_refs[0], 0, rows, on_value(
                        _chunk_of(run_refs[0], 0, rows), lidx, applied, bits))
                _for_tile_chunks(tile, chunk, bits)
        if held:
            v = run_refs[0][0]
        return v.reshape(2, block)

    def launch(kernel, lead_bits=()):
        """run(planes, iv, fv): the kernel over every tile, one tile in
        and one tile out a grid step.  An unled segment's step is its
        tile.  A led one's grid is (orbit, member), the member
        innermost, and runs one orbit longer than the ket has: step
        (o, j) reads tile j of orbit o and writes tile j of orbit
        o - 1 (led_kernel), the read clamped at the last orbit and the
        write at the first, whose blocks the next orbit's steps write
        again with what belongs there.  The result is aliased to the
        planes: a donated ket is swept in place (a tile is fetched at
        least ``m`` steps before the step that writes it, and the
        pipeline fetches one step ahead), one that is not donated is
        copied first by XLA and comes back unchanged."""
        name = segment_kernel_name(seg, bp)
        m = 1 << len(lead_bits)
        if lead_bits:
            last = nblk // m - 1
            grid = (nblk // m + 1, m)
            in_spec = pl.BlockSpec((2, block), lambda o, j: (0, orbit_tile(
                lead_bits, jnp.minimum(o, last), j)))
            out_spec = pl.BlockSpec((2, block), lambda o, j: (0, orbit_tile(
                lead_bits, jnp.maximum(o - 1, 0), j)))
        else:
            grid = (nblk,)
            in_spec = out_spec = pl.BlockSpec((2, block), lambda i: (0, i))

        def run(planes, iv, fv):
            # two orbits of cast tiles: the one read and the one written
            scratch = [pltpu.VMEM(shape, planes.dtype) for shape in
                       ([(2, m, 2) + tile] if lead_bits else []) + run_scratch]
            if runs:
                scratch += [
                    pltpu.VMEM((sum(may_fold) + 1, 4) + fold_vreg,
                               planes.dtype),
                    pltpu.VMEM((max(sum(may_fold), 1), 3) + fold_vreg,
                               jnp.int32)]
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((2, 1 << n), planes.dtype),
                grid=grid,
                in_specs=[_SCALAR_SPEC, _SCALAR_SPEC, in_spec],
                out_specs=out_spec,
                scratch_shapes=scratch,
                input_output_aliases={2: 0},  # the planes, after iv and fv
                compiler_params=_COMPILER_PARAMS,
                interpret=interpret,
                name=name,
                metadata={"qrack_kernel": name},
            )(iv, fv, planes)

        return run

    if xgen is None:
        def kernel(iv_ref, fv_ref, in_ref, out_ref, *run_refs):
            out_ref[...] = in_tile_ops(load(in_ref), pl.program_id(0), iv_ref,
                                       fv_ref, lambda: pl.program_id(0) == 0,
                                       run_refs)

        return launch(kernel)

    def led_kernel(mix, lead_bits):
        """The kernel of a led segment from its lead's ``mix(tiles,
        member, blk, iv_ref, fv_ref)``, ``tiles(k)`` the orbit's
        ``k``-th tile.  Step (o, j) casts the tile it is handed, tile j
        of orbit o, into one half of a VMEM scratch, and computes tile j
        of orbit o - 1 from the other half, which that orbit's steps
        filled: every step loads one tile, casts one in and one out and
        stores one, as an unled step does, whatever the lead mixes."""
        orbits = nblk >> len(lead_bits)

        def kernel(iv_ref, fv_ref, in_ref, out_ref, orbit_ref, *run_refs):
            o, member = pl.program_id(0), pl.program_id(1)

            @pl.when(o < orbits)
            def _():
                # + 0.0: arithmetic on the cast value makes Mosaic cast
                # it in registers and store full vregs; a cast that
                # feeds a store alone becomes one sublane-strided store
                # per row, twice a register cast's time (PERF.md s.6,
                # PR 37).  It turns a -0.0 into 0.0 and nothing else
                orbit_ref[o & 1, member] = load(in_ref) + 0.0

            @pl.when(o > 0)
            def _():
                blk = orbit_tile(lead_bits, o - 1, member)
                nv = mix(lambda k: orbit_ref[(o - 1) & 1, k], member, blk,
                         iv_ref, fv_ref)
                out_ref[...] = in_tile_ops(
                    nv, blk, iv_ref, fv_ref,
                    lambda: (o == 1) & (member == 0), run_refs)

        return launch(kernel, lead_bits)

    if xgen[1] == "u4":
        # the two-target op above the tile: the quad's members are the
        # orbit's tiles in the order this member meets them, itself
        # first (the order tile_quad_mix sums in), and the low partner
        # inside each where the low target is in the tile
        lo, hi = xgen[2]
        foff_x = slots[xgen[0]][0]
        lead_bits = tuple(t - bp for t in xgen[2] if t >= bp)

        def mix(tiles, member, blk, iv_ref, fv_ref):
            seen = [tiles(member ^ x) for x in range(1 << len(lead_bits))]
            if lo < bp:
                lidx = _tile_index(tile)
                members = tuple((v, tile_partner(v, lidx, lo)) for v in seen)
                b1, b2 = own_bit(lidx, lo), member != 0
            else:
                members = (seen[:2], seen[2:])
                b1, b2 = (member & 1) != 0, (member & 2) != 0
            # a bit that is one scalar for the tile picks by jnp.where
            b1, b2 = (b if callable(b) else functools.partial(jnp.where, b)
                      for b in (b1, b2))
            return tile_quad_mix(members, b1, b2, _u4_scalars(fv_ref, foff_x))

        return led_kernel(mix, lead_bits)

    # cross-tile segment: the leading inv/gen, one or a pair, mix the
    # orbit's two or four tiles, in their order
    leads = seg["leads"]
    lead_bits = tuple(sorted(slot[2] - bp for slot in leads))

    def lead_row(slot, b, fv_ref):
        """``(m0r, m0i, m1r, m1i)``: the lead's row for the tiles whose
        target bit is ``b``."""
        foff = slots[slot[0]][0]
        if slot[1] == "gen":
            # my row of the matrix: row b -> (m[b,0], m[b,1]);
            # fv holds mtrx_planes flat: [re00,re01,re10,re11,im...]
            return (jnp.where(b == 0, fv_ref[foff + 0, 0], fv_ref[foff + 2, 0]),
                    jnp.where(b == 0, fv_ref[foff + 4, 0], fv_ref[foff + 6, 0]),
                    jnp.where(b == 0, fv_ref[foff + 1, 0], fv_ref[foff + 3, 0]),
                    jnp.where(b == 0, fv_ref[foff + 5, 0], fv_ref[foff + 7, 0]))
        # inv rows: (0, tr) and (bl, 0); fv holds [tr.re,tr.im,bl...]
        zero = jnp.zeros((), fv_ref.dtype)
        return (jnp.where(b == 0, zero, fv_ref[foff + 2, 0]),
                jnp.where(b == 0, zero, fv_ref[foff + 3, 0]),
                jnp.where(b == 0, fv_ref[foff + 0, 0], zero),
                jnp.where(b == 0, fv_ref[foff + 1, 0], zero))

    def mix(tiles, member, blk, iv_ref, fv_ref):
        # a lead's row, read once: a tile that a later lead visits
        # differs from this member's own only in the later lead's bit,
        # so a lead applies the row of the member's own bit wherever it
        # is applied
        rows = {}

        def after(upto, k, tile_id):
            """Tile ``k`` of the orbit (its id ``tile_id``) behind the
            first ``upto`` leads: the later lead's row over the two
            tiles across its bit, each behind the leads ahead of it (for
            a pair: the first lead's row on two tiles, the second's over
            those, six complex multiply-adds an amplitude in the order
            two launches make them).  A lead's control is tested on the
            tile it computes, so a lead controlled by the other's target
            finds that bit in the tile's id."""
            if not upto:
                return tiles(k)
            _, _, target, has_ctrl = leads[upto - 1]
            if len(leads) == 1:  # the orbit's two tiles, by name
                b, lo, hi = k, tiles(0), tiles(1)
            else:
                at = lead_bits.index(target - bp)
                b, bit, high = (member >> at) & 1, 1 << at, 1 << (target - bp)
                lo = after(upto - 1, k & ~bit, tile_id & ~high)
                hi = after(upto - 1, k | bit, tile_id | high)
            if upto not in rows:
                rows[upto] = lead_row(leads[upto - 1], b, fv_ref)
            m0r, m0i, m1r, m1i = rows[upto]
            nr = m0r * lo[0] - m0i * lo[1] + m1r * hi[0] - m1i * hi[1]
            nim = m0r * lo[1] + m0i * lo[0] + m1r * hi[1] + m1i * hi[0]
            nv = jnp.stack([nr, nim])
            if has_ctrl:
                cm, cv = _slot_masks(leads[upto - 1], slots, iv_ref)
                lidx = _tile_index(tile)
                sel = (((lidx & (cm & lbits)) == (cv & lbits))
                       & ((tile_id & (cm >> bp)) == (cv >> bp)))
                nv = jnp.where(sel, nv, tiles(k) if upto == 1
                               else jnp.where(b == 0, lo, hi))
            return nv

        return after(len(leads), member, blk)

    return led_kernel(mix, lead_bits)


def make_window_fn(n: int, structure: Tuple,
                   block_pow: int = DEFAULT_BLOCK_POW,
                   interpret: bool = False):
    """The parametric window kernel: fn(planes, iv, fv) on the packed
    scalar columns of fusion.pack_operands (``fv`` in the planes'
    dtype; ``iv`` with the runs' plans behind the masks, as
    ``pack_operands`` lays them out for ``fusion.kernel_runs`` of this
    ``block_pow``), lowering to ``fn.sweeps`` Pallas sweeps (one per
    planned segment).  Trace it under jit exactly like fusion.window_fn —
    fusion.kernel_window_program does, with the shared structure-only
    cache key."""
    bp = min(block_pow, n)
    segments = plan_window(structure, bp)
    slots, _, _ = _operand_slots(structure)
    plans, rows = _run_plan_slots(structure, bp)
    programs = [_segment_program(n, bp, seg, slots, plans, interpret)
                for seg in segments]

    # named for the compiled module (jit_qrack_kernel_window), as
    # fusion.window_fn's is; the columns go to the launches as they came
    def qrack_kernel_window(planes, iv, fv):
        if iv.shape[0] != rows:
            raise ValueError(
                f"iv has {iv.shape[0]} rows where the window's masks and "
                f"its runs' plans are {rows}: pack_operands(runs=...)")
        with jax.named_scope("qrack.fuse.kernel_window"):
            for run in programs:
                planes = run(planes, iv, fv)
        return planes

    qrack_kernel_window.sweeps = len(segments)
    qrack_kernel_window.block_pow = bp
    return qrack_kernel_window
