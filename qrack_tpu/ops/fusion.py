"""Lazy gate-stream fusion: windowed op-queues lowered to parametric
(constant-free) compiled windows.

The eager engine path mirrors the reference's per-gate dispatch chain:
every Mtrx/MCMtrx is its own jitted full-ket sweep (engines/tpu.py:88),
so an N-gate circuit pays N HBM round trips and N dispatches.  Gate
fusion into multi-op windows is the standard lever in large-scale ket
simulators (mpiQulacs fuses gate runs to cut inter-node sweeps,
arXiv:2203.16044; single-GPU simulators take their headline speedups
from the same transform, arXiv:2304.14969).  This module makes fusion
the *default* execution mode of the dense engines:

* :class:`GateStreamFuser` — a bounded pending window of gate
  descriptors (``QRACK_TPU_FUSE_WINDOW``, default 32) attached to an
  engine.  Gate ops append instead of dispatching; every read/boundary
  (Prob*/M*/device_get/checkpoint capture/failover snapshot/serror
  batch edge) lands on the engine's ``_state`` property, whose getter
  flushes the window first.  Neighbor gates on the same target+controls
  merge algebraically before lowering (QCircuit.AppendGate's peephole,
  reference src/qcircuit.cpp:101), so a flushed window can dispatch
  fewer sweeps than gates queued ("sweeps saved").

* Parametric window programs — a window lowers to ONE jitted program
  whose payload matrices and control masks are *runtime operands*, not
  trace constants.  The program is keyed only by the window's
  **structure** (per-op kind, target axis, controlled-or-not), so two
  same-shaped windows with different rotation angles dispatch through
  one compiled executable (compile.fuse hit, not a recompile) — unlike
  QCircuit.compile_fn, which bakes matrices as literals and recompiles
  per angle.  Programs live in the bounded telemetry
  :class:`~qrack_tpu.telemetry.ProgramCache` (``fuse``) and dispatch
  through the guarded site ``tpu.fuse.flush`` (watchdog / retry /
  breaker / fault injection — docs/RESILIENCE.md).

Operand layout.  A window's operands are two host (numpy) columns,
packed by :func:`pack_operands` at the offsets of
``pallas_kernels._operand_slots`` and handed to the window program as
its two arguments after the planes; every window body reads its scalars
from them by static offset (:func:`operand_views`).  Per op, in window
order:

  fv (F, 1), the planes' dtype          iv (I, 1) int32, iff controlled
  cphase  2  [d1.re, d1.im]             dense    2  [cmask, cval]
  diag    4  [d0.re,d0.im,d1.re,d1.im]  sharded  cphase 2: the combined
  inv     4  [tr.re,tr.im,bl.re,bl.im]           mask's (local, page)
  gen     8  mtrx_planes (2,2,2),                halves; diag/gen 4:
             row-major                           split_masks' four
  u4     32  mtrx_planes (2,4,4),       (never controlled)
             row-major

(``iv`` keeps one dead slot where no op is controlled).  The values are
rounded to the planes' dtype by numpy, on the host, once; nothing is
put on the device per op.

"cphase" is the measured hot case (controlled phase with d0 == 1 and
positive controls — all 231 QFT phases): the factor select collapses to
one combined-mask test, (idx & (tmask|cmask)) == (tmask|cmask).
Uncontrolled ops hold NO mask slots, so apply_2x2/apply_invert keep
their static cmask==0 short-circuit inside the trace.

"u4" is the two-target op: any uncontrolled two-qubit gate (Swap, ISwap,
FSim, Apply4x4 ...) as one 4x4 on ``target = (lo, hi)``, row and column
index ``(bit hi << 1) | bit lo``.  Its kind never depends on its values
(a Swap is a "u4" too), so the draws of a random circuit share programs.
"""

from __future__ import annotations

import os
from typing import List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import matrices as mat
from .. import telemetry as _tele
from ..telemetry import roofline as _roofline
from .. import resilience as _res
from ..utils.bits import control_offset
from . import gatekernels as gk

# merged ops a pending window holds.  32 since PR 46 (16 was PR 5's, for
# the XLA chain on a CPU): wide enough that a cycle of a random circuit's
# roots waits for its couplers and composes into them on the host, and
# that a launch carries two of a QFT's runs; not wider, because a
# window's float operands reach the kernel as one (N, 1) column in SMEM
# (512 bytes an entry) and 64 ``u4`` are the whole of a v5e's 1 MiB
# (tests/test_chip_compile.py; PERF.md section 7)
DEFAULT_WINDOW = 32

# structure-keyed parametric window programs, shared by the engine
# fusers AND QCircuit.RunFused (layers/qcircuit.py) — same structure,
# one compiled program, regardless of who lowered it
PROGRAMS = _tele.ProgramCache("fuse", cap_env="QRACK_TPU_FUSE_CACHE_CAP",
                              default_cap=256)


def window_len() -> int:
    """Pending-window bound. <=1 disables fusion (exact per-gate path)."""
    try:
        w = int(os.environ.get("QRACK_TPU_FUSE_WINDOW", str(DEFAULT_WINDOW)))
    except ValueError:
        w = DEFAULT_WINDOW
    return max(1, w)


# ---------------------------------------------------------------------------
# lowering: QCircuitGate window -> flat op descriptors
# ---------------------------------------------------------------------------

class FusedOp:
    """One lowered gate: classification + static placement + payload.
    ``target`` is a qubit, or the pair ``(lo, hi)`` of a "u4"."""

    __slots__ = ("kind", "target", "cmask", "cval", "m")

    def __init__(self, kind: str, target: int, cmask: int, cval: int, m):
        self.kind = kind
        self.target = target
        self.cmask = cmask
        self.cval = cval
        self.m = m


def classify(m, cmask: int, cval: int) -> str:
    if mat.is_phase(m):
        # d0 == 1 with positive controls: factor select collapses to one
        # combined-mask test (the dominant case — QFT controlled phases)
        if m[0, 0] == 1.0 and cval == cmask:
            return "cphase"
        return "diag"
    if mat.is_invert(m):
        return "inv"
    return "gen"


class TwoQubitGate:
    """The queued record of an uncontrolled two-qubit gate: a 4x4 on
    ``target = (lo, hi)``, indexed ``(bit hi << 1) | bit lo``.  It stands
    in the fuser's window beside the ``QCircuitGate``s and answers what
    the merge walk asks of them."""

    __slots__ = ("target", "m")

    def __init__(self, q1: int, q2: int, m4):
        m = np.asarray(m4, dtype=np.complex128).reshape(4, 4)
        if q1 > q2:  # the caller's index is (bit q2 << 1) | bit q1
            q1, q2 = q2, q1
            m = m[np.ix_(_SWAP_ROWS, _SWAP_ROWS)]
        self.target = (q1, q2)
        self.m = m

    def qubits(self) -> Tuple[int, int]:
        return self.target

    def can_merge(self, later) -> bool:
        """A later gate that acts inside this pair composes onto it:
        another two-qubit gate on the pair, a single-qubit gate on one
        of the two, or a gate controlled by one on the other.  Decided
        by the qubits alone, never by a value."""
        return set(later.qubits()) <= set(self.target)

    def merge(self, later) -> None:
        self.m = self.embed(later) @ self.m

    def absorb_behind(self, earlier) -> None:
        """Compose a gate that ran BEFORE this one into it."""
        self.m = self.m @ self.embed(earlier)

    def embed(self, g) -> np.ndarray:
        """``g`` (inside this pair) as a 4x4 in this pair's index."""
        if isinstance(g, TwoQubitGate):
            return g.m
        lo, _ = self.target
        eye = np.eye(2, dtype=np.complex128)
        out = np.zeros((4, 4), dtype=np.complex128)
        # per value of the other qubit: its projector beside the payload
        # that value selects (every value, where g has no control)
        for v in (0, 1):
            block = g.payloads.get(v if g.controls else 0, eye)
            proj = np.zeros((2, 2), dtype=np.complex128)
            proj[v, v] = 1.0
            out += (np.kron(proj, block) if g.target == lo
                    else np.kron(block, proj))
        return out

    def is_identity(self) -> bool:
        return bool(np.allclose(self.m, np.eye(4), rtol=0.0, atol=1e-12))

    def clone(self) -> "TwoQubitGate":
        return TwoQubitGate(*self.target, self.m.copy())


_SWAP_ROWS = [0, 2, 1, 3]  # a 4x4's index with its two qubits exchanged


def lower_gates(gates) -> List[FusedOp]:
    """Flatten the merged window into op descriptors (payload perms in
    sorted order for a deterministic structure)."""
    ops: List[FusedOp] = []
    for g in gates:
        if isinstance(g, TwoQubitGate):
            ops.append(FusedOp("u4", g.target, 0, 0, g.m))
            continue
        for perm in sorted(g.payloads):
            m = g.payloads[perm]
            cmask = 0
            for c in g.controls:
                cmask |= 1 << c
            cval = control_offset(g.controls, perm)
            ops.append(FusedOp(classify(m, cmask, cval), g.target, cmask, cval, m))
    return ops


def controls_perm(op: FusedOp) -> Tuple[Tuple[int, ...], int]:
    """Reconstruct a (controls, perm) pair from an op's (cmask, cval) —
    the inverse of lower_gates' control_offset, in ascending bit order —
    so a single-op window can re-enter an engine's eager `_k_apply_*`
    funnel unchanged."""
    controls = tuple(c for c in range(op.cmask.bit_length())
                     if (op.cmask >> c) & 1)
    perm = 0
    for j, c in enumerate(controls):
        if (op.cval >> c) & 1:
            perm |= 1 << j
    return controls, perm


def structure_of(ops: Sequence[FusedOp]) -> Tuple:
    """The program-cache identity of a window: per-op (kind, target,
    controlled?).  Payload values and control placement are runtime
    operands and deliberately NOT part of the key."""
    return tuple((op.kind, op.target, op.cmask != 0) for op in ops)


# ---------------------------------------------------------------------------
# a window's operands: two packed host columns (module docstring)
# ---------------------------------------------------------------------------

_PAYLOAD_SHAPE = {"cphase": (2,), "diag": (2, 2), "inv": (2, 2),
                  "gen": (2, 2, 2), "u4": (2, 4, 4)}


def _payload(kind: str, m) -> tuple:
    """An op's floats, in the order of its slot."""
    if kind == "cphase":
        return (m[1, 1].real, m[1, 1].imag)
    if kind == "diag":
        return (m[0, 0].real, m[0, 0].imag, m[1, 1].real, m[1, 1].imag)
    if kind == "inv":
        return (m[0, 1].real, m[0, 1].imag, m[1, 0].real, m[1, 0].imag)
    return (*m.real.ravel(), *m.imag.ravel())  # mtrx_planes, row-major


def _whole_ket_groups(ops: Sequence[FusedOp]) -> List[range]:
    """The stretches of ``ops`` that each act on all of the ket alike:
    an uncontrolled op, or the ``2**k`` ops that lower_gates makes of
    one gate with a payload for every value of its ``k`` controls (a
    bond's two ``diag``).  A ``cphase`` packs one entry of its matrix
    and a lone controlled payload acts on the share of the ket that
    only the ket knows: neither is in a group."""
    groups: List[range] = []
    at = 0
    while at < len(ops):
        op = ops[at]
        values = 1 << bin(op.cmask).count("1")
        span = ops[at:at + values]
        if len({o.cval for o in span}) == values and all(
                o.kind != "cphase" and o.target == op.target
                and o.cmask == op.cmask for o in span):
            groups.append(range(at, at + values))
            at += values
        else:
            at += 1
    return groups


def _norm_kept_float32(ops: Sequence[FusedOp], payloads: List) -> List:
    """``payloads`` (per op its floats, _payload) as a float32 window
    holds them.

    A unitary rounded to float32 is none: RX(0.2) and RZ(0.2) round to
    ``1 + 2.3e-8`` times a unitary, the same way every time, so a w28
    Trotter step (55 of them) multiplies the squared norm by
    ``1 + 1.26e-6`` and an observable read off the planes after ``s``
    steps is off by ``s`` times that (PERF.md section 6, PR 47).  Where
    ops act on all of the ket alike (_whole_ket_groups) and are unitary
    that gain is known here, from the rounded floats alone.  Such a
    group is rounded from its floats times ``gain ** -0.5``, ``gain``
    the product over the window's groups so far, wherever that leaves
    the product nearer 1 than plain rounding does: upstream's
    ``1 / sqrt(runningNorm)`` on the next gate's matrix
    (``QEngine::Apply2x2``), computed and not reduced, and never carried
    past the window.  The scale is within a few float32 ulp of 1, so an
    exact gate stays exact until the gain passes half an ulp.  Every
    other op is rounded to nearest and counts nothing; an op's kind was
    decided on the matrix it came with (lower_gates)."""
    groups = _whole_ket_groups(ops)
    members = [i for group in groups for i in group]
    unitary = dict.fromkeys(members, False)
    for dim in (2, 4):  # one product for all the 2x2, one for the 4x4
        idx = [i for i in members if len(ops[i].m) == dim]
        if idx:
            ms = np.stack([np.asarray(ops[i].m) for i in idx])
            off = np.einsum("nji,njk->nik", ms.conj(), ms) - np.eye(dim)
            unitary.update(zip(idx, np.abs(off).max(axis=(1, 2)) <= 1e-9))
    out = list(payloads)
    gain = 1.0
    for group in groups:
        if not all(unitary[i] for i in group):
            continue
        exact = np.array([v for i in group for v in payloads[i]])
        dim = sum(len(ops[i].m) for i in group)
        vals = exact.astype(np.float32).astype(np.float64)
        by = float(vals @ vals) / dim
        if gain != 1.0:
            kept = (exact * gain ** -0.5).astype(np.float32).astype(
                np.float64)
            kept_by = float(kept @ kept) / dim
            if abs(gain * kept_by - 1.0) < abs(gain * by - 1.0):
                vals, by = kept, kept_by
        gain *= by
        at = 0
        for i in group:
            out[i] = vals[at:at + len(payloads[i])]
            at += len(payloads[i])
    return out


def pack_operands(ops: Sequence[FusedOp], dtype, split_at: int = None,
                  runs=None):
    """``(iv, fv)``: the window's int32 masks and its float payloads in
    the planes' ``dtype``, numpy columns laid out by
    ``pallas_kernels._operand_slots``.  ``split_at`` gives the sharded
    layout: masks split at that many local bits, 'inv' folded into
    'gen' (sharded_structure_of).  ``runs`` gives the kernel lowering's
    ``iv``: behind the masks, the plan of every run of diagonal ops the
    lowering found (its plan's ``"runs"``: _run_plan_rows).  Float32
    payloads are rounded with the window's norm kept
    (_norm_kept_float32).  Host work only."""
    from . import pallas_kernels as pk
    from .sharded import split_masks

    split = split_at is not None
    structure = sharded_structure_of(ops) if split else structure_of(ops)
    slots, nf, ni = pk._operand_slots(structure, split)
    floats = [0.0] * nf
    ints = [0] * ni
    payloads = [_payload(kind, np.asarray(op.m))
                for op, (kind, _, _) in zip(ops, structure)]
    if np.dtype(dtype) == np.float32:
        payloads = _norm_kept_float32(ops, payloads)
    for op, vals, (kind, _, _), (f, i) in zip(ops, payloads, structure,
                                             slots):
        floats[f:f + len(vals)] = vals
        if not op.cmask:
            continue
        if not split:
            masks = (op.cmask, op.cval)
        elif kind == "cphase":
            comb = (1 << op.target) | op.cmask
            masks = (comb & ((1 << split_at) - 1), comb >> split_at)
        else:
            masks = split_masks(op.cmask, op.cval, split_at)
        ints[i:i + len(masks)] = masks
    if runs is not None:
        ints += _run_plan_rows(ops, runs, split_at)
    # from Python ints: a mask past int32 raises, as it always did
    iv = np.array(ints, dtype=np.int32).reshape(-1, 1)
    fv = np.array(floats, dtype=np.float64).astype(dtype).reshape(-1, 1)
    return iv, fv


def operand_views(structure: Tuple, iv, fv, split: bool = False) -> List:
    """Per op ``(payload, masks)`` cut from the packed columns by static
    offset: the payload in its shape (``_PAYLOAD_SHAPE``), the
    masks a tuple of scalars, empty where the op is uncontrolled.
    Columns traced (inside a window body) or numpy (on the host)."""
    from . import pallas_kernels as pk

    slots, _, _ = pk._operand_slots(structure, split)
    out: List = []
    for (kind, _, has_ctrl), (f, i) in zip(structure, slots):
        p = fv[f:f + pk._NFLOATS[kind], 0].reshape(_PAYLOAD_SHAPE[kind])
        out.append((p, tuple(iv[i + j, 0] for j in range(
            pk._nints(kind, has_ctrl, split)))))
    return out


def per_op_operands(ops: Sequence[FusedOp], dtype,
                    split_at: int = None) -> List:
    """The packed columns as one host array per payload and per mask, in
    window order: for the bodies that take an operand per op (the
    vmapped trajectory window, the compressed engine's window kernels).
    Views of :func:`pack_operands`' arrays, not a second encoding."""
    split = split_at is not None
    structure = sharded_structure_of(ops) if split else structure_of(ops)
    out: List = []
    for p, masks in operand_views(structure,
                                  *pack_operands(ops, dtype, split_at),
                                  split=split):
        out.append(p)
        out.extend(masks)
    return out


# ---------------------------------------------------------------------------
# dense (single-shard) parametric window program
# ---------------------------------------------------------------------------

def window_fn(n: int, structure: Tuple):
    """Traced body: fn(planes, iv, fv) applying the window in order.
    Pure and jit-safe; operand layout per module docstring."""

    # the function's name is the compiled module's (jit_qrack_xla_window):
    # what a device trace knows this program by when locations carry no
    # name stack; the scope is what it knows its operations by when they do
    def qrack_xla_window(planes, iv, fv):
        with jax.named_scope("qrack.fuse.xla_window"):
            views = operand_views(structure, iv, fv)
            for (kind, target, has_ctrl), (p, masks) in zip(structure, views):
                cm, cv = masks if has_ctrl else (0, 0)
                if kind == "cphase":
                    comb = ((1 << target) | cm) if has_ctrl else (1 << target)
                    hit = (gk.iota_for(planes) & comb) == comb
                    one = jnp.ones((), planes.dtype)
                    zero = jnp.zeros((), planes.dtype)
                    planes = gk.cmul(jnp.where(hit, p[0], one),
                                     jnp.where(hit, p[1], zero), planes)
                elif kind == "diag":
                    planes = gk.apply_diag(planes, p[0, 0], p[0, 1], p[1, 0],
                                           p[1, 1], n, 1 << target, cm, cv)
                elif kind == "inv":
                    planes = gk.apply_invert(planes, p[0, 0], p[0, 1], p[1, 0],
                                             p[1, 1], n, target, cm, cv)
                elif kind == "u4":
                    planes = gk.apply_4x4(planes, p, n, *target)
                else:
                    planes = gk.apply_2x2(planes, p, n, target, cm, cv)
        return planes

    return qrack_xla_window


def stored_program(key, make_fn, **jit_kw):
    """``jax.jit(make_fn(), **jit_kw)`` through the program store beside
    the compile cache (checkpoint/warmstart.py): where a cache directory
    is configured, a process that finds the program there traces no
    window body."""
    from ..checkpoint import warmstart

    return warmstart.stored_program(key, make_fn, **jit_kw)


def timed_build(build):
    """A window program's builder under the span ``fuse.build``: what a
    ``ProgramCache`` miss costs inside ``fuse.lower`` (planning and
    wrapping; tracing and compiling happen at the first call)."""
    def run():
        with _tele.span("fuse.build"):
            return build()
    return run


def dense_window_program(n: int, structure: Tuple, dtype):
    """One guarded jitted program per (width, dtype, structure) — payload
    values ride the two operand columns, so every same-structure window
    is a compile.fuse hit."""
    key = ("dense", n, str(jnp.dtype(dtype)), structure)

    def build():
        return _res.instrument_dispatch(
            "tpu.fuse.flush",
            _tele.instrument_jit("fuse.window", stored_program(
                key, lambda: window_fn(n, structure), donate_argnums=(0,))))

    return PROGRAMS.get_or_build(key, timed_build(build))


# ---------------------------------------------------------------------------
# single-sweep Pallas kernel lowering — what every multi-op window
# takes on the TPU, in place of the XLA window chain above.  The kernel
# streams the ket through VMEM once per planned segment
# (ops/pallas_kernels.py) instead of once per gate, with the SAME
# runtime-operand layout and structure-only cache keys, so choosing it
# never changes retrace behavior — only the lowering.
# ---------------------------------------------------------------------------

def kernel_mode() -> str:
    """``QRACK_TPU_FUSE_KERNEL``: auto (default — kernel on TPU-class
    backends, XLA chain elsewhere), on (force the kernel everywhere;
    interpret-lowered off-TPU, parity-grade not perf-grade), off (PR 5
    XLA window path, byte-for-byte)."""
    v = os.environ.get("QRACK_TPU_FUSE_KERNEL", "auto").strip().lower()
    return v if v in ("auto", "on", "off") else "auto"


def kernel_lowering(n: int, structure: Tuple, backend: str = None):
    """Should this window flush through the Pallas kernel?

    Returns ``(plan, fallback_reason)`` — exactly one is non-None.
    ``plan`` is ``{"interpret": bool, "block_pow": int, "sweeps": int,
    "cross": int, "dense": int, "paired": int, "twoq": dict, "runs":
    list}`` (``cross``: the cross-tile segments 2x2s lead among the sweeps;
    ``dense``: the sweeps whose kernel body computes on the dense
    ``(rows, 128)`` tile, pallas_kernels.dense_tile; ``paired``: the
    second leads that joined a segment, pallas_kernels.plan_window;
    ``twoq``: pallas_kernels.twoq_counts, the window's two-target ops
    and the sweeps that carry them; ``runs``: kernel_runs, the window's
    runs of diagonal ops, which pack_operands plans from the masks).

    The decision inputs are the mode, the backend and the window length
    (the plan's counts depend on the op mix, width and block_pow, the
    choice does not):

    * mode off — never (reason ``mode_off``).
    * mode on — always; off-TPU the kernel runs under the Pallas
      interpreter (correctness harness, ~14x slower than the XLA chain
      on CPU — docs/PERFORMANCE.md).
    * mode auto — TPU-class backends only (reason ``cpu_backend``
      elsewhere: the CPU XLA chain is measured compute-bound at these
      widths, so a single-sweep lowering cannot beat it and interpret
      certainly cannot).  On TPU every window takes the kernel, a
      window of bare cross-tile gen (as many segments as ops) and a
      window of one op included: on the chip a chain op costs three
      passes over the ket behind its barrier (31.9 ms at w28) and up
      to two kets of temporaries, where a one-op kernel sweep costs
      7.6-11.7 ms in place (PERF.md §6, PR 35, PR 43: a w30 ket has no
      room for a second).
    """
    from . import pallas_kernels as pk

    return _lowering(structure, backend, min(pk.DEFAULT_BLOCK_POW, n),
                     pk.plan_counts)


# rows the two packed operand columns of a window may have where Mosaic
# compiles it: an (N, 1) column in SMEM pads every entry to 512 bytes
# and a v5e core has 1 MiB, so the chip's compiler refuses 2048 rows (64
# ``u4``: RESOURCE_EXHAUSTED, tests/test_chip_compile.py) and takes the
# 1024 of the default bound's widest window.  Three quarters of SMEM:
# only a ``QRACK_TPU_FUSE_WINDOW`` above 32 reaches it
SMEM_OPERAND_ROWS = 1536


def kernel_runs(structure: Tuple, bp: int, split_at: int = None) -> List:
    """``[(bp, local, run), ...]``: the runs of diagonal ops the kernel
    lowering applies as one operator each for a window
    (``pallas_kernels.window_runs`` for tiles of ``bp`` bits), in the
    order their plans lie behind the masks in ``iv``.  ``local`` is
    None in the dense layout; with ``split_at`` (``structure`` then the
    sharded one) it is the local run whose per-page kernel holds the
    run, whose slots are that kernel's ops (_sharded_run_structure)."""
    from . import pallas_kernels as pk

    if split_at is None:
        return [(bp, None, run) for run in pk.window_runs(structure, bp)]
    return [(bp, local, run)
            for kind, local in _sharded_segments(structure, split_at)
            if kind == "run"
            for run in pk.window_runs(
                _sharded_run_structure(local, split_at), bp)]


def _run_plan_rows(ops: Sequence[FusedOp], runs, split_at: int) -> List[int]:
    """The rows behind a kernel window's masks in ``iv``: the plan of
    each of ``runs`` (kernel_runs; ``pallas_kernels.run_planner``).
    Which ops of a run share a slot depends on where their controls
    lie, so the host plans from the masks as the run's kernel reads
    them: a per-page kernel's are host values though its payloads are
    not (_sharded_run_masks), and its plans ride the window's ``iv``
    through to it (_sharded_run_operands)."""
    from . import pallas_kernels as pk

    rows, masks = [], {}
    for bp, local, run in runs:
        if id(local) not in masks:
            masks[id(local)] = (
                [(op.cmask, op.cval) for op in ops] if local is None
                else _sharded_run_masks(ops, local, split_at))
        rows += pk.run_planner(run, masks[id(local)], bp)[0]
    return rows


def _lowering(structure: Tuple, backend, bp: int, counts,
              split_at: int = None):
    """The choice both lowerings share; ``counts(structure, bp)`` gives
    the plan's ``(sweeps, cross, dense, paired)``.  A window whose operand
    columns (``split_at``: in the sharded layout), its runs' plans
    among them, would not fit a chip's SMEM takes the chain (reason
    ``smem_operands``) where the chip's compiler would refuse its
    program."""
    mode = kernel_mode()
    if mode == "off":
        return None, "mode_off"
    if backend is None:
        backend = jax.default_backend()
    from . import pallas_kernels as pk

    runs = kernel_runs(structure, bp, split_at)
    if backend == "tpu":
        _, floats, ints = pk._operand_slots(structure, split_at is not None)
        plans = sum(pk._PLAN_HEAD + len(run) for _, _, run in runs)
        if floats + ints + plans > SMEM_OPERAND_ROWS:
            return None, "smem_operands"
    sweeps, cross, dense, paired = counts(structure, bp)
    plan = {"interpret": backend != "tpu", "block_pow": bp,
            "sweeps": sweeps, "cross": cross, "dense": dense,
            "paired": paired, "twoq": pk.twoq_counts(structure, bp),
            "runs": runs}
    if mode == "on":
        return plan, None
    if backend != "tpu":
        return None, "cpu_backend"
    return plan, None


def kernel_window_program(n: int, structure: Tuple, dtype,
                          interpret: bool = False,
                          block_pow: int = None):
    """The Pallas twin of :func:`dense_window_program`: one guarded
    jitted program per (lowering, width, dtype, structure) in the SAME
    shared cache — same-structure windows with different angles are a
    compile.fuse hit on this path too."""
    from . import pallas_kernels as pk

    bp = min(pk.DEFAULT_BLOCK_POW, n) if block_pow is None else block_pow
    key = ("kernel", "interp" if interpret else "mosaic", bp, n,
           str(jnp.dtype(dtype)), structure)

    def build():
        return _res.instrument_dispatch(
            "tpu.fuse.flush",
            _tele.instrument_jit("fuse.window", stored_program(
                key, lambda: pk.make_window_fn(n, structure, block_pow=bp,
                                               interpret=interpret),
                donate_argnums=(0,))))

    return PROGRAMS.get_or_build(key, timed_build(build))


# what the kernel lowered for a flushed window, by counter under
# ``fuse.kernel.``: pallas_kernels.diag_run_counts, then .stretch_counts
KERNEL_WINDOW_COUNTERS = ("diag_runs", "diag_run.ops", "diag_run.tile_ops",
                          "diag_run.folded_ops",
                          "stretches", "stretch.ops", "stretch.passes",
                          "whole_tile_ops")


def count_kernel_window(ops: Sequence[FusedOp], block_pow: int,
                        split_at: int = None) -> dict:
    """``{counter: count}`` over KERNEL_WINDOW_COUNTERS for a flushed
    window: ``pallas_kernels.diag_run_counts`` (the runs of diagonal ops
    the kernel applies through a phase tile, and those of their ops
    with a high part that it folds into a slot's accumulators) and
    ``.stretch_counts`` (the stretches of in-tile ops between runs that
    it applies chunk by chunk, their ops and passes, and the ops it
    still applies on a whole tile), from the structure and the masks the
    host packed: the dense layout's, or with ``split_at`` those of each
    per-page kernel run (_sharded_run_masks).  Host work only."""
    from . import pallas_kernels as pk

    def counts(structure, masks):
        return (pk.diag_run_counts(structure, masks, block_pow)
                + pk.stretch_counts(structure, block_pow))

    if split_at is None:
        total = counts(structure_of(ops), [(op.cmask, op.cval) for op in ops])
    else:
        total = (0,) * len(KERNEL_WINDOW_COUNTERS)
        for kind, run in _sharded_segments(sharded_structure_of(ops), split_at):
            if kind == "run":
                total = tuple(map(sum, zip(total, counts(
                    _sharded_run_structure(run, split_at),
                    _sharded_run_masks(ops, run, split_at)))))
    return dict(zip(KERNEL_WINDOW_COUNTERS, total))


def record_kernel_flush(name: str, nops: int, sweeps: int,
                        width=None, esize: int = 4, cross: int = 0,
                        dense: int = 0, paired: int = 0, twoq=None,
                        lowered=None) -> None:
    """A window flushed through the Pallas kernel: count it, the HBM
    sweeps it actually paid (telemetry_report derives sweeps/window),
    how many of them were cross-tile pair segments, how many computed
    on the dense tile and how many second leads joined a segment (each
    a sweep not paid); with ``twoq`` (the plan's), its
    two-target ops and the sweeps that carried them, by placement; with
    ``lowered`` (a call that gives :func:`count_kernel_window`, made
    only while telemetry is on), the runs of diagonal ops the kernel
    applied through a phase tile and the stretches it applied chunk by
    chunk.
    Callers that supply the plane width also feed the sweep's planned
    bytes into the roofline ledger (`roofline.tpu.fuse.flush.*`)."""
    if _tele._ENABLED:
        _tele.inc("fuse.kernel.windows")
        _tele.inc("fuse.kernel.ops", nops)
        _tele.inc("fuse.kernel.sweeps", sweeps)
        _tele.inc("fuse.kernel.sweeps.cross", cross)
        _tele.inc("fuse.kernel.sweeps.dense", dense)
        _tele.inc("fuse.kernel.leads.paired", paired)
        for key, count in (twoq or {}).items():
            if count:
                _tele.inc(f"fuse.kernel.twoq.{key}", count)
        if lowered is not None:
            for key, count in lowered().items():
                if count:
                    _tele.inc(f"fuse.kernel.{key}", count)
        if width is not None:
            _roofline.note_bytes(
                "tpu.fuse.flush",
                sweeps * _roofline.plane_pass_bytes(width, esize))


def record_xla_flush(name: str, nops: int,
                     width=None, esize: int = 4) -> None:
    """A multi-op window flushed through the XLA op chain (~one sweep
    per op)."""
    if _tele._ENABLED:
        _tele.inc("fuse.xla.windows")
        _tele.inc("fuse.xla.ops", nops)
        _tele.inc("fuse.xla.sweeps", nops)
        if width is not None:
            _roofline.note_bytes(
                "tpu.fuse.flush",
                nops * _roofline.plane_pass_bytes(width, esize))


def record_kernel_fallback(reason: str) -> None:
    if _tele._ENABLED:
        _tele.inc(f"fuse.kernel.fallback.{reason}")


# ---------------------------------------------------------------------------
# communication-minimizing qubit remapping (mpiQulacs discipline,
# arXiv:2203.16044): the pager keeps a logical->physical placement table
# and the planner below swaps hot globally-placed target qubits into the
# local range before a window flushes, so runs of high-order gates
# execute as local sweeps.  The swaps lower into the SAME shard_map
# program as the window (apply_remap prologue), so a remapped span is
# still one dispatch.
# ---------------------------------------------------------------------------

def remap_mode() -> str:
    """``QRACK_TPU_REMAP``: auto (default — plan remaps on multi-page
    pagers), on (alias of auto; reserved for future forced-eager
    variants), off (identity table, PR 9 exchange behavior)."""
    v = os.environ.get("QRACK_TPU_REMAP", "auto").strip().lower()
    return v if v in ("auto", "on", "off") else "auto"


#: exchange cost of one paged-target 2x2, in units of state nbytes
#: (half a page out + half back, summed over pages)
GEN_GLOBAL_COST = 1.0
#: exchange cost of one remap transposition touching a page bit when it
#: ships alone (a 1-pair batch, or a page-page transposition: half the
#: state).  The planner's deferral ceiling: a hit that can wait for a
#: later prologue is never worth more than this.
REMAP_PAIR_COST = 0.5


def batched_exchange_cost(gbits, weights=None) -> float:
    """Cost of one k-pair batched mixed exchange over page bits
    ``gbits``, in state-nbytes units: sum over the 2^k - 1 non-zero
    XOR offsets of 2^-k, each priced at the most expensive page-bit
    axis it crosses (uniform weights give 1 - 2^-k)."""
    k = len(gbits)
    if not k:
        return 0.0
    tot = 0.0
    for d in range(1, 1 << k):
        w = 1.0
        if weights:
            w = max(weights[gbits[j]] for j in range(k) if (d >> j) & 1)
        tot += w
    return tot / (1 << k)


def plan_remaps(ops: Sequence[FusedOp], L: int, qmap: Sequence[int],
                lookahead=None, weights=None):
    """Score the pending window (+ multi-window lookahead) and pick
    placement swaps that turn globally-placed gen targets into local
    sweeps.  Returns ``(swaps, new_qmap)``: PHYSICAL transpositions for
    the window prologue and the table after them.  cphase/diag are
    collective-free at any placement, so only non-diagonal hits score.

    The cost model (units of state nbytes, scaled by the per-page-bit
    ``weights`` when the mesh spans DCN): all k mixed pairs
    of one prologue ship together for ``batched_exchange_cost`` — the
    marginal pair is nearly free — so candidates are ranked jointly.  A
    hot global's benefit is its in-window hits (which MUST otherwise pay
    GEN_GLOBAL_COST each, this window) plus lookahead hits capped at
    REMAP_PAIR_COST (deferring to a later prologue never costs more
    than a 1-pair batch).  A victim's charge is the same quantity for
    the hits it will pay from the inherited global slot.  The best
    hot-desc/cold-asc prefix with positive net fires as ONE batch.

    When ``weights`` are non-uniform (multi-host mesh: DCN bits cost
    more than ICI bits, parallel/cluster.py page_bit_weights) a second
    pass swaps hot global qubits off expensive page bits onto cheaper
    ones — pure page-bit transpositions that fold into the same
    prologue's composed page permutation."""
    n = len(qmap)
    if L >= n:
        return (), list(qmap)
    win = [0.0] * n
    look = [0.0] * n
    for op in ops:
        if op.kind in ("gen", "inv") and op.target < n:
            win[op.target] += 1.0
    if lookahead:
        for kind, target in lookahead:
            if kind in ("gen", "inv") and 0 <= target < n:
                look[target] += 1.0

    def wt(pos):
        if weights is None or pos < L:
            return 1.0
        return weights[pos - L]

    new_qmap = list(qmap)
    swaps = []
    def worth(q, pos):
        return (win[q] * GEN_GLOBAL_COST
                + min(look[q], REMAP_PAIR_COST)) * wt(pos)

    hot = sorted(((worth(q, new_qmap[q]), q) for q in range(n)
                  if new_qmap[q] >= L and (win[q] or look[q])),
                 key=lambda t: (-t[0], t[1]))
    # equally cold victims: the highest local position first.  The top k
    # local bits are the batched exchange's carriers (sharded.
    # plan_exchange), so a victim there needs no pass over the page to
    # get onto one and none to get off it afterwards
    cold = sorted(((win[q] * GEN_GLOBAL_COST + min(look[q],
                                                   REMAP_PAIR_COST), q)
                   for q in range(n) if new_qmap[q] < L),
                  key=lambda t: (t[0], -new_qmap[t[1]]))
    best_k, best_net = 0, 0.0
    for k in range(1, min(len(hot), len(cold)) + 1):
        gbits = [new_qmap[q] - L for _, q in hot[:k]]
        net = -batched_exchange_cost(gbits, weights)
        for (ben, hq), (esc, cq) in zip(hot[:k], cold[:k]):
            net += ben - esc * wt(new_qmap[hq])
        if net > best_net + 1e-9:
            best_k, best_net = k, net
    for (_, hq), (_, cq) in zip(hot[:best_k], cold[:best_k]):
        p_g, p_v = new_qmap[hq], new_qmap[cq]
        swaps.append((p_v, p_g))
        new_qmap[hq], new_qmap[cq] = p_v, p_g
    if weights is not None and len(set(weights)) > 1:
        h = [win[q] + look[q] for q in range(n)]
        used = {p - L for pair in swaps for p in pair if p >= L}
        while True:
            best = None
            for q in range(n):
                pq = new_qmap[q]
                if pq < L or (pq - L) in used or h[q] <= 0:
                    continue
                for r in range(n):
                    pr = new_qmap[r]
                    if r == q or pr < L or (pr - L) in used:
                        continue
                    gain = ((h[q] - h[r]) * (wt(pq) - wt(pr))
                            - REMAP_PAIR_COST * max(wt(pq), wt(pr)))
                    if gain > 1e-9 and (best is None or gain > best[0]):
                        best = (gain, q, r)
            if best is None:
                break
            _, q, r = best
            pq, pr = new_qmap[q], new_qmap[r]
            swaps.append((pr, pq))
            new_qmap[q], new_qmap[r] = pr, pq
            used.add(pq - L)
            used.add(pr - L)
    return tuple(swaps), new_qmap


def translate_ops(ops: Sequence[FusedOp], qmap: Sequence[int]):
    """Rewrite ops from logical qubit indices to physical bit positions
    under ``qmap``.  Fresh FusedOps — the caller's (possibly re-flushed)
    window must keep its logical form for escalation replays."""
    if all(q == p for q, p in enumerate(qmap)):
        return list(ops)
    out = []
    for op in ops:
        cmask = 0
        cval = 0
        m = op.cmask
        q = 0
        while m:
            if m & 1:
                p = qmap[q]
                cmask |= 1 << p
                if (op.cval >> q) & 1:
                    cval |= 1 << p
            m >>= 1
            q += 1
        out.append(FusedOp(op.kind, qmap[op.target], cmask, cval, op.m))
    return out


# ---------------------------------------------------------------------------
# sharded ('pages'-mesh) parametric window lowering — QPager wraps the
# body in ONE shard_map program (parallel/pager.py _p_fuse_window), so a
# flushed window costs one dispatch regardless of how many paged-target
# exchanges it contains
# ---------------------------------------------------------------------------

def sharded_structure_of(ops: Sequence[FusedOp]) -> Tuple:
    """Pager program-cache identity.  'inv' folds into 'gen': the pager
    gate path has no invert specialization (both route through the
    local/global 2x2 kernels), so keeping them distinct would compile
    the same program twice."""
    return tuple((("gen" if op.kind == "inv" else op.kind),
                  op.target, op.cmask != 0) for op in ops)


def sharded_window_body(L: int, npg: int, structure: Tuple, remap=()):
    """Per-shard traced body fn(local, iv, fv) for one window, on the
    sharded layout of :func:`pack_operands`.  Masks arrive pre-split
    host-side into (local, page) int32 halves — same exact-past-int32
    discipline as the eager pager kernels: cphase holds 2 combined-mask
    scalars, diag/gen hold 4 split-mask scalars, and uncontrolled ops
    hold none (their masks stay static in the trace).
    ``remap`` is the planner's physical-transposition prologue — applied
    before the ops, inside the same program."""
    from . import sharded as shb

    lbits = (1 << L) - 1

    def qrack_sharded_xla_window(local, iv, fv):  # the module's name
        if remap:
            local = shb.apply_remap(local, npg, L, remap)
        views = operand_views(structure, iv, fv, split=True)
        for (kind, target, has_ctrl), (p, masks) in zip(structure, views):
            if kind == "cphase":
                if has_ctrl:
                    clo, chi = masks
                else:
                    comb = 1 << target
                    clo, chi = comb & lbits, comb >> L
                hit = ((gk.iota_for(local) & clo) == clo) & \
                      ((shb.page_id() & chi) == chi)
                one = jnp.ones((), local.dtype)
                zero = jnp.zeros((), local.dtype)
                local = gk.cmul(jnp.where(hit, p[0], one),
                                jnp.where(hit, p[1], zero), local)
                continue
            lm, lv, gm, gv = masks if has_ctrl else (0, 0, 0, 0)
            if kind == "diag":
                tmask = 1 << target
                local = shb.apply_diag(local, p[0, 0], p[0, 1], p[1, 0],
                                       p[1, 1], tmask & lbits, tmask >> L,
                                       lm, lv, gm, gv)
            elif target < L:
                local = shb.apply_local_2x2(local, p, L, target,
                                            lm, lv, gm, gv)
            else:
                local = shb.apply_global_2x2(local, p, npg, target - L,
                                             lm, lv, gm, gv)
        return local

    return qrack_sharded_xla_window


# ---------------------------------------------------------------------------
# per-page Pallas variant of the sharded window — local runs stream each
# page's shard through the single-sweep kernel; paged-target 2x2s keep
# the ppermute pair-exchange path byte-for-byte (the exchange IS the
# sweep there, and Mosaic can't express cross-device pairs anyway)
# ---------------------------------------------------------------------------

def _sharded_segments(structure: Tuple, L: int):
    """Split a sharded window structure into kernel-lowered local runs
    and pass-through global (paged-target) gens."""
    segs: List[Tuple] = []
    cur: List[Tuple] = []
    for idx, (kind, target, has_ctrl) in enumerate(structure):
        if kind == "gen" and target >= L:
            if cur:
                segs.append(("run", cur))
                cur = []
            segs.append(("global", (idx, target, has_ctrl)))
        else:
            cur.append((idx, kind, target, has_ctrl))
    if cur:
        segs.append(("run", cur))
    return segs


def _sharded_run_structure(run, L: int) -> Tuple:
    """Dense-kernel structure for one local run.  Page-level mask and
    target bits can't ride the dense masks (they sit above the shard),
    so they fold into the runtime payloads against page_id instead:
    every mapped op is 'controlled' with the LOCAL mask halves, and a
    page-bit cphase/diag degrades to a target-agnostic diag whose two
    factors are equal (d0 == d1 makes the target bit irrelevant)."""
    out = []
    for (idx, kind, target, has_ctrl) in run:
        if target >= L:  # cphase/diag on a page bit
            out.append(("diag", 0, True))
        else:
            out.append((kind, target, True))
    return tuple(out)


def _sharded_run_masks(ops: Sequence[FusedOp], run, L: int) -> List:
    """The ``(cmask, cval)`` of a local run's ops as its kernel reads
    them (_sharded_run_operands), on the host: the local halves, the
    page-level tests being in the payload."""
    lbits = (1 << L) - 1
    return [(ops[idx].cmask & lbits, ops[idx].cval & lbits)
            for idx, _, _, _ in run]


def _sharded_run_operands(run, L: int, views, pid, dtype, plan):
    """Traced per-shard ``(iv, fv)`` of one local run, in the dense
    layout of its kernel (_sharded_run_structure: every op controlled):
    local masks pass through, page-level tests collapse into the payload
    (identity payload when this page misses the page-mask).  This
    rewrite depends on ``page_id`` and so stays inside the program; the
    window's own columns (``views``) came packed from the host, and so
    did ``plan``, the rows of the run's plans (_run_plan_rows), which
    go behind its masks as they came."""
    lbits = (1 << L) - 1
    one = jnp.ones((), dtype)
    zero = jnp.zeros((), dtype)
    ident_planes = jnp.asarray(
        [[[1.0, 0.0], [0.0, 1.0]], [[0.0, 0.0], [0.0, 0.0]]], dtype)
    payloads: List = []
    ints: List = []
    for (idx, kind, target, has_ctrl) in run:
        p, masks = views[idx]
        if kind == "cphase":
            if has_ctrl:
                clo, chi = masks
            else:
                comb = 1 << target
                clo = jnp.int32(comb & lbits)
                chi = jnp.int32(comb >> L)
            page_ok = (pid & chi) == chi
            fre = jnp.where(page_ok, p[0], one)
            fim = jnp.where(page_ok, p[1], zero)
            if target < L:
                payloads.append(jnp.stack([fre, fim]))
                cm = clo & jnp.int32(~(1 << target) & lbits)
            else:
                d = jnp.stack([fre, fim])
                payloads.append(jnp.stack([d, d]))
                cm = clo
            ints += [cm, cm]
            continue
        if has_ctrl:
            lm, lv, gm, gv = masks
        else:
            lm = lv = gm = gv = jnp.int32(0)
        page_ok = (pid & gm) == gv
        if kind == "diag":
            if target < L:
                ident = jnp.asarray([[1.0, 0.0], [1.0, 0.0]], dtype)
                payloads.append(jnp.where(page_ok, p, ident))
            else:
                tb = (pid & jnp.int32((1 << target) >> L)) != 0
                d = jnp.where(tb, p[1], p[0])
                dre = jnp.where(page_ok, d[0], one)
                dim = jnp.where(page_ok, d[1], zero)
                d = jnp.stack([dre, dim])
                payloads.append(jnp.stack([d, d]))
        else:  # gen, target < L (globals were split out)
            payloads.append(jnp.where(page_ok, p, ident_planes))
        ints += [lm, lv]
    iv = jnp.stack([jnp.asarray(x, jnp.int32) for x in ints])
    fv = jnp.concatenate([q.reshape(-1) for q in payloads])
    return jnp.concatenate([iv.reshape(-1, 1), plan]), fv.reshape(-1, 1)


def sharded_kernel_counts(structure: Tuple, L: int,
                          block_pow: int) -> Tuple[int, int, int, int]:
    """``(sweeps, cross, dense, paired)`` of the per-page kernel
    lowering: one sweep per planned kernel segment inside each local run
    and one per ppermute exchange; ``cross`` counts the runs' cross-tile
    pair segments, ``dense`` their dense-tile ones and ``paired`` the
    second leads that joined one (an exchange is no kernel launch)."""
    from . import pallas_kernels as pk

    total = [0, 0, 0, 0]
    for seg in _sharded_segments(structure, L):
        if seg[0] == "global":
            total[0] += 1
        else:
            total = list(map(sum, zip(total, pk.plan_counts(
                _sharded_run_structure(seg[1], L), block_pow))))
    return tuple(total)


def sharded_kernel_lowering(L: int, structure: Tuple, backend: str = None):
    """Pager twin of :func:`kernel_lowering` — same mode/backend gates,
    sweeps counted through the run/exchange split."""
    from . import pallas_kernels as pk

    return _lowering(structure, backend, min(pk.DEFAULT_BLOCK_POW, L),
                     lambda st, bp: sharded_kernel_counts(st, L, bp),
                     split_at=L)


def sharded_kernel_window_body(L: int, npg: int, structure: Tuple,
                               block_pow: int = None,
                               interpret: bool = False, remap=()):
    """Per-shard traced body fn(local, iv, fv) — SAME sharded operand
    layout as :func:`sharded_window_body`, kernel-lowered local runs,
    with the optional remap prologue ahead of the first segment."""
    from . import pallas_kernels as pk
    from . import sharded as shb

    bp = min(pk.DEFAULT_BLOCK_POW if block_pow is None else block_pow, L)
    segments = _sharded_segments(structure, L)
    runs = {id(seg): pk.make_window_fn(L, _sharded_run_structure(seg[1], L),
                                       block_pow=bp, interpret=interpret)
            for seg in segments if seg[0] == "run"}
    # where each run's plans lie in the window's iv: behind its masks,
    # in the runs' order (pack_operands)
    plans, at = {}, pk._operand_slots(structure, split=True)[2]
    for seg in segments:
        if seg[0] == "run":
            rows = pk.run_plan_len(_sharded_run_structure(seg[1], L), bp)
            plans[id(seg)] = (at, at + rows)
            at += rows

    def qrack_sharded_kernel_window(local, iv, fv):  # the module's name
        if remap:
            local = shb.apply_remap(local, npg, L, remap)
        pid = shb.page_id()
        views = operand_views(structure, iv, fv, split=True)
        for seg in segments:
            if seg[0] == "global":
                idx, target, has_ctrl = seg[1]
                p, masks = views[idx]
                lm, lv, gm, gv = masks if has_ctrl else (0, 0, 0, 0)
                local = shb.apply_global_2x2(local, p, npg, target - L,
                                             lm, lv, gm, gv)
            else:
                start, stop = plans[id(seg)]
                local = runs[id(seg)](local, *_sharded_run_operands(
                    seg[1], L, views, pid, local.dtype, iv[start:stop]))
        return local

    return qrack_sharded_kernel_window


# ---------------------------------------------------------------------------
# the pending window
# ---------------------------------------------------------------------------

class GateStreamFuser:
    """Bounded pending-gate window attached to one engine.

    The engine's gate funnel calls :meth:`queue`; its ``_state`` (or
    codes/scales) property getter calls :meth:`flush` on every read and
    :meth:`drop` on every blind overwrite.  The engine supplies two
    hooks: ``_fuse_admit(m, target, controls) -> bool`` (can this op
    join a window?) and ``_fuse_flush(gates) -> int`` (lower + dispatch,
    returning programs dispatched).  On a flush failure the window is
    KEPT — the resilience retry/failover machinery re-reads state under
    faults.suspended(), which re-runs the flush."""

    __slots__ = ("engine", "window", "gates", "_raw", "_flushing",
                 "lookahead", "lookahead_pos", "_head")

    def __init__(self, engine, window: int):
        self.engine = engine
        self.window = window
        self.gates: List = []   # merged QCircuitGate window
        self._raw = 0           # gates queued since last flush (pre-merge)
        self._flushing = False
        # multi-window lookahead for the remap planner: (kind, target)
        # LOGICAL tuples for the gates a circuit/batch driver is about
        # to stream, consumed one entry per queued gate.  Heuristic —
        # identity-skipped gates drift the cursor, which only costs
        # planning accuracy, never correctness.
        self.lookahead = None
        self.lookahead_pos = 0
        # the gate that forced a "paged_target" flush, as the planner's
        # first lookahead entry while that flush runs
        self._head = ()

    @property
    def pending(self) -> bool:
        return bool(self.gates)

    def set_lookahead(self, entries) -> None:
        self.lookahead = tuple(entries)
        self.lookahead_pos = 0

    def clear_lookahead(self) -> None:
        self.lookahead = None
        self.lookahead_pos = 0

    def lookahead_rest(self):
        """Entries beyond the pending window (the window itself is
        scored from its lowered ops): the driver's stream or, where
        there is none, the gate that is closing the window
        (:meth:`_heads_a_window`)."""
        la = self.lookahead
        if not la:
            return self._head or None
        return la[self.lookahead_pos:] or None

    def queue(self, controls, m, target: int, perm: int) -> bool:
        """Admit one gate into the window.  Returns False (after flushing
        any pending window, to preserve order) when the op cannot join —
        the caller then dispatches it eagerly."""
        if self.lookahead is not None and self.lookahead_pos < len(self.lookahead):
            # the gate is consumed from the driver's stream either way
            # (fused or eager), so the cursor advances unconditionally
            self.lookahead_pos += 1
        if not self.engine._fuse_admit(m, target, controls):
            self.flush("ineligible")
            return False
        from ..layers.qcircuit import QCircuitGate

        if controls:
            gate = QCircuitGate.controlled(controls, target, m, perm)
        else:
            gate = QCircuitGate.single(target, m)
        self._admit(gate)
        return True

    def queue_2q(self, m4, q1: int, q2: int) -> bool:
        """Admit one uncontrolled two-qubit gate as ONE op (a
        :class:`TwoQubitGate`; index ``(bit q2 << 1) | bit q1``).  Only
        an engine whose window bodies hold the "u4" kind calls this.  As
        :meth:`queue`: False after a flush (reason ``twoq_ineligible``,
        the one flush a two-qubit call can force) where the engine does
        not admit it."""
        if not self.engine._fuse_admit(m4, (q1, q2), ()):
            self.flush("twoq_ineligible")
            return False
        self._admit(TwoQubitGate(q1, q2, m4))
        return True

    def _admit(self, gate) -> None:
        eng = self.engine
        self._append_merge(gate)
        self._raw += 1
        if _tele._ENABLED:
            _tele.inc(f"fuse.{eng._tele_name}.queued")
            _tele.gauge(f"fuse.{eng._tele_name}.queue_depth",
                        float(len(self.gates)))
        # per-LOGICAL-gate engine accounting (drift escalation cadence):
        # ticked here, not at flush, because merged-away gates (H·H)
        # never flush yet were still requested.  May itself force a
        # flush (a drift check reads the state).
        eng._fuse_tick()

    def _append_merge(self, gate) -> None:
        # QCircuit.AppendGate's peephole: walk back past disjoint-qubit
        # gates; compose onto a same-target partner (of the same controls,
        # or a diagonal gate onto one whose controls hold its own), or
        # onto a two-qubit gate whose pair holds every qubit of this one
        i = len(self.gates) - 1
        gset = set(gate.qubits())
        while i >= 0:
            g = self.gates[i]
            if g.can_merge(gate):
                if _tele._ENABLED and not isinstance(g, TwoQubitGate) \
                        and len(g.controls) > len(gate.controls):
                    # onto a partner with a larger control set (a bond's
                    # RZ onto its first CNOT: QCircuitGate.can_merge)
                    _tele.inc(f"fuse.{self.engine._tele_name}.merged.nested")
                g.merge(gate)
                if g.is_identity():
                    del self.gates[i]
                return
            if set(g.qubits()) & gset:
                break
            i -= 1
        behind = (self._singles_behind(gate)
                  if isinstance(gate, TwoQubitGate) else [])
        # a gate that would grow a full window flushes it BEFORE it is
        # admitted, and takes nothing out of it first: when the flush
        # escalates past in-place repair (DispatchGiveUp ->
        # wrapper-level failover), the failover snapshot re-runs the
        # kept window and the wrapper replays the TRIGGERING CALL on
        # the fallback — a gate living in both would apply twice.
        # Keeping the trigger out of the flushed window makes the two
        # disjoint, which is the exactly-once property the integrity
        # replay path (resilience/integrity.py) also leans on.  (A gate
        # that merged, above, grew nothing and flushed nothing.)
        if len(self.gates) - len(behind) >= self.window:
            self.flush("window_full")
            behind = []
        elif self.gates and self._heads_a_window(gate):
            self._head = (("gen", gate.target),)
            try:
                self.flush("paged_target")
            finally:
                self._head = ()
        gate = gate.clone()
        for i in behind:
            gate.absorb_behind(self.gates[i])
            del self.gates[i]
        self.gates.append(gate)

    def _heads_a_window(self, gate) -> bool:
        """True where ``gate`` is non-diagonal, its target sits on a
        page bit of the engine's placement table (QPager with the remap
        planner on; no other engine has a table), the pending window
        holds no such gate on that qubit yet and no driver primed a
        lookahead (a stream of bare gate calls: with one, the planner
        sees past the window and is left to it).  A remap prologue runs
        at a window's head alone (:func:`plan_remaps`), so a gate that
        needs one opens a window instead of landing wherever the count
        puts it.  The window it opens is young when its prologue is
        planned, so the carrier bits are cold and serve as victims (no
        pass over the page before or after the exchange); the gate never
        stays on its page bit (a full-state pair exchange) because a
        window most of which lies before it left no qubit cold; and
        window edges follow the circuit, so a periodic stream repeats
        its plan from its second period on (PERF.md section 6, PR 46).
        The window it closes is planned with this gate as its lookahead:
        a prologue that window needs anyway takes this qubit along."""
        qmap = getattr(self.engine, "_qmap", None)
        if (qmap is None or self.lookahead is not None
                or isinstance(gate, TwoQubitGate)):
            return False
        eng, target = self.engine, gate.target
        if qmap[target] < eng.local_bits or not eng._remap_active():
            return False

        def hits(g):
            return (not isinstance(g, TwoQubitGate) and g.target == target
                    and not all(mat.is_phase(m) for m in g.payloads.values()))

        return hits(gate) and not any(hits(g) for g in self.gates)

    def _singles_behind(self, gate: TwoQubitGate) -> List[int]:
        """Where the window holds an uncontrolled single-qubit gate that
        is the last to touch one of ``gate``'s qubits, highest index
        first: nothing between it and the window's end acts on its
        qubit, so it commutes up to ``gate`` and composes into it.  By
        the gates' qubits alone, as every merge."""
        found = []
        for q in gate.target:
            for i in range(len(self.gates) - 1, -1, -1):
                g = self.gates[i]
                if q in g.qubits():
                    if g.qubits() == (q,):
                        found.append(i)
                    break
        return sorted(found, reverse=True)

    def flush(self, reason: str = "read") -> None:
        """Lower + dispatch the pending window (guarded site
        ``tpu.fuse.flush``).  No-op when empty or re-entered (the
        engine's state getter fires during the flush's own dispatch).

        Elastic recovery happens HERE, not at the wrapper's failover
        replay: when the dispatch escalates and the engine can shrink
        (QPager, docs/ELASTICITY.md), re-page in place and re-dispatch
        the SAME kept window.  The re-entry guard keeps the shrink's
        state gather raw (no recursive flush), so the gathered ket
        excludes the window and the retry applies it exactly once —
        a wrapper-level replay of the *triggering call* could not
        distinguish gates already captured by the failover snapshot."""
        if not self.gates or self._flushing:
            return
        eng = self.engine
        guard = None
        if _res._ACTIVE:
            from ..resilience import integrity as _integ

            if _integ.enabled():
                guard = _integ
        self._flushing = True
        try:
            # one window, whatever the engine: parent of the engine's
            # fuse.lower / fuse.operands / fuse.dispatch
            with _tele.span("fuse.flush"):
                while True:
                    try:
                        if guard is not None:
                            # snapshot → dispatch → verify → replay:
                            # silent corruption inside the window
                            # restores the pre-flush planes and
                            # re-dispatches the SAME kept gates; repeated
                            # corruption escalates as DispatchGiveUp into
                            # the shrink path below with good planes
                            # already restored (integrity.py)
                            dispatched = guard.guarded_flush(
                                eng, lambda: eng._fuse_flush(self.gates))
                        else:
                            dispatched = eng._fuse_flush(self.gates)
                        break
                    except Exception as e:  # noqa: BLE001 — filtered below
                        from ..resilience.errors import FAILOVER_ERRORS

                        if not isinstance(e, FAILOVER_ERRORS):
                            raise
                        can_shrink = getattr(eng, "can_shrink", None)
                        if can_shrink is None or not can_shrink():
                            raise  # wrapper-level failover takes over
                        eng.shrink_pages()
        finally:
            self._flushing = False
        raw = self._raw
        self.gates = []
        self._raw = 0
        if _tele._ENABLED:
            name = eng._tele_name
            _tele.inc(f"fuse.{name}.flush.{reason}")
            _tele.inc(f"fuse.{name}.gates", raw)
            _tele.inc(f"fuse.{name}.sweeps_saved",
                      max(0, raw - int(dispatched)))
            _tele.observe(f"fuse.{name}.window_len", float(raw))
            _tele.gauge(f"fuse.{name}.queue_depth", 0.0)

    def drop(self, reason: str = "overwritten") -> None:
        """Discard the pending window — correct only when the caller is
        about to blind-overwrite the state the gates would have acted on
        (SetPermutation/SetQuantumState/checkpoint restore)."""
        if not self.gates:
            return
        n = len(self.gates)
        self.gates = []
        self._raw = 0
        if _tele._ENABLED:
            _tele.inc(f"fuse.{self.engine._tele_name}.dropped.{reason}", n)
            _tele.gauge(f"fuse.{self.engine._tele_name}.queue_depth", 0.0)


def make_fuser(engine):
    """Install-time factory: None when fusion is off (window <= 1) or the
    engine opted out (``_fuse_capable``).  With the integrity guard
    plane armed a window-1 fuser is forced even when fusion is off —
    the flush envelope is where snapshot/verify/replay lives, so
    per-gate dispatch still gets corruption repair (docs/INTEGRITY.md)."""
    if not getattr(engine, "_fuse_capable", False):
        return None
    w = window_len()
    if w <= 1:
        if _res._ACTIVE:
            from ..resilience import integrity as _integ

            if _integ.enabled():
                return GateStreamFuser(engine, 1)
        return None
    return GateStreamFuser(engine, w)
