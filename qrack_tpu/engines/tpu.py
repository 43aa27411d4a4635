"""QEngineTPU: dense state vector in TPU HBM as split real/imag planes.

The TPU-native successor of the reference's GPU engines (reference:
include/qengine_opencl.hpp:168 QEngineOCL / qengine_cuda.hpp). Design
mapping (SURVEY.md §7 step 4):

  * The reference's QueueItem chain + event callbacks (opencl.cpp:412)
    become JAX async dispatch: every void gate op returns immediately,
    device work is ordered by data dependence, and only non-void ops
    (Prob/M/amplitude reads) synchronize — the reference's
    clFinish-on-read discipline (opencl.cpp:329).
  * The 8 apply2x2 kernel variants (opencl.cpp:810-1016) collapse into
    three jitted XLA program families (generic/diagonal/invert) whose
    compile-cache keys are (width, target axis) only — control
    placement, control count, and matrix values are dynamic operands.
  * Amplitudes are (2, 2^n) float32 planes (TPUs have no complex ALU;
    see ops/gatekernels.py). bf16 storage is a dtype switch.
  * Buffers are donated back to XLA on every gate, so the ket updates
    in place in HBM like the reference's persistent stateBuffer.
  * The OpenCL binary-kernel cache (oclengine.cpp:150-202) is XLA's
    own compilation cache.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from ..interface.alu import AluMixin
from ..ops import alu_kernels as alu
from ..ops import gatekernels as gk
from ..ops import register_kernels as rk
from .qengine import QEngine
from .. import matrices as mat
from .. import telemetry as _tele
from ..telemetry import roofline as _roofline
from .. import resilience as _res


# ---------------------------------------------------------------------------
# module-level jitted programs, shared by every engine instance.  The
# telemetry wrapper classifies each call as compile.<name>.miss (the jit
# cache grew — XLA compiled) or .hit; with telemetry disabled it is a
# single boolean test over the raw jitted callable.  The resilience
# wrapper outside it guards the whole compile-or-dispatch at site
# "tpu.compile" (watchdog / retry / breaker) — same off-by-default
# one-boolean-test discipline.
# ---------------------------------------------------------------------------

def _jit(name, fn, **kw):
    return _res.instrument_dispatch(
        "tpu.compile", _tele.instrument_jit(f"tpu.{name}", jax.jit(fn, **kw)))


def _device_get(fn, *args):
    """Host-read boundary (site "tpu.device_get"): the sync that
    proves completion whatever block_until_ready does — and therefore
    the one that hangs when the backend hangs mid-flight."""
    with _tele.span("engine.read"):
        if _res._ACTIVE:
            out = _res.call_guarded("tpu.device_get", fn, args)
            from ..resilience import integrity as _integ

            if _integ.enabled():
                # boundary invariant piggybacked on the value the caller
                # already forced to host — no extra HBM sweep
                _integ.check_host("tpu.device_get", out)
            return out
        return fn(*args)


def _discover(device_id: int):
    """jax.devices() backend init (site "discover") — the single worst
    hang site (CLAUDE.md: wedges for hours).  With resilience active it
    is breaker-gated and, under QRACK_TPU_PROBE_FIRST=1, preceded by a
    SIGTERM-first subprocess probe so the wedge is detected by a
    killable child instead of this process."""
    if device_id < 0:
        return None
    if not _res._ACTIVE:
        return jax.devices()[device_id]
    import os as _os

    if _os.environ.get("QRACK_TPU_PROBE_FIRST", "") not in ("", "0"):
        from ..resilience import probe as _probe
        from ..resilience.errors import DispatchGiveUp, DispatchTimeout

        r = _probe.ensure_backend()
        if not r.ok:
            _res.get_breaker().record_failure("discover")
            raise DispatchGiveUp(
                "discover", DispatchTimeout("discover", detail="probe failed"))
    return _res.call_guarded("discover", lambda: jax.devices()[device_id])


_j_apply_2x2 = _jit("apply_2x2", gk.apply_2x2, static_argnums=(2, 3), donate_argnums=(0,))
_j_apply_diag = _jit("apply_diag", gk.apply_diag, static_argnums=(5,), donate_argnums=(0,))
_j_apply_invert = _jit("apply_invert", gk.apply_invert, static_argnums=(5, 6), donate_argnums=(0,))
_j_apply_4x4 = _jit("apply_4x4", gk.apply_4x4, static_argnums=(2, 3, 4), donate_argnums=(0,))
_j_swap_bits = _jit("swap_bits", gk.swap_bits, static_argnums=(1, 2, 3), donate_argnums=(0,))
_j_gather = _jit("gather", gk.gather, donate_argnums=(0,))
_j_phase_apply = _jit("phase_apply", gk.phase_factor_apply, donate_argnums=(0,))
_j_prob_mask = _jit("prob_mask", gk.prob_mask_sum)
_j_normalize = _jit("normalize", gk.normalize, donate_argnums=(0,))
_j_probs = _jit("probs", gk.probs)
_j_sum_sqr_diff = _jit("sum_sqr_diff", gk.sum_sqr_diff)
_j_sample = _jit("sample", gk.sample)
_j_multishot = _jit("multishot", gk.multishot_mask_keys)
_j_uc_2x2 = _jit("uc_2x2", gk.uc_2x2, static_argnums=(2, 3, 4), donate_argnums=(0,))
# out-of-place device copy for the copy-on-write boundary below — never
# donates (its whole job is to leave the source buffer alive)
_j_copy = _jit("copy_planes", jnp.copy)


def qrack_fill(planes, perm, phase, n, dtype):
    """|perm> times `phase` as (2, 2^n) planes: one write of the ket.
    `planes` is the ket to write over — donated, never read, the result
    takes its buffer — or None, and then the one ket is allocated here.
    `perm` and `phase` are runtime operands: a new basis state never
    retraces.  The name is the compiled module's (``jit_qrack_fill``).
    Zeros and an update in place: a select on an iota keeps a predicate
    of the ket's length beside the ket (1 GiB at w30, by the compiler)."""
    return jax.lax.dynamic_update_slice(
        jnp.zeros((2, 1 << n), dtype), phase.astype(dtype)[:, None],
        (jnp.zeros_like(perm), perm))


# keyed by width and plane type (and by whether a ket is handed in);
# keep_unused: the donated ket is a parameter the result can alias
_j_fill = _jit("fill", qrack_fill, static_argnums=(3, 4),
               donate_argnums=(0,), keep_unused=True)

def qrack_alu_rotate(into, planes, shift, block_bits):
    """The ket rotated by ``shift`` inside every aligned block of
    ``2^block_bits`` amplitudes: ``out[b + m] = planes[b + (m - shift)
    mod 2^block_bits]``, ``0 <= shift < 2^block_bits``.  That is an add
    of a constant on a contiguous register (``INC(a, start, L)``:
    ``block_bits = start + L``, ``shift = a << start``): one read and
    one write of the ket, no index array.  ``shift`` is a runtime
    operand: every constant shares the program of its register's top
    bit.  The name is the compiled module's (``jit_qrack_alu_rotate``).

    A dynamic slice of the ket laid end to end with itself, which XLA
    lowers as one loop fusion that reads at the offset (compiled for a
    v5e at w28: no temporary, no ``gather``).  Where the block is not
    the whole ket the amplitudes that wrap inside their block come from
    a second slice, ``block`` further on; its ket sits behind a barrier
    so that the two slices are not one doubled ket, which XLA would
    write out (4 GiB at w28).

    A rotation cannot write the planes it reads (donated, XLA guards
    the aliased result with a whole-ket copy: two more passes).  It
    writes ``into``, a second ket that is donated, never read, and whose
    buffer the result takes, or a fresh ket where ``into`` is None."""
    size = planes.shape[-1]
    block = 1 << block_bits

    def doubled_from(x, offset):
        return jax.lax.dynamic_slice_in_dim(
            jax.lax.concatenate((x, x), 1), offset, size, axis=1)

    out = doubled_from(planes, size - shift)
    if block < size:
        idx = jax.lax.broadcasted_iota(gk.IDX_DTYPE, planes.shape, 1)
        wrapped = doubled_from(jax.lax.optimization_barrier(planes),
                               block - shift)
        out = jnp.where((idx & (block - 1)) >= shift, out, wrapped)
    return out


# keep_unused: the donated destination is a parameter the result can alias
_j_alu_rotate = _jit("alu_rotate", qrack_alu_rotate, static_argnums=(3,),
                     donate_argnums=(0,), keep_unused=True)

# below 2^7 amplitudes a block is narrower than the chip's 128 lanes and
# the index gather keeps it; at 30 qubits the doubled axis would pass
# int32 (and a ket beside its result the chip: PERF.md section 7)
ROTATE_MIN_BITS = 7
ROTATE_MAX_QB = 29

def qrack_alu_modn_slice(planes, table, n, in_start, length, out_start, ol):
    """What an out-of-place modular call reads of the ket, ``2^(n - ol)``
    amplitudes as ``(2, ...)`` planes in the ket's own order: with
    ``table`` None the amplitudes whose out register reads 0 (the
    forward calls), else, for every other bit of the index, the one
    amplitude at out register ``table[in register]`` (``IMULModNOut``).
    Where the out register is the ket's top bits the forward slice is
    the ket's first rows; elsewhere a gather of the slice's size."""
    if table is None and out_start + ol == n:
        return planes[:, :1 << out_start]
    j = jax.lax.iota(gk.IDX_DTYPE, 1 << (n - ol))
    src = ((j >> out_start) << (out_start + ol)) | (j & ((1 << out_start) - 1))
    if table is not None:
        src |= table[(src >> in_start) & ((1 << length) - 1)] << out_start
    return planes[:, src]


def qrack_alu_modn(planes, sl, table, n, in_start, length, out_start, ol,
                   kernel):
    """``out[rest, v, x] = sl[rest, x] where v == table[x], else 0``: an
    out-of-place modular call (``POWModNOut``, ``MULModNOut``; with a
    table of zeros ``IMULModNOut``) as one write of the ket from the
    slice ``qrack_alu_modn_slice`` took and a table of ``2^length``
    int32, a runtime operand: every base and modulus share the program
    of their registers.  ``planes`` is the ket to write over: donated,
    never read, its buffer the result's, as ``qrack_fill``'s.  The name
    is the compiled module's (``jit_qrack_alu_modn``).  ``kernel``: the
    body (ops/register_kernels.py), None for the view, else whether the
    Pallas one runs under the interpreter."""
    if kernel is None:
        dims, out_axis, in_axis = rk.modn_view(n, in_start, length,
                                               out_start, ol)
        return rk.modn_write_view(sl, table, planes.shape, dims, out_axis,
                                  in_axis)
    return rk.modn_write_kernel(n, out_start, ol, interpret=kernel)(
        planes, sl, rk.modn_row_table(table, in_start, length, out_start))


def qrack_prob_reg(planes, n, start, length, kernel):
    """The ``2^length`` probabilities of a contiguous register: one read
    of the planes, summed in float32 over every other bit.  The name is
    the compiled module's (``jit_qrack_prob_reg``); ``kernel`` as
    ``qrack_alu_modn``'s."""
    if kernel is None:
        return rk.prob_reg_view(planes, n, start, length)
    return rk.prob_reg_kernel(n, start, length, interpret=kernel)(planes)


def qrack_collapse(planes, mask, val, nrm_sq):
    """Projective collapse and renormalisation (reference kernels
    applym/applymreg, qengine.cl:1013-1045): the amplitudes whose masked
    bits read ``val`` scaled by ``1 / sqrt(nrm_sq)``, the rest 0, as one
    fusion over the donated ket.  The plane's number rides the index's
    sign bit, which no mask holds: a predicate of the index alone XLA
    computes once for both planes and keeps beside the ket (256 MiB at
    w28, 1 GiB at w30, compiled for a described v5e)."""
    idx = jax.lax.broadcasted_iota(gk.IDX_DTYPE, planes.shape, 1)
    plane = jax.lax.broadcasted_iota(gk.IDX_DTYPE, planes.shape, 0)
    keep = ((idx | (plane << 31)) & mask) == val
    scale = (1.0 / jnp.sqrt(nrm_sq)).astype(planes.dtype)
    return jnp.where(keep, planes * scale, jnp.zeros((), planes.dtype))


_j_alu_modn_slice = _jit("alu_modn_slice", qrack_alu_modn_slice,
                         static_argnums=(2, 3, 4, 5, 6))
# keep_unused: the donated ket is a parameter the result can alias
_j_alu_modn = _jit("alu_modn", qrack_alu_modn,
                   static_argnums=(3, 4, 5, 6, 7, 8), donate_argnums=(0,),
                   keep_unused=True)
_j_prob_reg = _jit("prob_reg", qrack_prob_reg, static_argnums=(1, 2, 3, 4))
_j_collapse = _jit("collapse", qrack_collapse, donate_argnums=(0,))


# A fresh ket is allocated behind a spacer of this many bytes, let go
# at once.  An engine is as a rule the first thing a process puts on
# the chip, and with the ket at the very start of HBM the cross-tile
# sweeps run 1.3 % slower than behind a freed stretch, into which the
# windows' small operand buffers then go: a Trotter step at w28 419.8 ms
# with no spacer, 415.6 to 417.5 with one of 64 KiB to 1.5 MiB, 417.0 to
# 417.4 with any of 2 MiB to 12 GiB (PERF.md §6, PR 43).  It has to be
# the result of a program: a pad put from the host changed nothing.
_KET_STAGGER_BYTES = 4 << 20


# ---------------------------------------------------------------------------
# plane pin registry (serve/prefix_cache.py): buffers whose identity is
# registered here were handed out as SHARED refs (a cache entry plus any
# number of seeded session engines may alias one buffer) and must NEVER
# be donated to a jitted program — donation would invalidate every other
# alias.  Keyed by id() of the jax array object — not by engine — because
# the executor's failover rollback re-assigns the SAME cached ref back
# into an engine (serve/executor.py pre_planes), and an engine-level flag
# would not survive that round trip.  A pin lives exactly as long as the
# buffer does (weakref finalizer), NOT as long as the cache entry: after
# an eviction, engines still aliasing the buffer remain protected from
# each other.  The dict is empty whenever the prefix cache is off, so the
# hot-path probe in _owned_state is one falsy check.
# ---------------------------------------------------------------------------

_PLANE_PINS: dict = {}


def pin_planes(planes) -> None:
    """Register `planes` as shared: donation sites copy-on-write."""
    if planes is None:
        return
    k = id(planes)
    if k in _PLANE_PINS:
        return
    import weakref

    try:
        _PLANE_PINS[k] = weakref.ref(
            planes, lambda _r, _k=k: _PLANE_PINS.pop(_k, None))
    except TypeError:
        _PLANE_PINS[k] = None  # unweakrefable buffer: pinned for life


def unpin_planes(planes) -> None:
    """Force-drop a pin (tests only — live aliases lose protection)."""
    if planes is not None:
        _PLANE_PINS.pop(id(planes), None)


def planes_pinned(planes) -> bool:
    return planes is not None and id(planes) in _PLANE_PINS


# one-chip dense f32 width ceiling: int32 flat indices + HBM for
# (2, 2^n) planes with gate transients (single source — the compressed
# engines derive their higher caps from it)
MAX_DENSE_QB = 30


class QEngineTPU(QEngine):
    """Dense ket on one accelerator device (TPU; CPU backend in tests)."""

    _xp = jnp
    _tele_name = "tpu"

    def __init__(self, qubit_count: int, init_state: int = 0, dtype=None,
                 device_id: int = -1, **kwargs):
        super().__init__(qubit_count, init_state=init_state, **kwargs)
        self._check_capacity(qubit_count)
        if dtype is None:
            # FPPOW policy (config.py): float32 default; float64 / bf16 /
            # f16 via QRACK_TPU_FPPOW (reference FPPOW,
            # include/common/qrack_types.hpp:88-138)
            from ..config import get_config

            dtype = get_config().device_real_dtype()
        self.dtype = jnp.dtype(dtype)  # plane dtype (f32/f64/bf16/f16)
        if self.dtype == jnp.dtype("float64") and not jax.config.jax_enable_x64:
            jax.config.update("jax_enable_x64", True)
        # f32 norm-drift escalation: every K gates compute total
        # probability; past the threshold, planes re-cast to float64 in
        # place (the deep-circuit failure class the bf16 matmul finding
        # proved matters on TPU hardware — ops/gatekernels.py PREC)
        import os as _os

        self._drift_thresh = float(_os.environ.get(
            "QRACK_TPU_AUTO_F64_DRIFT", "0"))
        self._drift_check_every = max(1, int(_os.environ.get(
            "QRACK_TPU_DRIFT_CHECK_GATES", "64")))
        self._gate_count = 0
        self._device = _discover(device_id)
        self._device_id = device_id
        # lazy gate-stream fusion (ops/fusion.py): install BEFORE the
        # first _state write so the property sees a fuser from day one
        from ..ops import fusion as _fusion

        self._fuser = _fusion.make_fuser(self)
        self._state_raw = None  # (2, 2^n) planes
        self.SetPermutation(init_state)

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------

    _fuse_capable = True

    @property
    def _state(self):
        """Resident planes.  EVERY read is a fusion boundary: a pending
        gate window flushes before the value escapes (Prob*/M*/sample/
        device_get/checkpoint capture/failover snapshot/serve batch edge
        all land here), so no reader can observe a ket that is behind
        the gate stream."""
        f = self._fuser
        if f is not None and f.gates and not f._flushing:
            f.flush("read")
        return self._state_raw

    @_state.setter
    def _state(self, planes) -> None:
        # a direct write while gates are pending is a blind overwrite
        # (SetPermutation/SetQuantumState/restore): the queued gates
        # acted on state that no longer exists — drop them.  Kernel
        # read-modify-writes never hit this: their RHS read flushed the
        # window first, and the flush's own write-back is re-entrant
        # (_flushing) so it passes straight through.
        self._drop_overwritten()
        self._state_raw = planes

    def _drop_overwritten(self) -> None:
        f = self._fuser
        if f is not None and f.gates and not f._flushing:
            f.drop("overwritten")

    def _owned_state(self):
        """The resident planes as a DONATABLE buffer.  When the serving
        prefix cache holds the current ref (_PLANE_PINS), return a fresh
        device copy and make IT resident first — copy-on-write at the
        donation boundary, so no jitted program ever consumes a buffer a
        cache entry still aliases.  One falsy dict probe when nothing is
        pinned."""
        st = self._state  # property read: flushes any pending window
        if _PLANE_PINS and id(st) in _PLANE_PINS:
            st = _j_copy(st)
            self._state_raw = st
            _tele.inc("serve.prefix.cow")
        return st

    @property
    def device_planes(self):
        """The resident (2, 2^n) split-plane ket on device.  The serving
        batcher stacks these across sessions into one (B, 2, 2^n) vmap
        operand and writes each slice back after the batched dispatch."""
        return self._state

    @device_planes.setter
    def device_planes(self, planes) -> None:
        self._state = planes

    def _check_capacity(self, qubit_count: int) -> None:
        # int32 index math and one-chip HBM both cap a dense shard at
        # MAX_DENSE_QB qubits; Compose/Allocate growth funnels through
        # this too.
        if qubit_count > MAX_DENSE_QB:
            raise MemoryError(
                f"QEngineTPU width {qubit_count} exceeds a single dense shard; "
                "use the QPager/QUnit layers above this engine"
            )

    def _put(self, arr):
        return jax.device_put(arr, self._device) if self._device is not None else jnp.asarray(arr)

    def _rand_phase(self) -> complex:
        if self.rand_global_phase:
            ang = 2.0 * math.pi * self.Rand()
            return complex(math.cos(ang), math.sin(ang))
        return 1.0 + 0.0j

    @staticmethod
    def _cmask_cval(controls, perm):
        from ..utils.bits import control_offset

        cmask = 0
        for c in controls:
            cmask |= 1 << c
        return cmask, control_offset(controls, perm)

    # ------------------------------------------------------------------
    # kernel contract
    # ------------------------------------------------------------------

    def _drift_tick(self) -> None:
        """Opt-in f32->f64 precision escalation (QRACK_TPU_AUTO_F64_DRIFT):
        every K gates read back total probability; unitary circuits keep
        it at 1, so sustained drift means the f32 planes are rotting —
        re-cast to float64 in place (QHybrid's dense halves inherit this,
        which is its precision-escalation policy).  Ticked from every
        MIXING kernel (2x2/invert/diag/4x4/uc); swaps and gathers are
        exact permutations and cannot drift the norm."""
        self._drift_tick_n(1)

    def _drift_tick_n(self, k: int) -> None:
        """Advance the drift accounting by `k` gates at once (a fused
        window applies its whole gate run in one dispatch)."""
        if self._drift_thresh <= 0 or self.dtype == jnp.dtype("float64"):
            return
        before = self._gate_count
        self._gate_count += k
        if (before // self._drift_check_every) == (
                self._gate_count // self._drift_check_every):
            return
        nrm = float(_j_prob_mask(self._state, 0, 0))  # total probability
        if abs(1.0 - nrm) > self._drift_thresh:
            self.EscalateToF64(nrm)

    def EscalateToF64(self, observed_norm: float = None) -> None:
        """Re-cast the resident planes to float64 (reference analogue:
        rebuilding at a higher FPPOW, qrack_types.hpp:88-138 — here it
        is a live dtype switch, no state round-trip).

        CAVEAT (the QRACK_TPU_AUTO_F64_DRIFT opt-in buys into this):
        float64 planes require ``jax_enable_x64``, and that flag is
        PROCESS-GLOBAL — flipping it mid-run changes default dtype
        promotion for every JAX computation in the process, not just
        this engine, and invalidates already-compiled programs (XLA
        recompiles on the next dispatch of each).  Engines created
        before the flip keep working — their f32 planes carry explicit
        dtypes — but any tracing that relied on x64-off weak-type
        defaults may see different dtypes from here on.  When the flip
        happens after tracing has begun (some program already compiled),
        an extra warning + telemetry event flags the recompile storm."""
        import warnings

        if not jax.config.jax_enable_x64:
            already_traced = False
            try:
                already_traced = _j_apply_2x2._cache_size() > 0
            except Exception:
                pass
            jax.config.update("jax_enable_x64", True)
            _tele.event("engine.tpu.x64_flip",
                        after_tracing=bool(already_traced),
                        observed_norm=observed_norm)
            if already_traced:
                warnings.warn(
                    "QRACK_TPU_AUTO_F64_DRIFT escalation enabled "
                    "jax_enable_x64 AFTER programs were already traced: "
                    "the flag is process-global, so every live jitted "
                    "program recompiles on next dispatch and non-qrack "
                    "JAX code in this process now sees x64 defaults",
                    RuntimeWarning)
        if self.dtype == jnp.dtype("float64"):
            return
        _tele.event("engine.tpu.f64_escalation",
                    observed_norm=observed_norm,
                    drift_thresh=self._drift_thresh,
                    width=self.qubit_count)
        warnings.warn(
            f"f32 norm drift {observed_norm!r} exceeded "
            f"QRACK_TPU_AUTO_F64_DRIFT={self._drift_thresh}: escalating "
            "amplitude planes to float64", RuntimeWarning)
        self.dtype = jnp.dtype(jnp.float64)
        if self._state is not None:
            self._state = self._state.astype(jnp.float64)

    # ------------------------------------------------------------------
    # fusion hooks (ops/fusion.py)
    # ------------------------------------------------------------------

    def _fuse_admit(self, m, target, controls) -> bool:
        # every 2x2 gate, and every uncontrolled two-qubit gate (target
        # a pair), lowers into a dense parametric window
        return True

    def _fuse_tick(self) -> None:
        # drift accounting advances per LOGICAL gate at queue time (the
        # eager kernels tick per dispatch; a fused window would otherwise
        # under-count merged-away gates).  A boundary crossing reads the
        # state norm, which flushes the pending window first.
        self._drift_tick()

    def _fuse_flush(self, gates) -> int:
        """Lower the pending window into ONE parametric program dispatch
        (guarded site tpu.fuse.flush).  Where the kernel lowers windows
        a window of one op is a kernel window too (one sweep in place:
        the eager program of a lone gate may hold two kets beside the
        donated one); elsewhere it reuses the shared per-gate program
        families instead of minting a one-op chain program.  Three host
        spans split the flush (docs/OBSERVABILITY.md): lower, operands,
        dispatch."""
        from ..ops import fusion as fu

        n = self.qubit_count
        plan = prog = None
        with _tele.span("fuse.lower"):
            ops = fu.lower_gates(gates)
            if ops:
                structure = fu.structure_of(ops)
                plan, why = fu.kernel_lowering(n, structure)
                if plan is not None:
                    prog = fu.kernel_window_program(
                        n, structure, self.dtype,
                        interpret=plan["interpret"],
                        block_pow=plan["block_pow"])
                elif len(ops) > 1:
                    fu.record_kernel_fallback(why)
                    prog = fu.dense_window_program(n, structure, self.dtype)
        if not ops:
            return 0
        eager = prog is None  # a lone op where no kernel lowers windows
        with _tele.span("fuse.operands"):
            if eager:
                prog, operands = self._one_op_program(ops[0])
            else:
                # two host columns, whatever the window holds: the
                # dispatch puts them on the device with the program
                operands = fu.pack_operands(
                    ops, self.dtype, runs=plan and plan["runs"])
        with _tele.span("fuse.dispatch"):
            self._state = prog(self._owned_state(), *operands)
        if _tele._ENABLED:
            # a window issues one put per operand column and its program
            _tele.inc(f"fuse.{self._tele_name}.programs",
                      1 if eager else len(operands) + 1)
        if eager:
            return 1
        esize = jnp.dtype(self.dtype).itemsize
        if plan is not None:
            fu.record_kernel_flush(self._tele_name, len(ops), plan["sweeps"],
                                   width=n, esize=esize, cross=plan["cross"],
                                   dense=plan["dense"], paired=plan["paired"],
                                   twoq=plan["twoq"],
                                   lowered=lambda: fu.count_kernel_window(
                                       ops, plan["block_pow"]))
        else:
            fu.record_xla_flush(self._tele_name, len(ops), width=n,
                                esize=esize)
        return 1

    def _one_op_program(self, op):
        """``(program, arguments after the planes)`` of a window that
        merged down to one op: the shared per-gate program families."""
        n, m = self.qubit_count, op.m
        if op.kind == "u4":
            if _tele._ENABLED:  # an eager whole-ket two-qubit program
                _tele.inc(f"gate.{self._tele_name}.4x4.w{n}")
            return _j_apply_4x4, (gk.mtrx_planes(m, self.dtype),
                                  n, *op.target)
        if op.kind in ("cphase", "diag"):
            d0, d1 = complex(m[0, 0]), complex(m[1, 1])
            return _j_apply_diag, (d0.real, d0.imag, d1.real, d1.imag,
                                   n, 1 << op.target, op.cmask, op.cval)
        if op.kind == "inv":
            tr, bl = complex(m[0, 1]), complex(m[1, 0])
            return _j_apply_invert, (tr.real, tr.imag, bl.real, bl.imag,
                                     n, op.target, op.cmask, op.cval)
        return _j_apply_2x2, (gk.mtrx_planes(m, self.dtype),
                              n, op.target, op.cmask, op.cval)

    def _k_apply_2x2(self, m2, target, controls, perm) -> None:
        cmask, cval = self._cmask_cval(controls, perm)
        if mat.is_invert(m2):
            tr, bl = m2[0, 1], m2[1, 0]
            self._state = _j_apply_invert(
                self._owned_state(), float(tr.real), float(tr.imag),
                float(bl.real), float(bl.imag),
                self.qubit_count, target, cmask, cval,
            )
        else:
            mp = gk.mtrx_planes(m2, self.dtype)
            self._state = _j_apply_2x2(self._owned_state(), mp,
                                       self.qubit_count, target, cmask, cval)
        self._drift_tick()

    def _k_apply_diag(self, d0, d1, target, controls, perm) -> None:
        cmask, cval = self._cmask_cval(controls, perm)
        d0, d1 = complex(d0), complex(d1)
        self._state = _j_apply_diag(
            self._owned_state(), d0.real, d0.imag, d1.real, d1.imag,
            self.qubit_count, 1 << target, cmask, cval,
        )
        self._drift_tick()

    def _k_apply_4x4(self, m4, q1, q2) -> None:
        mp = gk.mtrx_planes(m4, self.dtype)
        self._state = _j_apply_4x4(self._owned_state(), mp,
                                   self.qubit_count, q1, q2)
        self._drift_tick()

    # ------------------------------------------------------------------
    # the uncontrolled two-qubit gates: one funnel into the pending
    # window (ops/fusion.py, kind "u4"); the eager whole-ket programs
    # above and _k_swap_bits run only where the engine has no fuser,
    # and gate.tpu.swap / gate.tpu.4x4 count only them
    # ------------------------------------------------------------------

    def _queue_2q(self, m4, q1: int, q2: int) -> bool:
        """One op of the pending window, where there is one."""
        self._check_qubit(q1)
        self._check_qubit(q2)
        if q1 == q2:
            raise ValueError("a two-qubit gate needs two qubits")
        fuser = self._fuser
        return fuser is not None and fuser.queue_2q(m4, q1, q2)

    def Swap(self, q1: int, q2: int) -> None:
        if q1 != q2 and not self._queue_2q(mat.SWAP4, q1, q2):
            super().Swap(q1, q2)

    def ISwap(self, q1: int, q2: int) -> None:
        if q1 != q2:
            self.Apply4x4(mat.ISWAP4, q1, q2)

    def IISwap(self, q1: int, q2: int) -> None:
        if q1 != q2:
            self.Apply4x4(mat.IISWAP4, q1, q2)

    def Apply4x4(self, m, q1: int, q2: int) -> None:
        # SqrtSwap, ISqrtSwap and FSim arrive here too (interface/gates.py)
        if not self._queue_2q(m, q1, q2):
            super().Apply4x4(m, q1, q2)

    def UCMtrx(self, controls, mtrxs, target, mtrx_skip_powers=(), mtrx_skip_value_mask=0) -> None:
        """Uniformly-controlled gate in one fused kernel (reference kernel
        uniformlycontrolled, qengine.cl:409)."""
        if mtrx_skip_powers:
            return super().UCMtrx(controls, mtrxs, target, mtrx_skip_powers, mtrx_skip_value_mask)
        stack = np.stack([np.asarray(m, dtype=np.complex128).reshape(2, 2) for m in mtrxs])
        mps = jnp.stack([
            jnp.asarray(stack.real, dtype=self.dtype),
            jnp.asarray(stack.imag, dtype=self.dtype),
        ])
        self._state = _j_uc_2x2(self._owned_state(), mps, self.qubit_count,
                                target, tuple(controls))
        self._drift_tick()

    # ------------------------------------------------------------------
    # the ALU (docs/OBSERVABILITY.md): every call that runs a whole-ket
    # program of its own is a span ``engine.alu`` and one of the counters
    # ``alu.tpu.rotate`` / ``.gather`` / ``.out_of_place`` / ``.phase_fn``;
    # a comparator flip that rides the pending window instead counts
    # ``alu.tpu.phase_queued`` and runs no program
    # ------------------------------------------------------------------

    # the add family as a rotation of the planes, the comparator flips as
    # ops of the window, the out-of-place modular calls as a table write,
    # a register measured by one reduction: the compressed subclass holds
    # codes, not planes
    _alu_on_planes = True

    def _alu(self, kind: str, split=None):
        if not _tele._ENABLED:
            return _tele._NULL_SPAN
        _tele.inc(f"alu.{self._tele_name}.{kind}")
        return _tele.span("engine.alu", arg=split[0][0] if split else kind)

    # the planes the last rotation read, kept as the next one's
    # destination until a host read proves the device done
    _alu_spare = None

    def _k_rotate(self, shift: int, block_bits: int) -> None:
        """``qrack_alu_rotate`` on the resident planes (the read of
        ``_state`` flushes the pending window: an ALU call is a
        barrier).  The planes it read are the next rotation's
        destination: the host runs ahead of the device, and a result
        allocated at every dispatch stood a third ket beside the two of
        ``DEC`` before ``INC`` had run (6.0 GiB at w28; PERF.md, PR 49).
        Planes the prefix cache pinned as shared are never a destination."""
        with self._alu("rotate"):
            planes = self._state
            into, self._alu_spare = self._alu_spare, None
            # the whole-register form alone: written over a second ket a
            # block rotation took 219 ms where a fresh result takes 15.9
            # (w28; PERF.md section 7)
            whole = block_bits == self.qubit_count
            if into is not None and (
                    not whole or into.shape != planes.shape
                    or into.dtype != planes.dtype
                    or into.is_deleted() or planes_pinned(into)):
                into = None
            self._state = _j_alu_rotate(into, planes, np.int32(shift),
                                        block_bits)
            if whole and not planes_pinned(planes):
                self._alu_spare = planes
        if _tele._ENABLED:
            # one read and one write of the planes (roofline.tpu.alu.rotate.*)
            _roofline.note_bytes("tpu.alu.rotate", _roofline.plane_pass_bytes(
                self.qubit_count, jnp.dtype(self.dtype).itemsize))

    def _rotates(self, block_bits: int) -> bool:
        return (self._alu_on_planes and block_bits >= ROTATE_MIN_BITS
                and self.qubit_count <= ROTATE_MAX_QB)

    def INC(self, to_add: int, start: int, length: int) -> None:
        """An add of a constant on a contiguous register rotates the ket
        along the register's axis (``DEC`` arrives here as the add of
        the complement, interface/alu.py)."""
        if not length or not self._rotates(start + length):
            return super().INC(to_add, start, length)
        self._check_range(start, length)
        to_add &= (1 << length) - 1
        if to_add:
            self._k_rotate(to_add << start, start + length)

    def INCDECC(self, to_add: int, start: int, length: int,
                carry_index: int) -> None:
        # a carry that sits on top of its register is the register's top bit
        if length and carry_index == start + length:
            return self.INC(to_add, start, length + 1)
        super().INCDECC(to_add, start, length, carry_index)

    def _comparator_flip(self, name: str, *args) -> None:
        """A comparator's phase flip is a few multi-controlled phase
        gates (interface/alu.py's synthesis: at most 2 L cubes), which
        the window takes with runtime masks: no flush, no whole-ket
        factor arrays.  Without a fuser it keeps ``_k_phase_fn``."""
        queued = self._fuser is not None and self._alu_on_planes
        if queued and _tele._ENABLED:
            _tele.inc(f"alu.{self._tele_name}.phase_queued")
        getattr(AluMixin if queued else QEngine, name)(self, *args)

    def ZeroPhaseFlip(self, start: int, length: int) -> None:
        self._comparator_flip("ZeroPhaseFlip", start, length)

    def PhaseFlipIfLess(self, greater_perm: int, start: int, length: int) -> None:
        self._comparator_flip("PhaseFlipIfLess", greater_perm, start, length)

    def CPhaseFlipIfLess(self, greater_perm: int, start: int, length: int,
                         flag_index: int) -> None:
        self._comparator_flip("CPhaseFlipIfLess", greater_perm, start, length,
                              flag_index)

    def PhaseFlip(self) -> None:
        self._comparator_flip("PhaseFlip")

    def _k_gather(self, src_fn, split=None) -> None:
        with self._alu("gather", split):
            st = self._owned_state()
            src = src_fn(gk.iota_for(st))
            self._state = _j_gather(st, src)

    def _register_kernel(self, fits: bool):
        """The body a register program takes (ops/register_kernels.py):
        None for the view, False for the Pallas kernel (True, which only
        tests ask for, runs it under the interpreter).  The kernel is the
        chip's, on float32 planes, where ``QRACK_TPU_FUSE_KERNEL`` has
        not switched kernels off."""
        from ..ops import fusion as fu

        if (fits and jax.default_backend() == "tpu"
                and fu.kernel_mode() != "off"
                and self.dtype == jnp.dtype("float32")):
            return False
        return None

    def _takes_register(self, fits: bool) -> bool:
        """Whether a register program runs here at all.  On the chip only
        where the kernel body takes the register: the view body would
        stand a ket or two of temporaries beside the ket there, and the
        call keeps the lowering it had."""
        return self._alu_on_planes and (
            jax.default_backend() != "tpu"
            or self._register_kernel(fits) is False)

    def _modn_writes(self, in_start, length, out_start, ol) -> bool:
        """Whether an uncontrolled out-of-place modular call on these
        registers is the table write: two registers inside the ket that
        do not overlap (else the scatter, and its errors)."""
        n = self.qubit_count
        return (length > 0 and ol > 0 and 0 <= in_start and 0 <= out_start
                and in_start + length <= n and out_start + ol <= n
                and (in_start + length <= out_start
                     or out_start + ol <= in_start)
                and self._takes_register(
                    rk.modn_kernel_fits(n, in_start, length, out_start, ol)))

    def _k_modn(self, name, table, in_start, length, out_start, ol,
                inverse=False) -> None:
        """``qrack_alu_modn`` over the resident planes: the slice first,
        by a small program, then the write over the donated ket.  The
        inverse (``IMULModNOut``) takes the table into the slice, one
        amplitude a column, and writes it where the out register is 0."""
        n = self.qubit_count
        geom = (n, in_start, length, out_start, ol)
        kernel = self._register_kernel(rk.modn_kernel_fits(*geom))
        with self._alu("modn", ((name,),)):
            planes = self._owned_state()
            sl = _j_alu_modn_slice(planes, table if inverse else None, *geom)
            if inverse:
                table = np.zeros_like(table)
            self._state = _j_alu_modn(planes, sl, table, *geom, kernel)
        if _tele._ENABLED:
            # one write of the planes (roofline.tpu.alu.modn.*)
            _roofline.note_bytes("tpu.alu.modn", _roofline.plane_pass_bytes(
                n, jnp.dtype(self.dtype).itemsize) // 2)

    def _modn_out(self, name, table_of, in_start, out_start, length, mod_n,
                  inverse=False) -> bool:
        ol = self._mod_out_len(mod_n)
        if not self._modn_writes(in_start, length, out_start, ol):
            return False
        self._k_modn(name, table_of(), in_start, length, out_start, ol,
                     inverse)
        return True

    def MULModNOut(self, to_mul, mod_n, in_start, out_start, length) -> None:
        if not self._modn_out(
                "MULModNOut", lambda: alu.mulmod_table(to_mul, mod_n, length),
                in_start, out_start, length, mod_n):
            super().MULModNOut(to_mul, mod_n, in_start, out_start, length)

    def IMULModNOut(self, to_mul, mod_n, in_start, out_start, length) -> None:
        if not self._modn_out(
                "IMULModNOut", lambda: alu.mulmod_table(to_mul, mod_n, length),
                in_start, out_start, length, mod_n, inverse=True):
            super().IMULModNOut(to_mul, mod_n, in_start, out_start, length)

    def POWModNOut(self, base, mod_n, in_start, out_start, length) -> None:
        if not self._modn_out(
                "POWModNOut", lambda: alu.powmod_table(base, mod_n, length),
                in_start, out_start, length, mod_n):
            super().POWModNOut(base, mod_n, in_start, out_start, length)

    def _k_out_of_place(self, src_idx, dst_idx, passthrough_cmask) -> None:
        with self._alu("out_of_place"):
            src_idx = jnp.asarray(src_idx, dtype=gk.IDX_DTYPE)
            dst_idx = jnp.asarray(dst_idx, dtype=gk.IDX_DTYPE)
            new = jnp.zeros_like(self._state)
            if passthrough_cmask is not None:
                idx = gk.iota_for(self._state)
                keep = (idx & passthrough_cmask) != passthrough_cmask
                new = jnp.where(keep, self._state, new)
            new = new.at[:, dst_idx].set(self._state[:, src_idx])
            self._state = new

    def _k_phase_fn(self, fn, split=None) -> None:
        with self._alu("phase_fn", split):
            st = self._owned_state()
            fre, fim = fn(jnp, gk.iota_for(st))
            self._state = _j_phase_apply(st, fre, fim)

    def _host_read(self, fn):
        """``fn`` of the resident planes, read on the host (site
        ``tpu.device_get``, span ``engine.read``).  The read proves the
        device done, so the ket a rotation kept as its next destination
        is let go behind it."""
        out = _device_get(fn, self._state)
        self._alu_spare = None
        return out

    def _k_probs(self) -> np.ndarray:
        return np.asarray(_j_probs(self._state), dtype=np.float64)

    def _k_prob_mask(self, mask, perm) -> float:
        p = float(_j_prob_mask(self._state, mask, perm))
        return min(max(p, 0.0), 1.0)

    def _k_collapse(self, mask, val, nrm_sq) -> None:
        self._state = _j_collapse(self._owned_state(), mask, val, nrm_sq)
        if _tele._ENABLED:
            # one read and one write of the planes: with a register's
            # reduction the measurement's ledger (roofline.tpu.measure.*)
            _roofline.note_bytes("tpu.measure", _roofline.plane_pass_bytes(
                self.qubit_count, jnp.dtype(self.dtype).itemsize))

    def _reduces_register(self, start, length) -> bool:
        # on the chip a row of 2^10 to 2^18 amplitudes, float32 planes
        return self._takes_register(
            rk.prob_kernel_fits(self.qubit_count, start, length))

    def _k_prob_reg_all(self, start, length) -> np.ndarray:
        """``qrack_prob_reg``: the register's probabilities reduced on
        the device, ``2^length`` float32 to the host."""
        n = self.qubit_count
        kernel = self._register_kernel(rk.prob_kernel_fits(n, start, length))
        if _tele._ENABLED:  # one read of the planes
            _roofline.note_bytes("tpu.measure", _roofline.plane_pass_bytes(
                n, jnp.dtype(self.dtype).itemsize) // 2)
        return self._host_read(lambda st: np.asarray(
            _j_prob_reg(st, n, start, length, kernel), dtype=np.float64))

    def MAll(self) -> int:
        """Device-side categorical sample; no 2^n host transfer
        (reference MAll ships probabilities to host)."""
        r = float(self.Rand())
        result = self._host_read(lambda st: int(_j_sample(st, r)))
        self.SetPermutation(result)
        return result

    def MultiShotMeasureMask(self, q_powers, shots: int) -> dict:
        """Batched sampling with device-side bit compaction: the draw,
        the masked-bit gather, and the key packing are one jitted
        program; only (shots,) small ints reach the host, which then
        histograms them with one np.unique (no per-shot Python loop)."""
        from ..utils.bits import log2

        u = jnp.asarray(self.rng.uniform(shots), dtype=self.dtype)
        bits = jnp.asarray([log2(int(pw)) for pw in q_powers],
                           dtype=gk.IDX_DTYPE)
        keys = np.asarray(_j_multishot(self._state, u, bits))
        vals, counts = np.unique(keys, return_counts=True)
        return {int(v): int(c) for v, c in zip(vals, counts)}

    def _k_compose(self, other, start) -> None:
        other_planes = gk.to_planes(other.GetQuantumState(), self.dtype)
        self._state = gk.compose(
            self._state, other_planes, self.qubit_count, other.qubit_count, start
        )

    def _k_decompose(self, start, length) -> np.ndarray:
        m = gk.split_matrix(self._state, self.qubit_count, start, length)
        row_norms = jnp.sum(m[0] ** 2 + m[1] ** 2, axis=1)
        r0 = int(jnp.argmax(row_norms))
        nrm = jnp.sqrt(row_norms[r0])
        dest = m[:, r0, :] / nrm  # (2, 2^L)
        # rem = M @ conj(dest): plane algebra
        rem_re = m[0] @ dest[0] + m[1] @ dest[1]
        rem_im = m[1] @ dest[0] - m[0] @ dest[1]
        rem = jnp.stack([rem_re, rem_im])
        rn = jnp.sqrt(jnp.sum(rem[0] ** 2 + rem[1] ** 2))
        self._state = jnp.where(rn > 0, rem / rn, rem)
        return gk.from_planes(dest)

    def _k_dispose(self, start, length, perm) -> None:
        m = gk.split_matrix(self._state, self.qubit_count, start, length)
        if perm is not None:
            rem = m[:, :, perm]
        else:
            row_norms = jnp.sum(m[0] ** 2 + m[1] ** 2, axis=1)
            r0 = int(jnp.argmax(row_norms))
            dest = m[:, r0, :] / jnp.sqrt(row_norms[r0])
            rem_re = m[0] @ dest[0] + m[1] @ dest[1]
            rem_im = m[1] @ dest[0] - m[0] @ dest[1]
            rem = jnp.stack([rem_re, rem_im])
        rn = jnp.sqrt(jnp.sum(rem[0] ** 2 + rem[1] ** 2))
        self._state = jnp.where(rn > 0, rem / rn, rem)

    def _k_allocate(self, start, length) -> None:
        self._state = gk.allocate(self._state, self.qubit_count, start, length)

    def _k_normalize(self, nrm_sq) -> None:
        self._state = _j_normalize(self._owned_state(), nrm_sq)

    def _k_sum_sqr_diff(self, other) -> float:
        if isinstance(other, QEngineTPU):
            b = other._state.astype(self.dtype)
        else:
            b = gk.to_planes(other.GetQuantumState(), self.dtype)
        return float(_j_sum_sqr_diff(self._state, b))

    def _k_swap_bits(self, q1, q2) -> None:
        self._state = _j_swap_bits(self._owned_state(),
                                   self.qubit_count, q1, q2)

    def ExpectationBitsAll(self, bits, offset: int = 0) -> float:
        """One device reduction; the distribution never reaches the host."""
        return float(gk.expectation_bits(self._state, tuple(bits), offset))

    # ------------------------------------------------------------------
    # state access (host boundary: complex <-> planes)
    # ------------------------------------------------------------------

    def GetQuantumState(self) -> np.ndarray:
        return self._host_read(gk.from_planes)

    def SetQuantumState(self, state) -> None:
        st = np.asarray(state).reshape(-1)
        if st.shape[0] != (1 << self.qubit_count):
            raise ValueError("state length mismatch")
        self._state = self._put(gk.to_planes(st, self.dtype))

    def GetAmplitude(self, perm: int) -> complex:
        amp = self._host_read(
            lambda st: np.asarray(st[:, perm], dtype=np.float64))
        return complex(amp[0], amp[1])

    def SetAmplitude(self, perm: int, amp: complex) -> None:
        amp = complex(amp)
        self._state = self._state.at[:, perm].set(
            jnp.asarray([amp.real, amp.imag], dtype=self.dtype)
        )

    def SetPermutation(self, perm: int, phase=None) -> None:
        """One program writes one ket (``jit_qrack_fill``): over the ket
        the engine owns, donated and aliased to the result, or a fresh
        one where it owns none (construction; planes the prefix cache
        pinned as shared are let go, never donated).  The old ket is
        never alive beside the new: a w30 ket is half the chip."""
        ph = self._rand_phase() if phase is None else complex(phase)
        self._drop_overwritten()  # a blind overwrite, as the _state setter's
        with _tele.span("engine.set_permutation"):
            st, self._state_raw = self._state_raw, None
            args = (np.int32(perm), np.asarray([ph.real, ph.imag],
                                               dtype=self.dtype))
            if st is None or planes_pinned(st) or st.is_deleted():
                _tele.inc("engine.fill.fresh")
                st = None  # let go before the new ket is allocated
                self._state_raw = self._fresh_ket(args)
            else:
                _tele.inc("engine.fill.in_place")
                self._state_raw = _j_fill(st, *args, self.qubit_count,
                                          self.dtype)
        self.running_norm = 1.0

    def _fresh_ket(self, args):
        """The fill where the engine owns no ket: the one ket allocated,
        behind a spacer that is let go when this returns."""
        spacer = jnp.zeros((_KET_STAGGER_BYTES,), jnp.uint8,
                           device=self._device)
        # the ket goes where its operands are, and is committed there
        # from its first write: a stored window program's result is
        # (checkpoint/warmstart.stored_program: jax.export's call commits
        # what it returns), and a ket that turned committed in mid-stream
        # would meet every program after it as a new argument signature,
        # compiled a second time
        args = jax.device_put(args, next(iter(spacer.devices())))
        return _j_fill(None, *args, self.qubit_count, self.dtype)

    def Clone(self) -> "QEngineTPU":
        c = QEngineTPU(
            self.qubit_count, dtype=self.dtype, device_id=self._device_id,
            rng=self.rng.spawn(), do_normalize=self.do_normalize,
            rand_global_phase=self.rand_global_phase,
        )
        c._state = jnp.array(self._state, copy=True)
        return c

    def CloneEmpty(self) -> "QEngineTPU":
        return QEngineTPU(
            self.qubit_count, dtype=self.dtype, device_id=self._device_id,
            rng=self.rng.spawn(), do_normalize=self.do_normalize,
            rand_global_phase=self.rand_global_phase,
        )

    # -- async discipline (reference: DispatchQueue / clFinish) --

    def Finish(self) -> None:
        if self._state is not None:
            self._host_read(lambda st: st.block_until_ready())

    # -- device placement (reference: SetDevice, opencl.cpp:535) --

    def SetDevice(self, device_id: int) -> None:
        if device_id == self._device_id:
            return
        self._device = _discover(device_id)
        self._device_id = device_id
        self._state = self._put(self._state)

    def GetDevice(self) -> int:
        return self._device_id

    # -- cross-engine data plane --

    def ZeroAmplitudes(self) -> None:
        self._state = jnp.zeros_like(self._state)

    def IsZeroAmplitude(self) -> bool:
        return not bool(jnp.any(self._state != 0))

    def GetAmplitudePage(self, offset: int, length: int) -> np.ndarray:
        return self._host_read(
            lambda st: gk.from_planes(st[:, offset:offset + length]))

    def SetAmplitudePage(self, page, offset: int) -> None:
        self._state = self._state.at[:, offset:offset + len(page)].set(
            gk.to_planes(page, self.dtype)
        )

    # ------------------------------------------------------------------
    # checkpoint protocol (checkpoint/registry.py)
    # ------------------------------------------------------------------

    _ckpt_kind = "tpu"

    def _ckpt_capture(self, capture_child):
        # bf16/f16 planes upcast losslessly to f32 for the archive; the
        # device dtype string restores the resident representation
        host_dt = (np.float64 if jnp.dtype(self.dtype).itemsize >= 8
                   else np.float32)
        planes = np.asarray(jax.device_get(self._state)).astype(host_dt)
        return {"kind": "tpu",
                "meta": {"n": self.qubit_count, "dtype": str(self.dtype),
                         "gate_count": int(self._gate_count),
                         "running_norm": float(self.running_norm)},
                "arrays": {"planes": planes}}

    def _ckpt_restore(self, arrays, meta, children, restore_child):
        if int(meta["n"]) != self.qubit_count:
            raise ValueError("checkpoint width mismatch")
        self.dtype = jnp.dtype(meta["dtype"])
        if self.dtype == jnp.dtype("float64") and not jax.config.jax_enable_x64:
            jax.config.update("jax_enable_x64", True)
        self._state = self._put(jnp.asarray(np.asarray(arrays["planes"]),
                                            dtype=self.dtype))
        self._gate_count = int(meta.get("gate_count", 0))
        self.running_norm = float(meta.get("running_norm", 1.0))
