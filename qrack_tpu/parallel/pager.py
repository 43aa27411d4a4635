"""QPager: one coherent ket sharded into pages across a TPU device mesh.

TPU-native re-design of the reference's QPager (reference:
include/qpager.hpp:31; src/qpager.cpp). Mapping (SURVEY.md §2.3):

  reference                                   here
  ------------------------------------------  ---------------------------
  page i = amplitudes [i*pageMaxQPower, ...)   shard i of one jax.Array
    (src/qpager_turboquant.cpp:12-21)          NamedSharding(mesh,'pages')
  in-page gate broadcast to every page         shard_map, no collective
    (src/qpager.cpp:369-397)
  paged-qubit gate: pair pages, host-staged    lax.ppermute pair exchange
    ShuffleBuffers (src/qpager.cpp:400-447)    over ICI — the headline win
  MetaControlled page-subset selection         dynamic page-index masks
    (src/qpager.cpp:453,563)                   inside the same programs
  MetaSwap page-pointer permutation            ppermute with bit-swapped
    (src/qpager.cpp:1314-1350)                 permutation
  CombineEngines for indivisible ops           host-staged fallback
    (src/qpager.cpp:316-367, :595)             (guarded by width)

Masks are always split into (local, page) parts, and a basis state is
split on the host into (page, offset in the page): the fill
(SetPermutation), the one-amplitude read and write (GetAmplitude,
SetAmplitude), the gate programs and the fused windows build no index
of the global axis, so they are exact at any width the pages hold (a
page is at most 2^30 amplitudes: w31 on two pages, w32 on four).  What
still indexes the global axis with int32: ``_k_gather`` without a
``split=`` form and ``_k_out_of_place`` (a gather or scatter over an
axis of 2^31: OverflowError at w31), and, sound at w31 and no wider,
``_global_iota`` (``_k_phase_fn`` without a ``split=`` form) and every
window of more than one amplitude read or written at a global offset
(``_fetch`` with ``length > 1``, ``SetAmplitudePage``): PERF.md §7 has
the table.
Multi-host DCN scale-out composes by constructing the Mesh over
jax.distributed processes; the kernels are unchanged.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engines.qengine import QEngine
from ..ops import gatekernels as gk
from ..utils.bits import log2, is_pow2
from .. import matrices as mat


# ---------------------------------------------------------------------------
# cached sharded programs, keyed on (n_pages, local_width, static params).
# Bounded LRU (QRACK_QPAGER_PROGRAM_CACHE_CAP): compiled shard_map
# programs close over their mesh, so an unbounded dict pins every mesh a
# long-lived process ever built; the mesh part of each key is weakly
# tied to the mesh (see QPager._key) so entries die with it.  Hit/miss/
# eviction traffic surfaces as compile.pager.* telemetry counters.
# ---------------------------------------------------------------------------

from .. import telemetry as _tele
from ..telemetry import roofline as _roofline
from .. import resilience as _res

_PROGRAMS = _tele.ProgramCache(
    "pager", cap_env="QRACK_QPAGER_PROGRAM_CACHE_CAP", default_cap=256)


def pager_devices_from_env():
    """Device list from QRACK_QPAGER_DEVICES (reference: the same env
    selecting pager devices, src/qpager.cpp:170), or None when unset.
    Unknown ids fail loudly — a typo must not silently fall back."""
    from ..config import get_config

    spec = get_config().pager_devices.strip()
    if not spec:
        return None
    ids = [int(t) for t in spec.split(",") if t.strip()]
    if not ids:
        raise ValueError(
            f"QRACK_QPAGER_DEVICES={spec!r} contains no device ids")
    if len(set(ids)) != len(ids):
        # a Mesh with duplicate devices constructs fine and then fails
        # at first dispatch with an opaque XLA internal error
        raise ValueError(
            f"QRACK_QPAGER_DEVICES={spec!r} repeats device ids")
    by_id = {d.id: d for d in jax.devices()}
    missing = [i for i in ids if i not in by_id]
    if missing:
        raise ValueError(
            f"QRACK_QPAGER_DEVICES names unknown device ids {missing} "
            f"(available: {sorted(by_id)})")
    return [by_id[i] for i in ids]


def _program(key, builder, site: str = "pager.dispatch"):
    # the resilience wrapper is cached WITH the program, so the per-call
    # disabled cost stays one boolean test (no per-gate allocation);
    # cross-page collectives pass site="pager.exchange" so fault
    # injection / breaker accounting can tell ICI traffic from
    # page-local dispatch
    return _PROGRAMS.get_or_build(
        key, lambda: _res.instrument_dispatch(site, builder()))


def _state_specs(n_scalars: int):
    """in_specs: sharded state first, replicated scalars after."""
    return (P(None, "pages"),) + (P(),) * n_scalars


from ..ops.sharded import exchange as _exchange
from ..ops.sharded import split_masks as _split_masks  # single source of truth


def _host_read_raw(x) -> np.ndarray:
    if x.is_fully_addressable:
        return np.asarray(x)
    return np.asarray(x.addressable_shards[0].data)


def _host_read(x) -> np.ndarray:
    """Host value of a program output (site "pager.device_get" — the
    completion-proving sync that hangs when the backend hangs).

    Multi-host safe for REPLICATED outputs (out_specs=P() /
    out_shardings P()): when the mesh spans jax.distributed processes
    the array is not fully addressable, but any process-local shard of
    a replicated array holds the whole value."""
    with _tele.span("engine.read"):
        if _res._ACTIVE:
            return _res.call_guarded("pager.device_get", _host_read_raw, (x,))
        return _host_read_raw(x)


class WindowPlan(NamedTuple):
    """What :meth:`QPager._plan_window` decides about one window."""
    swaps: tuple          # the planner's physical transpositions (prologue)
    new_qmap: Sequence    # the placement table after them
    tops: Sequence        # the window's ops on that table
    structure: Optional[tuple]  # None: one op, the shared eager programs
    kernel: Optional[dict]      # the per-page kernel lowering, or None
    why: Optional[str]          # why not, where it is None

    # benchmarks/tests/test_qft_w31.py still reads it (ROADMAP C14)
    batched = property(lambda self: True)


class QPager(QEngine):
    """Paged dense engine over a 1-D 'pages' mesh axis."""

    _xp = jnp
    _tele_name = "pager"
    _fuse_capable = True  # gate stream fuses into sharded window programs

    def __init__(self, qubit_count: int, init_state: int = 0, devices=None,
                 n_pages: Optional[int] = None, dtype=None,
                 remap: Optional[str] = None,
                 dcn_bits: Optional[int] = None, **kwargs):
        super().__init__(qubit_count, init_state=init_state, **kwargs)
        if dtype is None:
            # FPPOW policy (config.py device_real_dtype; enables x64
            # for float64) — same default resolution as QEngineTPU
            from ..config import get_config

            dtype = get_config().device_real_dtype()
        if devices is None:
            devices = pager_devices_from_env() or jax.devices()
        # power-of-two device prefix (reference: page-count policy,
        # src/qpager.cpp:89-292)
        if n_pages is None:
            n_pages = 1 << log2(len(devices))
        if not is_pow2(n_pages):
            raise ValueError("n_pages must be a power of two")
        if n_pages > len(devices):
            raise ValueError(
                f"n_pages={n_pages} exceeds available devices ({len(devices)}); "
                "a JAX mesh needs distinct devices — use fewer pages (larger "
                "local shards are equivalent)"
            )
        dev_list = list(devices)[:n_pages]
        self.n_pages = n_pages
        self.g_bits = log2(n_pages)
        self._max_g = self.g_bits
        self._all_devices = dev_list
        # devices beyond the page prefix: integrity quarantine swaps one
        # in when a chip is excluded, keeping full page count when it can
        self._spare_devices = list(devices)[n_pages:]
        # last integrity-quarantine epoch acted on (healthy-path cost of
        # the job-boundary probe: one module read + int compare)
        self._quarantine_epoch = 0
        # elastic degradation marker: construction page exponent to grow
        # back to, set by shrink_pages, cleared by expand_pages (None =
        # healthy).  docs/ELASTICITY.md
        self._elastic_target_g: Optional[int] = None
        self._check_capacity(qubit_count)
        self.dtype = jnp.dtype(dtype)
        self.mesh = Mesh(np.array(dev_list), ("pages",))
        self.sharding = NamedSharding(self.mesh, P(None, "pages"))
        from ..ops import fusion as _fusion

        self._fuser = _fusion.make_fuser(self)
        self._state_raw = None
        # per-instance remap-planner override (None = QRACK_TPU_REMAP):
        # soaks/tests arm the placement table without touching process env
        self._remap = remap
        # per-instance DCN stand-in (None = QRACK_TPU_DCN_BITS / mesh
        # process topology) — same discipline
        self._dcn_bits = dcn_bits
        self._xw_mesh = None
        self._map_reset()
        self.SetPermutation(init_state)

    # ------------------------------------------------------------------

    @property
    def _state(self):
        # every read (kernel RHS, Prob*/M*, Dump, compose, snapshot)
        # forces the pending gate window out first — laziness is never
        # observable (ops/fusion.py)
        f = self._fuser
        if f is not None and f.gates and not f._flushing:
            f.flush("read")
        return self._state_raw

    def _settle(self) -> None:
        # a flush can shrink the pager in place (fusion escalation,
        # ELASTICITY.md), so kernels that build mesh-keyed operands —
        # iota, cached programs, sharding, local_bits masks — before
        # their first `_state` read must force the pending window out
        # FIRST, or the dispatch pairs a post-shrink state with
        # pre-shrink operands (mixed-device ValueError)
        f = self._fuser
        if f is not None and f.gates and not f._flushing:
            f.flush("read")

    @_state.setter
    def _state(self, local) -> None:
        # blind overwrite (SetPermutation/SetQuantumState/restore):
        # queued gates acted on state that no longer exists.  Kernel
        # read-modify-writes are unaffected — their RHS read flushed the
        # window, so the setter sees it empty.
        f = self._fuser
        if f is not None and f.gates and not f._flushing:
            f.drop("overwritten")
        self._state_raw = local

    # ------------------------------------------------------------------
    # logical->physical placement table (mpiQulacs-style qubit remapping,
    # arXiv:2203.16044).  ``_qmap[l]`` is the ket bit position holding
    # logical qubit ``l``; ``_qinv`` is the inverse.  The remap planner
    # (ops/fusion.py plan_remaps) swaps hot globally-placed qubits into
    # the local range ahead of a fused window; every host-visible read/
    # write translates through the table (docs/PERFORMANCE.md).
    # ------------------------------------------------------------------

    def _map_reset(self, n: Optional[int] = None) -> None:
        n = self.qubit_count if n is None else n
        self._qmap = list(range(n))
        self._qinv = list(range(n))

    def _map_assign(self, qmap) -> None:
        self._qmap = list(qmap)
        inv = [0] * len(self._qmap)
        for q, p in enumerate(self._qmap):
            inv[p] = q
        self._qinv = inv

    def _map_nonid(self) -> bool:
        return any(q != p for q, p in enumerate(self._qmap))

    def placement(self) -> Tuple[int, ...]:
        """The placement table: the bit position, in the planes the
        pager holds, of each logical qubit.  A read of the table alone:
        it flushes no pending window (whose prologue may still move the
        table) and undoes no remap, so what runs next is unchanged."""
        return tuple(self._qmap)

    def _map_index(self, idx: int) -> int:
        """Logical basis index -> physical basis index (exact at any
        width: pure Python ints)."""
        out = 0
        q = 0
        while idx:
            if idx & 1:
                out |= 1 << self._qmap[q]
            idx >>= 1
            q += 1
        return out

    def _unmap_index(self, idx: int) -> int:
        out = 0
        p = 0
        while idx:
            if idx & 1:
                out |= 1 << self._qinv[p]
            idx >>= 1
            p += 1
        return out

    def _map_mask(self, mask: int, val: int):
        """Translate a (mask, val) control/selection pair bitwise."""
        pm = pv = 0
        q = 0
        while mask:
            if mask & 1:
                p = self._qmap[q]
                pm |= 1 << p
                if (val >> q) & 1:
                    pv |= 1 << p
            mask >>= 1
            q += 1
        return pm, pv

    def _remap_active(self) -> bool:
        from ..ops import fusion as fu

        mode = self._remap if self._remap is not None else fu.remap_mode()
        return mode != "off" and self.n_pages > 1

    @property
    def _exchange_weights(self):
        """Per-page-bit planner weights (DCN > ICI) for the CURRENT
        mesh, or None when uniform — recomputed lazily whenever the
        mesh changes (elastic/quarantine re-paging)."""
        mesh = self.mesh
        if self._xw_mesh is not mesh:
            from . import cluster as _cluster

            self._xw = _cluster.page_bit_weights(
                list(mesh.devices.flat), dcn_bits=self._dcn_bits)
            self._xw_mesh = mesh
        return self._xw

    def _p_remap(self, swaps):
        """One program applying a batch of physical transpositions —
        free local axis shuffles, one batched mixed exchange and one
        composed page permutation (ops/sharded.py plan_exchange), all
        inside one shard_map dispatch."""
        from ..ops import sharded as shb

        L, mesh, npg = self.local_bits, self.mesh, self.n_pages

        def build():
            def f(local):
                return shb.apply_remap(local, npg, L, swaps)

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P(None, "pages"),
                out_specs=P(None, "pages")), donate_argnums=(0,))

        return _program(self._key("remap", swaps), build,
                        site="pager.exchange")

    def _tele_remap(self, swaps) -> None:
        """Count placement-transposition traffic, mirroring the lowering
        exactly (ops/sharded.py exchange_cost): a prologue ships
        (1-2^-k) of the state for k mixed pairs plus the displaced-page
        fraction of any composed page permutation."""
        if not (_tele._ENABLED and swaps):
            return
        from ..ops import sharded as shb

        L = self.local_bits
        nb = self._state_raw.nbytes
        _tele.inc("remap.pager.pairs", len(swaps))
        frac = shb.exchange_cost(L, self.g_bits, swaps)
        if frac <= 0:
            return
        if sum(1 for p1, p2 in swaps if max(p1, p2) >= L) >= 2:
            _tele.inc("remap.pager.batched")
        _tele.inc("exchange.pager.collective_bytes", frac * nb)
        # the prologue by what its lowering sends: k pairs across the
        # page boundary in one batch, then whole pages where a
        # permutation of the page bits is left over
        plan = shb.plan_exchange(L, self.g_bits, swaps)
        _tele.inc(f"remap.pager.prologues.k{plan.k}")
        if plan.page_dest is not None:
            _tele.inc("remap.pager.page_perms")
        self._tele_exchange("remap", frac * nb)

    def _unmap(self) -> None:
        """Physically restore logical bit order (identity table) in one
        remap dispatch — selection-sort cycle decomposition, <= n-1
        transpositions.  Structural reshapes and split-index kernels
        assume logical==physical and call this first."""
        self._settle()
        if not self._map_nonid():
            return
        qmap = list(self._qmap)
        qinv = list(self._qinv)
        swaps = []
        for l in range(len(qmap)):
            p = qmap[l]
            if p == l:
                continue
            o = qinv[l]
            swaps.append((l, p))
            qmap[l], qmap[o] = l, p
            qinv[l], qinv[p] = l, o
        if _tele._ENABLED:
            _tele.inc("remap.pager.unmap")
        self._tele_remap(tuple(swaps))
        self._state = self._p_remap(tuple(swaps))(self._state)
        self._map_reset()

    @property
    def local_bits(self) -> int:
        return self.qubit_count - self.g_bits

    def _check_capacity(self, qubit_count: int) -> None:
        local = qubit_count - self.g_bits
        if local < 0:
            raise ValueError(
                f"QPager width {qubit_count} smaller than page count 2^{self.g_bits}"
            )
        if local > 30:
            raise MemoryError(
                f"QPager page width {local} exceeds a single shard; "
                "add devices/pages or stack QUnit above"
            )
        if qubit_count > self.config.max_paging_qubits:
            raise MemoryError(
                f"QPager width {qubit_count} exceeds QRACK_MAX_PAGING_QB="
                f"{self.config.max_paging_qubits}"
            )

    def _rand_phase(self) -> complex:
        if self.rand_global_phase:
            ang = 2.0 * math.pi * self.Rand()
            return complex(math.cos(ang), math.sin(ang))
        return 1.0 + 0.0j

    def _split(self, mask, val=None):
        if val is None:
            val = mask
        return _split_masks(mask, val, self.local_bits)

    @staticmethod
    def _cmask_cval(controls, perm):
        from ..utils.bits import control_offset

        cmask = 0
        for c in controls:
            cmask |= 1 << c
        return cmask, control_offset(controls, perm)

    # ------------------------------------------------------------------
    # sharded kernel programs
    # ------------------------------------------------------------------

    def _key(self, *parts):
        # mesh_token == id(mesh), but weakly tied: when the mesh is
        # collected, every cached program keyed to it is dropped
        return (self.n_pages, self.local_bits,
                _PROGRAMS.mesh_token(self.mesh)) + parts

    def _tele_exchange(self, op: str, nbytes: float) -> None:
        """Count one ICI exchange dispatch and its payload bytes
        (host-side accounting of what the collective moves).  The same
        bytes enter the roofline ledger as `roofline.pager.exchange.*`,
        so the ledger's exchange accounting is the collective byte math
        by construction."""
        _tele.inc(f"exchange.pager.{op}")
        _tele.inc("exchange.pager.bytes", nbytes)
        _roofline.note_bytes("pager.exchange", nbytes)

    def _p_local_2x2(self, target):
        from ..ops import sharded as shb

        L, mesh = self.local_bits, self.mesh

        def build():
            def f(local, mp, lmask, lval, gmask, gval):
                return shb.apply_local_2x2(local, mp, L, target, lmask, lval, gmask, gval)

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=_state_specs(5), out_specs=P(None, "pages")
            ), donate_argnums=(0,))

        return _program(self._key("l2x2", target), build)

    def _p_global_2x2(self, gpos):
        from ..ops import sharded as shb

        mesh, npg = self.mesh, self.n_pages

        def build():
            def f(local, mp, lmask, lval, gmask, gval):
                return shb.apply_global_2x2(local, mp, npg, gpos, lmask, lval, gmask, gval)

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=_state_specs(5), out_specs=P(None, "pages")
            ), donate_argnums=(0,))

        return _program(self._key("g2x2", gpos), build,
                        site="pager.exchange")

    def _p_diag(self):
        from ..ops import sharded as shb

        mesh = self.mesh

        def build():
            return jax.jit(jax.shard_map(
                shb.apply_diag, mesh=mesh, in_specs=_state_specs(10),
                out_specs=P(None, "pages")
            ), donate_argnums=(0,))

        return _program(self._key("diag"), build)

    def _p_prob_mask(self):
        mesh = self.mesh

        def build():
            def f(local, lmask, lval, gmask, gval):
                pid = jax.lax.axis_index("pages")
                idx = gk.iota_for(local)
                p = local[0] ** 2 + local[1] ** 2
                ok = ((idx & lmask) == lval) & ((pid & gmask) == gval)
                return jax.lax.psum(jnp.sum(jnp.where(ok, p, 0.0)), "pages")

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=_state_specs(4), out_specs=P()
            ))

        return _program(self._key("probmask"), build)

    def _p_collapse(self):
        mesh = self.mesh

        def build():
            def f(local, lmask, lval, gmask, gval, nrm_sq):
                pid = jax.lax.axis_index("pages")
                idx = gk.iota_for(local)
                ok = ((idx & lmask) == lval) & ((pid & gmask) == gval)
                scale = (1.0 / jnp.sqrt(nrm_sq)).astype(local.dtype)
                return jnp.where(ok, local * scale, jnp.zeros((), local.dtype))

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=_state_specs(5), out_specs=P(None, "pages")
            ), donate_argnums=(0,))

        return _program(self._key("collapse"), build)

    def _p_page_probs(self):
        mesh = self.mesh

        def build():
            def f(local):
                return jnp.sum(local[0] ** 2 + local[1] ** 2).reshape(1)

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=_state_specs(0), out_specs=P("pages")
            ))

        return _program(self._key("pageprobs"), build)

    def _p_meta_swap(self, g1, g2):
        """Swap two paged qubits: pure page permutation over ICI
        (reference MetaSwap, src/qpager.cpp:1314)."""
        mesh, npg = self.mesh, self.n_pages

        def build():
            def permute(j):
                b1 = (j >> g1) & 1
                b2 = (j >> g2) & 1
                if b1 == b2:
                    return j
                return j ^ ((1 << g1) | (1 << g2))

            perm = [(j, permute(j)) for j in range(npg)]

            def f(local):
                return _exchange(local, perm)

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P(None, "pages"), out_specs=P(None, "pages")
            ), donate_argnums=(0,))

        return _program(self._key("metaswap", g1, g2), build,
                        site="pager.exchange")

    def _p_local_swap(self, q1, q2):
        L, mesh = self.local_bits, self.mesh

        def build():
            def f(local):
                return gk.swap_bits(local, L, q1, q2)

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P(None, "pages"), out_specs=P(None, "pages")
            ), donate_argnums=(0,))

        return _program(self._key("lswap", q1, q2), build)

    def _p_sum_sqr_diff(self):
        mesh = self.mesh

        def build():
            def f(a, b):
                re = jax.lax.psum(jnp.sum(a[0] * b[0] + a[1] * b[1]), "pages")
                im = jax.lax.psum(jnp.sum(a[0] * b[1] - a[1] * b[0]), "pages")
                return jnp.maximum(0.0, 1.0 - (re * re + im * im))

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=(P(None, "pages"), P(None, "pages")), out_specs=P()
            ))

        return _program(self._key("ssd"), build)

    # -- one basis state or one amplitude, by (page, offset in the page):
    #    nothing of the ket's length is an index, so these are exact at
    #    any width (an int32 holds every offset of a page) --

    def _split_index(self, phys: int):
        """A PHYSICAL basis index as the programs below take it."""
        L = self.local_bits
        return np.int32(phys >> L), np.int32(phys & ((1 << L) - 1))

    def _p_page_fill(self, owned: bool):
        """|perm> times a phase, one write of every page (module
        ``jit_qrack_page_fill``): each page writes zeros, then the
        amplitude at its offset where the page id is its own and a zero
        there where it is not.  ``owned``: the first operand is the ket
        the pager holds, donated and never read, and the result takes
        its buffers; else the one ket is allocated here.  As
        ``engines/tpu.qrack_fill``: zeros and an update in place, no
        select on an iota of the page's length."""
        L, mesh, dtype = self.local_bits, self.mesh, self.dtype

        def build():
            def qrack_page_fill(*operands):
                page, off, amp = operands[-3:]
                mine = jax.lax.axis_index("pages") == page
                amp = jnp.where(mine, amp, jnp.zeros((), dtype))[:, None]
                return jax.lax.dynamic_update_slice(
                    jnp.zeros((2, 1 << L), dtype), amp,
                    (jnp.zeros_like(off), off))

            # keep_unused: the donated ket is a parameter the result
            # can alias
            return jax.jit(jax.shard_map(
                qrack_page_fill, mesh=mesh,
                in_specs=_state_specs(3) if owned else (P(),) * 3,
                out_specs=P(None, "pages")),
                donate_argnums=(0,) if owned else (), keep_unused=True)

        return _program(self._key("pagefill", str(dtype), owned), build)

    def _p_page_read(self):
        """One amplitude, replicated: the page that holds it gives it,
        every other page a zero, summed over the mesh (legal on a mesh
        that spans processes, and a completion barrier of every page)."""
        mesh = self.mesh

        def build():
            def qrack_page_read(local, page, off):
                amp = jax.lax.dynamic_slice(
                    local, (jnp.zeros_like(off), off), (2, 1))
                mine = jax.lax.axis_index("pages") == page
                return jax.lax.psum(
                    jnp.where(mine, amp, jnp.zeros((), local.dtype)), "pages")

            return jax.jit(jax.shard_map(
                qrack_page_read, mesh=mesh, in_specs=_state_specs(2),
                out_specs=P()))

        return _program(self._key("pageread"), build)

    def _p_page_write(self):
        """One amplitude written in place on the page that holds it."""
        mesh = self.mesh

        def build():
            def qrack_page_write(local, page, off, amp):
                at = (jnp.zeros_like(off), off)
                mine = jax.lax.axis_index("pages") == page
                old = jax.lax.dynamic_slice(local, at, (2, 1))
                return jax.lax.dynamic_update_slice(
                    local, jnp.where(mine, amp[:, None], old), at)

            return jax.jit(jax.shard_map(
                qrack_page_write, mesh=mesh, in_specs=_state_specs(3),
                out_specs=P(None, "pages")), donate_argnums=(0,))

        return _program(self._key("pagewrite"), build)

    # ------------------------------------------------------------------
    # kernel contract
    # ------------------------------------------------------------------

    def _k_apply_2x2(self, m2, target, controls, perm) -> None:
        self._settle()
        cmask, cval = self._cmask_cval(controls, perm)
        if self._map_nonid():
            cmask, cval = self._map_mask(cmask, cval)
            target = self._qmap[target]
        self._apply_2x2_phys(m2, target, cmask, cval)

    def _apply_2x2_phys(self, m2, target, cmask, cval) -> None:
        """2x2 on PHYSICAL bit positions — placement already applied."""
        lmask, lval, gmask, gval = _split_masks(cmask, cval, self.local_bits)
        mp = gk.mtrx_planes(m2, self.dtype)
        if target < self.local_bits:
            self._state = self._p_local_2x2(target)(self._state, mp, lmask, lval, gmask, gval)
        else:
            gpos = target - self.local_bits
            if _tele._ENABLED:
                # pair exchange: half a page out + half back per page
                self._tele_exchange("global_2x2", self._state.nbytes)
            self._state = self._p_global_2x2(gpos)(self._state, mp, lmask, lval, gmask, gval)

    def _k_apply_diag(self, d0, d1, target, controls, perm) -> None:
        self._settle()
        cmask, cval = self._cmask_cval(controls, perm)
        if self._map_nonid():
            cmask, cval = self._map_mask(cmask, cval)
            target = self._qmap[target]
        lmask, lval, gmask, gval = _split_masks(cmask, cval, self.local_bits)
        tmask = 1 << target
        tlo = tmask & ((1 << self.local_bits) - 1)
        thi = tmask >> self.local_bits
        d0, d1 = complex(d0), complex(d1)
        self._state = self._p_diag()(
            self._state, d0.real, d0.imag, d1.real, d1.imag,
            tlo, thi, lmask, lval, gmask, gval,
        )

    # ------------------------------------------------------------------
    # gate-stream fusion hooks (ops/fusion.py GateStreamFuser)
    # ------------------------------------------------------------------

    def _fuse_admit(self, m, target, controls) -> bool:
        # every 2x2 gate lowers into the sharded window body, paged
        # targets included (the pair exchange runs inside the program)
        return True

    def _p_fuse_window(self, structure, kernel_plan=None, remap=()):
        """The window's shard_map program: the paged state, then the two
        packed operand columns (fusion.pack_operands), replicated."""
        from ..ops import fusion as fu

        L, mesh, npg = self.local_bits, self.mesh, self.n_pages

        if kernel_plan is None:
            parts = ("fusewin", str(self.dtype), structure, remap)

            def make():
                body = fu.sharded_window_body(L, npg, structure, remap=remap)
                return jax.shard_map(body, mesh=mesh,
                                     in_specs=_state_specs(2),
                                     out_specs=P(None, "pages"))
        else:
            interpret = kernel_plan["interpret"]
            bp = kernel_plan["block_pow"]
            parts = ("fusewin-k", "interp" if interpret else "mosaic", bp,
                     str(self.dtype), structure, remap)

            def make():
                body = fu.sharded_kernel_window_body(L, npg, structure,
                                                     block_pow=bp,
                                                     interpret=interpret,
                                                     remap=remap)
                # pallas_call inside shard_map trips the replication
                # checker on per-shard refs; the body is manifestly
                # per-page, so the check is safely off for this one program
                return jax.shard_map(body, mesh=mesh,
                                     in_specs=_state_specs(2),
                                     out_specs=P(None, "pages"),
                                     check_vma=False)

        def build():
            # the store's key: the in-process one less the mesh's id
            return _tele.instrument_jit("fuse.window", fu.stored_program(
                (npg, L) + parts, make, donate_argnums=(0,)))

        return _program(self._key(*parts), fu.timed_build(build),
                        site="tpu.fuse.flush")

    def _fuse_flush(self, gates) -> int:
        from ..ops import fusion as fu

        ops = fu.lower_gates(gates)
        la = self._fuser.lookahead_rest() if self._fuser is not None else None
        return self._dispatch_ops(ops, lookahead=la)

    def _run_fused_ops(self, ops) -> None:
        """RunFused entry (layers/qcircuit.py): dispatch a whole lowered
        circuit as one sharded window program, remap planning included —
        the full gate list IS the planning horizon here."""
        if not ops:
            return
        self._settle()
        self._dispatch_ops(ops)

    def _plan_window(self, ops, lookahead=None) -> WindowPlan:
        """Everything about one window of LOGICAL ops that is decided
        before its operands are packed, from the table and the ops
        alone: the planner's swaps, the ops on the table after them and
        the program's structure and kernel lowering."""
        from ..ops import fusion as fu

        L = self.local_bits
        swaps = ()
        new_qmap = self._qmap
        if self._remap_active():
            with _tele.span("remap.plan"):
                swaps, new_qmap = fu.plan_remaps(
                    ops, L, self._qmap, lookahead,
                    weights=self._exchange_weights)
        tops = (fu.translate_ops(ops, new_qmap)
                if (swaps or self._map_nonid()) else ops)
        # merged down to one op on the current placement: the shared
        # eager programs already exist and are cheaper than a fresh
        # one-op window structure
        if len(tops) == 1 and not swaps:
            return WindowPlan(swaps, new_qmap, tops, None, None, None)
        structure = fu.sharded_structure_of(tops)
        kernel, why = fu.sharded_kernel_lowering(L, structure)
        return WindowPlan(swaps, new_qmap, tops, structure, kernel, why)

    def _dispatch_ops(self, ops, lookahead=None) -> int:
        """Lower + dispatch one window of LOGICAL ops: plan placement
        swaps against the window + lookahead, translate ops onto the
        post-remap table, run remap prologue + window as ONE shard_map
        program, and commit the table only after the dispatch returns —
        shrink-retry and exception paths replan from the unchanged
        table (the kept window stays logical).  The dense engine's three
        host spans split it (lower, operands, dispatch)."""
        from ..ops import fusion as fu

        L = self.local_bits
        with _tele.span("fuse.lower"):
            swaps, new_qmap, tops, structure, plan, why = \
                self._plan_window(ops, lookahead)
            one_op = structure is None
            if not one_op:
                prog = self._p_fuse_window(structure, kernel_plan=plan,
                                           remap=swaps)
        with _tele.span("fuse.operands"):
            if one_op:
                prog, operands = self._one_op_program(tops[0])
            else:
                operands = fu.pack_operands(
                    tops, self.dtype, split_at=L,
                    runs=plan and plan["runs"])
        if _tele._ENABLED:
            # a window issues one put per operand column and its program
            _tele.inc(f"fuse.{self._tele_name}.programs",
                      1 if one_op else len(operands) + 1)
            if not one_op:
                nb = self._state.nbytes
                for kind, target, _ in structure:
                    if kind == "gen" and target >= L:
                        self._tele_exchange("global_2x2", nb)
                if swaps:
                    _tele.inc("remap.pager.windows")
                self._tele_remap(swaps)
        with _tele.span("fuse.dispatch"):
            self._state = prog(self._state, *operands)
        if one_op:
            return 1
        self._map_assign(new_qmap)
        if plan is not None:
            fu.record_kernel_flush(self._tele_name, len(ops), plan["sweeps"],
                                   width=self.qubit_count,
                                   cross=plan["cross"], dense=plan["dense"],
                                   paired=plan["paired"],
                                   lowered=lambda: fu.count_kernel_window(
                                       tops, plan["block_pow"], split_at=L))
        else:
            fu.record_kernel_fallback(why)
            fu.record_xla_flush(self._tele_name, len(ops),
                                width=self.qubit_count)
        return 1

    def _one_op_program(self, op):
        """``(program, arguments after the state)`` of a window that
        merged down to one op: the shared eager programs."""
        L = self.local_bits
        m = np.asarray(op.m)
        masks = _split_masks(op.cmask, op.cval, L)
        if op.kind in ("cphase", "diag"):
            tmask = 1 << op.target
            d0, d1 = complex(m[0, 0]), complex(m[1, 1])
            return self._p_diag(), (d0.real, d0.imag, d1.real, d1.imag,
                                    tmask & ((1 << L) - 1), tmask >> L,
                                    *masks)
        mp = gk.mtrx_planes(m, self.dtype)
        if op.target < L:
            return self._p_local_2x2(op.target), (mp, *masks)
        if _tele._ENABLED:
            self._tele_exchange("global_2x2", self._state.nbytes)
        return self._p_global_2x2(op.target - L), (mp, *masks)

    def _k_apply_4x4(self, m4, q1, q2) -> None:
        # decompose into primitive ops through the pager paths
        from ..interface.synth import apply_small_unitary_via_primitive

        apply_small_unitary_via_primitive(self, np.asarray(m4, dtype=np.complex128), (q1, q2))

    def _k_swap_bits(self, q1, q2) -> None:
        self._settle()
        L = self.local_bits
        # a Swap is a pure basis relabeling: applying the PHYSICAL
        # transposition of the two qubits' current positions implements
        # it exactly, at any table state
        p1, p2 = self._qmap[q1], self._qmap[q2]
        if p1 > p2:
            p1, p2 = p2, p1
        if p2 < L:
            self._state = self._p_local_swap(p1, p2)(self._state)
        elif p1 >= L:
            if _tele._ENABLED:
                # page-pointer permutation: the half of the pages whose
                # g1/g2 bits differ ship their whole local buffer
                self._tele_exchange("meta_swap", self._state.nbytes / 2)
            self._state = self._p_meta_swap(p1 - L, p2 - L)(self._state)
        else:
            # mixed local/global: ONE half-buffer placement transposition
            # (was 3 controlled inverts through the pair-exchange path —
            # 3 full-state exchanges vs half of one)
            if _tele._ENABLED:
                _tele.inc("remap.pager.swap")
                self._tele_exchange("remap", self._state.nbytes / 2)
            self._state = self._p_remap(((p1, p2),))(self._state)

    def _global_iota(self):
        """Sharded full-width index vector (int32-safe only to 31 qubits)."""
        n = self.qubit_count
        sh = NamedSharding(self.mesh, P("pages"))

        def build():
            # closure binds only locals: cached programs must not pin
            # engine instances (and their kets) via `self`
            return jax.jit(lambda: jax.lax.iota(gk.IDX_DTYPE, 1 << n),
                           out_shardings=sh)

        return _program(self._key("iota", n), build)()

    def _p_phase_apply(self):
        sh = self.sharding

        def build():
            return jax.jit(gk.phase_factor_apply, out_shardings=sh,
                           donate_argnums=(0,))

        return _program(self._key("phaseapply"), build)

    def _k_phase_fn(self, fn, split=None) -> None:
        # split-index diagonals compute factors from the LOGICAL basis
        # index — restore identity placement first
        self._unmap()
        if split is not None and self._wide_alu:
            self._phase_fn_wide(split)
            return
        if self.qubit_count > 31:
            raise NotImplementedError(
                "this diagonal op lacks a split-index form for >31-qubit "
                "pagers (see the `split=` forms in engines/qengine.py)")
        # factors computed eagerly (captured values stay out of any trace),
        # then applied by one cached program
        fre, fim = fn(jnp, self._global_iota())
        self._state = self._p_phase_apply()(self._state, fre, fim)

    def _phase_fn_wide(self, split) -> None:
        """Width-generic diagonal: per-shard factors from split (page,
        local) indices — collective-free and exact at any width
        (reference width-generic phase kernels, qheader_alu.cl:780-810)."""
        from ..ops import sharded as shb

        key, body, targs = split
        L, mesh = self.local_bits, self.mesh

        def build():
            def f(local, *ta):
                pid = shb.page_id()
                lidx = gk.iota_for(local)
                fre, fim = body(jnp, pid, lidx, L, *ta)
                return gk.cmul(fre, fim, local).astype(local.dtype)

            return jax.jit(jax.shard_map(
                f, mesh=mesh,
                in_specs=(P(None, "pages"),) + (P(),) * len(targs),
                out_specs=P(None, "pages"),
            ), donate_argnums=(0,))

        prog = _program(self._key("phasefw") + tuple(key), build)
        self._state = prog(self._state, *[jnp.asarray(t) for t in targs])

    def _p_gather(self):
        sh = self.sharding

        def build():
            return jax.jit(lambda s, i: s[:, i], out_shardings=sh,
                           donate_argnums=(0,))

        return _program(self._key("gather"), build)

    # test/driver hook: force the width-generic split path at any size
    force_wide_alu = False

    @property
    def _wide_alu(self) -> bool:
        return self.force_wide_alu or self.qubit_count > 31

    def _k_gather(self, src_fn, split=None) -> None:
        # basis permutations are written against logical bit order
        self._unmap()
        if not self._wide_alu:
            src = src_fn(self._global_iota())
            self._state = self._p_gather()(self._state, src)
            return
        if split is None:
            raise NotImplementedError(
                "this basis permutation lacks a split-index form for "
                ">31-qubit pagers (see alu_kernels split variants)")
        self._gather_wide(split)

    def _gather_wide(self, split) -> None:
        """Run a split-index permutation as a ring-gather program
        (reference width-generic ALU kernels, qheader_alu.cl:13-810)."""
        from ..ops import sharded as shb

        key, body, targs = split
        L, npg, mesh = self.local_bits, self.n_pages, self.mesh

        def build():
            def f(local, *ta):
                return shb.gather_ring(local, npg, L, body, ta)

            return jax.jit(jax.shard_map(
                f, mesh=mesh,
                in_specs=(P(None, "pages"),) + (P(),) * len(targs),
                out_specs=P(None, "pages"),
            ), donate_argnums=(0,))

        prog = _program(self._key("gatherw") + tuple(key), build,
                        site="pager.exchange")
        args = [jnp.asarray(t, dtype=gk.IDX_DTYPE) for t in targs]
        if _tele._ENABLED:
            # ring gather: n_pages-1 full-buffer rotations
            self._tele_exchange(
                "ring_gather", self._state.nbytes * (self.n_pages - 1))
        self._state = prog(self._state, *args)

    def _p_out_of_place(self, with_passthrough: bool):
        sh = self.sharding

        def build():
            if with_passthrough:
                def f(state, s_idx, d_idx, cmask):
                    idx = jax.lax.iota(gk.IDX_DTYPE, state.shape[-1])
                    keep = (idx & cmask) != cmask
                    new = jnp.where(keep, state, jnp.zeros((), state.dtype))
                    return new.at[:, d_idx].set(state[:, s_idx])
            else:
                def f(state, s_idx, d_idx):
                    new = jnp.zeros_like(state)
                    return new.at[:, d_idx].set(state[:, s_idx])

            return jax.jit(f, out_shardings=sh)

        return _program(self._key("oop", with_passthrough), build)

    def _k_out_of_place(self, src_idx, dst_idx, passthrough_cmask) -> None:
        if self.qubit_count > 31:
            # every public wide op routes through the split-index gather
            # forms (MUL/DIV/*ModNOut included); reaching this kernel
            # wide means a new op needs its own split form
            raise NotImplementedError("see the `split=` gather forms")
        self._unmap()
        src_idx = jnp.asarray(src_idx, dtype=gk.IDX_DTYPE)
        dst_idx = jnp.asarray(dst_idx, dtype=gk.IDX_DTYPE)
        if passthrough_cmask is not None:
            self._state = self._p_out_of_place(True)(
                self._state, src_idx, dst_idx, passthrough_cmask)
        else:
            self._state = self._p_out_of_place(False)(self._state, src_idx, dst_idx)

    def _k_probs(self) -> np.ndarray:
        self._settle()
        if self._map_nonid() or not self._state.is_fully_addressable:
            # _fetch returns the LOGICAL view (host-side unpermute)
            planes = self._fetch(0, 1 << self.qubit_count)
            return planes[0] ** 2 + planes[1] ** 2
        return np.asarray(jax.jit(gk.probs)(self._state), dtype=np.float64)

    def _k_prob_mask(self, mask, perm) -> float:
        self._settle()
        if self._map_nonid():
            # collective-free under any placement: the mask translates
            mask, perm = self._map_mask(mask, perm)
        lmask, lval, gmask, gval = _split_masks(mask, perm, self.local_bits)
        p = float(_host_read(self._p_prob_mask()(self._state, lmask, lval, gmask, gval)))
        return min(max(p, 0.0), 1.0)

    def _k_collapse(self, mask, val, nrm_sq) -> None:
        self._settle()
        if self._map_nonid():
            mask, val = self._map_mask(mask, val)
        lmask, lval, gmask, gval = _split_masks(mask, val, self.local_bits)
        self._state = self._p_collapse()(self._state, lmask, lval, gmask, gval, nrm_sq)

    def MAll(self) -> int:
        """Two-stage sample: page marginals (psum over mesh), then an
        in-page draw — only one page ever reaches the host.  The draw
        runs in PHYSICAL order (the marginals are physical) and the
        result translates back through the table."""
        self._settle()
        pp = self._p_page_probs()(self._state)
        if not pp.is_fully_addressable:
            from jax.experimental import multihost_utils

            pp = multihost_utils.process_allgather(pp, tiled=True)
        page_probs = np.asarray(pp, dtype=np.float64)
        page = int(self.rng.choice_from_probs(page_probs, 1)[0])
        L = self.local_bits
        local = self._fetch(page << L, 1 << L, raw=True)
        p_local = local[0] ** 2 + local[1] ** 2
        sub = int(self.rng.choice_from_probs(p_local, 1)[0])
        result = self._unmap_index((page << L) | sub)
        self.SetPermutation(result)
        return result

    def _k_normalize(self, nrm_sq) -> None:
        self._state = jax.jit(gk.normalize, donate_argnums=(0,))(self._state, nrm_sq)

    def _k_sum_sqr_diff(self, other) -> float:
        self._unmap()
        if isinstance(other, QPager) and other.n_pages == self.n_pages:
            other._unmap()
            b = other._state
        else:
            b = jax.device_put(gk.to_planes(other.GetQuantumState(), self.dtype), self.sharding)
        return float(_host_read(self._p_sum_sqr_diff()(self._state, b)))

    # -- structural ops: device-side sharded programs (reference rebalances
    #    pages device-side, src/qpager.cpp:316-367; here XLA/GSPMD inserts
    #    the collectives for the outer products / reductions).  Host
    #    staging survives only as the fallback when the result is so
    #    small the page mesh itself must shrink. --

    def _desired_g(self, new_width: int) -> int:
        """Page-count policy for a new width: re-grow to the construction
        page count as soon as the ket is big enough again (reference:
        SeparateEngines/CombineEngines, src/qpager.cpp:316-367)."""
        return min(self._max_g, max(new_width, 0))

    def _mesh_would_change(self, new_width: int) -> bool:
        return self._desired_g(new_width) != self.g_bits

    def _p_compose(self, n1, n2, start):
        dtype = self.dtype
        sh = self.sharding

        def build():
            hi, lo = 1 << (n1 - start), 1 << start

            def f(a, b):
                ar = a[0].reshape(hi, lo)
                ai = a[1].reshape(hi, lo)
                br, bi = b[0], b[1]
                # out[h, j, l] = a[h, l] * b[j]  (other's qubits at `start`)
                o_r = (jnp.einsum("hl,j->hjl", ar, br)
                       - jnp.einsum("hl,j->hjl", ai, bi))
                o_i = (jnp.einsum("hl,j->hjl", ar, bi)
                       + jnp.einsum("hl,j->hjl", ai, br))
                return jnp.stack([o_r.reshape(-1), o_i.reshape(-1)]).astype(dtype)

            return jax.jit(f, out_shardings=sh)

        return _program(self._key("compose", n1, n2, start), build)

    def _p_compose_ring(self, n1, n2, start):
        from ..ops import sharded as shb

        mesh, npg, L = self.mesh, self.n_pages, self.local_bits

        def build():
            def f(a, b):
                return shb.compose_ring(a, b, npg, L, start, n1, n2)

            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=(P(None, "pages"), P()),
                out_specs=P(None, "pages")), donate_argnums=(0,))

        return _program(self._key("composering", n1, n2, start), build,
                        site="pager.exchange")

    def _k_compose(self, other, start) -> None:
        self._settle()
        n1, n2 = self.qubit_count, other.qubit_count
        if self._mesh_would_change(n1 + n2):
            # ket was below the page count (tiny): host-stage the regrow
            # (_fetch returns the logical view under any placement)
            a = self._fetch(0, 1 << n1)
            a = a[0] + 1j * a[1]
            b = np.asarray(other.GetQuantumState())
            full = gk.compose(gk.to_planes(a, self.dtype),
                              gk.to_planes(b, self.dtype), n1, n2, start)
            self._state = jax.device_put(full, self._sharding_for(n1 + n2))
            self._map_reset(n1 + n2)
            return
        self._unmap()  # the outer-product reshape assumes logical order
        if (isinstance(other, QPager)
                and list(other.mesh.devices.flat) == list(self.mesh.devices.flat)):
            other._unmap()
            b = other._state  # device-to-device: same device set
        else:
            b = gk.to_planes(np.asarray(other.GetQuantumState()), self.dtype)
        if (n1 <= 31 and n2 <= self.local_bits
                and (n1 + n2 - self.g_bits) <= 31):
            # ring outer product: per-device memory bounded to one A
            # page + replicated B + the output block (reference
            # CombineEngines discipline, src/qpager.cpp:316-367) —
            # GSPMD's einsum partitioning is free to all-gather A.
            # B IS replicated here, so the path is gated on B at most
            # one page's size (n2 <= local_bits); bigger composed-in
            # states keep the einsum form, where GSPMD may shard B
            if _tele._ENABLED:
                # B is replicated; the A pages ring-rotate npg-1 times
                self._tele_exchange(
                    "compose_ring", self._state.nbytes * (self.n_pages - 1))
            new_state = self._p_compose_ring(n1, n2, start)(self._state, b)
        else:
            new_state = self._p_compose(n1, n2, start)(self._state, b)
        self._sharding_for(n1 + n2)
        self._state = new_state
        self._map_reset(n1 + n2)

    def _p_decompose(self, n, start, length, with_dest: bool):
        dtype = self.dtype
        rem_sh = self.sharding

        def build():
            hi = 1 << (n - start - length)
            mid = 1 << length
            lo = 1 << start

            def f(s):
                # layout convention matches the host oracle (gatekernels.
                # split_matrix): dominant REST branch fixes the span
                # state's phase, rem is the exact projection so that
                # rem (x) dest == state bit-for-bit on product states
                a = s.reshape(2, hi, mid, lo)
                at = a.transpose(0, 2, 1, 3).reshape(2, mid, hi * lo)
                pm = jnp.sum(at[0] ** 2 + at[1] ** 2, axis=0)  # (rest,)
                f0 = jnp.argmax(pm)
                nrm = jnp.sqrt(jnp.maximum(pm[f0], jnp.asarray(1e-30, pm.dtype)))
                dr = jnp.take(at[0], f0, axis=1) / nrm  # (mid,) span state
                di = jnp.take(at[1], f0, axis=1) / nrm
                # rem[r] = sum_m a[m, r] * conj(dest[m])
                rr = jnp.einsum("mr,m->r", at[0], dr) + jnp.einsum("mr,m->r", at[1], di)
                ri = jnp.einsum("mr,m->r", at[1], dr) - jnp.einsum("mr,m->r", at[0], di)
                rem = jnp.stack([rr, ri]).astype(dtype)
                if not with_dest:
                    return rem
                return rem, jnp.stack([dr, di])

            outs = (rem_sh, NamedSharding(self.mesh, P())) if with_dest else rem_sh
            return jax.jit(f, out_shardings=outs)

        return _program(self._key("decompose", n, start, length, with_dest), build)

    def _host_split(self, start, length, perm):
        """Host-staged split fallback (mesh shrink / tiny results)."""
        n = self.qubit_count
        planes = self._fetch(0, 1 << n)
        hi, mid, lo = 1 << (n - start - length), 1 << length, 1 << start
        a = (planes[0] + 1j * planes[1]).reshape(hi, mid, lo)
        if perm is not None:
            rem = a[:, perm, :].reshape(-1)
            dest = None
        else:
            # same convention as _p_decompose: dominant rest branch
            at = a.transpose(1, 0, 2).reshape(mid, hi * lo)
            pm = (np.abs(at) ** 2).sum(axis=0)
            f0 = int(np.argmax(pm))
            dest = at[:, f0] / math.sqrt(max(pm[f0], 1e-300))
            rem = np.einsum("mr,m->r", at, np.conj(dest))
        nrm = np.linalg.norm(rem)
        if nrm > 0:
            rem = rem / nrm
        self._state = jax.device_put(
            gk.to_planes(rem, self.dtype), self._sharding_for(n - length))
        return dest

    def _k_decompose(self, start, length) -> np.ndarray:
        self._unmap()  # the span reshape assumes logical order
        n = self.qubit_count
        if self._mesh_would_change(n - length):
            dest = self._host_split(start, length, None)
            self._map_reset(n - length)
            return dest
        rem, dest = self._p_decompose(n, start, length, True)(self._state)
        self._state = rem
        self._map_reset(n - length)
        d = np.asarray(_host_read(dest), dtype=np.float64)
        vec = d[0] + 1j * d[1]
        nrm = np.linalg.norm(vec)
        return vec / nrm if nrm > 0 else vec

    def _p_dispose_perm(self, n, start, length):
        dtype = self.dtype
        rem_sh = self.sharding

        def build():
            hi = 1 << (n - start - length)
            mid = 1 << length
            lo = 1 << start

            def f(s, perm):
                a = s.reshape(2, hi, mid, lo)
                rem = jnp.take(a, perm, axis=2).reshape(2, -1)
                nrm2 = jnp.sum(rem[0] ** 2 + rem[1] ** 2)
                rem = rem / jnp.sqrt(jnp.maximum(nrm2, jnp.asarray(1e-30, nrm2.dtype)))
                return rem.astype(dtype)

            return jax.jit(f, out_shardings=rem_sh)

        return _program(self._key("disposeperm", n, start, length), build)

    def _k_dispose(self, start, length, perm) -> None:
        self._unmap()
        n = self.qubit_count
        if self._mesh_would_change(n - length):
            self._host_split(start, length, perm)
            self._map_reset(n - length)
            return
        if perm is not None:
            self._state = self._p_dispose_perm(n, start, length)(self._state, perm)
        else:
            self._state = self._p_decompose(n, start, length, False)(self._state)
        self._map_reset(n - length)

    def _p_allocate(self, n, start, length):
        dtype = self.dtype
        sh = self.sharding

        def build():
            hi, lo = 1 << (n - start), 1 << start

            def f(s):
                a = s.reshape(2, hi, lo)
                out = jnp.zeros((2, hi, 1 << length, lo), dtype=dtype)
                out = out.at[:, :, 0, :].set(a)
                return out.reshape(2, -1)

            return jax.jit(f, out_shardings=sh)

        return _program(self._key("allocate", n, start, length), build)

    def _k_allocate(self, start, length) -> None:
        self._unmap()
        n = self.qubit_count
        new_state = self._p_allocate(n, start, length)(self._state)
        self._sharding_for(n + length)
        self._state = new_state
        self._map_reset(n + length)

    def _device_pool(self):
        """Device preference order for (re-)paging: the construction
        prefix with integrity-quarantined chips excluded, then spares —
        so a quarantined chip is replaced by a spare at the next
        re-page instead of capping capacity (docs/INTEGRITY.md).  Falls
        back to the construction list rather than return an empty pool:
        a fully-quarantined mesh still has to serve."""
        if not _res._ACTIVE:
            return self._all_devices
        from ..resilience import integrity as _integ

        q = _integ.quarantined()
        if not q:
            return self._all_devices
        pool = [d for d in self._all_devices + self._spare_devices
                if d.id not in q]
        return pool if pool else self._all_devices

    def _sharding_for(self, qubit_count):
        """Sharding for a new width: drops pages when the ket gets
        smaller than the page count and re-grows back to the
        construction page count when it recovers (reference:
        SeparateEngines/CombineEngines page-count rebalance,
        src/qpager.cpp:316-367)."""
        new_g = self._desired_g(qubit_count)
        if new_g != self.g_bits:
            devs = self._device_pool()[: 1 << new_g]
            self.n_pages = 1 << new_g
            self.g_bits = new_g
            self.mesh = Mesh(np.array(devs), ("pages",))
            self.sharding = NamedSharding(self.mesh, P(None, "pages"))
        if qubit_count - self.g_bits > 30:
            raise MemoryError(
                f"QPager page width {qubit_count - self.g_bits} exceeds a "
                "single shard; add devices/pages or stack QUnit above")
        return self.sharding

    # ------------------------------------------------------------------
    # elastic re-paging (docs/ELASTICITY.md): on device loss, halve the
    # page count and keep serving on the surviving device prefix; on
    # recovery (health probe at a call boundary), grow back.  Distinct
    # from _sharding_for's width-driven rebalance: these transitions are
    # fault-driven and move the page-count CEILING (_max_g), so every
    # later width change respects the degraded capacity too.
    # ------------------------------------------------------------------

    #: optional zero-arg probe override — set on an INSTANCE (tests,
    #: soak harnesses); None = the shared resilience/elastic.py probe
    elastic_probe = None

    @property
    def elastic_degraded(self) -> bool:
        return self._elastic_target_g is not None

    def can_shrink(self) -> bool:
        """True when a 2^g → 2^(g-1) re-shard is possible: more than
        one page left and the doubled local width still fits a shard."""
        return (self.n_pages > 1
                and (self.qubit_count - (self.g_bits - 1)) <= 30)

    def shrink_pages(self, state=None) -> "QPager":
        """Re-shard from 2^g to 2^(g-1) pages onto the surviving device
        prefix, in place.  ``state`` is the already-captured ket (the
        failover snapshot path hands it in so nothing re-reads the
        failing mesh); None gathers it here through the guarded-read
        suspension, same as a failover snapshot would."""
        if not self.can_shrink():
            raise MemoryError(
                f"QPager cannot shrink below {self.n_pages} page(s) at "
                f"width {self.qubit_count}")
        new_g = self.g_bits - 1
        if self._elastic_target_g is None:
            self._elastic_target_g = self._max_g
        if state is not None:
            devs = self._device_pool()[: 1 << new_g]
            mesh = Mesh(np.array(devs), ("pages",))
            sharding = NamedSharding(mesh, P(None, "pages"))
            st = np.asarray(state).reshape(-1)
            planes = jax.device_put(gk.to_planes(st, self.dtype), sharding)
            self.n_pages = 1 << new_g
            self.g_bits = new_g
            self.mesh = mesh
            self.sharding = sharding
            self._state = planes
            # `state` is a LOGICAL-order ket (failover snapshots read
            # through GetQuantumState), so the placement table resets
            self._map_reset()
        else:
            self._repage(new_g)
        self._max_g = new_g
        if _tele._ENABLED:
            # event() bumps the same-named counter itself
            _tele.event("elastic.repage.shrink", pages=self.n_pages,
                        target_pages=1 << self._elastic_target_g)
            _tele.gauge("elastic.pages", self.n_pages)
        return self

    def _repage(self, new_g: int) -> None:
        """Gather the whole ket and re-split it across 2^new_g pages.
        Exception-safe: the new mesh/sharding/state are built in locals
        and committed only after the device_put lands, so a failed
        re-shard leaves the current working topology untouched."""
        with _res.faults.suspended():
            # suspension: the gather must not advance fault-spec call
            # counters (a probe would change when a flap fires) nor be
            # refused by an open breaker — same discipline as failover
            # snapshots (docs/RESILIENCE.md caveats)
            planes = self._fetch(0, 1 << self.qubit_count)
        devs = self._device_pool()[: 1 << new_g]
        mesh = Mesh(np.array(devs), ("pages",))
        sharding = NamedSharding(mesh, P(None, "pages"))
        new_state = jax.device_put(
            np.asarray(planes, dtype=self.dtype), sharding)
        self.n_pages = 1 << new_g
        self.g_bits = new_g
        self.mesh = mesh
        self.sharding = sharding
        self._state = new_state
        # the gathered planes were the LOGICAL view (_fetch unpermutes),
        # so the re-paged ket starts from an identity table
        self._map_reset()

    def expand_pages(self) -> bool:
        """Grow back toward the construction page count.  True on
        success (or when already healthy); on failure the pager STAYS
        degraded-but-serving at its current size and returns False."""
        target = self._elastic_target_g
        if target is None:
            return True
        if _res._ACTIVE:
            # quarantine caps recovery: never expand onto more pages
            # than the healthy pool (spares included) can host
            pool_g = log2(max(1, len(self._device_pool())))
            target = min(target, pool_g)
        self._max_g = target
        new_g = self._desired_g(self.qubit_count)
        try:
            if new_g != self.g_bits:
                self._repage(new_g)
        except Exception:
            self._max_g = self.g_bits
            if _tele._ENABLED:
                _tele.inc("elastic.repage.expand_failed")
                _tele.gauge("elastic.pages", self.n_pages)
            return False
        self._elastic_target_g = None
        if _tele._ENABLED:
            _tele.event("elastic.repage.expand", pages=self.n_pages)
            _tele.gauge("elastic.pages", self.n_pages)
        return True

    def _quarantine_repage(self) -> bool:
        """Move the ket OFF freshly-quarantined chips at a job boundary:
        re-page at the SAME page count when the healthy pool (spares
        included) still covers it, else shrink a level and keep serving
        (docs/INTEGRITY.md quarantine semantics)."""
        pool = self._device_pool()
        if len(pool) >= self.n_pages:
            try:
                self._repage(self.g_bits)
            except Exception:  # noqa: BLE001 — stay on current topology
                if _tele._ENABLED:
                    _tele.inc("integrity.quarantine.repage_failed")
                return False
            if _tele._ENABLED:
                _tele.event("integrity.quarantine.repage",
                            pages=self.n_pages)
            return True
        if self.can_shrink():
            self.shrink_pages()
            if _tele._ENABLED:
                _tele.event("integrity.quarantine.shrink",
                            pages=self.n_pages)
            return True
        if _tele._ENABLED:
            _tele.inc("integrity.quarantine.repage_failed")
        return False

    def maybe_reexpand(self) -> bool:
        """Call-boundary hook (ResilientEngine / QHybrid / the serve
        executor): expand when degraded AND the health probe passes.
        One attribute test when healthy — cheap enough for hot paths.
        Also the integrity quarantine consumer: when the quarantine
        epoch moved and this pager still holds planes on a quarantined
        chip, re-page off it first."""
        if _res._ACTIVE:
            from ..resilience import integrity as _integ

            ep = _integ._EPOCH
            if ep != self._quarantine_epoch:
                self._quarantine_epoch = ep
                q = _integ.quarantined()
                if q and any(d.id in q for d in self.mesh.devices.flat):
                    self._quarantine_repage()
        if self._elastic_target_g is None:
            return False
        probe = self.__dict__.get("elastic_probe") or type(self).elastic_probe
        if probe is not None:
            if not probe():
                return False
        else:
            from ..resilience import elastic as _elastic

            if not _elastic.health_probe():
                return False
        return self.expand_pages()

    # ------------------------------------------------------------------
    # structure-aware lossy checkpoints (reference: per-page streams +
    # device ids, src/qpager_turboquant.cpp:24-45) — pages stage through
    # the host one at a time, so peak host memory is one page, not the
    # whole ket
    # ------------------------------------------------------------------

    def LossySaveStateVector(self, path: str, bits: int = 8, block_pow: int = 12) -> None:
        import json

        from ..checkpoint.container import save_container
        from ..storage.turboquant import _npz_path, quantize_blocks

        L = self.local_bits
        arrays = {}
        for p in range(self.n_pages):
            page = self.GetAmplitudePage(p << L, 1 << L)
            scales, codes, n = quantize_blocks(page, bits=bits, block_pow=block_pow)
            arrays[f"scales_{p}"] = scales
            arrays[f"codes_{p}"] = codes
        meta = {"format": "qpager-turboquant-v2", "bits": bits,
                "qubit_count": self.qubit_count, "n_pages": self.n_pages,
                "page_len": 1 << L, "device_ids": self.GetDeviceList()}
        # the json "meta" member keeps the pre-container layout readable
        # by older loaders; the manifest adds checksums + versioning
        arrays["meta"] = np.frombuffer(json.dumps(meta).encode(),
                                       dtype=np.uint8)
        save_container(_npz_path(path), arrays, meta=meta,
                       kind="qpager-turboquant")

    def LossyLoadStateVector(self, path: str) -> None:
        import json

        from ..checkpoint.container import load_container
        from ..storage.turboquant import (_npz_path, dequantize_blocks,
                                          dequantize_blocks_v1, lossy_load)

        kind, meta, z = load_container(_npz_path(path), legacy_ok=True)
        if kind is None and "meta" in z:
            # legacy (pre-container) per-page archive: json-in-npz meta
            meta = json.loads(bytes(z["meta"]).decode())
            kind = "qpager-turboquant"
        if kind not in ("qpager-turboquant", None, "turboquant-lossy-ket"):
            raise ValueError(f"unsupported QPager checkpoint kind {kind!r}")
        if kind != "qpager-turboquant":
            self.SetQuantumState(lossy_load(path))  # whole-ket fallback
            return
        fmt = meta.get("format")
        if fmt == "qpager-turboquant-v1":
            decode = dequantize_blocks_v1  # pre-rotation round-<=3 archive
        elif fmt == "qpager-turboquant-v2":
            decode = dequantize_blocks
        else:
            raise ValueError(f"unsupported QPager checkpoint format {fmt!r}")
        if meta["qubit_count"] != self.qubit_count:
            raise ValueError("checkpoint width mismatch")
        plen = meta["page_len"]
        if meta["n_pages"] * plen != (1 << self.qubit_count):
            raise ValueError("checkpoint page layout inconsistent")
        total = 0.0
        for i in range(meta["n_pages"]):
            # keep raw magnitudes: the stored scales carry each
            # page's weight, so only ONE global renormalization runs.
            # Offsets are checkpoint-relative (i * plen), so a pager
            # with a different page count loads the same ket.
            page = decode(z[f"scales_{i}"], z[f"codes_{i}"],
                          plen, meta["bits"], normalize=False)
            total += float(np.sum(np.abs(page) ** 2))
            self.SetAmplitudePage(page, i * plen)
        if total > 0:
            self._k_normalize(total)

    # ------------------------------------------------------------------
    # state access
    # ------------------------------------------------------------------

    def _host_unpermute(self, planes: np.ndarray) -> np.ndarray:
        """Reorder a full-ket host window from physical to logical bit
        order — a pure axis transpose, zero exchange bytes (the table
        pays nothing on full-ket reads)."""
        n = self.qubit_count
        a = np.asarray(planes).reshape((2,) + (2,) * n)
        axes = [0] * (n + 1)
        for l in range(n):
            # index bit b lives on axis (n - b); logical bit l reads
            # from physical bit _qmap[l]
            axes[n - l] = n - self._qmap[l]
        return np.ascontiguousarray(np.transpose(a, axes)).reshape(2, -1)

    def _fetch(self, offset: int, length: int, raw: bool = False) -> np.ndarray:
        """(2, length) host-side planes window, float64, in LOGICAL bit
        order (``raw=True`` reads the physical layout as stored — MAll's
        page draw and checkpoint capture want exactly that).

        Under a non-identity placement table a full-ket read unpermutes
        host-side (free), a single amplitude translates its index, and
        any other window physically restores logical order first.

        Multi-host safe: when this process cannot address every shard
        (a mesh spanning jax.distributed processes), the window is
        replicated through a collective program first — the only legal
        read pattern on such meshes (see parallel/cluster.py)."""
        self._settle()
        if not raw and self._map_nonid():
            if offset == 0 and length == (1 << self.qubit_count):
                return self._host_unpermute(self._fetch(0, length, raw=True))
            if length == 1:
                return self._fetch(self._map_index(offset), 1, raw=True)
            self._unmap()
        if _tele._ENABLED:
            itemsize = jnp.dtype(self.dtype).itemsize
            _tele.inc("exchange.pager.host_fetch")
            _tele.inc("exchange.pager.host_fetch_bytes", 2 * length * itemsize)
        if length == 1:  # by (page, offset): no index of the global axis
            prog, at = self._p_page_read(), self._split_index(offset)

            def read(st):
                return np.asarray(_host_read_raw(prog(st, *at)),
                                  dtype=np.float64)
        elif self._state.is_fully_addressable:
            def read(st):
                return np.asarray(
                    jax.device_get(st[:, offset:offset + length]),
                    dtype=np.float64)
        else:
            from .cluster import replicate_program

            prog = _program(self._key("replicate", length),
                            lambda: replicate_program(self.mesh, length))
            return np.asarray(_host_read(prog(self._state, offset)),
                              dtype=np.float64)
        with _tele.span("engine.read"):
            if _res._ACTIVE:  # site "pager.device_get": the completion sync
                planes = _res.call_guarded("pager.device_get", read,
                                           (self._state,))
                from ..resilience import integrity as _integ

                if _integ.enabled():
                    # boundary invariant piggybacked on the fetched
                    # window — no extra HBM sweep (docs/INTEGRITY.md)
                    _integ.check_host("pager.device_get", planes)
                return planes
            return read(self._state)

    def GetQuantumState(self) -> np.ndarray:
        planes = self._fetch(0, 1 << self.qubit_count)
        return planes[0] + 1j * planes[1]

    def SetQuantumState(self, state) -> None:
        st = np.asarray(state).reshape(-1)
        if st.shape[0] != (1 << self.qubit_count):
            raise ValueError("state length mismatch")
        self._state = jax.device_put(gk.to_planes(st, self.dtype), self.sharding)
        self._map_reset()

    def GetAmplitude(self, perm: int) -> complex:
        amp = self._fetch(perm, 1)
        return complex(amp[0, 0], amp[1, 0])

    def SetAmplitude(self, perm: int, amp: complex) -> None:
        amp = complex(amp)
        self._settle()
        perm = self._map_index(perm) if self._map_nonid() else perm
        self._state = self._p_page_write()(
            self._state, *self._split_index(perm),
            np.asarray([amp.real, amp.imag], dtype=self.dtype))

    def _ket_is_mine_to_overwrite(self, st) -> bool:
        """The planes the fill may take: those of this mesh, width and
        plane type (a re-paged or re-typed pager fills a fresh ket)."""
        return (st is not None and not st.is_deleted()
                and st.shape == (2, 1 << self.qubit_count)
                and st.dtype == self.dtype and st.sharding == self.sharding)

    def SetPermutation(self, perm: int, phase=None) -> None:
        """One program writes every page once (``jit_qrack_page_fill``):
        over the ket the pager owns, donated and aliased to the result,
        or a fresh one where it owns none (construction).  The old ket
        is never alive beside the new: a w31 page is a quarter of a
        chip.  ``perm`` goes in as (page, offset), split on the host."""
        ph = self._rand_phase() if phase is None else complex(phase)
        # a blind overwrite: the setter drops a pending window unflushed
        st, self._state = self._state_raw, None
        operands = (*self._split_index(perm),
                    np.asarray([ph.real, ph.imag], dtype=self.dtype))
        with _tele.span("engine.set_permutation"):
            if self._ket_is_mine_to_overwrite(st):
                _tele.inc("pager.fill.in_place")
                self._state_raw = self._p_page_fill(True)(st, *operands)
            else:
                _tele.inc("pager.fill.fresh")
                st = None  # let go before the new ket is allocated
                self._state_raw = self._p_page_fill(False)(*operands)
        self._map_reset()
        self.running_norm = 1.0

    def Clone(self) -> "QPager":
        self._settle()
        c = QPager(
            self.qubit_count, n_pages=self.n_pages,
            devices=list(self.mesh.devices.flat), dtype=self.dtype,
            remap=self._remap,
            rng=self.rng.spawn(), do_normalize=self.do_normalize,
            rand_global_phase=self.rand_global_phase,
        )
        c._state = jax.jit(jnp.copy)(self._state)
        c._map_assign(self._qmap)  # physical copy carries the placement
        return c

    def CloneEmpty(self) -> "QPager":
        return QPager(
            self.qubit_count, n_pages=self.n_pages,
            devices=list(self.mesh.devices.flat), dtype=self.dtype,
            remap=self._remap,
            rng=self.rng.spawn(), do_normalize=self.do_normalize,
            rand_global_phase=self.rand_global_phase,
        )

    def Finish(self) -> None:
        if self._state is not None:
            self._state.block_until_ready()

    def GetDeviceList(self):
        return [d.id for d in self.mesh.devices.flat]

    # -- cross-engine data plane --

    def ZeroAmplitudes(self) -> None:
        self._state = jax.device_put(
            jnp.zeros_like(self._state), self.sharding
        )
        self._map_reset()

    def IsZeroAmplitude(self) -> bool:
        self._settle()

        def build():
            return jax.jit(lambda s: jnp.any(s != 0),
                           out_shardings=NamedSharding(self.mesh, P()))

        return not bool(_host_read(_program(self._key("iszero"), build)(self._state)))

    def GetAmplitudePage(self, offset: int, length: int) -> np.ndarray:
        planes = self._fetch(offset, length)
        return planes[0] + 1j * planes[1]

    def SetAmplitudePage(self, page, offset: int) -> None:
        self._unmap()  # the window writes at logical offsets
        sh = self.sharding

        def build():
            return jax.jit(
                lambda s, v, o: jax.lax.dynamic_update_slice(s, v, (0, o)),
                out_shardings=sh,
            )

        prog = _program(self._key("setpage", len(page)), build)
        self._state = prog(self._state, gk.to_planes(page, self.dtype), offset)

    # ------------------------------------------------------------------
    # checkpoint protocol: exact per-page shards, staged through the
    # host one page per array (checkpoint/registry.py).  Offsets on
    # restore are checkpoint-relative, so a pager with a different page
    # count (device layout changed between save and restore) loads the
    # same ket.
    # ------------------------------------------------------------------

    _ckpt_kind = "pager"

    def _ckpt_capture(self, capture_child):
        self._settle()
        L = self.local_bits
        arrays = {}
        for p in range(self.n_pages):
            # RAW (physical-layout) pages: capture must not dispatch a
            # device-side unmap, and the table rides the meta instead
            planes = self._fetch(p << L, 1 << L, raw=True)
            arrays[f"page_{p}"] = planes[0] + 1j * planes[1]
        return {"kind": "pager",
                "meta": {"n": self.qubit_count, "dtype": str(self.dtype),
                         "n_pages": self.n_pages, "page_len": 1 << L,
                         "running_norm": float(self.running_norm),
                         "qmap": list(self._qmap)},
                "arrays": arrays}

    def _ckpt_restore(self, arrays, meta, children, restore_child):
        if int(meta["n"]) != self.qubit_count:
            raise ValueError("checkpoint width mismatch")
        plen = int(meta["page_len"])
        if int(meta["n_pages"]) * plen != (1 << self.qubit_count):
            raise ValueError("checkpoint page layout inconsistent")
        qm = meta.get("qmap")
        if qm is not None and len(qm) != self.qubit_count:
            raise ValueError("checkpoint placement table inconsistent")
        self._settle()
        self._map_reset()  # pages land raw; the saved table re-attaches
        for i in range(int(meta["n_pages"])):
            self.SetAmplitudePage(np.asarray(arrays[f"page_{i}"],
                                             dtype=np.complex128), i * plen)
        if qm is not None:
            self._map_assign([int(x) for x in qm])
        self.running_norm = float(meta.get("running_norm", 1.0))
