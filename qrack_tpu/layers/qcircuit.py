"""QCircuit: gate intermediate representation with algebraic merging.

Re-design of the reference's circuit IR (reference:
include/qcircuit.hpp:52 QCircuitGate — {target, payloads: map<control
permutation -> 2x2>, controls}; AppendGate merging src/qcircuit.cpp:101;
Run :173; PastLightCone :824). TPU-native addition: `compile_fn` traces
the whole circuit into ONE jittable XLA program over split-plane kets —
the reference's per-gate GPU dispatch chain becomes a single fused
executable (SURVEY.md §7 step 4 "batched command path").
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import matrices as mat


def _is_unitary_2x2(m: np.ndarray, tol: float = 1e-9) -> bool:
    """Local unitarity check (route.features has the same predicate, but
    layers must not import route — route imports layers)."""
    m = np.asarray(m, dtype=np.complex128).reshape(2, 2)
    return bool(np.allclose(m.conj().T @ m, np.eye(2), atol=tol))


def _is_phase_gate(m: np.ndarray, tol: float = 1e-9) -> bool:
    """Diagonal with entries of modulus one.  A recorded measurement's
    projector is diagonal too (lightcone/engine.py ForceM) and is not
    one: the stacks factor a controlled diagonal by dividing its
    entries (layers/qunit.py)."""
    return (mat.is_phase(m) and abs(abs(m[0, 0]) - 1.0) <= tol
            and abs(abs(m[1, 1]) - 1.0) <= tol)


class QCircuitGate:
    __slots__ = ("target", "controls", "payloads")

    def __init__(self, target: int, payloads: Dict[int, np.ndarray],
                 controls: Tuple[int, ...] = ()):
        self.target = target
        self.controls = tuple(controls)
        self.payloads = {p: np.asarray(m, dtype=np.complex128).reshape(2, 2)
                         for p, m in payloads.items()}

    @classmethod
    def single(cls, target: int, m: np.ndarray) -> "QCircuitGate":
        return cls(target, {0: m})

    @classmethod
    def controlled(cls, controls, target: int, m: np.ndarray, perm: int) -> "QCircuitGate":
        return cls(target, {perm: m}, tuple(controls))

    def qubits(self) -> Tuple[int, ...]:
        return (self.target,) + self.controls

    def can_merge(self, later: "QCircuitGate") -> bool:
        """Does ``later`` compose onto this gate?  Same target, and the
        same controls, or (reference: include/qcircuit.hpp CanCombine /
        AddControl) ``later``'s controls a subset of this gate's where
        ``later`` is a phase gate (_is_phase_gate) and the merged gate
        holds no more payloads than the two hold together.  So the RZ
        between a bond's two CNOTs composes onto the first (``{1: X}`` +
        ``{0: RZ}`` -> ``{0: RZ, 1: RZ X}``, two for two) and the second
        CNOT makes the bond the diagonal operator it is: two controlled
        ``diag``.  A gate that is no phase gate keeps the rule of equal
        controls (an RX behind a bond would become two controlled
        general gates), a phase behind a Toffoli would become four ops
        for two, and an earlier gate never takes controls from a later
        one (a QFT's H ahead of its cphase would).  By the gates' qubits
        and by whether a payload is a phase, nothing else."""
        if self.target != later.target:
            return False
        if self.controls == later.controls:
            return True
        if not set(later.controls) < set(self.controls) \
                or not all(_is_phase_gate(m) for m in later.payloads.values()):
            return False
        merged = set(self.payloads) | set(self._over_my_controls(later))
        return len(merged) <= len(self.payloads) + len(later.payloads)

    def _over_my_controls(self, later: "QCircuitGate") -> Dict[int, np.ndarray]:
        """``later``'s payloads over this gate's control perms (the
        reference's AddControl): each at every perm that agrees with its
        own on ``later``'s controls."""
        if later.controls == self.controls:
            return later.payloads
        where = [self.controls.index(c) for c in later.controls]
        out = {}
        for perm in range(1 << len(self.controls)):
            own = sum(((perm >> at) & 1) << j for j, at in enumerate(where))
            if own in later.payloads:
                out[perm] = later.payloads[own]
        return out

    def merge(self, later: "QCircuitGate") -> None:
        """Compose `later`'s payloads after self's (matrix product)."""
        behind = self._over_my_controls(later)
        for perm in set(self.payloads) | set(behind):
            a = self.payloads.get(perm, mat.I2)
            b = behind.get(perm, mat.I2)
            self.payloads[perm] = b @ a
        # drop only removable payloads: exact identity always; identity up
        # to global phase only when uncontrolled (a controlled e^{i0}I is a
        # physical phase on the control subspace and must be kept)
        def removable(m):
            return mat.is_identity(m) and (not self.controls or abs(m[0, 0] - 1.0) <= 1e-12)

        for perm in [p for p, m in self.payloads.items() if removable(m)]:
            del self.payloads[perm]

    def is_identity(self) -> bool:
        return not self.payloads

    def is_phase(self) -> bool:
        return all(mat.is_phase(m) for m in self.payloads.values())

    def clone(self) -> "QCircuitGate":
        return QCircuitGate(self.target, {p: m.copy() for p, m in self.payloads.items()},
                            self.controls)


class QCircuit:
    def __init__(self, qubit_count: int = 0):
        self.qubit_count = qubit_count
        self.gates: List[QCircuitGate] = []
        # memoized structure_digest — the serving plane hashes a
        # circuit once per submit AND once per dispatch, and sha1 over
        # every payload's bytes is milliseconds on ~100-gate circuits
        self._digest_cache: Optional[str] = None
        # memoized rolling prefix-digest chain (prefix_digest): entry k
        # hashes gates[:k+1].  AppendGate's peephole merging can mutate
        # or delete EARLIER gates, so any append invalidates the whole
        # chain, exactly like _digest_cache.
        self._prefix_chain: Optional[List[str]] = None

    # ------------------------------------------------------------------

    def AppendGate(self, gate: QCircuitGate) -> None:
        """Append with peephole merging (reference: src/qcircuit.cpp:101 —
        algebraic combining of same-target/controls neighbors and
        commuting past disjoint gates)."""
        self.qubit_count = max(self.qubit_count, max(gate.qubits()) + 1)
        self._digest_cache = None
        self._prefix_chain = None
        # walk back past gates on disjoint qubits to find a merge partner
        i = len(self.gates) - 1
        gset = set(gate.qubits())
        while i >= 0:
            g = self.gates[i]
            if g.can_merge(gate):
                g.merge(gate)
                if g.is_identity():
                    del self.gates[i]
                return
            if set(g.qubits()) & gset:
                break  # overlapping, cannot commute further back
            i -= 1
        self.gates.append(gate.clone())

    def append_1q(self, target: int, m: np.ndarray) -> None:
        self.AppendGate(QCircuitGate.single(target, m))

    def append_ctrl(self, controls, target: int, m: np.ndarray, perm: int) -> None:
        self.AppendGate(QCircuitGate.controlled(controls, target, m, perm))

    def GetDepth(self) -> int:
        depth: Dict[int, int] = {}
        d = 0
        for g in self.gates:
            lvl = 1 + max((depth.get(q, 0) for q in g.qubits()), default=0)
            for q in g.qubits():
                depth[q] = lvl
            d = max(d, lvl)
        return d

    def GetGateCount(self) -> int:
        return len(self.gates)

    # ------------------------------------------------------------------

    def _lookahead_entries(self) -> List[Tuple[str, int]]:
        """(kind, target) stream for the remap planner's multi-window
        lookahead (ops/fusion.py plan_remaps) — same iteration order as
        :meth:`Run`'s dispatch loop, so the fuser's cursor tracks it."""
        out: List[Tuple[str, int]] = []
        for g in self.gates:
            for _perm, m in g.payloads.items():
                out.append(("diag" if mat.is_phase(m) else "gen", g.target))
        return out

    def Run(self, qsim) -> None:
        """Execute on any QInterface (reference: src/qcircuit.cpp:173)."""
        if getattr(qsim, "_is_routed", False):
            # library-path routing admission: plan + realize on the
            # caller thread, then dispatch into the chosen stack (the
            # serve path splits these across threads — route/router.py)
            qsim = qsim.route_for(self)
        # prime the engine fuser's lookahead with the full gate list so
        # the remap planner sees past the pending window; never clobber
        # a horizon an outer driver (serve batch) already installed
        fuser = getattr(qsim, "_fuser", None)
        primed = False
        if fuser is not None and fuser.lookahead is None:
            fuser.set_lookahead(self._lookahead_entries())
            primed = True
        try:
            for g in self.gates:
                for perm, m in g.payloads.items():
                    qsim.MCMtrxPerm(g.controls, m, g.target, perm)
        finally:
            if primed:
                fuser.clear_lookahead()

    def _check_fused_range(self, n: int) -> None:
        # the per-gate path validates through _check_qubit; the fused
        # paths must reject out-of-range qubits just as loudly
        for g in self.gates:
            for q in g.qubits():
                if q < 0 or q >= n:
                    raise ValueError(f"qubit index {q} out of range (n={n})")

    def RunFused(self, qsim) -> None:
        """Execute, preferring one fused XLA program when the target is a
        plane-backed dense engine: the circuit lowers through the
        PARAMETRIC window compiler (ops/fusion.py) — gate payloads ride
        the operand vector, so the compiled program is keyed only by the
        circuit's structure and lives in the bounded shared
        fusion.PROGRAMS / pager program cache.  Two circuits with the
        same gate skeleton but different rotation angles (every
        QFT width, every VQE sweep) dispatch through ONE executable, and
        an engine's own gate-stream fuser windows hit the same entries
        where structures coincide.  Per-gate dispatch otherwise (which
        on a fuse-capable engine still windows through its fuser)."""
        from ..engines.hybrid import QHybrid
        from ..engines.tpu import QEngineTPU
        from ..engines.turboquant import QEngineTurboQuant
        from ..ops import fusion as fu
        from ..parallel.pager import QPager

        if getattr(qsim, "_is_routed", False):
            return self.RunFused(qsim.route_for(self))
        if isinstance(qsim, QHybrid):
            # fuse onto whatever engine the width switch currently holds
            inner = qsim._engine
            if isinstance(inner, (QEngineTPU, QPager)):
                return self.RunFused(inner)
        if isinstance(qsim, QEngineTurboQuant):
            # the compressed engine fuses chunk-wise through its own
            # gate-window funnel (engines/turboquant.py _fuse_flush);
            # materializing full f32 planes here would defeat it and is
            # unsound past the dense width cap
            return self.Run(qsim)
        if isinstance(qsim, QEngineTPU) and self.gates:
            n = qsim.qubit_count
            self._check_fused_range(n)
            ops = fu.lower_gates(self.gates)
            if not ops:
                return
            prog = fu.dense_window_program(n, fu.structure_of(ops),
                                           qsim.dtype)
            qsim._state = prog(qsim._owned_state(),
                               *fu.pack_operands(ops, qsim.dtype))
            return
        if isinstance(qsim, QPager) and self.gates:
            n = qsim.qubit_count
            self._check_fused_range(n)
            ops = fu.lower_gates(self.gates)
            if not ops:
                return
            # whole circuit in one horizon: the engine plans remaps over
            # the entire op list and lowers remap + windows into one
            # shard_map program (pager._run_fused_ops)
            qsim._run_fused_ops(ops)
            return
        self.Run(qsim)

    def PastLightCone(self, qubits: Sequence[int]) -> "QCircuit":
        """Sub-circuit causally relevant to `qubits` (reference:
        include/qcircuit.hpp:824; used by QTensorNetwork)."""
        cone = set(qubits)
        keep: List[QCircuitGate] = []
        for g in reversed(self.gates):
            if set(g.qubits()) & cone:
                cone |= set(g.qubits())
                keep.append(g)
        out = QCircuit(self.qubit_count)
        out.gates = [g.clone() for g in reversed(keep)]
        return out

    def Inverse(self) -> "QCircuit":
        out = QCircuit(self.qubit_count)
        for g in reversed(self.gates):
            out.gates.append(QCircuitGate(
                g.target,
                {p: np.conj(m.T) for p, m in g.payloads.items()},
                g.controls,
            ))
        return out

    def clone(self) -> "QCircuit":
        out = QCircuit(self.qubit_count)
        out.gates = [g.clone() for g in self.gates]
        return out

    def structure_digest(self) -> str:
        """Stable content hash of the gate sequence — targets, controls,
        AND payload values.  Two circuits share a digest iff they trace
        to the same jaxpr with the same baked-in gate constants
        (compile_fn embeds matrices as literals), which is the batch
        identity the serving layer keys on.

        Memoized per instance (invalidated by AppendGate): the serving
        plane hashes every submit on its caller thread and every
        dispatch in batch_program, and recomputing sha1 over ~100
        payload buffers each time was a measurable per-batch host cost
        competing with the dispatch owner for the core."""
        if self._digest_cache is not None:
            return self._digest_cache
        import hashlib

        h = hashlib.sha1()
        for g in self.gates:
            h.update(f"t{g.target};c{g.controls};".encode())
            for perm in sorted(g.payloads):
                h.update(f"p{perm}:".encode())
                h.update(np.ascontiguousarray(g.payloads[perm]).tobytes())
        self._digest_cache = h.hexdigest()
        return self._digest_cache

    def _prefix_digests(self) -> List[str]:
        """Rolling digest chain: entry k is the digest of gates[:k+1],
        built in ONE pass over the gate list (hashlib digests are
        readable mid-stream).  Entry -1 equals structure_digest() —
        same per-gate byte encoding, whole-circuit scope."""
        if self._prefix_chain is None:
            import hashlib

            chain: List[str] = []
            h = hashlib.sha1()
            for g in self.gates:
                h.update(f"t{g.target};c{g.controls};".encode())
                for perm in sorted(g.payloads):
                    h.update(f"p{perm}:".encode())
                    h.update(np.ascontiguousarray(g.payloads[perm]).tobytes())
                chain.append(h.hexdigest())
            self._prefix_chain = chain
        return self._prefix_chain

    def prefix_digest(self, k: int) -> str:
        """Digest of the first `k` gates — O(1) per call once the memoized
        chain builds (invalidated by AppendGate like structure_digest).
        Two circuits share prefix_digest(k) iff their first k gates are
        equal (targets, controls, payload bytes).  k=0 is the fixed
        empty-prefix digest; k=len(gates) equals structure_digest()."""
        if k <= 0:
            import hashlib

            return hashlib.sha1().hexdigest()
        chain = self._prefix_digests()
        if k > len(chain):
            raise IndexError(f"prefix length {k} > gate count {len(chain)}")
        return chain[k - 1]

    def shareable_prefix_len(self) -> int:
        """Longest gate prefix safe to share across tenants as a cached
        ket: every payload must be unitary.  A non-unitary payload (a
        recorded measurement/projection draws rng and collapses — its
        outcome is per-tenant) terminates the shareable prefix."""
        for i, g in enumerate(self.gates):
            for m in g.payloads.values():
                if not _is_unitary_2x2(m):
                    return i
        return len(self.gates)

    def split_at(self, k: int) -> Tuple["QCircuit", "QCircuit"]:
        """(prefix, suffix) copies split before gate index `k`.  Gates
        copy verbatim — NOT through AppendGate, whose peephole merging
        could reshape the sequence the prefix digest hashed."""
        pre = QCircuit(self.qubit_count)
        pre.gates = [g.clone() for g in self.gates[:k]]
        suf = QCircuit(self.qubit_count)
        suf.gates = [g.clone() for g in self.gates[k:]]
        return pre, suf

    def shape_key(self, n: int) -> Tuple[int, int, str]:
        """Batch-bucket key at engine width `n`: (width, gate-count
        bucket, structure digest).  The digest already implies the gate
        count; the log2 bucket rides along so occupancy reports group
        circuits of similar size without parsing digests."""
        return (n, len(self.gates).bit_length(), self.structure_digest())

    # ------------------------------------------------------------------
    # TPU batch path: the whole circuit as one traced program
    # ------------------------------------------------------------------

    def compile_batched_fn(self, n: int):
        """fn(stacked) applying the circuit over (B, 2, 2^n) stacked
        kets via vmap over :meth:`compile_fn` — one XLA program for a
        whole batch of independent sessions (serve/batcher.py)."""
        import jax

        self._check_fused_range(n)
        body = jax.vmap(self.compile_fn(n))

        def fn(stacked):
            # the name a device trace knows the served batch's ops by
            with jax.named_scope("qrack.serve.dispatch"):
                return body(stacked)

        return fn

    def compile_sharded_fn(self, mesh, n: int):
        """One jitted program applying the whole circuit to a ket sharded
        across the 'pages' mesh axis: in-page gates per device, paged
        targets over lax.ppermute, diagonals always collective-free.
        Returns (fn, sharding); ``fn`` donates its argument."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..ops import gatekernels as gk
        from ..ops import sharded as sh
        from ..utils.bits import control_offset

        npg = mesh.devices.size
        g_bits = npg.bit_length() - 1
        assert (1 << g_bits) == npg, "page count must be a power of two"
        L = n - g_bits
        sharding = NamedSharding(mesh, P(None, "pages"))
        gates = [(g.target, g.controls, dict(g.payloads)) for g in self.gates]

        def body(local):
            for (target, controls, payloads) in gates:
                for perm, m in payloads.items():
                    cmask = 0
                    for c in controls:
                        cmask |= 1 << c
                    cval = control_offset(controls, perm)
                    lm, lv, gm, gv = sh.split_masks(cmask, cval, L)
                    if mat.is_phase(m):
                        tmask = 1 << target
                        local = sh.apply_diag(
                            local, m[0, 0].real, m[0, 0].imag,
                            m[1, 1].real, m[1, 1].imag,
                            tmask & ((1 << L) - 1), tmask >> L, lm, lv, gm, gv)
                    elif target < L:
                        mp = gk.mtrx_planes(m, local.dtype)
                        local = sh.apply_local_2x2(local, mp, L, target, lm, lv, gm, gv)
                    else:
                        mp = gk.mtrx_planes(m, local.dtype)
                        local = sh.apply_global_2x2(local, mp, npg, target - L,
                                                    lm, lv, gm, gv)
            return local

        fn = jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=P(None, "pages"),
                          out_specs=P(None, "pages")),
            donate_argnums=(0,),
        )
        return fn, sharding

    def compile_fn(self, n: int):
        """Return a pure jittable fn(planes) applying the whole circuit
        over (2, 2^n) split planes — one fused XLA executable."""
        from ..ops import gatekernels as gk

        gates = [(g.target, g.controls, dict(g.payloads)) for g in self.gates]

        def fn(planes):
            for (target, controls, payloads) in gates:
                for perm, m in payloads.items():
                    cmask = 0
                    cval = 0
                    for j, c in enumerate(controls):
                        cmask |= 1 << c
                        if (perm >> j) & 1:
                            cval |= 1 << c
                    if mat.is_phase(m):
                        planes = gk.apply_diag(
                            planes, m[0, 0].real, m[0, 0].imag,
                            m[1, 1].real, m[1, 1].imag,
                            n, 1 << target, cmask, cval)
                    elif mat.is_invert(m):
                        planes = gk.apply_invert(
                            planes, m[0, 1].real, m[0, 1].imag,
                            m[1, 0].real, m[1, 0].imag,
                            n, target, cmask, cval)
                    else:
                        mp = gk.mtrx_planes(m, planes.dtype)
                        planes = gk.apply_2x2(planes, mp, n, target, cmask, cval)
            return planes

        return fn
