"""Shot-parallel Monte-Carlo trajectories: the batched channel engine.

A (circuit, NoiseModel, B) job lowers into ONE window program with a
leading trajectory axis.  The stochastic part — which Kraus branch fired
at each channel-application slot of each trajectory — is sampled
host-side from the counter-based rng (:func:`channels.traj_uniform`)
into per-trajectory **runtime operand tensors**, exactly like the
parametric gate payloads in :mod:`qrack_tpu.ops.fusion`: the traced
structure is `(kind, target, controlled?)` per op, never the branch
values, so same-structure windows never retrace regardless of which
branches fired.  The whole B-trajectory batch then runs as one
``jax.vmap``-ed dispatch through the existing ``tpu.fuse.flush``
guarded site — thousands of noisy shots for one compile and one
devget-honest read.

Memory: B dense kets of width w are ``B * 16 * 2^w`` resident bytes
(route/cost.py's dense coefficient).  ``QRACK_NOISE_TRAJ_CHUNK``
overrides the trajectory chunk; by default the largest chunk that fits
:func:`route.cost.hbm_budget_bytes` is used and the batch runs as
ceil(B/chunk) dispatches (telemetry ``noise.traj.chunked``).

Windowing: by default the whole lowered stream is one program.
``QRACK_NOISE_TRAJ_WINDOW=k`` splits it into k-op windows (the parity
tests drive this at 1 and 16) with the ket planes and the trajectory
weight threaded between windows.
"""

from __future__ import annotations

import os
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import resilience as _res
from .. import telemetry as _tele
from ..config import get_config
from ..ops import fusion as fu
from ..ops import gatekernels as gk
from ..resilience import faults as _faults
from ..telemetry import roofline as _roofline
from .channels import MEASURE_DOMAIN, KrausChannel, NoiseModel, traj_uniform

# Structure-keyed program cache, sibling of fusion.PROGRAMS: emits
# compile.noise.{hit,miss,eviction}.
PROGRAMS = _tele.ProgramCache("noise", cap_env="QRACK_NOISE_CACHE_CAP",
                              default_cap=64)


def traj_window_len() -> int:
    """Ops per trajectory window; 0 (default) = whole stream as ONE
    program."""
    try:
        w = int(os.environ.get("QRACK_NOISE_TRAJ_WINDOW", "0"))
    except ValueError:
        w = 0
    return max(0, w)


def traj_chunk(width: int, trajectories: int) -> int:
    """Trajectory chunk size: ``QRACK_NOISE_TRAJ_CHUNK`` override, else
    the largest chunk whose resident batch (chunk · 16 · 2^w, the
    route/cost.py dense coefficient) fits the HBM budget."""
    env = os.environ.get("QRACK_NOISE_TRAJ_CHUNK", "")
    if env:
        try:
            return max(1, min(int(trajectories), int(env)))
        except ValueError:
            pass
    from ..route import cost as _cost

    budget = _cost.hbm_budget_bytes()
    per = float(_cost.DENSE_BYTES_PER_AMP) * float(2 ** int(width))
    fit = int(budget // per) if per > 0 else int(trajectories)
    return max(1, min(int(trajectories), fit))


# ---------------------------------------------------------------------------
# lowering: (circuit, NoiseModel) -> flat noisy op stream
# ---------------------------------------------------------------------------

class NoiseSlot:
    """One channel application in the schedule: channel `ch` on `qubit`
    at application counter `seq` (the rng coordinate)."""

    __slots__ = ("qubit", "ch", "seq")

    def __init__(self, qubit: int, ch: KrausChannel, seq: int):
        self.qubit = qubit
        self.ch = ch
        self.seq = seq


def lower_noisy(circuit, model: NoiseModel) -> List[object]:
    """Interleave the circuit's lowered gate ops with the model's
    channel slots: per QCircuitGate, its FusedOps (payload perms in
    sorted order), then one slot per touched qubit per attached channel
    — the same schedule :meth:`channels.QNoisy.run_circuit` walks, with
    `seq` numbering the slots monotonically."""
    ops: List[object] = []
    seq = 0
    for g in circuit.gates:
        ops.extend(fu.lower_gates([g]))
        for q, ch in model.slots_for((g.target,) + tuple(g.controls)):
            ops.append(NoiseSlot(q, ch, seq))
            seq += 1
    return ops


def structure_of(ops: Sequence[object]) -> Tuple:
    """Program-cache identity.  Mixed-unitary noise slots are
    structurally plain "gen" ops — which branch fired is operand data —
    while general-Kraus slots get their own "kraus" kind (they carry a
    prior operand and touch the weight)."""
    out = []
    for op in ops:
        if isinstance(op, NoiseSlot):
            out.append(("kraus" if not op.ch.unitary else "gen",
                        op.qubit, False))
        else:
            out.append((op.kind, op.target, op.cmask != 0))
    return tuple(out)


# ---------------------------------------------------------------------------
# the traced bodies
# ---------------------------------------------------------------------------

def _traj_body(n: int, structure: Tuple):
    """Single-trajectory traced body: fn(planes, weight, *operands) ->
    (planes, weight).  Gate dispatch mirrors fusion.window_fn; the
    "kraus" kind applies the raw branch, renormalizes, and accumulates
    the importance weight ‖K|ψ⟩‖²/q."""

    def fn(planes, weight, *operands):
        i = 0
        for kind, target, has_ctrl in structure:
            p = operands[i]
            i += 1
            if kind == "kraus":
                prior = operands[i]
                i += 1
                planes = gk.apply_2x2(planes, p, n, target)
                n2 = jnp.sum(planes * planes)
                # a branch can annihilate the state (e.g. amplitude
                # damping's K1 on a qubit with no |1> amplitude): the
                # trajectory is dead — weight 0, ket reset to |0...0>
                # so the rest of the schedule stays finite.  QNoisy
                # mirrors this exactly (rng parity contract).
                dead = n2 <= jnp.zeros((), dtype=n2.dtype)
                safe = jnp.where(dead, jnp.ones_like(n2), n2)
                reset = jnp.zeros_like(planes).at[0, 0].set(1)
                planes = jnp.where(
                    dead, reset,
                    planes * jax.lax.rsqrt(safe).astype(planes.dtype))
                weight = jnp.where(
                    dead, jnp.zeros_like(weight),
                    weight * (n2.astype(weight.dtype) / prior))
                continue
            if has_ctrl:
                cm = operands[i]
                cv = operands[i + 1]
                i += 2
            else:
                cm = 0
                cv = 0
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
            else:
                planes = gk.apply_2x2(planes, p, n, target, cm, cv)
        return planes, weight

    return fn


def _traj_final(n: int, structure: Tuple):
    """Final-window traced body: runs the ops, then computes the
    per-trajectory readout on device — per-qubit P(1), the categorical
    measurement draw from uniform `u` — so only O(B·n) scalars cross to
    the host, never B·2^n amplitudes."""
    body = _traj_body(n, structure)

    def fn(planes, weight, u, *operands):
        planes, weight = body(planes, weight, *operands)
        p = planes[0] * planes[0] + planes[1] * planes[1]
        idx = gk.iota_for(planes)
        norm = jnp.sum(p)
        p1 = jnp.stack([
            jnp.sum(jnp.where(((idx >> q) & 1) == 1, p, 0.0))
            for q in range(n)]) / norm
        cdf = jnp.cumsum(p)
        s = jnp.searchsorted(cdf, u.astype(p.dtype) * cdf[-1], side="right")
        s = jnp.minimum(s, p.shape[0] - 1)
        return planes, weight, p1, s

    return fn


def _program(n: int, structure: Tuple, batch: int, dtype, final: bool):
    """One guarded vmapped program per (width, dtype, structure, chunk,
    final?) — branch payloads ride the operand vector, so every
    same-shape window is a compile.noise hit.  Dispatch goes through
    the same ``tpu.fuse.flush`` guarded site as the gate fuser."""
    key = ("traj", n, str(jnp.dtype(dtype)), structure, int(batch),
           bool(final))

    def build():
        body = _traj_final(n, structure) if final else _traj_body(n, structure)
        return _res.instrument_dispatch(
            "tpu.fuse.flush",
            _tele.instrument_jit(
                "noise.window", jax.jit(jax.vmap(body),
                                        donate_argnums=(0,))))

    return PROGRAMS.get_or_build(key, build)


# ---------------------------------------------------------------------------
# host-side branch pre-sampling (the noise.sample guarded site)
# ---------------------------------------------------------------------------

def _sample_operands(ops: Sequence[object], key: int,
                     tids: Sequence[int], dtype) -> List:
    """Materialize the runtime operand vector for one window and one
    trajectory chunk: gate payloads broadcast across the batch, noise
    slots sampled per trajectory from (key, trajectory_id, seq)."""
    directive = _faults.check("noise.sample")
    if directive:
        raise RuntimeError(f"noise.sample injected fault: {directive}")
    B = len(tids)
    out: List = []
    for op in ops:
        if isinstance(op, NoiseSlot):
            idxs = [op.ch.sample(traj_uniform(key, t, op.seq))
                    for t in tids]
            mats = np.stack([op.ch.branch_matrix(i) for i in idxs])
            out.append(jnp.asarray(
                np.stack([mats.real, mats.imag], axis=1), dtype=dtype))
            if not op.ch.unitary:
                out.append(jnp.asarray(
                    np.asarray([op.ch.priors[i] for i in idxs]),
                    dtype=jnp.float32))
            continue
        single = fu.per_op_operands([op], dtype)
        for arr in single:
            out.append(jnp.broadcast_to(arr, (B,) + arr.shape))
    return out


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

class TrajectoryResult:
    """Per-trajectory readout + the channel-averaged aggregate.

    `p1` is (B, n) per-qubit P(1), `weights` (B,) importance weights
    (all-ones for mixed-unitary models), `samples` (B,) the terminal
    measurement draw of each trajectory, `aggregate_p1` the
    weight-averaged per-qubit P(1) — the Monte-Carlo estimate of the
    channel-averaged observable.
    """

    __slots__ = ("width", "key", "trajectory_ids", "p1", "weights",
                 "samples", "chunks", "planes")

    def __init__(self, width: int, key: int, trajectory_ids, p1, weights,
                 samples, chunks: int, planes=None):
        self.width = int(width)
        self.key = int(key)
        self.trajectory_ids = np.asarray(trajectory_ids, dtype=np.int64)
        self.p1 = np.asarray(p1, dtype=np.float64)
        self.weights = np.asarray(weights, dtype=np.float64)
        self.samples = np.asarray(samples, dtype=np.int64)
        self.chunks = int(chunks)
        self.planes = planes

    @property
    def trajectories(self) -> int:
        return int(self.p1.shape[0])

    @property
    def aggregate_p1(self) -> np.ndarray:
        w = self.weights
        return (w[:, None] * self.p1).sum(axis=0) / w.sum()

    def expectation_z(self, qubit: int) -> float:
        """Channel-averaged <Z_qubit> = 1 - 2 P(1)."""
        return float(1.0 - 2.0 * self.aggregate_p1[int(qubit)])

    def to_dict(self) -> dict:
        return {
            "width": self.width,
            "key": self.key,
            "trajectory_ids": self.trajectory_ids.tolist(),
            "p1": self.p1.tolist(),
            "weights": self.weights.tolist(),
            "samples": self.samples.tolist(),
            "chunks": self.chunks,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TrajectoryResult":
        return cls(d["width"], d["key"], d["trajectory_ids"], d["p1"],
                   d["weights"], d["samples"], d["chunks"])


# ---------------------------------------------------------------------------
# the job object (chunk loop + mid-batch checkpoint)
# ---------------------------------------------------------------------------

class TrajectoryJob:
    """Chunked execution of a trajectory batch with mid-batch
    checkpointing.

    Because every trajectory is a pure function of (key, trajectory_id),
    a snapshot needs only the finished chunks' outputs and the next
    chunk index — resuming re-derives the remaining trajectories'
    randomness from the counters and lands bit-identical to an
    uninterrupted run.
    """

    def __init__(self, circuit, model: NoiseModel, trajectories: int, *,
                 width: int, key: int = 0,
                 trajectory_ids: Optional[Sequence[int]] = None,
                 dtype=None, keep_planes: bool = False):
        self.circuit = circuit
        self.model = model
        self.width = int(width)
        self.key = int(key)
        if trajectory_ids is None:
            trajectory_ids = range(int(trajectories))
        self.tids = [int(t) for t in trajectory_ids]
        if len(self.tids) != int(trajectories):
            raise ValueError("trajectory_ids length != trajectories")
        self.dtype = dtype if dtype is not None else \
            get_config().device_real_dtype()
        self.keep_planes = bool(keep_planes)
        self.chunk = traj_chunk(self.width, len(self.tids))
        self._ops = lower_noisy(circuit, model)
        self._next = 0
        self._done: List[dict] = []
        self._planes: List[np.ndarray] = []

    # -- chunk geometry ------------------------------------------------

    @property
    def n_chunks(self) -> int:
        B = len(self.tids)
        return max(1, (B + self.chunk - 1) // self.chunk)

    def _chunk_tids(self, ci: int) -> List[int]:
        return self.tids[ci * self.chunk:(ci + 1) * self.chunk]

    @property
    def finished(self) -> bool:
        return self._next >= self.n_chunks

    # -- execution -----------------------------------------------------

    def _windows(self) -> List[List[object]]:
        w = traj_window_len()
        if w <= 0 or w >= len(self._ops):
            return [list(self._ops)]
        return [list(self._ops[i:i + w])
                for i in range(0, len(self._ops), w)]

    def step(self) -> None:
        """Run the next trajectory chunk: one vmapped dispatch per
        window, devget-honest read of the final outputs."""
        if self.finished:
            return
        tids = self._chunk_tids(self._next)
        C = len(tids)
        n = self.width
        esize = jnp.dtype(self.dtype).itemsize
        planes_np = np.zeros((C, 2, 1 << n), dtype=np.dtype(str(jnp.dtype(
            self.dtype))) if jnp.dtype(self.dtype) != jnp.bfloat16
            else np.float32)
        planes_np[:, 0, 0] = 1.0
        planes = jnp.asarray(planes_np, dtype=self.dtype)
        weight = jnp.ones((C,), dtype=jnp.float32)
        windows = self._windows()
        u = jnp.asarray(
            [traj_uniform(self.key, t, 0, domain=MEASURE_DOMAIN)
             for t in tids], dtype=jnp.float32)
        for wi, ops in enumerate(windows):
            struct = structure_of(ops)
            operands = _sample_operands(ops, self.key, tids, self.dtype)
            final = wi == len(windows) - 1
            prog = _program(n, struct, C, self.dtype, final)
            if final:
                planes, weight, p1, samp = prog(planes, weight, u, *operands)
            else:
                planes, weight = prog(planes, weight, *operands)
            if _tele._ENABLED:
                _tele.inc("noise.traj.windows")
            _roofline.note_bytes(
                "tpu.fuse.flush",
                len(ops) * C * _roofline.plane_pass_bytes(n, esize))
        # devget-honest settle: host reads are the only trustworthy
        # completion signal on a remote-attached device (CLAUDE.md timing honesty)
        p1_h = jax.device_get(p1)
        self._done.append({
            "tids": tids,
            "p1": np.asarray(p1_h, dtype=np.float64),
            "weights": np.asarray(jax.device_get(weight), dtype=np.float64),
            "samples": np.asarray(jax.device_get(samp), dtype=np.int64),
        })
        if self.keep_planes:
            self._planes.append(np.asarray(
                jax.device_get(planes), dtype=np.float64))
        self._next += 1

    def run(self) -> "TrajectoryJob":
        while not self.finished:
            self.step()
        return self

    # -- checkpoint / resume -------------------------------------------

    def snapshot(self) -> dict:
        """JSON-serializable mid-batch state: finished chunk outputs +
        the resume cursor.  The rng needs no saved position — it is the
        (key, trajectory_id, seq) counters."""
        return {
            "kind": "noise.trajectories",
            "width": self.width,
            "key": self.key,
            "trajectory_ids": list(self.tids),
            "chunk": self.chunk,
            "next": self._next,
            "done": [{
                "tids": list(d["tids"]),
                "p1": d["p1"].tolist(),
                "weights": d["weights"].tolist(),
                "samples": d["samples"].tolist(),
            } for d in self._done],
        }

    @classmethod
    def resume(cls, circuit, model: NoiseModel, snap: dict,
               dtype=None) -> "TrajectoryJob":
        job = cls(circuit, model, len(snap["trajectory_ids"]),
                  width=snap["width"], key=snap["key"],
                  trajectory_ids=snap["trajectory_ids"], dtype=dtype)
        job.chunk = int(snap["chunk"])
        job._next = int(snap["next"])
        job._done = [{
            "tids": [int(t) for t in d["tids"]],
            "p1": np.asarray(d["p1"], dtype=np.float64),
            "weights": np.asarray(d["weights"], dtype=np.float64),
            "samples": np.asarray(d["samples"], dtype=np.int64),
        } for d in snap["done"]]
        return job

    # -- assembly ------------------------------------------------------

    def result(self) -> TrajectoryResult:
        if not self.finished:
            raise RuntimeError("trajectory job not finished")
        tids = [t for d in self._done for t in d["tids"]]
        p1 = np.concatenate([d["p1"] for d in self._done]) if self._done \
            else np.zeros((0, self.width))
        weights = np.concatenate([d["weights"] for d in self._done]) \
            if self._done else np.zeros((0,))
        samples = np.concatenate([d["samples"] for d in self._done]) \
            if self._done else np.zeros((0,), dtype=np.int64)
        planes = np.concatenate(self._planes) if self._planes else None
        return TrajectoryResult(self.width, self.key, tids, p1, weights,
                                samples, self.n_chunks, planes=planes)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def run_trajectories(circuit, model: NoiseModel, trajectories: int, *,
                     width: Optional[int] = None, key: int = 0,
                     trajectory_ids: Optional[Sequence[int]] = None,
                     dtype=None, keep_planes: bool = False
                     ) -> TrajectoryResult:
    """Run B noisy Monte-Carlo trajectories of `circuit` under `model`
    as vmapped batch dispatches (docs/NOISE.md).

    Telemetry: ``noise.traj.batches/trajectories/chunks/windows/slots``
    counters, ``noise.traj.chunk_size`` gauge, ``noise.traj.wall_s``
    histogram, ``noise.traj.rate`` gauge (trajectories/s, devget-honest
    wall); compile behavior under ``compile.noise.*``.
    """
    if width is None:
        width = max((max((g.target,) + tuple(g.controls))
                     for g in circuit.gates), default=0) + 1
    B = int(trajectories)
    if B <= 0:
        raise ValueError("trajectories must be positive")
    t0 = time.perf_counter()
    job = TrajectoryJob(circuit, model, B, width=width, key=key,
                        trajectory_ids=trajectory_ids, dtype=dtype,
                        keep_planes=keep_planes)
    job.run()
    wall = time.perf_counter() - t0
    if _tele._ENABLED:
        _tele.inc("noise.traj.batches")
        _tele.inc("noise.traj.trajectories", float(B))
        _tele.inc("noise.traj.chunks", float(job.n_chunks))
        if job.n_chunks > 1:
            _tele.inc("noise.traj.chunked")
        nslots = sum(1 for op in job._ops if isinstance(op, NoiseSlot))
        _tele.inc("noise.traj.slots", float(nslots * B))
        _tele.gauge("noise.traj.chunk_size", job.chunk)
        _tele.observe("noise.traj.wall_s", wall)
        if wall > 0:
            _tele.gauge("noise.traj.rate", B / wall)
    return job.result()
