#!/usr/bin/env python3
"""chip_smoke.py — a check on the chip of the two paths that have no cell yet.

The dense engine and the pager are the benchmark's (seven cells of
BENCHMARK.json, through benchmarks/run.py).  One process drives what is
left through the entry points a user calls, on one TPU v5e, and checks
every result against a closed form that is itself checked against
QEngineCPU at a small width first:

1. the default stack, w24: create_quantum_interface("optimal"), GHZ, a
   layer of RY and a CZ chain, so that the stabilizer layer hands the
   ket to the dense engine, 64 sampled amplitudes (until ROADMAP B2);
2. one in-process QrackService, w22, 8 sessions, each submitting the
   same X(k)-prepared QFT circuit inside one batch window, 16 sampled
   amplitudes per session (until ROADMAP B11).

Every phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and the exit code is 0 only if every check passed on a TPU.  Without a
TPU the script fails before any phase.  ``--rehearse-cpu`` runs the same
phases on the CPU at tiny widths to find wrong paths and arguments; it
ends non-zero and ``"ok": false``: a rehearsal is never a chip run.
"""

import argparse
import cmath
import json
import math
import sys
import time

import numpy as np

import jax

# widths: (chip, rehearsal)
W_STACK = (24, 14)
# served width: the batcher compiles the whole circuit as ONE vmapped
# program (ROADMAP C3) — see SERVE_WIDTH_REASON, printed with the phase
W_SERVE = (22, 10)
SERVE_WIDTH_REASON = (
    "w22: the widest of w16/w18/w20/w22 whose whole-circuit batch program "
    "compiled for a described v5e in under two minutes (8 lanes: w16 6 s, "
    "w18 18 s, w20 61 s, w22 66 s; PR 25, compiled without a chip)")
N_SESSIONS = 8
REL_TOL = 2e-4  # |got - want| / |want| on one f32 amplitude


class _Compiles:
    """Backend compiles JAX really made (a persistent-cache hit is not
    one), counted by JAX's own monitoring events."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self._EVENT:
            self.count += 1
            self.seconds += duration

    def mark(self):
        return self.count, self.seconds


def _device_dict():
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes(device):
    stats = device.memory_stats()  # None on the CPU backend
    return None if stats is None else stats["peak_bytes_in_use"]


def _emit(phase, width, t0, compiles, mark, device, **extra):
    n0, s0 = mark
    print(json.dumps({
        "phase": phase, "width": width, "device": _device_dict(),
        "seconds_cold": time.perf_counter() - t0,
        "compiles": compiles.count - n0,
        "compile_seconds": compiles.seconds - s0,
        "peak_bytes_in_use": _peak_bytes(device), **extra}), flush=True)


# ---------------------------------------------------------------------------
# closed forms
# ---------------------------------------------------------------------------

def _bitrev(y, n):
    return int(format(y, f"0{n}b")[::-1], 2)


def qft_amp(x, y, n):
    """<y| QFT |x> under Qrack's convention (no final swaps): the
    textbook transform with the output register bit-reversed."""
    return cmath.exp(2j * math.pi * x * _bitrev(y, n) / (1 << n)) / math.sqrt(1 << n)


def ghz_ry_cz_amp(thetas, y):
    """<y| CZ-chain . RY-layer |GHZ>: two product states, then a sign
    for every adjacent pair of set bits."""
    a = b = 1.0
    for i, th in enumerate(thetas):
        c, s = math.cos(th / 2), math.sin(th / 2)
        bit = (y >> i) & 1
        a *= s if bit else c      # RY|0> = c|0> + s|1>
        b *= c if bit else -s     # RY|1> = -s|0> + c|1>
    sign = -1.0 if bin(y & (y >> 1)).count("1") & 1 else 1.0
    return sign * (a + b) / math.sqrt(2)


def _ghz_ry_cz(q, thetas):
    n = len(thetas)
    q.H(0)
    for i in range(n - 1):
        q.CNOT(i, i + 1)
    for i, th in enumerate(thetas):
        q.RY(float(th), i)
    for i in range(n - 1):
        q.CZ(i, i + 1)


def _served_circuit(w, k):
    """X on the set bits of k, then the QFT: |0..0> -> QFT|k>."""
    from qrack_tpu import matrices as mat
    from qrack_tpu.layers.qcircuit import QCircuit
    from qrack_tpu.models.qft import qft_qcircuit

    circ = QCircuit(w)
    for b in range(w):
        if (k >> b) & 1:
            circ.append_1q(b, mat.X2)
    for g in qft_qcircuit(w).gates:
        circ.AppendGate(g)
    return circ


def _check_amps(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    err = float(np.max(np.abs(got - want) / np.abs(want)))
    assert err < REL_TOL, f"{what}: sampled amplitudes off by {err:.3g} (relative)"
    return err


def check_closed_forms(seed):
    """Both closed forms against QEngineCPU, every amplitude."""
    from qrack_tpu import create_quantum_interface
    from qrack_tpu.utils.rng import QrackRandom

    rng = np.random.default_rng(seed)
    n = 12
    x = int(rng.integers(1, 1 << n))
    cpu = create_quantum_interface("cpu", n, rng=QrackRandom(seed),
                                   rand_global_phase=False)
    cpu.SetPermutation(x)
    cpu.QFT(0, n)
    want = np.array([qft_amp(x, y, n) for y in range(1 << n)])
    assert np.max(np.abs(cpu.GetQuantumState() - want)) < 1e-9, "QFT closed form"
    served = create_quantum_interface("cpu", n, rng=QrackRandom(seed),
                                      rand_global_phase=False)
    _served_circuit(n, x).Run(served)
    assert np.max(np.abs(served.GetQuantumState() - want)) < 1e-9, "served circuit"
    thetas = rng.uniform(0.2, 2.9, n)
    cpu = create_quantum_interface("cpu", n, rng=QrackRandom(seed),
                                   rand_global_phase=False)
    _ghz_ry_cz(cpu, thetas)
    want = np.array([ghz_ry_cz_amp(thetas, y) for y in range(1 << n)])
    assert np.max(np.abs(cpu.GetQuantumState() - want)) < 1e-9, "GHZ closed form"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def _assert_on(planes, devices):
    assert planes.devices() == set(devices), (planes.devices(), devices)


def phase_stack(n, seed, compiles):
    from qrack_tpu import create_quantum_interface
    from qrack_tpu.engines.tpu import QEngineTPU
    from qrack_tpu.utils.rng import QrackRandom

    device = jax.devices()[0]
    mark, t0 = compiles.mark(), time.perf_counter()
    rng = np.random.default_rng(seed + 1)
    thetas = rng.uniform(0.2, 2.9, n)
    ys = [int(y) for y in rng.integers(0, 1 << n, 64)]
    q = create_quantum_interface("optimal", n, rng=QrackRandom(seed),
                                 rand_global_phase=False)
    _ghz_ry_cz(q, thetas)
    got = [q.GetAmplitude(y) for y in ys]
    err = _check_amps(got, [ghz_ry_cz_amp(thetas, y) for y in ys], "GHZ+RY+CZ")
    # QUnit -> QStabilizerHybrid -> QHybrid -> QEngineTPU, one unit
    hybrid = q.shards[0].unit.engine
    terminal = hybrid._engine
    assert type(terminal) is QEngineTPU, type(terminal)
    assert terminal.qubit_count == n, terminal.qubit_count
    _assert_on(terminal._state, [device])
    _emit("optimal_stack", n, t0, compiles, mark, device, max_rel_err=err,
          terminal=type(terminal).__name__)


def phase_served(n, seed, compiles):
    from qrack_tpu import telemetry as tele
    from qrack_tpu.engines.tpu import QEngineTPU
    from qrack_tpu.serve.service import QrackService

    device = jax.devices()[0]
    mark, t0 = compiles.mark(), time.perf_counter()
    rng = np.random.default_rng(seed + 2)
    k = int(rng.integers(1, 1 << n))
    ys = [int(y) for y in rng.integers(0, 1 << n, 16)]
    want = [qft_amp(k, y, n) for y in ys]

    def read(engine):
        assert type(engine) is QEngineTPU, type(engine)
        _assert_on(engine._state, [device])
        return [engine.GetAmplitude(y) for y in ys]

    errs = []
    # the queue budget covers the cold compile of the batch program
    with QrackService(engine_layers="tpu", batch_window_ms=500.0,
                      max_batch=N_SESSIONS, queue_budget_ms=900e3) as svc:
        sids = [svc.create_session(n, seed=seed + s, rand_global_phase=False)
                for s in range(N_SESSIONS)]
        handles = [svc.submit(sid, _served_circuit(n, k)) for sid in sids]
        for h in handles:
            h.result(900)
        for sid in sids:
            got = svc.call(sid, read, mutates=False).result(120)
            errs.append(_check_amps(got, want, f"session {sid}"))
    counters = tele.snapshot()["counters"]
    assert counters.get("serve.batch.dispatches", 0) >= 1, counters
    assert counters.get("serve.batch.failovers", 0) == 0, counters
    _emit("served_batch", n, t0, compiles, mark, device,
          sessions=N_SESSIONS, width_reason=SERVE_WIDTH_REASON,
          max_rel_err=max(errs),
          batch_dispatches=counters["serve.batch.dispatches"],
          batch_jobs=counters.get("serve.batch.jobs", 0))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the phases on the CPU at tiny widths; never ok")
    args = ap.parse_args()

    platform = jax.devices()[0].platform
    on_chip = platform == "tpu"
    if not on_chip and not args.rehearse_cpu:
        print(f"chip_smoke: JAX found platform {platform!r}, not a TPU",
              file=sys.stderr)
        return 2
    pick = 0 if on_chip else 1

    from qrack_tpu import resilience
    from qrack_tpu import telemetry as tele
    from qrack_tpu.checkpoint.warmstart import enable_compile_cache

    assert not resilience._ACTIVE  # a TPU->CPU failover would hide the device
    cache_dir = enable_compile_cache()
    tele.enable()
    compiles = _Compiles()
    print(json.dumps({"phase": "start", "device": _device_dict(),
                      "jax": jax.__version__, "compile_cache": cache_dir,
                      "rehearsal": not on_chip}), flush=True)
    check_closed_forms(args.seed)
    t0 = time.perf_counter()
    phase_stack(W_STACK[pick], args.seed, compiles)
    phase_served(W_SERVE[pick], args.seed, compiles)
    print(json.dumps({"phase": "total", "seconds": time.perf_counter() - t0,
                      "compiles": compiles.count,
                      "compile_seconds": compiles.seconds}), flush=True)
    print(json.dumps({"ok": on_chip, "device": _device_dict()}), flush=True)
    return 0 if on_chip else 3


if __name__ == "__main__":
    sys.exit(main())
