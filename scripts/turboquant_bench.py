"""Per-gate wall for the block-compressed resident ket (VERDICT r4 #4
done-criterion: measured per-gate cost at int8 w>=28 showing the O(1)-
dispatch chunked programs).

Times K chained engine-level gates on QEngineTurboQuant — chunk-local H,
cross-chunk CNOT (pair path), and a diagonal T above the chunk boundary —
synced through a real 1-element device read of the scales array
(`block_until_ready` is dishonest on a remote-attached device,
docs/TPU_EVIDENCE.md).  Implied compressed-HBM traffic assumes one
read+write of the resident codes+scales per gate.

Usage: python scripts/turboquant_bench.py [width] [bits] [chain] [samples]
Emits one JSON line per gate kind.

Two extra child modes ride the same harness:

  --fuse-ab [width] [bits] [n_gates] [samples]
      Single-pass fused-window A/B: the SAME chunk-local gate stream
      through window 1 (per-gate: one decompress+recompress sweep pair
      per gate) and window 16 (one pair per window), devget-honest
      walls plus the counted `tq.sweeps` / `fuse.tq.*` evidence, and a
      final summary line with the sweep and wall ratios.

  --routed [width] [bits] [max_gates]
      Route a dense-shaped QFT through the ladder (the memory-axis
      cost model must pick turboquant past the dense HBM budget), run
      it on the routed engine, and report the chunk-mass drift |sum(m)
      - 1| — the over-f32-width fidelity proxy (docs/ROUTING.md).  At
      oracle-feasible widths (<= 24) also reports state fidelity.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fuse_ab() -> None:
    import numpy as np

    import jax

    w = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    bits = int(sys.argv[3]) if len(sys.argv) > 3 else 8
    n_gates = int(sys.argv[4]) if len(sys.argv) > 4 else 32
    samples = int(sys.argv[5]) if len(sys.argv) > 5 else 3

    from qrack_tpu import telemetry as tele
    from qrack_tpu.engines.turboquant import QEngineTurboQuant
    from qrack_tpu.utils.rng import QrackRandom

    tele.enable()
    results = {}
    for window in (1, 16):
        os.environ["QRACK_TPU_FUSE_WINDOW"] = str(window)
        eng = QEngineTurboQuant(w, bits=bits, rng=QrackRandom(7),
                                rand_global_phase=False)
        ca = eng._tq_chunk_pow
        rng = np.random.default_rng(5)

        def stream(eng=eng, ca=ca, rng=rng):
            # chunk-local rotations on distinct low targets: every gate
            # is window-admissible, none merge away (distinct angles)
            for k in range(n_gates):
                eng.RZ(float(rng.uniform(0, 2 * np.pi)), k % min(ca, w))
                eng.H(k % min(ca, w))

        def sync(eng=eng):
            np.asarray(jax.device_get(eng._scales[:1]))

        eng.H(0)
        stream()         # warm/compile — excluded
        sync()
        snap0 = tele.snapshot(include_events=False)["counters"]
        times = []
        for _ in range(samples):
            t0 = time.perf_counter()
            stream()
            sync()
            times.append(time.perf_counter() - t0)
        snap1 = tele.snapshot(include_events=False)["counters"]
        delta = {k: snap1.get(k, 0) - snap0.get(k, 0)
                 for k in ("tq.sweeps", "fuse.tq.windows", "fuse.tq.ops",
                           "fuse.tq.sweeps_saved")}
        wall = min(times) / samples
        results[window] = (wall, delta)
        print(json.dumps({
            "mode": "fuse_ab", "window": window, "width": w, "bits": bits,
            "n_gates": 2 * n_gates, "samples": samples,
            "wall_s": round(wall, 8), "sweeps": delta["tq.sweeps"],
            "fuse_windows": delta["fuse.tq.windows"],
            "fuse_ops": delta["fuse.tq.ops"],
            "sweeps_saved": delta["fuse.tq.sweeps_saved"],
            "platform": jax.default_backend(),
        }), flush=True)
    w1, w16 = results[1], results[16]
    print(json.dumps({
        "mode": "fuse_ab_summary", "width": w, "bits": bits,
        "sweep_ratio": round(w1[1]["tq.sweeps"]
                             / max(w16[1]["tq.sweeps"], 1), 2),
        "wall_ratio": round(w1[0] / max(w16[0], 1e-12), 3),
        "platform": jax.default_backend(),
    }), flush=True)


def _routed() -> None:
    import numpy as np

    import jax

    w = int(sys.argv[2]) if len(sys.argv) > 2 else 30
    bits = int(sys.argv[3]) if len(sys.argv) > 3 else 8
    max_gates = int(sys.argv[4]) if len(sys.argv) > 4 else 0

    from qrack_tpu import create_quantum_interface
    from qrack_tpu import telemetry as tele
    from qrack_tpu.models.qft import qft_qcircuit
    from qrack_tpu.utils.rng import QrackRandom

    tele.enable()
    circ = qft_qcircuit(w)
    if max_gates:
        circ.gates = circ.gates[:max_gates]
    q = create_quantum_interface(("route",), w, rng=QrackRandom(7),
                                 rand_global_phase=False, bits=bits)
    d = q.plan(circ)
    q.apply_plan()
    t0 = time.perf_counter()
    circ.Run(q)
    if q.current_stack() in ("turboquant", "turboquant_pager"):
        # QRouted never forwards underscore attributes; reach the built
        # terminal directly (unwrapping ResilientEngine if armed)
        inner = q._engine
        inner = getattr(inner, "engine", inner)
        masses = inner._chunk_masses(*inner._chunk3())  # device_get — honest
        n_chunks = int(masses.size)
        total = float(masses.sum())
    else:  # budget admitted dense at this width: mass from the ket
        st = np.asarray(q.GetQuantumState())
        n_chunks = 1
        total = float(np.sum(np.abs(st) ** 2))
    wall = time.perf_counter() - t0
    out = {
        "mode": "routed", "width": w, "bits": bits,
        "stack": d.stack, "built": q.current_stack(),
        "gates": len(circ.gates), "wall_s": round(wall, 6),
        "mass_total": round(total, 9),
        "chunk_mass_drift": round(abs(total - 1.0), 9),
        "n_chunks": n_chunks,
        "platform": jax.default_backend(),
    }
    if w <= 24:
        from qrack_tpu import QEngineCPU

        oracle = QEngineCPU(w, rng=QrackRandom(7), rand_global_phase=False)
        circ.Run(oracle)
        a = np.asarray(oracle.GetQuantumState())
        b = np.asarray(q.GetQuantumState())
        out["fidelity"] = round(float(
            abs(np.vdot(a, b)) ** 2
            / (np.vdot(a, a).real * np.vdot(b, b).real)), 9)
    print(json.dumps(out), flush=True)


def main() -> None:
    import numpy as np

    import jax

    # the cost model picks the single-sweep Pallas kernel per window on
    # TPU-class backends; the campaign quotes the resolved choice and
    # the sweep counts it actually paid (ROADMAP item 3)
    os.environ.setdefault("QRACK_TPU_FUSE_KERNEL", "auto")

    w = int(sys.argv[1]) if len(sys.argv) > 1 else 28
    bits = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    chain = int(sys.argv[3]) if len(sys.argv) > 3 else 4
    samples = int(sys.argv[4]) if len(sys.argv) > 4 else 3

    from qrack_tpu import telemetry as tele
    from qrack_tpu.engines.turboquant import QEngineTurboQuant
    from qrack_tpu.ops import fusion as fu
    from qrack_tpu.utils.rng import QrackRandom

    tele.enable()

    eng = QEngineTurboQuant(w, bits=bits, rng=QrackRandom(7),
                            rand_global_phase=False)
    eng.H(0)  # spread a little mass so gates do real work

    def sync() -> None:
        np.asarray(jax.device_get(eng._scales[:1]))

    def empty_sync_s(reps: int = 3) -> float:
        out = []
        for _ in range(reps):
            t0 = time.perf_counter()
            sync()
            out.append(time.perf_counter() - t0)
        return min(out)

    res_bytes = eng.resident_bytes()
    gates = [
        ("h_local", lambda: eng.H(1)),
        ("cnot_cross_chunk", lambda: eng.CNOT(0, w - 1)),
        ("t_above_chunk", lambda: eng.T(w - 1)),
        ("cz_mixed", lambda: eng.CZ(1, w - 1)),
    ]
    for name, g in gates:
        g()          # warm/compile — excluded
        sync()
        s0 = empty_sync_s()
        snap0 = tele.snapshot(include_events=False)["counters"]
        times = []
        for _ in range(samples):
            t0 = time.perf_counter()
            for _ in range(chain):
                g()
            sync()
            times.append(max(time.perf_counter() - t0 - s0, 0.0) / chain)
        snap1 = tele.snapshot(include_events=False)["counters"]
        sweeps = {k: snap1.get(k, 0) - snap0.get(k, 0)
                  for k in ("fuse.kernel.windows", "fuse.kernel.sweeps",
                            "fuse.xla.windows", "fuse.xla.sweeps")
                  if snap1.get(k, 0) != snap0.get(k, 0)}
        avg = sum(times) / len(times)
        # one formula, one peak table: the shared roofline ledger
        # (decompress + recompress = 2 passes over the compressed
        # residency per gate)
        from qrack_tpu.telemetry import roofline

        sample = roofline.record("tq.sweep", 2 * res_bytes, avg, width=w,
                                 platform=jax.default_backend())
        line = {
            "gate": name, "width": w, "bits": bits,
            "wall_s": round(avg, 8), "min_s": round(min(times), 8),
            "std_s": round(statistics.pstdev(times), 8),
            "chain": chain, "samples": samples,
            "sync_overhead_s": round(s0, 8),
            "resident_bytes": int(res_bytes),
            "n_chunks": eng._n_chunks(),
            "implied_codes_gbps": sample["implied_hbm_gbps"],
            "hbm_roofline_frac": sample["hbm_roofline_frac"],
            "device_class": sample["device_class"],
            "platform": jax.default_backend(),
            "fuse_kernel": fu.kernel_mode(),
            "remap": fu.remap_mode(),
            "sweeps": sweeps,
        }
        if sample["clamped"]:
            line["suspect_timing"] = True
            line["roofline_clamped"] = True
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--fuse-ab":
        _fuse_ab()
    elif len(sys.argv) > 1 and sys.argv[1] == "--routed":
        _routed()
    else:
        main()
