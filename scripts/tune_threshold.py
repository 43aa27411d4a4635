"""QHybrid threshold tuner: measure the CPU-vs-TPU crossover width.

SURVEY §7 calls this "correctness-of-performance critical": below the
crossover, TPU dispatch latency dwarfs the math on tiny kets.  For each
width this runs the SAME random circuit (test_random_circuit shape:
1q rotations + CNOT chain + prob reads, gate-at-a-time — the dispatch-
bound regime the threshold exists for) on the numpy engine and on the
TPU engine, prints per-width wall times, and recommends the smallest
width where the TPU engine wins.  Record the result in
QRACK_TPU_THRESHOLD_QB / config.hybrid_tpu_threshold_qubits with the
log as provenance.

Run ONLY under a hard timeout from a parent (the backend can hang).
"""

import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def run_circuit(q, width: int, depth: int, seed: int) -> float:
    from qrack_tpu.utils.rng import QrackRandom

    rng = QrackRandom(seed)
    for _ in range(depth):
        for i in range(width):
            q.RY(rng.rand(), i)
        for i in range(width - 1):
            q.CNOT(i, i + 1)
    return q.Prob(width - 1)


def time_engine(make, width: int, depth: int = 4, samples: int = 3) -> float:
    times = []
    for s in range(samples + 1):
        q = make(width)
        t0 = time.perf_counter()
        run_circuit(q, width, depth, 7)
        if hasattr(q, "Finish"):
            q.Finish()
        dt = time.perf_counter() - t0
        if s:  # first sample = compile warm-up, excluded
            times.append(dt)
    return min(times)


def main() -> None:
    from qrack_tpu.engines.cpu import QEngineCPU
    from qrack_tpu.engines.tpu import QEngineTPU
    from qrack_tpu.utils.rng import QrackRandom

    mk_cpu = lambda w: QEngineCPU(w, rng=QrackRandom(1))
    mk_tpu = lambda w: QEngineTPU(w, rng=QrackRandom(1))

    crossover = None
    for w in range(6, 24, 2):
        t_cpu = time_engine(mk_cpu, w)
        t_tpu = time_engine(mk_tpu, w)
        print(json.dumps({"width": w, "cpu_s": round(t_cpu, 6),
                          "tpu_s": round(t_tpu, 6),
                          "tpu_wins": t_tpu < t_cpu}), flush=True)
        if crossover is None and t_tpu < t_cpu:
            crossover = w
    print(json.dumps({"recommended_QRACK_TPU_THRESHOLD_QB": crossover}),
          flush=True)


if __name__ == "__main__":
    main()
