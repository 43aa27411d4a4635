"""Per-gate kernel micro-benchmarks (reference: the test_x/test_h/
test_cnot/test_ccnot/test_swap/test_t sections of test/benchmarks.cpp,
which sweep one gate per kernel dispatch).

Times K chained applications of ONE jitted gate program over a
(2, 2^w) split-plane ket, synced through a 1-amplitude device read
(`block_until_ready` is dishonest on a remote-attached device — see
docs/TPU_EVIDENCE.md), and reports wall per application plus the
implied HBM throughput for the 1-read+1-write pass each gate is.

Usage: python scripts/microbench.py [width] [chain] [samples]
Emits one JSON line per gate.
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import numpy as np

    import jax

    from qrack_tpu import matrices as mat
    from qrack_tpu.models import qft as qftm
    from qrack_tpu.ops import gatekernels as gk
    from qrack_tpu.utils import timing

    w = int(sys.argv[1]) if len(sys.argv) > 1 else 22
    chain = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    samples = int(sys.argv[3]) if len(sys.argv) > 3 else 3
    from qrack_tpu.telemetry import roofline

    n_bytes_pass = roofline.plane_pass_bytes(w)  # read+write both f32 planes

    def g_h(p):
        return gk.apply_2x2(p, gk.mtrx_planes(np.asarray(mat.H2)), w, 3)

    def g_x(p):
        return gk.apply_invert(p, 1.0, 0.0, 1.0, 0.0, w, 3)

    def g_t(p):
        c = float(np.cos(np.pi / 4))
        return gk.apply_diag(p, 1.0, 0.0, c, c, w, 1 << 3)

    def g_cnot(p):
        return gk.apply_invert(p, 1.0, 0.0, 1.0, 0.0, w, 3,
                               cmask=1 << 5, cval=1 << 5)

    def g_ccnot(p):
        m = (1 << 5) | (1 << 7)
        return gk.apply_invert(p, 1.0, 0.0, 1.0, 0.0, w, 3,
                               cmask=m, cval=m)

    def g_swap(p):
        return gk.swap_bits(p, w, 2, w - 2)

    def g_iswap_pair(p):
        return gk.apply_4x4(p, gk.mtrx_planes(np.asarray(
            [[1, 0, 0, 0], [0, 0, 1j, 0], [0, 1j, 0, 0], [0, 0, 0, 1]])),
            w, 2, 3)

    gates = [("h", g_h), ("x", g_x), ("t", g_t), ("cnot", g_cnot),
             ("ccnot", g_ccnot), ("swap", g_swap), ("iswap", g_iswap_pair)]

    planes = qftm.basis_planes(w, 123 & ((1 << w) - 1))

    for name, fn in gates:
        jfn = jax.jit(fn, donate_argnums=(0,))
        planes = jfn(planes)          # warm (compile) — excluded
        timing.devget_sync(planes)
        sync_s = timing.empty_queue_sync_s(planes)
        times, planes = timing.time_chain(jfn, planes, chain, samples,
                                          sync_s)
        avg = sum(times) / len(times)
        sample = roofline.record("gate.kernel", n_bytes_pass, avg, width=w,
                                 platform=jax.default_backend())
        line = {
            "gate": name, "width": w, "wall_s": round(avg, 8),
            "min_s": round(min(times), 8),
            "std_s": round(statistics.pstdev(times), 8),
            "chain": chain, "samples": samples,
            "sync_overhead_s": round(sync_s, 8),
            "implied_hbm_gbps": sample["implied_hbm_gbps"],
            "hbm_roofline_frac": sample["hbm_roofline_frac"],
            "device_class": sample["device_class"],
        }
        if sample["clamped"]:
            line["suspect_timing"] = True
            line["roofline_clamped"] = True
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
