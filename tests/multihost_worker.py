"""One process of a multi-process CPU-backend cluster (see
tests/test_multihost.py).

Brings up jax.distributed via qrack_tpu.parallel.cluster (env-driven:
QRACK_COORDINATOR / QRACK_NUM_PROCESSES / QRACK_PROCESS_ID), builds a
QPager over the GLOBAL device mesh spanning both processes, runs a
circuit whose paged-target gates ppermute across the process boundary,
and prints the resulting state + a measurement for the parent to check
against the numpy oracle.  This is the proof that the sharded kernels
are mesh-shape agnostic across hosts (reference analogue: the cluster
hooks SnuCL/GVirtuS, CMakeLists.txt:110,201-203 — never exercised
there; exercised here)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from qrack_tpu.utils.platform import pin_host_cpu

pin_host_cpu(int(os.environ.get("QRACK_WORKER_LOCAL_DEVICES", "4")))

from qrack_tpu.parallel.cluster import init_cluster, process_count, process_index

init_cluster()

import jax
import numpy as np

from qrack_tpu.parallel import QPager
from qrack_tpu.utils.rng import QrackRandom


def main() -> None:
    n = 7  # 8 pages x 16-amplitude shards
    # identical seed on every process: host-side measurement draws must
    # agree cluster-wide (see parallel/cluster.py docstring)
    q = QPager(n, rng=QrackRandom(777), rand_global_phase=False,
               devices=jax.devices(), n_pages=8)
    q.SetPermutation(0)
    for i in range(n):
        q.H(i)
    for i in range(n - 1):
        q.CNOT(i, i + 1)        # crosses local->paged at the boundary
    q.CZ(4, 6)                  # paged-paged diagonal
    q.Swap(0, 5)                # mixed local/paged swap
    q.T(6)                      # paged diagonal
    q.H(6)                      # paged target: ppermute pair exchange
    state = q.GetQuantumState()  # replicated collective fetch
    p3 = q.Prob(3)
    m = q.MAll()                 # collapse: identical draw everywhere

    # 2) the QFT and RCS families through RunFused over the SAME global
    #    mesh: the whole circuit lowers into the pager's sharded window
    #    program, run with shards owned by different processes (gloo as
    #    the DCN stand-in); reads go through the replicated collective
    #    fetch, the only read pattern legal when no process addresses
    #    every shard
    from qrack_tpu.models import qft as qftm
    from qrack_tpu.models import rcs as rcsm

    def paged():
        return QPager(n, rng=QrackRandom(777), rand_global_phase=False,
                      devices=jax.devices(), n_pages=8)

    qq = paged()
    qq.SetPermutation(5)
    qftm.qft_qcircuit(n).RunFused(qq)
    qamps = np.asarray(qq.GetQuantumState())

    rq = paged()
    rcsm.rcs_qcircuit(n, 4, 11).RunFused(rq)
    ramps = np.asarray(rq.GetQuantumState())

    # 3) the sharded COMPRESSED ket over the same global mesh: chunked
    #    shard_map programs + b-bit ppermute pair exchange across the
    #    process boundary; reads go through the multi-host-safe paths
    #    (psum'd prob, all-gathered masses, replicated chunk decompress)
    from qrack_tpu.parallel.turboquant_pager import QPagerTurboQuant

    tq = QPagerTurboQuant(n, bits=16, chunk_qb=3, block_pow=2,
                          devices=jax.devices(), n_pages=8,
                          rng=QrackRandom(777), rand_global_phase=False)
    for i in range(n):
        tq.H(i)
    tq.CNOT(0, 6)       # page-bit target: cross-process code exchange
    tq.T(6)
    tq.CZ(5, 6)
    tq_p3 = tq.Prob(3)
    tq_p6 = tq.Prob(6)
    tq_amp0 = tq.GetAmplitude(0)      # block-local replicated fetch
    tq_m = tq.MAll()

    print("RESULT " + json.dumps({
        "proc": process_index(),
        "procs": process_count(),
        "n_global_devices": len(jax.devices()),
        "re": [float(x) for x in state.real],
        "im": [float(x) for x in state.imag],
        "prob3": float(p3),
        "mall": int(m),
        "qft_re": [float(x) for x in qamps.real],
        "qft_im": [float(x) for x in qamps.imag],
        "rcs_norm": float((np.abs(ramps) ** 2).sum()),
        "tq_prob3": float(tq_p3),
        "tq_prob6": float(tq_p6),
        "tq_amp0_abs": abs(tq_amp0),
        "tq_mall": int(tq_m),
    }), flush=True)


if __name__ == "__main__":
    main()
