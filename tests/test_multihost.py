"""Multi-host distribution: REAL 2- and 4-process jax.distributed runs.

N subprocesses each own 8/N virtual CPU devices; cluster bring-up
(parallel/cluster.py) joins them into one 8-device global mesh, and a
QPager shards one coherent 7-qubit ket across every process.  The
paged-target gates in the worker circuit ppermute shard halves across
the process boundary (gloo standing in for DCN), proving the sharded
kernels are mesh-shape agnostic — the exact property SURVEY.md §2.3
prescribes for the TPU-native cluster axis (reference's dormant
equivalents: CMakeLists.txt:110 SnuCL, :201-203 GVirtuS)."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from qrack_tpu import QEngineCPU
from qrack_tpu.utils.rng import QrackRandom

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "multihost_worker.py")
PAGER_WORKER = os.path.join(HERE, "multihost_pager_worker.py")

# coordinator bring-up failures are ENVIRONMENT, not regression: the
# free port can be stolen between bind and use, and CI sandboxes can
# forbid the loopback listener outright — skip, never hang or fail
_INIT_FAIL_MARKERS = (
    "Address already in use",
    "address already in use",
    "Connection refused",
    "DEADLINE_EXCEEDED",
    "UNAVAILABLE",
    "failed to connect",
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_cluster(worker, n_procs, timeout=240, extra_env=None):
    """Launch n_procs copies of ``worker`` wired to one coordinator and
    return their parsed RESULT dicts.  Worker crashes that smell like
    coordinator bring-up failure skip the test; timeouts kill the whole
    cohort and fail (tier-1 must never hang on a wedged rendezvous)."""
    local = 8 // n_procs
    port = _free_port()
    procs = []
    for pid in range(n_procs):
        env = dict(
            os.environ,
            QRACK_COORDINATOR=f"localhost:{port}",
            QRACK_NUM_PROCESSES=str(n_procs),
            QRACK_PROCESS_ID=str(pid),
            QRACK_WORKER_LOCAL_DEVICES=str(local),
            # the parent test process pins 8 virtual devices via
            # XLA_FLAGS (conftest); workers get 8/n_procs each
            XLA_FLAGS=f"--xla_force_host_platform_device_count={local}",
        )
        if extra_env:
            env.update(extra_env)
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    outs = []
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                pytest.fail(f"multihost worker timed out after {timeout}s "
                            "(coordinator rendezvous wedged?)")
            if p.returncode != 0:
                if any(m in err for m in _INIT_FAIL_MARKERS):
                    pytest.skip("cluster bring-up unavailable here: "
                                + err.strip().splitlines()[-1][:200])
                assert False, f"worker failed:\n{err[-3000:]}"
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    results = []
    for out in outs:
        lines = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        assert lines, f"no RESULT line in worker output:\n{out[-2000:]}"
        results.append(json.loads(lines[0][len("RESULT "):]))
    return results


def _oracle_state_and_prob():
    q = QEngineCPU(7, rng=QrackRandom(777), rand_global_phase=False)
    q.SetPermutation(0)
    for i in range(7):
        q.H(i)
    for i in range(6):
        q.CNOT(i, i + 1)
    q.CZ(4, 6)
    q.Swap(0, 5)
    q.T(6)
    q.H(6)
    return q.GetQuantumState(), q.Prob(3)


@pytest.mark.parametrize("n_procs", [2, 4])
def test_cluster_matches_oracle(n_procs):
    results = _run_cluster(WORKER, n_procs)

    ref_state, ref_p3 = _oracle_state_and_prob()
    # the oracle of the QFT the workers ran through RunFused(QPager)
    oq = QEngineCPU(7, rng=QrackRandom(777), rand_global_phase=False)
    oq.SetPermutation(5)
    oq.QFT(0, 7)
    ref_qft = np.asarray(oq.GetQuantumState())
    for r in results:
        assert r["procs"] == n_procs
        assert r["n_global_devices"] == 8
        got = np.asarray(r["re"]) + 1j * np.asarray(r["im"])
        np.testing.assert_allclose(got, ref_state, atol=3e-5)
        assert abs(r["prob3"] - ref_p3) < 3e-5
        # the families ran on the engine over the multi-process mesh
        got_qft = np.asarray(r["qft_re"]) + 1j * np.asarray(r["qft_im"])
        np.testing.assert_allclose(got_qft, ref_qft, atol=3e-5)
        assert abs(r["rcs_norm"] - 1.0) < 1e-3
        # sharded compressed ket over the same cluster (16-bit lossy
        # tolerance): uniform superposition -> both marginals 1/2
        assert abs(r["tq_prob3"] - 0.5) < 1e-3
        assert abs(r["tq_prob6"] - 0.5) < 1e-3
        # block-local amplitude read before MAll: uniform superposition
        # amplitude magnitude 2^-3.5
        assert abs(r["tq_amp0_abs"] - 2 ** -3.5) < 1e-3
    # host-side measurement draws must agree across processes
    assert len({r["mall"] for r in results}) == 1
    assert len({r["tq_mall"] for r in results}) == 1


def test_multihost_pager_w20_qft(tmp_path):
    """2-process / 8-device global mesh: a remap-on QPager runs a w20
    QFT end-to-end with the BATCHED exchange collective riding the
    inter-host page axis (top page bit = DCN stand-in), stays at
    fidelity ~1.0 vs the CPU oracle, and a checkpoint written under the
    global mesh restores bit-identically on every process."""
    results = _run_cluster(
        PAGER_WORKER, 2, timeout=360,
        extra_env={"QRACK_CKPT_DIR": str(tmp_path),
                   "QRACK_TPU_FUSE_WINDOW": "16"})
    assert len(results) == 2
    for r in results:
        assert r["procs"] == 2 and r["n_global_devices"] == 8
        # pages 0-3 live on process 0, 4-7 on process 1: the TOP page
        # bit is the process-spanning (DCN) axis, the low two are ICI
        assert r["kinds"] == ["ici", "ici", "dcn"]
        assert r["fidelity"] > 1 - 1e-6, r["fidelity"]
        assert abs(r["prob3_diff"]) < 3e-5
        # the planner fired at least one >= 2-pair batched prologue and
        # its collective crossed the wire (bytes counted by the
        # lowering's accounting twin)
        assert r["remap_batched"] >= 1
        assert r["remap_pairs"] >= 2
        assert r["exchange_bytes"] > 0
        assert r["collective_bytes"] > 0
        assert r["restore_identical"] and r["restore_qmap_ok"]
