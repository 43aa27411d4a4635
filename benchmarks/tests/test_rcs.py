"""The family ``rcs`` and its references, without a chip: the two plain
routes against ``reference.py``, the device reference against them (and
that it is caught in bfloat16), what the fuser plans for the cell at
w28, a whole rehearsed run, and a run with one coupler altered."""

import argparse
import importlib

import numpy as np
import pytest

import harness
import reference
import reference_jnp
import structure
from families import CONFIGS, LIMITS, PARAMS, family

WIDTH = 12


@pytest.fixture(scope="module")
def rcs():
    return family("rcs")


@pytest.mark.parametrize("seed", [1, 2147483777])
def test_both_plain_routes_are_the_reference(rcs, seed):
    params = dict(PARAMS["rcs"], circuit_seed=seed)
    assert harness.self_check(rcs, params, reference, WIDTH, seed) < 1e-12
    # and the draw keeps Arute et al.'s rule, on the brick wall
    circuit = rcs.draw(28, params["cycles"], np.random.default_rng(seed))
    roots = np.array([r for r, _ in circuit])
    assert roots.min() >= 0 and roots.max() <= 2
    assert np.all(roots[1:] != roots[:-1])
    assert [len(p) for _, p in circuit] == [14, 13] * 4
    assert circuit[0][1][-1] == (26, 27) and circuit[1][1][0] == (1, 2)
    assert sum(len(r) for r, _ in circuit) == 224


def test_the_cut_the_configuration_names_is_the_one_it_runs():
    cfg = CONFIGS["dense_rcs_w28"]
    assert cfg["cycles"] == cfg["circuit"]["cycles"] == 8


def _ket_error(rcs, seed, dtype=None):
    circuit = rcs.draw(WIDTH, PARAMS["rcs"]["cycles"],
                       np.random.default_rng(seed))
    want = rcs.evolve_blocks(WIDTH, circuit, 0)
    re, im = reference_jnp.Simulator(WIDTH, dtype=dtype).run(
        rcs.blocks(circuit))
    got = np.asarray(re, np.float64) + 1j * np.asarray(im, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_device_reference_is_the_plain_route(rcs, seed):
    """Every group of qubits and the pair (6, 7) that lies in two."""
    assert _ket_error(rcs, seed) < 0.1 * LIMITS["rcs"]["ket_rel_err"]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_device_reference_in_bfloat16_is_not_correct(rcs, seed):
    import jax.numpy as jnp

    assert _ket_error(rcs, seed, jnp.bfloat16) > 10 * LIMITS["rcs"]["ket_rel_err"]


def _placements(windows):
    from qrack_tpu.ops import pallas_kernels as pk

    out = {}
    for w in windows:
        for seg in pk.plan_window(w["structure"], 16):
            name = pk.segment_kernel_name(seg, 16)[len("qrack_window_"):]
            out[name] = out.get(name, 0) + 1
    return out


def test_rcs_w28_structure(rcs):
    """332 gate calls are 218 ops: a root composes into a coupler that
    stands in the window; the same windows whatever the draw."""
    plans = [structure.plan_application(rcs, 28, PARAMS["rcs"], seed)
             for seed in (1, 2, 2147483777)]
    s = structure.summary(plans[0])
    assert s["ops"] == 218
    assert s["windows"] == s["kernel_windows"] == 14 and s["programs"] == 12
    assert s["kernel_sweeps"] == 102 and s["fallbacks"] == []
    assert _placements(plans[0]) == {
        "intile": 4, "cross": 44, "twoq_intile": 6, "twoq_pair": 4,
        "twoq_quad": 44}
    two_target = sum(kind == "u4" for w in plans[0]
                     for kind, _, _ in w["structure"])
    assert two_target == 108
    for other in plans[1:]:
        assert [w["structure"] for w in other] == \
            [w["structure"] for w in plans[0]]


def _args(trace):
    return argparse.Namespace(workload="rcs_w28.library", seed=3000000019,
                              seconds=0.5, trace=trace, rehearse_cpu=True)


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    return importlib.import_module("run")


def test_a_rehearsed_run_reads_the_new_metrics(run, capsys):
    code, line, checks = run.execute(_args(trace=1))
    assert code == 3 and checks.correct, checks.failures
    assert line["attempted"] > 1 and line["failed"] == 0
    out = capsys.readouterr().out
    assert '"kernel.twoq_sweeps_per_circuit"' in out
    assert '"twoq.eager_programs_per_circuit"' in out
    compared = {r["check"] for r in checks.records}
    assert {"last_ket.rel_err", "last_ket.amplitudes",
            "last_ket.norm_drift_per_step"} <= compared


def test_one_coupler_altered_is_not_correct(run, monkeypatch):
    from qrack_tpu.engines.tpu import QEngineTPU

    real = QEngineTPU.ISwap
    monkeypatch.setattr(
        QEngineTPU, "ISwap", lambda self, a, b:
        self.Swap(a, b) if (a, b) == (4, 5) else real(self, a, b))
    code, line, checks = run.execute(_args(trace=0))
    assert not checks.correct and line["correct"] is False
    assert "last_ket.rel_err" in checks.failures
