"""The family ``grover`` and its references, without a chip: ``gates``
against ``amplitude`` (the harness's own check, and the target's row,
which a random start never draws), the closed form of every read against
``reference.py`` over 20 iterations, what the fuser plans for the cell at
w28 between its two rotations, a whole rehearsed run with the new
metrics, and runs with the oracle's arithmetic altered."""

import argparse
import importlib
import math

import numpy as np
import pytest

import harness
import reference
import structure
from families import CONFIGS, PARAMS, family

WIDTH = 12
CELL = "grover_w28.library"


@pytest.fixture(scope="module")
def grover():
    return family("grover")


@pytest.mark.parametrize("seed", [1, 2147483777, 3000000019])
def test_gates_are_the_closed_form(grover, seed):
    plan = grover.Plan(28, PARAMS["grover"], seed)
    assert 0 <= plan.target < 1 << 28 and plan.target not in plan.others
    assert len(plan.others) == 63
    assert harness.self_check(grover, plan.params, reference, WIDTH,
                              seed) < 1e-12
    # the row the harness's random start never draws: the target's own
    t = grover.target_of(WIDTH, plan.params)
    assert t == plan.target & 0xFFF
    state = reference.run(WIDTH, grover.gates(WIDTH, plan.params), t)
    want = np.array([grover.amplitude(WIDTH, plan.params, t, y)
                     for y in range(1 << WIDTH)])
    assert np.max(np.abs(state - want)) < 1e-12 and want[t] > 0.99


def test_the_gate_list_is_the_source_order(grover):
    """DEC and INC as carry cascades (no gate of the engine's ALU), two
    multi-controlled Z between layers of X, two layers of H, one -1."""
    n, t = 6, 0b101101
    gates = grover.gates(n, {"target": t})
    flips = [c for c, m, _ in gates if np.allclose(m, grover.Z2)]
    assert flips == [tuple(range(n - 1))] * 2  # the two ZeroPhaseFlip
    assert sum(1 for c, m, _ in gates if not c
               and np.allclose(m, grover.H2)) == 2 * n
    assert np.allclose(gates[-1][1], -np.eye(2))
    # the cascades undo each other around the flip: DEC then INC is 1
    dec = grover._add(n, (1 << n) - t)
    inc = grover._add(n, t)
    x = 0b010011
    state = reference.run(n, dec + inc, x)
    assert abs(state[x] - 1.0) < 1e-12
    assert abs(reference.run(n, dec, t)[0] - 1.0) < 1e-12


def test_the_cut_the_configuration_names_is_the_one_it_runs():
    cfg = CONFIGS["dense_grover_w28"]
    assert cfg["iterations"] == cfg["circuit"]["iterations"] == 1
    assert cfg["reduced"] == ["qubit_count", "iterations"]
    assert cfg["stack"] == CONFIGS["dense_tfim_w28"]["stack"]
    assert cfg["engine"] == CONFIGS["dense_tfim_w28"]["engine"]
    assert cfg["guarantees"][:2] == CONFIGS["dense_tfim_w28"]["guarantees"][:2]
    assert set(cfg["limits"]) == {"amplitude_rel_err", "rest_rel_err",
                                  "norm_drift_per_step"}


@pytest.mark.parametrize("seed", [5, 6])
def test_expected_is_the_reference_over_20_iterations(grover, seed):
    """Every read's closed form, and the one value of all the other
    amplitudes, against the gate list applied 20 times at w10."""
    n = 10
    plan = grover.Plan(n, PARAMS["grover"], seed)
    gates = grover.gates(n, plan.params)
    state = np.full(1 << n, 2.0 ** (-n / 2), dtype=np.complex128)
    for i in range(20):
        state = reference.evolve(state, n, gates)
        plan.applied += 1
        plan.after[i] = plan.applied
        assert grover.read_index(plan, i) == plan.target
        assert abs(state[plan.target] - grover.expected(plan, i)) < 1e-12
        rest = np.delete(state, plan.target)
        assert np.max(np.abs(rest - grover.closed_form(n, i + 1)[1])) < 1e-12
    assert abs(grover.expected(plan, 19) - math.sin(
        41 * math.asin(2.0 ** -5))) < 1e-15


def test_a_read_near_a_zero_past_the_start_is_another_amplitude(grover):
    """At w12 the target's amplitude comes back to 0 after ~100
    iterations: the read there is a seeded other one, at its largest."""
    plan = grover.Plan(WIDTH, PARAMS["grover"], 9)
    theta = math.asin(2.0 ** (-WIDTH / 2))
    for k in range(1, 260):
        plan.after[k] = k
        index, want = plan.read(k)
        angle = (2 * k + 1) * theta
        near_zero = (angle > math.pi / 2
                     and abs(math.sin(angle)) < grover.NEAR_ZERO)
        assert (index != plan.target) == near_zero, k
        if near_zero:
            assert index in plan.others
            assert abs(want) > 0.99 * 2.0 ** (-WIDTH / 2)
        else:
            assert abs(want) >= grover.NEAR_ZERO or angle < math.pi / 2
    assert any(plan.read(k)[0] != plan.target for k in range(1, 260))


def test_grover_w28_structure(grover, monkeypatch):
    """One iteration at w28: the two rotations are barriers, so the
    oracle's flip is a window of its own; the diffusion's 58 calls are 58
    ops (the last ``H`` layer's PhaseFlip finds no gate on qubit 0 left
    in its window to merge into) in two windows at the bound of 32.  27
    sweeps, 24 of them led by a ``gen`` above the tile; whatever the
    target."""
    rotations = []

    class WithRotations(structure.PlanOnlyEngine):
        def _k_rotate(self, shift, block_bits):
            if self._fuser.gates:  # an ALU call is a barrier
                self._fuser.flush("read")
            rotations.append((shift, block_bits))

    monkeypatch.setattr(structure, "PlanOnlyEngine", WithRotations)
    plans = []
    for seed in (1, 2, 2147483777):
        del rotations[:]
        windows = structure.plan_application(grover, 28, PARAMS["grover"],
                                             seed)
        target = grover.Plan(28, PARAMS["grover"], seed).target
        assert rotations == [((1 << 28) - target, 28), (target, 28)]
        plans.append(windows)
    s = structure.summary(plans[0])
    assert (s["ops"], s["windows"], s["kernel_windows"]) == (59, 3, 2)
    assert s["fallbacks"] == [("single_op", 1)]
    assert s["kernel_sweeps_by_window"] == [13, 13]
    assert s["cross_tile_segments"] == 24
    assert plans[0][0]["structure"] == (("diag", 0, True),)
    assert [len(w["structure"]) for w in plans[0]] == [1, 32, 26]
    for other in plans[1:]:
        assert [w["structure"] for w in other] == \
            [w["structure"] for w in plans[0]]


def _args(trace, seed=3000000019, seconds=0.5):
    return argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=trace, rehearse_cpu=True)


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    return importlib.import_module("run")


def test_a_rehearsed_run_reads_the_new_metrics(run, capsys):
    code, line, checks = run.execute(_args(trace=1))
    assert code == 3 and checks.correct, checks.failures
    assert line["attempted"] > 1 and line["failed"] == 0
    out = capsys.readouterr().out
    assert '"alu.programs_per_circuit"' in out
    assert '"alu.gather_programs_per_circuit"' in out
    compared = {r["check"] for r in checks.records}
    assert {"warmup_0_amplitudes", "window_read_amplitudes",
            "evolved_ket.norm_drift_per_step", "evolved_ket.target_amplitude",
            "evolved_ket.rest", "post_window_amplitudes"} <= compared


def test_the_counters_say_two_rotations_and_no_gather(run):
    """What the two counter metrics read in a rehearsed traced window."""
    from qrack_tpu import telemetry

    per_layer = harness.load_module("per_layer", "alu.programs_per_circuit")
    gathers = harness.load_module("per_layer",
                                  "alu.gather_programs_per_circuit")
    telemetry.reset()  # an earlier traced run of this process counted too
    try:
        code, line, checks = run.execute(_args(trace=1, seed=77))
        counters = telemetry.snapshot(include_events=False)["counters"]
    finally:
        telemetry.disable()
        telemetry.reset()
    assert checks.correct, checks.failures
    # set-up's warm-up and the post-window iteration ran rotations too
    extra = 2 * (1 + 1 + 1)  # warm-up, barrier probe, post-window
    n = line["attempted"]
    window = {"alu.tpu.rotate": counters["alu.tpu.rotate"] - extra,
              "alu.tpu.phase_queued": 3 * n}
    ctx = {"window_counters": window, "attempted": n}
    assert per_layer.read(ctx) == 2.0 and gathers.read(ctx) == 0.0
    assert "alu.tpu.gather" not in counters
    assert "alu.tpu.phase_fn" not in counters
    # a parent of PR 49 counts no ALU call: the readers read nothing
    assert per_layer.read({"window_counters": {}, "attempted": n}) is None
    assert gathers.read({"window_counters": {"fuse.tpu.gates": 5},
                         "attempted": n}) is None


def test_the_rotation_bytes_are_the_programs_own():
    import roofline
    import roofline_alu
    from qrack_tpu.telemetry import roofline as program_roofline

    for width in (12, 28):
        assert roofline_alu.rotate_bytes(width, 3) \
            == 3 * program_roofline.plane_pass_bytes(width, 4) \
            == 3 * roofline.sweep_bytes(width)
    assert roofline_alu.rotate_bytes(28, 1) == 4 << 30


ALTERED = {
    # the oracle's INC dropped: the register stays moved down by t
    "dropped_inc": lambda real, self, a, start, length, calls:
        None if calls % 2 == 0 else real(self, a, start, length),
    # every INC (not DEC's) by one too many, one too few
    "inc_by_t_plus_1": lambda real, self, a, start, length, calls:
        real(self, a + (calls % 2 == 0), start, length),
    "inc_by_t_minus_1": lambda real, self, a, start, length, calls:
        real(self, a - (calls % 2 == 0), start, length),
}


@pytest.mark.parametrize("case", sorted(ALTERED))
def test_altered_arithmetic_is_not_correct(run, monkeypatch, case):
    """``DEC`` reaches ``INC`` with the complement (interface/alu.py), so
    the engine's ``INC`` sees DEC, INC, DEC, INC ...: the second of each
    pair is the oracle's own ``INC``."""
    from qrack_tpu.engines.tpu import QEngineTPU

    real, calls = QEngineTPU.INC, [0]

    def altered(self, to_add, start, length):
        calls[0] += 1
        return ALTERED[case](real, self, to_add, start, length, calls[0])

    monkeypatch.setattr(QEngineTPU, "INC", altered)
    code, line, checks = run.execute(_args(trace=0))
    assert calls[0] >= 4
    assert not checks.correct and line["correct"] is False
    assert {"warmup_0_amplitudes", "window_read_amplitudes",
            "evolved_ket.rest"} & set(checks.failures)
