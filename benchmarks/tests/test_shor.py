"""The family ``shor`` and its references, without a chip: ``gates``
against ``amplitude`` from three start states, the register's closed-form
distribution against the reference's ket, what the fuser, the ALU and the
measurement plan for the cell at w28, a whole rehearsed run with the new
metrics, and runs with the table, a window, the collapse's scale and the
sampler altered underneath."""

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
CELL = "shor_w28.library"


@pytest.fixture(scope="module")
def shor():
    return family("shor")


@pytest.mark.parametrize("seed", [1, 2147483777, 3000000019])
def test_the_plan_draws_a_semiprime_and_coprime_bases(shor, seed):
    plan = shor.Plan(28, PARAMS["shor"], seed)
    assert plan.N.bit_length() == 14 and plan.N % 2 == 1
    factors = [p for p in range(3, 128, 2) if plan.N % p == 0]
    p = factors[0]
    q = plan.N // p
    assert p != q and all(q % d for d in range(2, int(q ** 0.5) + 1))
    assert all(p % d for d in range(2, int(p ** 0.5) + 1))
    for i in (plan.WARM, 0, 1, 299, plan.POST):
        a, x = plan.draw(i)
        assert 1 < a < plan.N and math.gcd(a, plan.N) == 1
        assert 0 <= x < 1 << 14
    # the same seed, the same inputs, whatever was asked first
    again = shor.Plan(28, PARAMS["shor"], seed)
    assert again.draw(299) == plan.draw(299) and again.N == plan.N
    assert shor.Plan(28, PARAMS["shor"], seed + 1).draw(0) != plan.draw(0)


@pytest.mark.parametrize("start", [0, 0b000000101101, 0b110101011010])
@pytest.mark.parametrize("N,a", [(55, 7), (33, 5), (57, 2)])
def test_gates_are_the_closed_form(shor, N, a, start):
    """From ``|0...0>`` (the engine's domain: the out register at 0), from
    another state of that domain, and from one off it."""
    params = dict(PARAMS["shor"], N=N, a=a)
    state = reference.run(WIDTH, shor.gates(WIDTH, params), start)
    want = np.array([shor.amplitude(WIDTH, params, start, y)
                     for y in range(1 << WIDTH)])
    assert np.max(np.abs(state - want)) < 1e-12
    assert abs(np.sum(np.abs(want) ** 2) - 1.0) < 1e-12


@pytest.mark.parametrize("N,a", [(55, 7), (33, 5), (39, 38), (51, 16)])
def test_closed_forms_from_zero_are_the_reference(shor, N, a):
    """``column``, ``register_distribution`` and the collapsed ket against
    the reference's ket; ``P(y)`` sums to 1."""
    n = WIDTH // 2
    state = reference.run(WIDTH, shor.gates(WIDTH, {"N": N, "a": a}), 0)
    ket = state.reshape(1 << n, 1 << n)  # [v, y]
    want = np.sum(np.abs(ket) ** 2, axis=0)
    got = shor.register_distribution(a, N, n)
    assert abs(np.sum(got) - 1.0) < 1e-12
    assert np.max(np.abs(got - want)) < 1e-13
    plan = shor.Plan(WIDTH, PARAMS["shor"], 3)
    plan.N = N
    for y in (0, 1, 17, 40, 63):
        assert np.max(np.abs(shor.column(a, N, n, y) - ket[:, y])) < 1e-13
        if want[y] > 1e-9:
            values = np.arange(1 << n)
            closed, p = shor._collapsed(plan, a, y, values)
            assert abs(p - want[y]) < 1e-13
            assert np.max(np.abs(closed - ket[:, y] / math.sqrt(want[y]))) < 1e-12
    # the lowest bit of the measured value is a fair coin, exactly
    assert abs(np.sum(got[::2]) - 0.5) < 1e-13


def test_the_host_table_of_the_engine_is_not_the_references(shor):
    """The reference's ``f`` is Python's ``pow``; the engine's table is
    built by doubling in numpy.  They agree, and share no code."""
    from qrack_tpu.ops import alu_kernels

    assert shor.table(7, 15943, 14).tolist() == \
        alu_kernels.powmod_table(7, 15943, 14).tolist()
    assert "alu_kernels" not in open(shor.__file__).read().replace(
        "qrack_tpu.ops", "")


def test_the_configuration_is_grovers_stack_and_one_more_guarantee():
    cfg, grover = CONFIGS["dense_shor_w28"], CONFIGS["dense_grover_w28"]
    assert cfg["stack"] == grover["stack"] and cfg["engine"] == grover["engine"]
    assert cfg["precision"] == grover["precision"]
    assert cfg["guarantees"][:4] == grover["guarantees"]
    assert len(cfg["guarantees"]) == 5 and "measurement" in cfg["guarantees"][4]
    assert cfg["reduced"] == ["qubit_count"]
    assert (cfg["qubit_count"], cfg["rehearse_qubit_count"]) == (28, 12)
    assert set(cfg["limits"]) == {"amplitude_rel_err", "register_prob_rel_err",
                                  "norm_drift_per_step"}
    assert cfg["limits"]["amplitude_rel_err"] < 1e-3  # test_run alters by that


class PlanOnlyShor(structure.PlanOnlyEngine):
    """No planes: the ALU's and the measurement's programs are recorded
    where they would run; each is a barrier, as a read of the planes."""

    programs = None

    def _barrier(self, name):
        if self._fuser.gates:
            self._fuser.flush("read")
        self.programs.append(name)

    def _k_modn(self, name, table, in_start, length, out_start, ol,
                inverse=False):
        assert len(table) == 1 << length and table.dtype == np.int32
        self._barrier(("modn", name, in_start, length, out_start, ol))

    def _k_out_of_place(self, *args):
        self._barrier("out_of_place")

    def _k_prob_reg_all(self, start, length):
        self._barrier(("prob_reg", start, length))
        return np.full(1 << length, 1.0 / (1 << length))

    def _k_prob_mask(self, mask, perm):
        self._barrier("prob_mask")
        return 0.5

    def _k_collapse(self, mask, val, nrm_sq):
        self._barrier(("collapse", mask))


def test_shor_w28_structure(shor, monkeypatch):
    """One attempt at w28: the table write is a barrier behind the 14 H
    (one window, one in-tile sweep), the measurement's reduction flushes
    IQFT(0, 14) (14 H and 91 cphase in four windows at the bound of 32):
    five sweeps, none led.  One ALU program, two of the measurement."""
    PlanOnlyShor.programs = []
    monkeypatch.setattr(structure, "PlanOnlyEngine", PlanOnlyShor)
    plans = []
    for seed in (1, 2, 2147483777):
        del PlanOnlyShor.programs[:]
        windows = structure.plan_application(shor, 28, PARAMS["shor"], seed)
        assert PlanOnlyShor.programs == [
            ("modn", "POWModNOut", 0, 14, 14, 14), ("prob_reg", 0, 14),
            ("collapse", (1 << 14) - 1)]
        plans.append(windows)
    s = structure.summary(plans[0])
    assert (s["ops"], s["windows"], s["kernel_windows"]) == (119, 5, 5)
    assert s["kernel_sweeps_by_window"] == [1] * 5 and not s["fallbacks"]
    assert s["cross_tile_segments"] == 0
    assert [len(w["structure"]) for w in plans[0]] == [14, 32, 32, 32, 9]
    assert plans[0][0]["structure"] == tuple(
        ("gen", q, False) for q in range(14))
    for other in plans[1:]:  # whatever N and the base
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
    assert '"measure.passes_per_circuit"' in out
    assert '"modn.scatter_programs_per_circuit"' in out
    compared = {r["check"] for r in checks.records}
    assert {"warmup_0.before_measurement", "warmup_0.collapsed",
            "window_read_amplitudes", "window_measured_values_are_possible",
            "window_low_bit_is_a_fair_coin",
            "post_window.before_measurement.norm_drift_per_step",
            "post_window.before_measurement.no_mass_outside_the_orbit",
            "post_window.register_distribution", "post_window.collapsed",
            "post_window.collapsed.norm_drift_per_step",
            "post_window.collapsed.no_mass_off_the_column"} <= compared


def test_the_counters_say_one_table_write_and_two_passes(run):
    """What the counter metrics read in a rehearsed traced window."""
    from qrack_tpu import telemetry

    import roofline_measure

    scatter = harness.load_module("per_layer",
                                  "modn.scatter_programs_per_circuit")
    passes = harness.load_module("per_layer", "measure.passes_per_circuit")
    telemetry.reset()  # an earlier traced run of this process counted too
    try:
        code, line, checks = run.execute(_args(trace=1, seed=77))
        counters = telemetry.snapshot(include_events=False)["counters"]
    finally:
        telemetry.disable()
        telemetry.reset()
    assert checks.correct, checks.failures
    n = line["attempted"]
    # set-up's warm-up and barrier probe, the two post-window applications
    assert counters["alu.tpu.modn"] == n + 4
    assert counters["measure.tpu.reg"] == n + 3
    assert counters["measure.tpu.passes"] == 2 * (n + 3)
    assert "alu.tpu.out_of_place" not in counters
    assert "measure.tpu.bit" not in counters
    width = 12
    assert counters[roofline_measure.MODN_PLANNED] == \
        roofline_measure.modn_write_bytes(width, n + 4)
    assert counters[roofline_measure.MEASURE_PLANNED] == \
        roofline_measure.measure_bytes(width, n + 3)
    window = {"alu.tpu.modn": n, "measure.tpu.reg": n,
              "measure.tpu.passes": 2 * n}
    ctx = {"window_counters": window, "attempted": n}
    assert scatter.read(ctx) == 0.0 and passes.read(ctx) == 2.0
    # a parent of PR 53 counts neither: the readers read nothing
    bare = {"window_counters": {}, "attempted": n}
    assert scatter.read(bare) is None and passes.read(bare) is None
    for name in ("modn.ms_per_circuit", "modn_write_roofline",
                 "measure.ms_per_circuit", "measure_roofline",
                 "measure.host_ms"):
        reader = harness.load_module("per_layer", name)
        assert reader.read(dict(bare, trace=None)) is None


def _a_table_for_another_base(monkeypatch):
    from qrack_tpu.ops import alu_kernels

    real = alu_kernels.powmod_table
    monkeypatch.setattr(alu_kernels, "powmod_table",
                        lambda base, mod_n, length: real(base + 1, mod_n, length))


def _a_dropped_window(monkeypatch):
    """A flush that returns the ket unchanged, once in a while."""
    from qrack_tpu.engines.tpu import QEngineTPU

    real, calls = QEngineTPU._fuse_flush, [0]

    def flush(self, gates):
        calls[0] += 1
        if calls[0] % 4 == 0:  # an IQFT window: the H layer's are odd
            return 1
        return real(self, gates)

    monkeypatch.setattr(QEngineTPU, "_fuse_flush", flush)


def _a_collapse_without_its_scale(monkeypatch):
    from qrack_tpu.engines.tpu import QEngineTPU

    real = QEngineTPU._k_collapse
    monkeypatch.setattr(QEngineTPU, "_k_collapse",
                        lambda self, mask, val, nrm_sq: real(self, mask, val, 1.0))


def _a_sampler_that_ignores_the_ket(monkeypatch):
    from qrack_tpu.engines.qengine import QEngine

    monkeypatch.setattr(QEngine, "_draw_reg",
                        lambda self, probs, result, do_force: 0)


@pytest.mark.parametrize("break_it", [
    _a_table_for_another_base, _a_dropped_window,
    _a_collapse_without_its_scale, _a_sampler_that_ignores_the_ket])
def test_a_broken_attempt_is_not_correct(run, break_it, monkeypatch):
    break_it(monkeypatch)
    code, line, checks = run.execute(_args(trace=0))
    assert not checks.correct and line["correct"] is False
    expect = {
        _a_table_for_another_base: "warmup_0.before_measurement",
        _a_dropped_window: "window_read_amplitudes",
        _a_collapse_without_its_scale: "warmup_0.collapsed",
        _a_sampler_that_ignores_the_ket: "window_low_bit_is_a_fair_coin",
    }[break_it]
    assert expect in checks.failures, checks.failures
