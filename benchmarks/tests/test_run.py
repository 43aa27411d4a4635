"""The harness end to end on the CPU: the result line's keys, a sound
rehearsal whose comparisons all hold, and the same run with the timed
path broken underneath, whose comparisons do not."""

import argparse
import importlib
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _args(workload, trace=0, seconds=0.5, seed=2147483777):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, rehearse_cpu=True)


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    return importlib.import_module("run")


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
@pytest.mark.parametrize("trace", [0, 1])
def test_a_sound_rehearsal_passes_every_comparison(run, cell, trace):
    code, line, checks = run.execute(_args(cell, trace))
    assert code == 3 and line["correct"] is False  # a rehearsal, never a run
    assert set(line) >= KEYS and line["metrics"] == {}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert checks.correct, checks.failures


ENGINES = {"QEngineTPU": "qrack_tpu.engines.tpu",
           "QPager": "qrack_tpu.parallel.pager"}


def _engine_class(cell):
    """The class the cell's configuration names: what the window drives."""
    import harness

    name = harness.Cell(cell).config["engine"]["class"]
    return getattr(importlib.import_module(ENGINES[name]), name)


def _drop_a_window(monkeypatch, engine):
    """A flush that returns the ket unchanged, once in a while."""
    real, calls = engine._fuse_flush, [0]

    def flush(self, gates):
        calls[0] += 1
        if calls[0] % 5 == 0:
            return 1  # claims a dispatch, applies nothing
        return real(self, gates)

    monkeypatch.setattr(engine, "_fuse_flush", flush)


def _alter_a_read(monkeypatch, engine):
    """An answer altered where it is produced."""
    real = engine.GetAmplitude
    monkeypatch.setattr(engine, "GetAmplitude",
                        lambda self, perm: real(self, perm) * (1 + 1e-3))


@pytest.mark.parametrize("cell", _cells())
@pytest.mark.parametrize("break_it", [_drop_a_window, _alter_a_read])
def test_a_broken_timed_path_is_not_correct(run, cell, break_it, monkeypatch):
    break_it(monkeypatch, _engine_class(cell))
    code, line, checks = run.execute(_args(cell))
    assert not checks.correct
    assert line["correct"] is False


def test_no_accelerator_no_result():
    """Without a TPU the command ends non-zero and prints no result."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         _cells()[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "correct" not in out.stdout


def test_the_last_line_is_the_result(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         _cells()[0], "--seed", "3000000019", "--seconds", "0.5", "--trace",
         "1", "--rehearse-cpu"],
        capture_output=True, text=True, cwd=tmp_path,
        env=dict(os.environ, BENCH_RUN="7"))
    assert out.returncode == 3
    lines = out.stdout.strip().splitlines()
    assert set(json.loads(lines[-1])) >= KEYS
    for earlier in lines[:-1]:
        json.loads(earlier)


def test_no_program_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files there is no system under test: non-zero, no result."""
    import shutil

    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", _cells()[0],
         "--seed", "1", "--seconds", "0.2", "--trace", "0", "--rehearse-cpu"],
        capture_output=True, text=True, cwd=tmp_path, env=env)
    assert out.returncode not in (0, 3)
    assert '"correct"' not in out.stdout
