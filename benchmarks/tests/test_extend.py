"""A later PR adds a cell, a traffic mix and a per-layer metric as new
files and entries, and edits no file that is there.  Dry check: a copy
of the benchmark in a temporary directory, plus new files only, and the
harness runs the new cell and reads the new metric."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

NEW_METRIC = '''"""Windows the fuser flushed for one application."""


def read(ctx):
    c = ctx["window_counters"]
    n = c.get("fuse.kernel.windows", 0) + c.get("fuse.xla.windows", 0)
    return n / ctx["attempted"] if n else None
'''


def test_new_files_and_entries_only(tmp_path):
    tree = tmp_path / "benchmarks"
    shutil.copytree(BENCH, tree, ignore=shutil.ignore_patterns(
        "__pycache__", "data", "tests"))
    before = {p: p.read_bytes() for p in tree.rglob("*") if p.is_file()}

    (tree / "traffic" / "library_one_warmup.json").write_text(json.dumps({
        "kind": "library", "loop": "closed", "callers": 1,
        "warmup_applications": 1, "traced_seconds": 1.0,
        "why": "a second mix for the dry check"}))
    (tree / "per_layer" / "fuser.windows_per_circuit.py").write_text(NEW_METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({
        "name": "tfim_w28.library_one_warmup", "config": "dense_tfim_w28",
        "traffic": "library_one_warmup", "chips": 1, "why": "dry check"})
    bench["per_layer"].append({
        "name": "fuser.windows_per_circuit", "unit": "count",
        "better": "lower", "source": "program_counter", "layer": "fuser",
        "moves": "circuit_ms.p50",
        "workloads": ["tfim_w28.library_one_warmup"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    def rehearse(cell):
        out = subprocess.run(
            [sys.executable, str(tree / "run.py"), "--workload", cell,
             "--seed", "5", "--seconds", "0.5", "--trace", "1",
             "--rehearse-cpu"], capture_output=True, text=True, cwd=tmp_path,
            env=dict(os.environ, PYTHONPATH=ROOT))
        assert out.returncode == 3, out.stderr[-2000:]
        lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
        return next(l["rehearsed_metrics"] for l in lines
                    if "rehearsed_metrics" in l), lines

    new, lines = rehearse("tfim_w28.library_one_warmup")
    assert "fuser.windows_per_circuit" in new
    assert any(l.get("checks_passed") is True for l in lines)
    old, _ = rehearse("tfim_w28.library")
    assert "fuser.windows_per_circuit" not in old  # its cells are listed
    assert all(p.read_bytes() == b for p, b in before.items())
