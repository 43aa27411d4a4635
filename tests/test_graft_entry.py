"""The driver's two entry points (``__graft_entry__``), what the models
package still exports, and the one door from ``RunFused`` to a window
program on the dense engine."""

import os
import subprocess
import sys

import numpy as np
import pytest

from qrack_tpu import QEngineCPU
from qrack_tpu.ops import gatekernels as gk
from qrack_tpu.utils.rng import QrackRandom

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_entry_jits_and_matches_the_oracle():
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    planes = np.asarray(args[0])
    assert planes.shape[0] == 2
    n = planes.shape[1].bit_length() - 1
    perm = int(np.argmax(planes[0]))
    out = jax.jit(fn)(*args)
    o = QEngineCPU(n, rng=QrackRandom(1), rand_global_phase=False)
    o.SetPermutation(perm)
    o.QFT(0, n)
    np.testing.assert_allclose(gk.from_planes(out), o.GetQuantumState(),
                               atol=2e-5)


@pytest.mark.parametrize("n_devices", [8, 4])
def test_dryrun_multichip_completes(n_devices):
    """In a process of its own: the function pins the host platform
    before any backend exists, and this process has one already."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-c",
         f"import __graft_entry__ as g; g.dryrun_multichip({n_devices}); "
         "print('DRYRUN_OK')"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "DRYRUN_OK" in p.stdout


def test_models_export_builders_and_references_only():
    """One builder per family and the references tests compare against;
    importing the package builds no program."""
    p = subprocess.run(
        [sys.executable, "-c",
         "import pkgutil\n"
         "import qrack_tpu.models as m\n"
         "from qrack_tpu.ops import fusion\n"
         "def public(mod):\n"
         "    return sorted(k for k, v in vars(mod).items()\n"
         "                  if not k.startswith('_')\n"
         "                  and getattr(v, '__module__', None) == mod.__name__)\n"
         "print(sorted(i.name for i in pkgutil.iter_modules(m.__path__)))\n"
         "print(public(m.qft))\n"
         "print(public(m.rcs))\n"
         "print(len(fusion.PROGRAMS))\n"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-3000:]
    mods, qft, rcs, programs = p.stdout.strip().splitlines()
    assert mods == "['algorithms', 'apps', 'qft', 'rcs']"
    assert qft == "['basis_planes', 'qft_qcircuit']"
    assert rcs == "['rcs_layers', 'rcs_qcircuit', 'reference_rcs_state']"
    assert programs == "0"


def test_runfused_on_dense_engine_ignores_use_pallas(monkeypatch):
    """``QRACK_USE_PALLAS`` belongs to the compressed engine: set or
    unset, RunFused on QEngineTPU asks for the one ``dense`` program."""
    from qrack_tpu.engines.tpu import QEngineTPU
    from qrack_tpu.models.qft import qft_qcircuit
    from qrack_tpu.ops import fusion as fu

    n = 6
    circ = qft_qcircuit(n)
    structure = fu.structure_of(fu.lower_gates(circ.gates))
    key = ("dense", n, "float32", structure)
    fu.PROGRAMS.clear()
    try:
        kets = []
        for value in (None, "1"):
            if value is None:
                monkeypatch.delenv("QRACK_USE_PALLAS", raising=False)
            else:
                monkeypatch.setenv("QRACK_USE_PALLAS", value)
            q = QEngineTPU(n, rng=QrackRandom(1), rand_global_phase=False)
            q.SetPermutation(5)
            circ.RunFused(q)
            kets.append(np.asarray(q.GetQuantumState()))
            assert len(fu.PROGRAMS) == 1 and key in fu.PROGRAMS
        np.testing.assert_array_equal(kets[0], kets[1])
    finally:
        fu.PROGRAMS.clear()
