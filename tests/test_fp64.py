"""FPPOW float64 device path (reference: fp16-fp128 via FPPOW,
include/common/qrack_types.hpp:88-138) + f32->f64 drift escalation.

Each case runs in a subprocess: jax_enable_x64 is process-global, and
the rest of the suite must keep the production f32 defaults.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, **env_extra) -> str:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu"}, **env_extra)
    res = subprocess.run(
        [sys.executable, "-c", f"import sys; sys.path.insert(0, {REPO!r})\n" + script],
        capture_output=True, text=True, timeout=420, env=env)
    assert res.returncode == 0, (res.stdout[-3000:], res.stderr[-3000:])
    return res.stdout


def test_fppow_float64_engine_matrix():
    """QRACK_TPU_FPPOW=float64 produces real f64 planes through the
    factory default, and conformance vs the complex128 oracle holds at
    f64 tolerance (not f32's)."""
    out = _run("""
import numpy as np
import jax.numpy as jnp
import qrack_tpu
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.engines.cpu import QEngineCPU
from qrack_tpu.parallel.pager import QPager
from qrack_tpu.utils.rng import QrackRandom

t = QEngineTPU(4, rng=QrackRandom(1), rand_global_phase=False)
assert t.dtype == jnp.dtype('float64'), t.dtype
assert t._state.dtype == jnp.dtype('float64'), t._state.dtype
d = QEngineCPU(4, rng=QrackRandom(1), rand_global_phase=False)
p = QPager(4, n_pages=2, rng=QrackRandom(1), rand_global_phase=False)
assert p.dtype == jnp.dtype('float64')
for eng in (t, d, p):
    eng.H(0); eng.CNOT(0, 1); eng.T(1); eng.RY(0.37, 2)
    eng.CZ(2, 3); eng.QFT(0, 4); eng.RZ(0.11, 3)
ref = d.GetQuantumState()
for eng, name in ((t, 'tpu'), (p, 'pager')):
    got = np.asarray(eng.GetQuantumState())
    err = np.max(np.abs(got - ref))
    assert err < 1e-12, (name, err)   # f32 planes would sit at ~1e-7
print('F64_MATRIX_OK')
""", QRACK_TPU_FPPOW="float64")
    assert "F64_MATRIX_OK" in out


def test_f64_beats_f32_on_deep_circuit():
    """A deep rotation chain accumulates visible f32 error that the f64
    path eliminates — the escalation policy's reason to exist."""
    out = _run("""
import numpy as np
import jax
jax.config.update('jax_enable_x64', True)
import jax.numpy as jnp
import qrack_tpu
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.engines.cpu import QEngineCPU
from qrack_tpu.utils.rng import QrackRandom

DEPTH = 1500
def circuit(eng):
    for i in range(DEPTH):
        q = i % 3
        eng.RY(0.1 + (i % 7) * 0.01, q)
        eng.RZ(0.2 + (i % 5) * 0.01, (q + 1) % 3)
        if i % 3 == 0:
            eng.CNOT(q, (q + 1) % 3)

f32 = QEngineTPU(3, dtype=jnp.float32, rng=QrackRandom(2), rand_global_phase=False)
f64 = QEngineTPU(3, dtype=jnp.float64, rng=QrackRandom(2), rand_global_phase=False)
ora = QEngineCPU(3, rng=QrackRandom(2), rand_global_phase=False)
for eng in (f32, f64, ora):
    circuit(eng)
ref = ora.GetQuantumState()
e32 = np.max(np.abs(np.asarray(f32.GetQuantumState()) - ref))
e64 = np.max(np.abs(np.asarray(f64.GetQuantumState()) - ref))
# f32 demonstrably degraded at this depth (over 1e-6 while a window's
# operands let the norm drift; 9.6e-7 since PR 47 keeps it a window)
assert e32 > 5e-7, e32
assert e64 < 1e-11, e64         # f64 stays at oracle precision
assert e64 * 100 < e32, (e32, e64)
print('DEEP_OK', e32, e64)
""")
    assert "DEEP_OK" in out


def test_auto_escalation_on_drift():
    """QRACK_TPU_AUTO_F64_DRIFT: sustained norm drift re-casts the
    resident planes to float64 mid-run with a warning."""
    out = _run("""
import warnings
import numpy as np
import jax.numpy as jnp
import qrack_tpu
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.utils.rng import QrackRandom

e = QEngineTPU(3, rng=QrackRandom(3), rand_global_phase=False)
assert e.dtype == jnp.dtype('float32')
e._state = e._state * np.float32(1.01)   # inject 2% norm drift
with warnings.catch_warnings(record=True) as rec:
    warnings.simplefilter('always')
    for i in range(8):
        e.H(i % 3)
assert e.dtype == jnp.dtype('float64'), e.dtype
assert e._state.dtype == jnp.dtype('float64')
assert any('escalating' in str(r.message) for r in rec)
# engine still operates correctly after the switch
e.CNOT(0, 1)
p = e.Prob(1)
assert 0.0 <= p <= 1.0
print('ESCALATE_OK')
""", QRACK_TPU_AUTO_F64_DRIFT="1e-3", QRACK_TPU_DRIFT_CHECK_GATES="4")
    assert "ESCALATE_OK" in out
