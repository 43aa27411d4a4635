"""Integrity guard plane: silent-corruption detection, scoped window
replay, quarantine-feeds-elastic, verified failover persist, pre-dispatch
shed, and serve-side canary verification (docs/INTEGRITY.md).

Engines in these tests are constructed AFTER ``res.enable()`` — the
forced window-1 fuser (the repair envelope for eager dispatch) only
builds when the resilience layer is up at construction time.  Fuser
drains happen OUTSIDE ``faults.suspended()`` so an armed spec still
fires inside the guarded flush (a suspended read flushes with
injection stood down and the test would test nothing).
"""

import os
import types

import numpy as np
import pytest

from qrack_tpu import QEngineCPU, create_quantum_interface
from qrack_tpu import resilience as res
from qrack_tpu import telemetry as tele
from qrack_tpu.resilience import faults
from qrack_tpu.resilience import integrity as integ
from qrack_tpu.resilience.errors import CorruptionDetected
from qrack_tpu.utils.rng import QrackRandom


@pytest.fixture(autouse=True)
def _clean_resilience():
    faults.clear()
    res.reset_breaker()
    res.configure(max_retries=2, backoff_s=0.0, timeout_s=0.0)
    integ.reset()
    yield
    faults.clear()
    res.reset_breaker()
    res.configure()  # re-read env (defaults)
    res.disable()
    integ.reset()
    integ.set_enabled(os.environ.get("QRACK_TPU_INTEGRITY", "") != "0")
    tele.disable()
    tele.reset()


N = 5

# fusable-only circuit (structural ops commit outside the fused-flush
# envelope, docs/INTEGRITY.md); H(4)/H(3) are GLOBAL qubits at
# n_pages=4, so the window-1 pager rows dispatch at pager.exchange
_OPS = [("H", (0,)), ("H", (4,)), ("CNOT", (0, 1)), ("T", (1,)),
        ("RY", (0.7, 2)), ("CZ", (1, 2)), ("X", (3,)), ("H", (3,)),
        ("RZ", (0.3, 4)), ("S", (2,))]


def _fidelity(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(abs(np.vdot(a, b)) ** 2
                 / (np.vdot(a, a).real * np.vdot(b, b).real))


# ---------------------------------------------------------------------------
# detector units
# ---------------------------------------------------------------------------

def test_drift_budget_schedule(monkeypatch):
    assert integ.drift_budget(0) == pytest.approx(1e-3)
    monkeypatch.setenv("QRACK_TPU_INTEGRITY_TOL", "0.5")
    monkeypatch.setenv("QRACK_TPU_INTEGRITY_TOL_PER_GATE", "0.01")
    assert integ.drift_budget(10) == pytest.approx(0.6)
    assert integ.drift_budget(-3) == pytest.approx(0.5)  # clamped


def test_host_fingerprint_pages():
    planes = np.zeros((2, 8))
    planes[0, 0] = 1.0          # page 0 (real plane)
    fp = integ.host_fingerprint(planes, n_pages=4)
    assert fp == pytest.approx([1.0, 0.0, 0.0, 0.0])
    planes[1, 5] = 2.0          # page 5 // 2 == 2 (imag plane)
    fp = integ.host_fingerprint(planes, n_pages=4)
    assert fp == pytest.approx([1.0, 0.0, 4.0, 0.0])
    # dense engine: one page, one scalar
    assert integ.host_fingerprint(planes, n_pages=1) == \
        pytest.approx([5.0])


def test_verify_passes_and_detects_on_live_engine():
    import jax.numpy as jnp

    res.enable()
    q = create_quantum_interface("tpu", 4, rng=QrackRandom(1),
                                 rand_global_phase=False)
    q.H(0)
    q.CNOT(0, 1)
    _ = q.Prob(0)               # drain the forced window-1 fuser
    eng = q.engine
    fp = integ.verify(eng, "t")
    assert fp.sum() == pytest.approx(1.0, abs=1e-6)
    good = np.asarray(eng._state_raw)
    # norm drift: scaled planes blow the budget
    eng._state_raw = jnp.asarray(good * 1.5)
    with pytest.raises(CorruptionDetected, match="norm drift"):
        integ.verify(eng, "t")
    # finiteness: a nan plane is caught before the norm check
    bad = good.copy()
    bad[0, 0] = np.nan
    eng._state_raw = jnp.asarray(bad)
    with pytest.raises(CorruptionDetected, match="non-finite"):
        integ.verify(eng, "t")


def test_check_host_invariants():
    integ.check_host("x.read", np.array([0.5, 0.5]))  # finite: fine
    with pytest.raises(CorruptionDetected):
        integ.check_host("x.read", np.array([0.5, np.nan]))
    with pytest.raises(CorruptionDetected):
        integ.check_host("x.read", np.array([0.9, 0.9]),
                         norm_expected=1.0)
    integ.check_host("x.read", np.array([1.0, 0.0]), norm_expected=1.0)
    # recovery reads (failover snapshot, re-page gather) are exempt
    with faults.suspended():
        integ.check_host("x.read", np.array([np.nan]))
    # non-float payloads (measurement ints) pass through untouched
    integ.check_host("x.read", np.array([3], dtype=np.int64))


def test_quarantine_strikes_and_reset(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_QUARANTINE_STRIKES", "2")
    epoch0 = integ._EPOCH
    integ.record_strike(7, "t")
    assert integ.strikes() == {7: 1} and not integ.quarantined()
    integ.record_strike(7, "t")
    assert integ.quarantined() == {7}
    assert integ._EPOCH == epoch0 + 1
    devs = [types.SimpleNamespace(id=i) for i in range(4)]
    assert [d.id for d in integ.healthy_devices(devs)] == [0, 1, 2, 3]
    integ.record_strike(2, "t")
    integ.record_strike(2, "t")
    assert [d.id for d in integ.healthy_devices(devs)] == [0, 1, 3]
    # a fully-quarantined mesh still serves (degraded beats dead)
    for i in (0, 1, 3):
        integ.record_strike(i, "t")
        integ.record_strike(i, "t")
    assert [d.id for d in integ.healthy_devices(devs)] == [0, 1, 2, 3]
    integ.reset()
    assert not integ.strikes() and not integ.quarantined()


# ---------------------------------------------------------------------------
# detect-and-repair matrix: every flush envelope site, windows 1 and 32
# ---------------------------------------------------------------------------

_MATRIX = [
    ("tpu", 1, "tpu.compile", {}),
    ("tpu", 32, "tpu.fuse.flush", {}),
    # remap off: the placement planner would turn the lone global op
    # into a remapped local window (tpu.fuse.flush) and the pair
    # exchange under test would never dispatch (test_remap.py covers
    # the planner path)
    ("pager", 1, "pager.exchange", {"n_pages": 4, "remap": "off"}),
    ("pager", 32, "tpu.fuse.flush", {"n_pages": 4}),
]


@pytest.mark.parametrize("stack,window,site,kw", _MATRIX,
                         ids=[f"{s}-w{w}-{t}" for s, w, t, _ in _MATRIX])
def test_detect_and_repair_matches_oracle(stack, window, site, kw,
                                          monkeypatch):
    """A one-shot amp-corrupt on the site that carries the trial's
    state commits is detected at the flush verify, repaired by scoped
    window replay, and the final state stays oracle-equivalent."""
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(window))
    tele.enable()
    res.enable()
    o = QEngineCPU(N, rng=QrackRandom(3), rand_global_phase=False)
    s = create_quantum_interface(stack, N, rng=QrackRandom(3),
                                 rand_global_phase=False, **kw)
    # unseeded: fires deterministically on the first matching dispatch
    faults.inject(site, "amp-corrupt", after_n=0, times=1)
    for name, args in _OPS:
        getattr(o, name)(*args)
        getattr(s, name)(*args)
    _ = s.Prob(0)  # drain the fuser OUTSIDE suspension
    c = tele.snapshot()["counters"]
    fired = sum(sp.fired for sp in faults.specs())
    assert fired == 1
    assert c.get("integrity.violation", 0) >= 1
    assert c.get("integrity.replay.repaired", 0) >= 1
    with faults.suspended():
        a = np.asarray(o.GetQuantumState())
        b = np.asarray(s.GetQuantumState())
    assert _fidelity(a, b) > 1 - 1e-6


def test_page_pinned_strike_attribution():
    """A corruption pinned to one page strikes that page's device —
    the clean replay of the same deterministic window is the oracle."""
    tele.enable()
    res.enable()
    s = create_quantum_interface("pager", N, n_pages=4, remap="off",
                                 rng=QrackRandom(3),
                                 rand_global_phase=False)
    s.H(4)          # global gate: the pager.exchange envelope
    _ = s.Prob(0)
    faults.inject("pager.exchange", "amp-corrupt", after_n=0, times=1,
                  page=2, n_pages=4)
    s.H(3)
    _ = s.Prob(0)
    assert sum(sp.fired for sp in faults.specs()) == 1
    dev2 = s.engine.GetDeviceList()[2]
    assert integ.strikes().get(dev2) == 1


# ---------------------------------------------------------------------------
# quarantine feeds elastic: repeated strikes swap the flaky chip out
# ---------------------------------------------------------------------------

def test_quarantine_feeds_elastic_repage(monkeypatch):
    """Three attributed strikes quarantine a device; the pager's next
    job-boundary probe re-pages onto the spare and serving continues
    oracle-equivalent on a mesh that excludes the flaky chip."""
    monkeypatch.setenv("QRACK_TPU_QUARANTINE_STRIKES", "3")
    tele.enable()
    res.enable()
    o = QEngineCPU(N, rng=QrackRandom(3), rand_global_phase=False)
    s = create_quantum_interface("pager", N, n_pages=4, remap="off",
                                 rng=QrackRandom(3),
                                 rand_global_phase=False)
    pager = s.engine
    before = list(pager.GetDeviceList())
    bad_dev = before[2]
    for k in range(3):
        faults.inject("pager.exchange", "amp-corrupt", after_n=0,
                      times=1, page=2, n_pages=4)
        getattr(o, "H")(4 if k % 2 else 3)
        getattr(s, "H")(4 if k % 2 else 3)
        _ = s.Prob(0)
        faults.clear()
    assert integ.strikes().get(bad_dev) == 3
    assert bad_dev in integ.quarantined()
    # job-boundary probe: returns False (no ELASTIC expand pending) but
    # consumes the quarantine epoch and re-pages off the flaky chip
    pager.maybe_reexpand()
    after = list(pager.GetDeviceList())
    assert bad_dev not in after and len(after) == 4
    o.CNOT(0, 1)
    s.CNOT(0, 1)
    o.H(4)
    s.H(4)
    _ = s.Prob(0)
    with faults.suspended():
        a = np.asarray(o.GetQuantumState())
        b = np.asarray(s.GetQuantumState())
    assert _fidelity(a, b) > 1 - 1e-6
    c = tele.snapshot()["counters"]
    assert c.get("integrity.quarantine.device", 0) >= 1
    assert c.get("integrity.quarantine.repage", 0) >= 1


# ---------------------------------------------------------------------------
# failover persist: verified before it replaces the previous good file
# ---------------------------------------------------------------------------

def test_persist_rejects_poisoned_snapshot(tmp_path, monkeypatch):
    """A nan-poisoned ket must NOT overwrite the newest good snapshot:
    the capture is verified and rejected before any file is written."""
    import jax.numpy as jnp

    from qrack_tpu.resilience.failover import _persist_snapshot

    monkeypatch.setenv("QRACK_TPU_FAILOVER_PERSIST", str(tmp_path))
    tele.enable()
    res.enable()
    q = create_quantum_interface("tpu", 4, rng=QrackRandom(1),
                                 rand_global_phase=False)
    q.H(0)
    _ = q.Prob(0)
    eng = q.engine
    good = np.asarray(eng._state_raw)
    # clean engine persists
    path = _persist_snapshot(eng, RuntimeError("evidence"))
    assert path is not None and os.path.exists(path)
    n_files = len(os.listdir(tmp_path))
    # poisoned engine is rejected: no new file, explicit event
    bad = good.copy()
    bad[0, 0] = np.nan
    eng._state_raw = jnp.asarray(bad)
    assert _persist_snapshot(eng, RuntimeError("poison")) is None
    assert len(os.listdir(tmp_path)) == n_files
    c = tele.snapshot()["counters"]
    assert c.get("resilience.failover.persist_rejected", 0) == 1
    # event + explicit inc both land on the counter: one persist >= 1
    assert c.get("resilience.failover.persisted", 0) >= 1


# ---------------------------------------------------------------------------
# serve: pre-dispatch shed + canary verification
# ---------------------------------------------------------------------------

def test_pre_dispatch_shed_of_budget_expired_jobs():
    """A job whose queue budget ran out while its batch was being
    assembled is shed at dispatch time, not executed stale."""
    from qrack_tpu.models.qft import qft_qcircuit
    from qrack_tpu.serve import QrackService
    from qrack_tpu.serve.errors import QueueBudgetExceeded

    tele.enable()
    # the heap-side expiry runs on every next_batch pop, so a job that
    # ages in the QUEUE is expired there; the pre-dispatch window is
    # the batch window itself — a batchable job is popped immediately
    # (young, survives expiry) and then held while the scheduler waits
    # for co-batchable peers that never arrive, outliving its budget
    svc = QrackService(max_batch=2, batch_window_ms=150.0,
                       queue_budget_ms=30.0, tick_s=30.0)
    try:
        # tpu layers: only planes engines key their circuits for
        # co-batching, and only batchable jobs see the batch window
        sid = svc.create_session(4, layers="tpu", seed=1)
        h = svc.submit(sid, qft_qcircuit(4))
        with pytest.raises(QueueBudgetExceeded):
            h.result(timeout=30)
        c = tele.snapshot()["counters"]
        assert c.get("serve.shed.pre_dispatch", 0) >= 1
    finally:
        svc.close()


def test_canary_off_by_default():
    from qrack_tpu.serve import QrackService

    assert os.environ.get("QRACK_SERVE_CANARY_RATE") in (None, "", "0")
    svc = QrackService(tick_s=30.0)
    try:
        assert svc.canary is None
    finally:
        svc.close()


def test_canary_samples_and_verifies_clean_jobs(monkeypatch):
    from qrack_tpu.models.qft import qft_qcircuit
    from qrack_tpu.serve import QrackService

    monkeypatch.setenv("QRACK_SERVE_CANARY_RATE", "1.0")
    tele.enable()
    svc = QrackService(batch_window_ms=5.0, tick_s=30.0)
    try:
        sid = svc.create_session(4, layers="cpu", seed=1)
        for _ in range(3):
            svc.submit(sid, qft_qcircuit(4)).result(timeout=60)
        svc.canary.drain()
        assert svc.canary.checked >= 1
        assert svc.canary.mismatches == 0
    finally:
        svc.close()


def test_canary_mismatch_strikes_devices():
    """A served result that disagrees with the oracle replay feeds one
    quarantine strike per device the job's engine was paged across."""
    from qrack_tpu.models.qft import qft_qcircuit
    from qrack_tpu.serve.canary import CanaryVerifier

    tele.enable()
    cv = CanaryVerifier(rate=1.0)
    width = 3
    circ = qft_qcircuit(width)
    # non-uniform pre: QFT of |0...0> is the uniform ket, where any
    # amplitude permutation is invisible to fidelity
    gen = np.random.Generator(np.random.PCG64(5))
    pre = gen.normal(size=1 << width) + 1j * gen.normal(size=1 << width)
    pre /= np.linalg.norm(pre)
    oracle = QEngineCPU(width)
    oracle.SetQuantumState(pre)
    circ.Run(oracle)
    doctored = gen.normal(size=1 << width) \
        + 1j * gen.normal(size=1 << width)
    post = doctored / np.linalg.norm(doctored)
    cv._verify(0, width, circ, pre, post, devs=[5, 6])
    assert cv.checked == 1 and cv.mismatches == 1
    assert integ.strikes().get(5) == 1 and integ.strikes().get(6) == 1
    # the clean post-state verifies without a strike
    cv._verify(0, width, circ, pre,
               np.asarray(oracle.GetQuantumState()), devs=[5])
    assert cv.checked == 2 and cv.mismatches == 1
    assert integ.strikes().get(5) == 1


# ---------------------------------------------------------------------------
# randomized soak (short slice; the full run is scripts/integrity_soak.py)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_integrity_soak_smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "integrity_soak", os.path.join(os.path.dirname(__file__),
                                       "..", "scripts",
                                       "integrity_soak.py"))
    soak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(soak)
    results = [soak.run_trial(t, seed=0) for t in range(6)]
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad
