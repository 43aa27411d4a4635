"""Serving subsystem: sessions, scheduler admission control, shape-
bucketed batch execution, and breaker-aware load shedding + failover.

Every test restores the global resilience/telemetry/batch-program
state (fixture below) so the rest of the suite runs with serving and
resilience disabled — the default off-path the <2% bench criterion is
measured on.
"""

import threading
import time

import numpy as np
import pytest

from qrack_tpu import QEngineCPU
from qrack_tpu import resilience as res
from qrack_tpu import telemetry as tele
from qrack_tpu.models.qft import qft_qcircuit
from qrack_tpu.resilience import faults
from qrack_tpu.resilience.breaker import CircuitBreaker
from qrack_tpu.serve import (LoadShed, QrackService, QueueBudgetExceeded,
                             QueueFull, ServiceStopped, SessionNotFound)
from qrack_tpu.serve import batcher
from qrack_tpu.utils.rng import QrackRandom

W = 6  # test width: big enough to batch, small enough to stay fast


@pytest.fixture(autouse=True)
def _clean_serve():
    faults.clear()
    res.reset_breaker()
    res.configure(max_retries=2, backoff_s=0.0, timeout_s=0.0)
    batcher.clear_programs()
    yield
    faults.clear()
    res.reset_breaker()
    res.configure()
    res.disable()
    tele.disable()
    tele.reset()
    batcher.clear_programs()


def _fidelity(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    return abs(np.vdot(a, b)) ** 2 / (np.vdot(a, a).real
                                      * np.vdot(b, b).real)


def _svc(**kw) -> QrackService:
    kw.setdefault("batch_window_ms", 5.0)
    kw.setdefault("queue_budget_ms", 60_000.0)
    kw.setdefault("tick_s", 0.02)
    return QrackService(**kw)


# ---------------------------------------------------------------------------
# tier-1 smoke: 8 concurrent CPU-engine sessions, full scheduler path
# ---------------------------------------------------------------------------

def test_eight_concurrent_cpu_sessions_match_oracles():
    with _svc(engine_layers="cpu") as svc:
        sids = [svc.create_session(W, seed=k, rand_global_phase=False)
                for k in range(8)]
        errors, states = [], {}

        def tenant(k: int, sid: str):
            try:
                svc.call(sid, lambda eng, k=k: eng.X(k % W)).result(30)
                svc.apply(sid, qft_qcircuit(W), timeout=60)
                states[k] = svc.get_state(sid, timeout=60)
            except BaseException as e:  # noqa: BLE001
                errors.append((k, e))

        threads = [threading.Thread(target=tenant, args=(k, sid))
                   for k, sid in enumerate(sids)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
        assert not errors, errors
        for k in range(8):
            oracle = QEngineCPU(W, rng=QrackRandom(k),
                                rand_global_phase=False)
            oracle.X(k % W)
            qft_qcircuit(W).Run(oracle)
            assert _fidelity(oracle.GetQuantumState(),
                             states[k]) > 1 - 1e-6


# ---------------------------------------------------------------------------
# batching
# ---------------------------------------------------------------------------

def test_same_shape_jobs_from_different_tenants_cobatch(no_prefix_cache):
    # the batcher's test: the cache would serve three of the four QFTs
    tele.enable()
    tele.reset()
    with _svc(engine_layers="tpu", batch_window_ms=500.0,
              max_batch=4) as svc:
        sids = [svc.create_session(W, seed=k, rand_global_phase=False)
                for k in range(4)]
        handles = [svc.submit(sid, qft_qcircuit(W)) for sid in sids]
        for h in handles:
            h.result(60)
        states = [svc.get_state(sid, timeout=60) for sid in sids]
    snap = tele.snapshot()
    # all four landed in ONE vmapped dispatch of one compiled program
    assert snap["counters"]["serve.batch.dispatches"] == 1
    assert snap["counters"]["serve.batch.jobs"] == 4
    assert snap["counters"]["compile.serve_batch.miss"] == 1
    oracle = QEngineCPU(W, rng=QrackRandom(0), rand_global_phase=False)
    qft_qcircuit(W).Run(oracle)
    expect = np.asarray(oracle.GetQuantumState())
    for st in states:
        assert _fidelity(expect, st) > 1 - 1e-6


def test_program_cache_reused_across_sessions(no_prefix_cache):
    """Satellite: two sessions, identical circuit shape -> exactly one
    compile (miss) and one cache hit, even submitted sequentially."""
    # the program cache's test: the prefix cache would split the second QFT
    tele.enable()
    tele.reset()
    with _svc(engine_layers="tpu") as svc:
        s1 = svc.create_session(W, seed=1)
        s2 = svc.create_session(W, seed=2)
        svc.apply(s1, qft_qcircuit(W), timeout=60)   # B=1 batch: compiles
        svc.apply(s2, qft_qcircuit(W), timeout=60)   # fresh object, same
        # digest, same B -> must reuse the program, not recompile
    snap = tele.snapshot()
    assert snap["counters"]["compile.serve_batch.miss"] == 1
    assert snap["counters"]["compile.serve_batch.hit"] == 1


def test_cobatching_never_reorders_a_tenants_stream():
    """Regression (caught by scripts/serve_soak.py): the batcher must
    not steal a session's LATER circuit into a batch while an EARLIER
    job of the same session is still queued."""
    gate = threading.Event()
    with _svc(engine_layers="tpu", batch_window_ms=50.0,
              max_batch=2) as svc:
        blocker = svc.create_session(W, seed=9)
        s1 = svc.create_session(W, seed=1, rand_global_phase=False)
        s2 = svc.create_session(W, seed=2, rand_global_phase=False)
        # park the executor so the next three jobs queue up together
        hold = svc.call(blocker, lambda eng: gate.wait(10))
        time.sleep(0.1)
        h1 = svc.submit(s1, qft_qcircuit(W))                 # batchable
        h2a = svc.call(s2, lambda eng: eng.X(0))             # earlier s2 job
        h2b = svc.submit(s2, qft_qcircuit(W))                # same shape
        gate.set()
        for h in (hold, h1, h2a, h2b):
            h.result(60)
        state = svc.get_state(s2, timeout=60)
    oracle = QEngineCPU(W, rng=QrackRandom(2), rand_global_phase=False)
    oracle.X(0)
    qft_qcircuit(W).Run(oracle)   # X BEFORE the QFT, as submitted
    assert _fidelity(oracle.GetQuantumState(), state) > 1 - 1e-6


# ---------------------------------------------------------------------------
# admission control / backpressure
# ---------------------------------------------------------------------------

def test_queue_full_is_typed_and_synchronous():
    gate = threading.Event()
    with _svc(engine_layers="cpu", max_depth=2) as svc:
        sid = svc.create_session(W, seed=0)
        hold = svc.call(sid, lambda eng: gate.wait(10))
        time.sleep(0.1)  # executor now parked on `hold`, queue empty
        keep = [svc.call(sid, lambda eng: None) for _ in range(2)]
        with pytest.raises(QueueFull):
            svc.call(sid, lambda eng: None)
        gate.set()
        for h in [hold] + keep:
            h.result(30)


def test_priority_orders_dispatch():
    gate = threading.Event()
    order = []
    with _svc(engine_layers="cpu", max_depth=16) as svc:
        s1 = svc.create_session(W, seed=1)
        s2 = svc.create_session(W, seed=2)
        blocker = svc.create_session(W, seed=3)
        hold = svc.call(blocker, lambda eng: gate.wait(10))
        time.sleep(0.1)
        lo = svc.call(s1, lambda eng: order.append("lo"), priority=0)
        hi = svc.call(s2, lambda eng: order.append("hi"), priority=5)
        gate.set()
        for h in (hold, lo, hi):
            h.result(30)
    assert order == ["hi", "lo"]


def test_queue_budget_expires_stale_jobs():
    gate = threading.Event()
    with _svc(engine_layers="cpu", queue_budget_ms=50.0) as svc:
        sid = svc.create_session(W, seed=0)
        hold = svc.call(sid, lambda eng: gate.wait(10))
        time.sleep(0.1)
        stale = svc.call(sid, lambda eng: None)
        time.sleep(0.2)   # exceed the 50ms budget while queued
        gate.set()
        hold.result(30)
        with pytest.raises(QueueBudgetExceeded):
            stale.result(30)


def test_session_lifecycle_errors():
    with _svc(engine_layers="cpu") as svc:
        with pytest.raises(SessionNotFound):
            svc.submit("s999999", qft_qcircuit(W))
        sid = svc.create_session(W, seed=0)
        svc.destroy_session(sid)
        with pytest.raises(SessionNotFound):
            svc.submit(sid, qft_qcircuit(W))


def test_stop_drains_queued_jobs_typed():
    gate = threading.Event()
    svc = _svc(engine_layers="cpu")
    sid = svc.create_session(W, seed=0)
    hold = svc.call(sid, lambda eng: gate.wait(10))
    time.sleep(0.1)
    queued = svc.call(sid, lambda eng: None)
    svc.close()
    gate.set()
    with pytest.raises(ServiceStopped):
        queued.result(30)
    with pytest.raises(ServiceStopped):
        svc.call(sid, lambda eng: None)
    hold.result(30)


def test_idle_sessions_evicted():
    with _svc(engine_layers="cpu", idle_evict_s=0.05, tick_s=0.02) as svc:
        sid = svc.create_session(W, seed=0)
        assert sid in svc.sessions.ids()
        deadline = time.monotonic() + 5.0
        while sid in svc.sessions.ids() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sid not in svc.sessions.ids()


# ---------------------------------------------------------------------------
# load shedding + failover (the acceptance flow)
# ---------------------------------------------------------------------------

def test_breaker_open_sheds_accelerator_jobs_and_failover_recovers():
    res.reset_breaker(CircuitBreaker(threshold=2, cooldown_s=60.0))
    with _svc(engine_layers="tpu") as svc:
        hurt = svc.create_session(W, seed=1, rand_global_phase=False)
        bystander = svc.create_session(W, seed=2, rand_global_phase=False)
        faults.inject("serve.dispatch", "raise", times=None)  # persistent
        # in-flight job: dispatch fails past retry, breaker trips, the
        # session fails over down the chain and the job replays there
        h = svc.submit(hurt, qft_qcircuit(W))
        h.result(60)
        assert res.get_breaker().snapshot()["state"] == "open"
        stats = {s["sid"]: s for s in svc.sessions.stats()}
        assert stats[hurt]["failovers"] >= 1
        assert stats[hurt]["engine"] == "QEngineCPU"
        # new accelerator-bound work is refused with the typed error + hint
        with pytest.raises(LoadShed) as exc:
            svc.submit(bystander, qft_qcircuit(W))
        assert exc.value.retry_in_s > 0
        # the failed-over (now CPU-backed) session keeps being served
        svc.apply(hurt, qft_qcircuit(W), timeout=60)
        state = svc.get_state(hurt, timeout=60)
    oracle = QEngineCPU(W, rng=QrackRandom(1), rand_global_phase=False)
    qft_qcircuit(W).Run(oracle)
    qft_qcircuit(W).Run(oracle)
    assert _fidelity(oracle.GetQuantumState(), state) > 1 - 1e-6


def test_sync_failure_failover_does_not_double_apply():
    """Regression (caught by scripts/serve_soak.py): when the batch
    dispatch lands but the honest device_get sync escalates, the
    engines must be rolled back to pre-batch planes before the replay
    — otherwise the circuit applies twice."""
    res.reset_breaker(CircuitBreaker(threshold=100, cooldown_s=0.0))
    with _svc(engine_layers="tpu") as svc:
        sid = svc.create_session(W, seed=4, rand_global_phase=False)
        faults.inject("serve.device_get", "device-loss", times=None)
        svc.apply(sid, qft_qcircuit(W), timeout=60)
        faults.clear()
        state = svc.get_state(sid, timeout=60)
    oracle = QEngineCPU(W, rng=QrackRandom(4), rand_global_phase=False)
    qft_qcircuit(W).Run(oracle)
    assert _fidelity(oracle.GetQuantumState(), state) > 1 - 1e-6


# ---------------------------------------------------------------------------
# elastic capacity: degraded serving + drain/adopt (docs/ELASTICITY.md)
# ---------------------------------------------------------------------------

def test_elastic_pager_serves_degraded_then_reexpands(monkeypatch):
    """Acceptance flow: a pager session loses its exchange collective
    mid-serve, re-pages down the elastic staircase, KEEPS serving jobs
    degraded, and grows back to its construction page count at the
    first job boundary after the device heals — all telemetry-visible."""
    # window=1 disables the fuser: gates dispatch eagerly inside the
    # call job, so the injected exchange loss fires while serving
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "1")
    tele.enable()
    tele.reset()
    Wp = 5
    with _svc(engine_layers="pager", n_pages=4) as svc:
        sid = svc.create_session(Wp, seed=3, rand_global_phase=False)
        svc.call(sid, lambda e: e.H(0)).result(60)    # healthy, 4 pages
        faults.inject("pager.exchange", "device-loss", times=None)
        # qubit 4 is global at 4 AND 2 pages, local at 1: the staircase
        # descends 4 -> 2 -> 1 and the replay lands on the single page
        svc.call(sid, lambda e: e.H(4)).result(60)
        # the degraded pager demonstrably serves jobs at reduced pages
        # (the pre-job recovery probe sees the loss window still open)
        info = svc.call(sid, lambda e: (e.n_pages,
                                        bool(e.elastic_degraded))).result(60)
        assert info == (1, True), info
        svc.call(sid, lambda e: e.CNOT(0, 1)).result(60)
        svc.call(sid, lambda e: e.T(1)).result(60)
        # device heals -> the next job boundary re-expands BEFORE the
        # job runs, so the same job observes the recovered topology
        faults.clear()
        info = svc.call(sid, lambda e: (e.n_pages,
                                        bool(e.elastic_degraded))).result(60)
        assert info == (4, False), info
        state = svc.get_state(sid, timeout=60)
    snap = tele.snapshot()
    assert snap["counters"]["elastic.repage.shrink"] == 2
    assert snap["counters"]["elastic.repage.expand"] == 1
    assert snap["gauges"]["elastic.pages"] == 4
    oracle = QEngineCPU(Wp, rng=QrackRandom(3), rand_global_phase=False)
    oracle.H(0)
    oracle.H(4)
    oracle.CNOT(0, 1)
    oracle.T(1)
    assert _fidelity(oracle.GetQuantumState(), state) > 1 - 1e-6


def test_drain_handoff_adopted_by_second_service(tmp_path):
    """drain() checkpoints idle sessions, disowns them, and releases
    the recovery lease; a peer sharing the store adopts the set with
    recover=True and serves the exact handed-over state."""
    ck = str(tmp_path / "ck")
    a = _svc(engine_layers="cpu", checkpoint_dir=ck)
    try:
        sid = a.create_session(W, seed=5, rand_global_phase=False)
        a.apply(sid, qft_qcircuit(W), timeout=60)
        assert a.stats()["lease"]["held"]
        out = a.drain()
        assert out == {"drained": [sid], "busy": []}
        assert sid not in a.sessions.ids()
        assert not a.lease_held
        with pytest.raises(SessionNotFound):
            a.get_state(sid, timeout=60)
        # the adopter: drain released the lease, so recover is admitted
        with _svc(engine_layers="cpu", checkpoint_dir=ck,
                  recover=True) as b:
            assert b.lease_held
            assert [s["sid"] for s in b.stats()["sessions"]] == [sid]
            state = b.get_state(sid, timeout=60)
    finally:
        a.close()
    oracle = QEngineCPU(W, rng=QrackRandom(5), rand_global_phase=False)
    qft_qcircuit(W).Run(oracle)
    assert _fidelity(oracle.GetQuantumState(), state) > 1 - 1e-6


def test_recover_refused_while_peer_holds_lease(tmp_path):
    """Two processes must never both replay the same WAL: while a live
    peer holds the store lease, recover=True fails with the typed
    error (and leaks no executor thread); after drain it is admitted."""
    from qrack_tpu.checkpoint import StoreLeaseHeld

    ck = str(tmp_path / "ck")
    with _svc(engine_layers="cpu", checkpoint_dir=ck) as a:
        sid = a.create_session(W, seed=1)
        with pytest.raises(StoreLeaseHeld) as exc:
            _svc(engine_layers="cpu", checkpoint_dir=ck, recover=True)
        assert "drain or stop" in str(exc.value)
        # the holder keeps serving; handing over unblocks the adopter
        assert a.drain() == {"drained": [sid], "busy": []}
        with _svc(engine_layers="cpu", checkpoint_dir=ck,
                  recover=True) as b:
            assert sid in b.sessions.ids()


# ---------------------------------------------------------------------------
# fault-spec parse-time validation (satellite)
# ---------------------------------------------------------------------------

def test_fault_spec_unknown_site_rejected_listing_valid():
    with pytest.raises(ValueError) as exc:
        faults.parse_spec("sreve.dispatch:raise:0")   # typo'd site
    msg = str(exc.value)
    assert "serve.dispatch" in msg and "tpu.compile" in msg
    with pytest.raises(ValueError):
        faults.load_env("serve.dispatch:raise:0,bogus.site:raise:0")
    assert faults.parse_spec("serve.dispatch:raise:0").site == "serve.dispatch"
    assert faults.parse_spec("serve.device_get:timeout:1+").times is None


def test_fault_spec_bad_counts_rejected_with_grammar():
    with pytest.raises(ValueError) as exc:
        faults.parse_spec("serve.dispatch:raise:soon")
    assert "after_n" in str(exc.value)
    with pytest.raises(ValueError):
        faults.parse_spec("serve.dispatch:raise:0:notaseed")


# ---------------------------------------------------------------------------
# randomized soak (short slice; the full run is scripts/serve_soak.py)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_soak_smoke():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "serve_soak", os.path.join(os.path.dirname(__file__),
                                   "..", "scripts", "serve_soak.py"))
    soak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(soak)
    results = [soak.run_trial(t, seed=123) for t in range(9)]
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad


@pytest.mark.slow
def test_elastic_soak_smoke():
    """3-trial slice of scripts/elastic_soak.py: two in-process
    device-loss/flap trials (fusion windows 1 and 16) plus one kill -9
    two-process handoff trial."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "elastic_soak", os.path.join(os.path.dirname(__file__),
                                     "..", "scripts", "elastic_soak.py"))
    soak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(soak)
    results = [soak.run_trial(t, seed=7) for t in range(3)]
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad
