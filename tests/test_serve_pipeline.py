"""Continuous-batching pipeline: submit-then-sync double buffering,
in-flight batch joining, aged-priority fairness, and the exactly-once
envelope with one batch in flight and one staged.

Same global-state hygiene as test_serve.py: every test restores
resilience/telemetry/batch-program state so the rest of the suite runs
with serving disabled.
"""

import threading
import time
from collections import deque

import numpy as np
import pytest

from qrack_tpu import QEngineCPU
from qrack_tpu import matrices as mat
from qrack_tpu import resilience as res
from qrack_tpu import telemetry as tele
from qrack_tpu.layers.qcircuit import QCircuit, QCircuitGate
from qrack_tpu.models.qft import qft_qcircuit
from qrack_tpu.resilience import faults
from qrack_tpu.resilience.breaker import CircuitBreaker
from qrack_tpu.serve import QrackService
from qrack_tpu.serve import batcher
from qrack_tpu.utils.rng import QrackRandom

W = 6


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


def _h_wall() -> QCircuit:
    """A circuit whose shape_key differs from qft_qcircuit(W): the
    second bucket for staged-batch tests."""
    c = QCircuit(W)
    for q in range(W):
        c.AppendGate(QCircuitGate.single(q, mat.H2))
    return c


def _park(svc, gate: threading.Event):
    """Park the executor on a blocker session so subsequent submits
    queue up together; returns the hold handle."""
    blocker = svc.create_session(W, seed=99)
    hold = svc.call(blocker, lambda eng: gate.wait(10))
    time.sleep(0.1)
    return hold


# ---------------------------------------------------------------------------
# fairness: waited-time aging beats strict-priority starvation
# ---------------------------------------------------------------------------

def test_aging_prevents_priority_starvation():
    """Regression: under the old (-priority, seq) heap a sustained
    priority-1 flood starves a priority-0 job forever; waited-time
    aging promotes it one band per aging_s, so it completes while the
    flood is still running."""
    stop = threading.Event()
    flood_err = []
    with _svc(engine_layers="cpu", max_depth=64, aging_s=0.1) as svc:
        lo_s = svc.create_session(W, seed=0)
        hi_s = svc.create_session(W, seed=1)

        def flood():
            # keep >= 5 priority-1 jobs queued at all times: the
            # executor never sees an empty high band, so only aging
            # can dispatch the priority-0 job
            pending = deque()
            try:
                while not stop.is_set():
                    while len(pending) < 6:
                        pending.append(svc.call(
                            hi_s, lambda eng: time.sleep(0.002),
                            priority=1))
                    pending.popleft().result(30)
                while pending:
                    pending.popleft().result(30)
            except BaseException as e:  # noqa: BLE001
                flood_err.append(e)

        t = threading.Thread(target=flood, daemon=True)
        t.start()
        time.sleep(0.2)  # flood established
        h = svc.call(lo_s, lambda eng: None, priority=0)
        try:
            h.result(10)  # starves forever without aging
        finally:
            stop.set()
            t.join(30)
        assert not flood_err, flood_err
        assert h.latency_s < 10


def test_weighted_round_robin_within_band():
    """Two tenants at equal priority, weights 3:1, submitting together
    while the executor is parked: the weight-3 tenant gets ~3x the
    dispatches across the merged stream."""
    gate = threading.Event()
    order = []
    with _svc(engine_layers="cpu", max_depth=64, aging_s=0.0) as svc:
        heavy = svc.create_session(W, seed=1, weight=3.0)
        light = svc.create_session(W, seed=2, weight=1.0)
        hold = _park(svc, gate)
        hs = []
        for k in range(8):
            hs.append(svc.call(heavy, lambda eng: order.append("h")))
            hs.append(svc.call(light, lambda eng: order.append("l")))
        gate.set()
        for h in [hold] + hs:
            h.result(30)
    # first 8 dispatches: heavy is charged 1/3 per job, light 1 per
    # job, so the WRR interleave runs 3 heavy : 1 light
    assert order[:8].count("h") == 6, order


# ---------------------------------------------------------------------------
# idle eviction under sustained load (time-based, not idle-tick-based)
# ---------------------------------------------------------------------------

def test_idle_eviction_under_sustained_load():
    """Regression: eviction used to run only when next_batch returned
    None, so a busy service never spilled idle sessions.  Keep the
    queue non-empty the whole time and assert the idle session still
    goes."""
    with _svc(engine_layers="cpu", idle_evict_s=0.05, tick_s=0.02) as svc:
        idle = svc.create_session(W, seed=0)
        busy = svc.create_session(W, seed=1)
        pending = deque()
        deadline = time.monotonic() + 10.0
        evicted = False
        while time.monotonic() < deadline:
            while len(pending) < 4:  # queue never drains
                pending.append(svc.call(busy, lambda eng: None))
            pending.popleft().result(30)
            if idle not in svc.sessions.ids():
                evicted = True
                break
        while pending:
            pending.popleft().result(30)
        assert evicted, "idle session survived 10s of sustained load"
        assert busy in svc.sessions.ids()


# ---------------------------------------------------------------------------
# in-flight batch joining
# ---------------------------------------------------------------------------

def test_inflight_join_matches_solo_submit(monkeypatch, no_prefix_cache):
    """Same-shape jobs that arrive while the previous batch's sync is
    in flight join the STAGED batch (one dispatch for all three) and
    land states identical to a solo submit."""
    # the pipeline's test: the prefix cache would split the identical walls
    tele.enable()
    tele.reset()
    entered, release = threading.Event(), threading.Event()
    orig = batcher.sync_scalar
    calls = []

    def slow_sync(arr):
        calls.append(1)
        if len(calls) == 1:  # first batch's honest sync only
            entered.set()
            release.wait(10)
        return orig(arr)

    monkeypatch.setattr(batcher, "sync_scalar", slow_sync)
    gate = threading.Event()
    wall = _h_wall()
    with _svc(engine_layers="tpu", max_batch=8) as svc:
        a = svc.create_session(W, seed=1, rand_global_phase=False)
        b = svc.create_session(W, seed=2, rand_global_phase=False)
        c = svc.create_session(W, seed=3, rand_global_phase=False)
        d = svc.create_session(W, seed=4, rand_global_phase=False)
        hold = _park(svc, gate)
        ha = svc.submit(a, qft_qcircuit(W))   # becomes the in-flight batch
        hb = svc.submit(b, wall)              # staged (different shape)
        gate.set()
        assert entered.wait(30)               # batch A is syncing
        hc = svc.submit(c, wall)              # arrive during the sync:
        hd = svc.submit(d, wall)              # join the staged batch
        release.set()
        for h in (hold, ha, hb, hc, hd):
            h.result(60)
        states = {s: svc.get_state(s, timeout=60) for s in (a, b, c, d)}
    snap = tele.snapshot()["counters"]
    assert snap.get("serve.overlap.staged", 0) >= 1
    assert snap.get("serve.overlap.join.jobs", 0) == 2
    # b, c, d landed in ONE dispatch of the wall program
    assert snap["serve.batch.dispatches"] == 2
    assert snap["serve.batch.jobs"] == 4
    for sid, seed, circ in ((a, 1, qft_qcircuit(W)), (b, 2, wall),
                            (c, 3, wall), (d, 4, wall)):
        oracle = QEngineCPU(W, rng=QrackRandom(seed),
                            rand_global_phase=False)
        circ.Run(oracle)
        assert _fidelity(oracle.GetQuantumState(), states[sid]) > 1 - 1e-6


# ---------------------------------------------------------------------------
# exactly-once with one batch in flight and one staged
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [1, 16])
@pytest.mark.parametrize("kind", ["timeout", "raise"])
def test_pipelined_sync_fault_exactly_once(kind, window, monkeypatch,
                                           no_prefix_cache):
    """The in-flight batch's honest sync escalates while a staged batch
    waits: the in-flight jobs must roll back and fail over exactly
    once, and the staged batch must dispatch against settled engines —
    every session's final state matches its CPU oracle."""
    # the pipeline's test: the prefix cache would take b's QFT and d's wall
    # out of the two batches whose overlap is under test
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(window))
    tele.enable()
    tele.reset()
    res.reset_breaker(CircuitBreaker(threshold=100, cooldown_s=0.0))
    gate = threading.Event()
    wall = _h_wall()
    with _svc(engine_layers="tpu", max_batch=8) as svc:
        a = svc.create_session(W, seed=1, rand_global_phase=False)
        b = svc.create_session(W, seed=2, rand_global_phase=False)
        c = svc.create_session(W, seed=3, rand_global_phase=False)
        d = svc.create_session(W, seed=4, rand_global_phase=False)
        hold = _park(svc, gate)
        # every devget sync escalates: both the in-flight batch (a, b)
        # and, later, the staged one (c, d) take the rollback + replay
        # path while the other is pending
        faults.inject("serve.device_get", kind, times=None)
        handles = [svc.submit(a, qft_qcircuit(W)),
                   svc.submit(b, qft_qcircuit(W)),
                   svc.submit(c, wall),
                   svc.submit(d, wall)]
        gate.set()
        for h in handles:
            h.result(60)
        faults.clear()
        stats = {s["sid"]: s for s in svc.sessions.stats()}
        states = {s: svc.get_state(s, timeout=60) for s in (a, b, c, d)}
    snap = tele.snapshot()["counters"]
    # the staged batch was assembled while the faulted batch was in
    # flight — the window under test actually existed
    assert snap.get("serve.overlap.staged", 0) >= 1
    assert snap.get("serve.batch.failovers", 0) >= 1
    for sid in (a, b, c, d):
        assert stats[sid]["failovers"] >= 1
        assert stats[sid]["jobs_completed"] == 1
        assert stats[sid]["jobs_failed"] == 0
    for sid, seed, circ in ((a, 1, qft_qcircuit(W)), (b, 2, qft_qcircuit(W)),
                            (c, 3, wall), (d, 4, wall)):
        oracle = QEngineCPU(W, rng=QrackRandom(seed),
                            rand_global_phase=False)
        circ.Run(oracle)
        # fidelity ~1.0: applied exactly once (a double-apply of either
        # circuit lands a measurably different state)
        assert _fidelity(oracle.GetQuantumState(), states[sid]) > 1 - 1e-6


@pytest.mark.parametrize("window", [1, 16])
def test_pipelined_amp_corrupt_detected_by_canary(window, monkeypatch):
    """Silent corruption of the in-flight batch's dispatch (amp-corrupt
    fires at site EXIT — the dispatch SUCCEEDS with wrong amplitudes)
    while a staged batch waits: the canary's oracle replay flags the
    corrupted jobs, and the staged batch — dispatched after — still
    lands oracle-exact."""
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(window))
    monkeypatch.setenv("QRACK_SERVE_CANARY_RATE", "1.0")
    tele.enable()
    tele.reset()
    gate = threading.Event()
    wall = _h_wall()
    with _svc(engine_layers="tpu", max_batch=8) as svc:
        a = svc.create_session(W, seed=1, rand_global_phase=False)
        b = svc.create_session(W, seed=2, rand_global_phase=False)
        c = svc.create_session(W, seed=3, rand_global_phase=False)
        hold = _park(svc, gate)
        # one-shot: corrupts exactly the first batched dispatch (a, b);
        # the staged wall batch (c) dispatches clean
        faults.inject("serve.dispatch", "amp-corrupt", after_n=0, times=1)
        handles = [svc.submit(a, qft_qcircuit(W)),
                   svc.submit(b, qft_qcircuit(W)),
                   svc.submit(c, wall)]
        gate.set()
        for h in [hold] + handles:
            h.result(60)
        svc.canary.drain()
        state_c = svc.get_state(c, timeout=60)
    snap = tele.snapshot()["counters"]
    assert sum(sp.fired for sp in faults.specs()) == 1
    assert snap.get("serve.overlap.staged", 0) >= 1
    assert snap.get("integrity.canary.mismatch", 0) >= 1
    oracle = QEngineCPU(W, rng=QrackRandom(3), rand_global_phase=False)
    wall.Run(oracle)
    assert _fidelity(oracle.GetQuantumState(), state_c) > 1 - 1e-6


# ---------------------------------------------------------------------------
# mode equivalence: the serial loop is preserved under PIPELINE=0
# ---------------------------------------------------------------------------

def test_serial_mode_matches_pipelined_results():
    """The same multi-tenant workload lands identical states in both
    dispatch modes (pipeline off = the original serial loop)."""
    results = {}
    for pipeline in (False, True):
        with _svc(engine_layers="tpu", pipeline=pipeline) as svc:
            sids = [svc.create_session(W, seed=k, rand_global_phase=False)
                    for k in range(4)]
            handles = [svc.submit(sid, qft_qcircuit(W)) for sid in sids]
            for h in handles:
                h.result(60)
            results[pipeline] = [np.asarray(svc.get_state(sid, timeout=60))
                                 for sid in sids]
        batcher.clear_programs()
    for st_serial, st_piped in zip(results[False], results[True]):
        assert _fidelity(st_serial, st_piped) > 1 - 1e-9
