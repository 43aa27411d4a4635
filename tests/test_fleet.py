"""Fleet control plane: placement cost model, heartbeat liveness, the
ndjson RPC codec, the SIGTERM→SIGKILL reap ladder, and the supervised
kill→adopt→restart flow end-to-end with real worker subprocesses.

The integration tests stand up small real fleets (2 workers over one
shared checkpoint store) with aggressive control-plane cadence so
death detection, adoption, and restart land in test time; the full
randomized battery is scripts/fleet_soak.py (slow-marked smoke at the
bottom runs a 2-trial slice).
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from qrack_tpu import QEngineCPU
from qrack_tpu import matrices as mat
from qrack_tpu import telemetry as tele
from qrack_tpu.fleet import (AdoptionStalled, AutoscaleConfig, Autoscaler,
                             FleetFrontDoor, FleetSupervisor,
                             NoHealthyWorkers, Placement, session_cost)
from qrack_tpu.fleet import heartbeat as hb
from qrack_tpu.fleet import rpc
from qrack_tpu.layers.qcircuit import QCircuit
from qrack_tpu import resilience
from qrack_tpu.resilience import faults
from qrack_tpu.resilience.probe import reap_child
from qrack_tpu.utils.rng import QrackRandom


@pytest.fixture(autouse=True)
def _clean_fleet():
    faults.clear()
    yield
    faults.clear()
    resilience.disable()    # faults.inject switches it on; clear() leaves it
    tele.disable()
    tele.reset()


def _bell(n=2):
    c = QCircuit(n)
    c.append_1q(0, mat.H2)
    c.append_ctrl([0], 1, mat.X2, 1)
    return c


def _fidelity(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(abs(np.vdot(a, b)) ** 2 / (np.vdot(a, a).real
                                            * np.vdot(b, b).real))


# ---------------------------------------------------------------------------
# placement cost model + bin packing
# ---------------------------------------------------------------------------

def test_session_cost_stabilizer_nearly_free_dense_budgeted():
    # a w100 Clifford costs ~nothing; dense doubles per qubit until it
    # owns a whole worker at the budget width
    assert session_cost("stabilizer", 100) == pytest.approx(0.01)
    assert session_cost(["unit", "stabilizer_hybrid"], 60) == \
        pytest.approx(0.01)
    assert session_cost("cpu", 22) == 1.0
    assert session_cost("cpu", 30) == 1.0          # clamped
    assert session_cost("cpu", 21) == 0.5
    assert session_cost("cpu", 12) == 2.0 ** -10
    assert session_cost("tpu", 20, budget_w=20) == 1.0  # explicit budget


def test_session_cost_env_budget(monkeypatch):
    monkeypatch.setenv("QRACK_FLEET_DENSE_BUDGET_W", "10")
    assert session_cost("cpu", 10) == 1.0
    monkeypatch.setenv("QRACK_FLEET_DENSE_BUDGET_W", "bogus")
    assert session_cost("cpu", 22) == 1.0  # falls back to the default


def test_placement_least_loaded_then_overflow():
    p = Placement()
    p.add_worker("a")
    p.add_worker("b")
    assert p.place("s1", "cpu", 22) in ("a", "b")       # cost 1.0
    first = p.owner_of("s1")
    other = "b" if first == "a" else "a"
    assert p.place("s2", "cpu", 22) == other            # least-loaded
    # both full: the overflow still lands (admission guidance, not a
    # hard refusal) on a least-loaded worker
    assert p.place("s3", "cpu", 22) in ("a", "b")
    assert p.load(p.owner_of("s3")) >= 1.0


def test_placement_state_gating_and_exclude():
    p = Placement()
    for n in ("a", "b", "c"):
        p.add_worker(n)
    p.set_state("a", "draining")
    p.set_state("b", "quarantined")
    assert p.place("s1", "cpu", 4) == "c"
    p.set_state("c", "dead")
    with pytest.raises(NoHealthyWorkers):
        p.place("s2", "cpu", 4)
    p.set_state("c", "healthy")
    with pytest.raises(NoHealthyWorkers):
        p.place("s2", "cpu", 4, exclude=["c"])
    with pytest.raises(ValueError):
        p.set_state("c", "zombie")


def test_placement_evict_and_first_fit_decreasing():
    p = Placement()
    for n in ("a", "b"):
        p.add_worker(n)
    p.assign("big", "a", 0.9)
    p.assign("t1", "a", 0.01)
    p.assign("t2", "a", 0.01)
    p.assign("peer", "b", 0.5)
    evicted = p.evict("a")
    assert sorted(sid for sid, _ in evicted) == ["big", "t1", "t2"]
    assert p.owner_of("big") is None and p.sessions_on("a") == []
    p.set_state("a", "dead")
    mapping = p.place_all(evicted, exclude=["a"])
    # FFD: the big one placed first, everything lands on b
    assert mapping == {"big": "b", "t1": "b", "t2": "b"}
    assert p.load("b") == pytest.approx(0.5 + 0.9 + 0.02)
    p.release("big")
    assert p.owner_of("big") is None


# ---------------------------------------------------------------------------
# heartbeat liveness
# ---------------------------------------------------------------------------

def test_heartbeat_atomic_write_read_age(tmp_path):
    path = str(tmp_path / "w.hb")
    assert hb.read_heartbeat(path) is None          # missing = no beat
    hb.write_heartbeat(path, {"pid": os.getpid(), "t": time.time()})
    rec = hb.read_heartbeat(path)
    assert rec["pid"] == os.getpid()
    assert hb.beat_age_s(path) < 5.0
    with open(path, "w") as f:
        f.write('{"pid": 1, "t"')                   # torn record
    assert hb.read_heartbeat(path) is None
    assert hb.beat_age_s(path) is None


def test_heartbeat_writer_beats_and_hang_fault(tmp_path):
    path = str(tmp_path / "w.hb")
    w = hb.HeartbeatWriter(path, interval_s=60,
                           info_fn=lambda: {"ready": True})
    assert w.beat() is True
    rec = hb.read_heartbeat(path)
    assert rec["ready"] is True and rec["seq"] == 1
    # the injected wedge: the site acts it out by NOT beating, while
    # the process (here: us) keeps running
    faults.inject("fleet.heartbeat", "hang")
    assert w.beat() is False
    assert hb.read_heartbeat(path)["seq"] == 1      # file untouched
    faults.clear()
    assert w.beat() is True
    assert hb.read_heartbeat(path)["seq"] == 2


def test_fleet_fault_sites_parse():
    assert faults.parse_spec("fleet.worker:kill:0").kind == "kill"
    assert faults.parse_spec("fleet.heartbeat:hang:3").site == \
        "fleet.heartbeat"
    with pytest.raises(ValueError):
        faults.load_env("fleet.bogus:kill:0")


def test_pid_alive():
    assert hb.pid_alive(os.getpid())
    p = subprocess.Popen([sys.executable, "-c", "pass"])
    p.wait()
    assert not hb.pid_alive(p.pid)


# ---------------------------------------------------------------------------
# RPC codec + framing
# ---------------------------------------------------------------------------

def test_rpc_circuit_codec_round_trip():
    a = QEngineCPU(2, rng=QrackRandom(3), rand_global_phase=False)
    b = QEngineCPU(2, rng=QrackRandom(3), rand_global_phase=False)
    circ = _bell()
    circ.Run(a)
    rpc.decode_circuit(rpc.encode_circuit(circ)).Run(b)
    assert np.array_equal(np.asarray(a.GetQuantumState()),
                          np.asarray(b.GetQuantumState()))


def test_rpc_array_codec_round_trip():
    x = (np.arange(8) - 4 + 1j * np.arange(8)).astype(np.complex128)
    y = rpc.decode_array(rpc.encode_array(x))
    assert y.dtype == x.dtype and np.array_equal(x, y)


def test_rpc_frames_over_socketpair():
    import socket as socketlib

    a, b = socketlib.socketpair()
    fa, fb = a.makefile("rwb"), b.makefile("rwb")
    rpc.send_frame(fa, {"op": "ping", "n": 3})
    assert rpc.recv_frame(fb) == {"op": "ping", "n": 3}
    fa.close(); a.close()
    with pytest.raises(rpc.FleetRPCError):
        rpc.recv_frame(fb)  # peer vanished mid-protocol
    fb.close(); b.close()


# ---------------------------------------------------------------------------
# reap ladder (resilience/probe.py)
# ---------------------------------------------------------------------------

def test_reap_child_sigterm_suffices():
    p = subprocess.Popen([sys.executable, "-c",
                          "import time; time.sleep(60)"])
    r = reap_child(p, term_grace_s=10.0)
    assert not r.killed and not r.abandoned
    assert p.poll() is not None


def test_reap_child_escalates_to_sigkill():
    p = subprocess.Popen(
        [sys.executable, "-c",
         "import signal, sys, time\n"
         "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
         "print('R', flush=True)\n"
         "time.sleep(60)"], stdout=subprocess.PIPE)
    assert p.stdout.read(1) == b"R"  # handler installed before reaping
    r = reap_child(p, term_grace_s=0.3)
    assert r.killed and not r.abandoned
    assert p.returncode == -signal.SIGKILL


# ---------------------------------------------------------------------------
# control-plane units: boot-failure budget, adoption retry, routing retry
# ---------------------------------------------------------------------------

def test_boot_failure_counts_against_restart_budget(tmp_path, monkeypatch):
    """A worker that crashes during every boot must consume its restart
    budget (real backoff, eventual quarantine), not respawn every
    monitor tick forever: placement already reads "dead" when _respawn
    runs, so _on_death's already-handled guard would swallow the crash
    — the boot-failure path has to record it directly."""
    sup = _mini_fleet(tmp_path, n=1, restart_threshold=2)
    h = sup._workers["w0"]
    monkeypatch.setattr(sup, "_spawn", lambda h: None)

    def never_ready(names=None, timeout_s=0.0):
        raise RuntimeError("worker w0 exited rc=1 during boot")

    monkeypatch.setattr(sup, "wait_ready", never_ready)
    sup.placement.set_state("w0", "dead")   # how _respawn is reached
    t0 = time.monotonic()
    sup._respawn(h)
    assert h.crashes == 1
    assert h.next_restart_at > t0           # armed backoff, not 0.0
    assert h.breaker.snapshot()["consecutive_failures"] == 1
    sup._respawn(h)
    assert h.crashes == 2
    # budget exhausted: the next restart attempt quarantines instead
    sup._maybe_restart(h)
    assert sup.placement.state("w0") == "quarantined"


def test_failed_adoption_keeps_sid_migrating_then_retries(
        tmp_path, monkeypatch):
    """An adoption RPC failure must not strip the sids from the
    migrating set (routing would then hand tenants a SessionNotFound
    from the not-yet-adopter): they stay migrating — route() answers
    "wait" — and the monitor tick re-attempts until adoption lands."""
    sup = _mini_fleet(tmp_path, n=2)
    sup.placement.assign("s1", "w0", 0.5)
    sup._migrating.add("s1")
    attempts = {"n": 0}

    def flaky(h, sids, timeout_s=60.0):
        attempts["n"] += 1
        if attempts["n"] == 1:
            return None
        return {"sessions": list(sids), "wal_replayed": 0,
                "wal_deduped": 0, "wal_skipped": 0}

    monkeypatch.setattr(sup, "_adopt_batch", flaky)
    assert sup._adopt_assigned("w0", ["s1"]) is False
    assert "s1" in sup._migrating           # routing keeps waiting
    assert sup.route("s1") is None
    assert sup.stats()["adopt_pending"] == 1
    # make the queued retry due now, then run the monitor-tick half
    sup._adopt_pending = [(n, b, 0.0) for n, b, _ in sup._adopt_pending]
    sup._retry_pending_adoptions()
    assert attempts["n"] == 2
    assert "s1" not in sup._migrating
    assert sup.route("s1") is not None


class _StubSup:
    """Just enough supervisor for front-door routing-retry units."""

    def __init__(self, client):
        self._client = client

    def route(self, sid):
        return self._client

    def tag_adopted(self, tag):
        return False


def test_frontdoor_retries_session_not_found_until_adoption():
    """Mid-migration race: routing points at an adopter whose scoped
    recovery has not landed yet — its typed SessionNotFound means "not
    adopted HERE yet" and must retry against routing, not leak to the
    tenant (the no-visible-error migration contract, docs/FLEET.md)."""
    calls = {"n": 0}

    class _Adopting:
        def prob(self, sid, qubit):
            calls["n"] += 1
            if calls["n"] < 3:
                raise rpc.FleetRemoteError("SessionNotFound", sid)
            return 0.5

    front = FleetFrontDoor(_StubSup(_Adopting()), route_timeout_s=10.0)
    assert front.prob("s1", 0) == 0.5
    assert calls["n"] == 3


def test_frontdoor_apply_retries_session_not_found():
    calls = {"n": 0}

    class _Adopting:
        def submit(self, sid, circuit, tag=None, priority=0):
            calls["n"] += 1
            if calls["n"] < 2:
                raise rpc.FleetRemoteError("SessionNotFound", sid)
            return True, {"ok": True}

    front = FleetFrontDoor(_StubSup(_Adopting()), route_timeout_s=10.0)
    out = front.apply("s1", _bell())
    assert out == {"resubmits": 0, "adopted": False}
    assert calls["n"] == 2


def test_frontdoor_other_remote_errors_still_raise():
    """Only the session-not-found class retries; every other typed
    worker refusal (bad qubit index, draining, ...) surfaces at once."""

    class _Typed:
        def prob(self, sid, qubit):
            raise rpc.FleetRemoteError("ValueError", "qubit out of range")

    front = FleetFrontDoor(_StubSup(_Typed()), route_timeout_s=2.0)
    with pytest.raises(rpc.FleetRemoteError):
        front.prob("s1", 0)


def test_submit_result_frame_not_bounded_by_transport_timeout(tmp_path):
    """A job legitimately outrunning the transport timeout must not
    surface as FleetRPCError(journaled=True) — the front door would
    report it adopted while it is still executing.  The result frame
    waits under result_timeout_s instead."""
    import socket as socketlib
    import threading

    path = str(tmp_path / "w.sock")
    server = socketlib.socket(socketlib.AF_UNIX, socketlib.SOCK_STREAM)
    server.bind(path)
    server.listen(1)

    def serve():
        conn, _ = server.accept()
        f = conn.makefile("rwb")
        rpc.recv_frame(f)
        rpc.send_frame(f, {"ok": True, "journaled": True})
        time.sleep(0.8)          # "execution" outlasting timeout_s
        rpc.send_frame(f, {"ok": True, "value": 7})
        f.close()
        conn.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    try:
        client = rpc.FleetClient(path, timeout_s=0.3,
                                 result_timeout_s=30.0)
        journaled, rep = client.submit("s1", _bell(), tag="t")
        assert journaled and rep["value"] == 7
    finally:
        t.join(5)
        server.close()


# ---------------------------------------------------------------------------
# supervised fleet end-to-end (real worker subprocesses)
# ---------------------------------------------------------------------------

def _mini_fleet(tmp_path, n=2, **kw):
    kw.setdefault("beat_s", 0.2)
    kw.setdefault("deadline_beats", 4)
    kw.setdefault("tick_s", 0.05)
    kw.setdefault("backoff_base_s", 0.05)
    kw.setdefault("restart_cooldown_s", 1.0)
    kw.setdefault("stable_s", 0.3)
    kw.setdefault("ready_timeout_s", 120.0)
    return FleetSupervisor(n, str(tmp_path / "fleet"), layers="cpu", **kw)


def _wait_states(sup, want, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        states = {w["state"] for w in sup.stats()["workers"].values()}
        if states == want:
            return
        time.sleep(0.05)
    raise AssertionError(f"fleet never reached {want}: {sup.stats()}")


def test_fleet_kill9_adopt_restart_zero_loss(tmp_path):
    """The acceptance flow: kill -9 the worker that owns a session
    mid-stream — the next apply rides adoption onto a peer with the
    exact state (fidelity 1 vs an uninterrupted CPU oracle), and the
    dead worker restarts back to healthy on its breaker budget."""
    with _mini_fleet(tmp_path) as sup:
        sup.start()
        front = FleetFrontDoor(sup)
        sid = front.create_session(2, seed=11, rand_global_phase=False)
        oracle = QEngineCPU(2, rng=QrackRandom(11), rand_global_phase=False)
        front.apply(sid, _bell())
        _bell().Run(oracle)

        owner = sup.owner_of(sid)
        os.kill(sup.stats()["workers"][owner]["pid"], signal.SIGKILL)
        # the very next apply must land exactly once despite the death
        front.apply(sid, _bell())
        _bell().Run(oracle)
        assert sup.owner_of(sid) != owner            # adopted by a peer
        assert _fidelity(oracle.GetQuantumState(),
                         front.get_state(sid)) > 1 - 1e-12
        _wait_states(sup, {"healthy"})               # victim restarted
        st = sup.stats()["workers"][owner]
        assert st["crashes"] == 1 and st["restarts"] >= 1
        front.destroy_session(sid)


def test_fleet_rolling_restart_migrates_live_session(tmp_path):
    with _mini_fleet(tmp_path) as sup:
        sup.start()
        front = FleetFrontDoor(sup)
        sid = front.create_session(2, seed=5, rand_global_phase=False)
        oracle = QEngineCPU(2, rng=QrackRandom(5), rand_global_phase=False)
        front.apply(sid, _bell())
        _bell().Run(oracle)
        out = sup.rolling_restart()
        assert set(out) == set(sup.worker_names())
        assert sum(len(v["migrated"]) for v in out.values()) >= 1
        # the session survived both restarts with exact state
        front.apply(sid, _bell())
        _bell().Run(oracle)
        assert _fidelity(oracle.GetQuantumState(),
                         front.get_state(sid)) > 1 - 1e-12
        _wait_states(sup, {"healthy"})


def test_fleet_flapping_worker_quarantined_then_probed(tmp_path):
    """Restart budget: a worker SIGKILLed on every comeback trips its
    breaker and is QUARANTINED (placement stops offering it); after
    the cooldown the half-open breaker admits exactly one probe
    restart, and a stable probe closes the budget again."""
    # stable_s long enough that the breaker can't close (and reset its
    # failure count) between the two kills
    with _mini_fleet(tmp_path, restart_threshold=2,
                     restart_cooldown_s=1.5, stable_s=30.0) as sup:
        sup.start()
        victim = sup.worker_names()[0]

        seen_quarantine = False
        kills = 0
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            st = sup.stats()["workers"][victim]
            if st["state"] == "quarantined":
                seen_quarantine = True
                break
            if st["state"] == "healthy" and kills < 2:
                os.kill(st["pid"], signal.SIGKILL)
                kills += 1
                time.sleep(0.3)
            time.sleep(0.05)
        assert seen_quarantine, sup.stats()
        # the probe restart brings it back without human intervention
        _wait_states(sup, {"healthy"}, timeout_s=90)
        assert sup.stats()["workers"][victim]["crashes"] >= 2


# ---------------------------------------------------------------------------
# randomized soak (short slice; the full run is scripts/fleet_soak.py)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_soak_smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fleet_soak", os.path.join(os.path.dirname(__file__),
                                   "..", "scripts", "fleet_soak.py"))
    soak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(soak)
    results = [soak.run_trial(t, seed=123) for t in range(2)]
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad


# ---------------------------------------------------------------------------
# fleet observability plane (telemetry enabled end to end)
# ---------------------------------------------------------------------------

def test_worker_info_op_returns_telemetry_snapshot(tmp_path):
    """The `info` RPC op: a real subprocess worker answers with its
    identity, readiness, and (telemetry propagated via spawn env) a
    cumulative snapshot whose serve counters/histograms reflect the
    jobs it actually ran."""
    tele.enable()
    tele.reset()
    with _mini_fleet(tmp_path, n=1) as sup:
        sup.start()
        front = FleetFrontDoor(sup)
        sid = front.create_session(2, seed=3, rand_global_phase=False)
        front.apply(sid, _bell())
        front.apply(sid, _bell())
        # the result frame races the executor's accounting by design
        # (_complete before _account): poll until both jobs are counted
        deadline = time.monotonic() + 10.0
        while True:
            info = sup.route(sid).info()
            done = (info["telemetry"]["counters"]
                    .get("serve.jobs.completed", 0))
            if done >= 2 or time.monotonic() >= deadline:
                break
            time.sleep(0.05)
        assert info["name"] == "w0"
        assert info["pid"] == sup.stats()["workers"]["w0"]["pid"]
        assert info["ready"] is True and info["draining"] is False
        assert info["sessions"] == 1
        snap = info["telemetry"]
        assert snap["enabled"] is True and snap["pid"] == info["pid"]
        assert snap["counters"]["serve.jobs.completed"] >= 2
        assert snap["hists"]["serve.latency"]["count"] >= 2
        assert snap["gauges"]["serve.latency.p50"] > 0
        front.destroy_session(sid)


def test_fleet_observability_acceptance(tmp_path):
    """The PR acceptance flow: a real 4-worker fleet under load with
    one kill -9 must yield (a) ONE merged Perfetto trace where a
    single submit's spans cross the front door and a worker, (b)
    fleet-wide latency histograms that count every job, with medians
    within 10% of hand-computed values over the same walls and a p99
    that all but a counted few of them lie under, and (c) the dead
    worker's black box recovered into a postmortem with its last events
    visible."""
    import json as _json

    from qrack_tpu.models.qft import qft_qcircuit
    from qrack_tpu.telemetry import Histogram

    tele.enable()
    tele.reset()
    with _mini_fleet(tmp_path, n=4) as sup:
        sup.start()
        front = FleetFrontDoor(sup)
        # w8 qft: execution dominates the wall, so the worker-local
        # serve.latency distribution tracks the client-observed walls
        # closely enough for the 10% comparison of the medians
        sids = [front.create_session(8, seed=k, rand_global_phase=False)
                for k in range(3)]
        circuit = qft_qcircuit(8)
        walls = []

        def load(n):
            for i in range(n):
                t0 = time.perf_counter()
                front.apply(sids[i % len(sids)], circuit)
                walls.append(time.perf_counter() - t0)

        # a rare OS preemption inside a span's edge (outside
        # t_submit->t_done) inflates a FEW windows by ~5-10ms, on one
        # clock and not on the other: at n=160 the p99 rank is the
        # second largest, so two p99s are never held against each other
        # below; the samples over the gauge are counted
        load(120)
        victim = sup.owner_of(sids[0])
        vpid = sup.stats()["workers"][victim]["pid"]
        os.kill(vpid, signal.SIGKILL)
        load(40)  # rides death detection + adoption mid-stream
        time.sleep(0.6)  # >=2 beats: snapshots + black boxes land

        # -- (a) one merged trace, submits crossing processes ----------
        trace_path = tmp_path / "fleet_trace.json"
        sup.write_merged_trace(str(trace_path))
        obj = _json.loads(trace_path.read_text())
        by_trace = {}
        for e in obj["traceEvents"]:
            if e.get("ph") == "X" and (e.get("args") or {}).get("trace"):
                by_trace.setdefault(e["args"]["trace"], []).append(e)
        worker_side = {"serve.execute", "worker.submit.journal",
                       "worker.submit.result"}
        cross = [t for t, evs in by_trace.items()
                 if "frontdoor.apply" in {e["name"] for e in evs}
                 and worker_side & {e["name"] for e in evs}
                 and len({e["pid"] for e in evs}) >= 2]
        assert cross, "no submit's spans crossed front door and worker"

        # -- (b) fleet metrics vs hand-computed values -----------------
        m = sup.metrics(write=True)

        def agrees(name, samples):
            """The median within 10 % (or 3 ms) of the samples' own, and
            all but a counted few of them under the p99 gauge."""
            p50 = m["gauges"][f"{name}.p50"]
            p99 = m["gauges"][f"{name}.p99"]
            want = sorted(samples)[len(samples) // 2]   # fleet_soak.py's
            assert (abs(p50 - want) / want < 0.10       # own formula
                    or abs(p50 - want) < 0.003), (name, p50, want)
            assert 0 < p50 <= p99, (name, p50, p99)
            over = sum(v > p99 * 1.10 + 0.003 for v in samples)
            assert over <= max(2, len(samples) // 50), (name, over, p99)

        fh = m["hists"]["fleet.frontdoor.apply"]
        assert fh["count"] == len(walls)
        # the shared helper counts the same walls
        assert Histogram.of(walls).count == fh["count"]
        agrees("fleet.frontdoor.apply", walls)
        # fleet-wide serve.latency (merged across worker incarnations,
        # one of them dead) against hand-computed values
        # for the same quantity.  Client walls are the WRONG reference:
        # they carry RPC/codec time and the kill's failover blip, which
        # worker-side latency never sees.  The honest reference is the
        # trace's serve.job spans — the executor re-emits each job's
        # exact t_submit->t_done interval as a raw duration, and those
        # reach us through a pipeline disjoint from the gauges (span
        # ring -> black box -> merged trace, vs histogram buckets ->
        # heartbeat snapshot -> supervisor merge -> nearest-rank).
        sl = m["hists"].get("serve.latency")
        assert sl is not None and sl["count"] >= int(0.7 * len(walls))
        spans = sorted(e["dur"] * 1e-6 for e in obj["traceEvents"]
                       if e.get("ph") == "X" and e.get("name") == "serve.job")
        assert len(spans) >= int(0.7 * len(walls))
        agrees("serve.latency", spans)
        # the merge loses no sample: every incarnation's count, the dead
        # worker's among them, is in the fleet's
        assert sl["count"] == sum(w["serve.latency"]["count"]
                                  for w in m["workers"].values()
                                  if w.get("serve.latency"))

        # -- (c) the dead worker's black box became a postmortem -------
        posts = [p for p in sup.stats()["postmortems"]
                 if p["worker"] == victim and p["pid"] == vpid]
        assert posts, sup.stats()["postmortems"]
        post = posts[-1]
        assert post["last_events"], "black box recovered but event tail empty"
        assert all("name" in e for e in post["last_events"])
        assert post["reason"] in ("heartbeat-timeout", "process-exit",
                                  "boot-failure") or post["reason"]
        # the fleet journal carries both record kinds for --fleet
        kinds = {(_json.loads(line)).get("kind")
                 for line in open(sup.telemetry_path)}
        assert {"fleet", "postmortem"} <= kinds
        for sid in sids:
            front.destroy_session(sid)


# ---------------------------------------------------------------------------
# autoscaling: spawn faults, elastic capacity, brownout ladder
# ---------------------------------------------------------------------------

def test_fleet_spawn_fault_specs_parse():
    assert faults.parse_spec("fleet.spawn:hang:0").site == "fleet.spawn"
    assert faults.parse_spec("fleet.spawn:raise:1").kind == "raise"
    with pytest.raises(ValueError):
        faults.load_env("fleet.spawner:hang:0")     # unknown site
    with pytest.raises(ValueError):
        faults.parse_spec("fleet.spawn:explode:0")  # unknown kind


def test_spawn_faults_charge_budget_placement_unstuck(tmp_path):
    """A hung boot (sleeper in the worker's place, never heartbeats)
    must time out, reap the sleeper, and charge the NEW worker's
    restart budget exactly like an organic boot failure — and a raise-
    kind fault (exec dies instantly) the same — while placement keeps
    serving on the existing workers throughout."""
    sup = _mini_fleet(tmp_path, n=1, restart_threshold=2,
                      ready_timeout_s=1.0)
    # hang: boot_worker spawns the sleeper, wait_ready deadlines
    faults.inject("fleet.spawn", "hang", times=2)
    t0 = time.monotonic()
    assert sup.boot_worker("wx", timeout_s=1.0) is False
    h = sup._workers["wx"]
    assert h.crashes == 1
    assert h.next_restart_at > t0                  # backoff armed
    assert h.breaker.snapshot()["consecutive_failures"] == 1
    assert h.proc is not None and h.proc.poll() is not None  # reaped
    assert sup.placement.state("wx") == "dead"
    # placement is NOT stuck: the dead boot is unplaceable, w0 serves
    assert sup.placement.place("s1", "cpu", 4) == "w0"
    # second hung boot exhausts the threshold-2 budget ...
    sup._respawn(h)
    assert h.crashes == 2
    # ... so the monitor's next restart attempt quarantines instead
    sup._maybe_restart(h)
    assert sup.placement.state("wx") == "quarantined"

    # raise: the InjectedFault fires before Popen — no process at all
    faults.clear()
    faults.inject("fleet.spawn", "raise")
    assert sup.boot_worker("wy", timeout_s=1.0) is False
    hy = sup._workers["wy"]
    assert hy.crashes == 1 and hy.proc is None
    assert sup.placement.place("s2", "cpu", 4) == "w0"


def test_scale_down_zero_loss_and_metrics_retention(tmp_path):
    """Scale-down = drain → evict → re-place → adopt → retire.  Two
    invariants pinned here: (a) the retired worker's session survives
    on a peer with exact state, and (b) the retired incarnation's final
    telemetry snapshot stays folded into the fleet merge keyed
    (name, pid) — fleet counters must be monotonic across the retire,
    never deflate."""
    tele.enable()
    tele.reset()
    with _mini_fleet(tmp_path, n=2) as sup:
        sup.start()
        front = FleetFrontDoor(sup)
        sids, oracles = [], []
        for k in range(2):
            sids.append(front.create_session(2, seed=20 + k,
                                             rand_global_phase=False))
            oracles.append(QEngineCPU(2, rng=QrackRandom(20 + k),
                                      rand_global_phase=False))
        # equal-cost sessions spread least-loaded: one per worker
        assert {sup.owner_of(s) for s in sids} == {"w0", "w1"}
        for sid, oracle in zip(sids, oracles):
            for _ in range(2):
                front.apply(sid, _bell())
                _bell().Run(oracle)
        # wait for the heartbeat ingest to carry all 4 completions
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            before = sup.metrics()["counters"].get(
                "serve.jobs.completed", 0)
            if before >= 4:
                break
            time.sleep(0.05)
        assert before >= 4, sup.metrics()["counters"]

        victim = sup.worker_names()[0]          # least-loaded tie -> w0
        vpid = sup.stats()["workers"][victim]["pid"]
        moved_sid = [s for s in sids if sup.owner_of(s) == victim][0]
        out = sup.scale_down()
        assert out is not None
        assert out["migrated"] == {moved_sid: "w1"}
        assert sup.worker_names() == ["w1"]

        # (b) monotonic fleet counters + the incarnation still merged
        m = sup.metrics()
        assert m["counters"].get("serve.jobs.completed", 0) >= before
        assert f"{victim}:{vpid}" in m["workers"]

        # (a) the migrated session keeps serving with exact state
        k = sids.index(moved_sid)
        front.apply(moved_sid, _bell())
        _bell().Run(oracles[k])
        assert _fidelity(oracles[k].GetQuantumState(),
                         front.get_state(moved_sid)) > 1 - 1e-12
        # refuses to retire the last healthy worker
        assert sup.scale_down() is None
        for sid in sids:
            front.destroy_session(sid)


def test_scale_down_orphan_hits_bounded_wait_typed_error(
        tmp_path, monkeypatch):
    """A session evicted during scale-down whose re-placement fails is
    STRANDED: migrating forever, no owner.  The front door must not
    wait out the full routing timeout — the migrate deadline surfaces
    the typed AdoptionStalled (with the not_adopted_yet counter), and
    the state stays durable on the store."""
    tele.enable()
    tele.reset()
    with _mini_fleet(tmp_path, n=2) as sup:
        sup.start()
        front = FleetFrontDoor(sup, route_timeout_s=60.0,
                               migrate_timeout_s=0.3)
        sid = front.create_session(2, seed=7, rand_global_phase=False)
        front.apply(sid, _bell())
        owner = sup.owner_of(sid)

        def no_room(moved, exclude=None):
            raise NoHealthyWorkers("injected: nowhere to re-place")

        monkeypatch.setattr(sup.placement, "place_all", no_room)
        out = sup.scale_down(owner)
        assert out is not None and out["migrated"] == {}
        assert owner not in sup.worker_names()
        assert sup.owner_of(sid) is None
        assert sid in sup.stats()["migrating"]

        t0 = time.monotonic()
        with pytest.raises(AdoptionStalled):
            front.prob(sid, 0)
        assert time.monotonic() - t0 < 10.0      # deadline, not timeout
        assert tele.snapshot()["counters"].get(
            "fleet.frontdoor.not_adopted_yet", 0) >= 1


def test_scheduler_brownout_sheds_by_band():
    from qrack_tpu.serve import Overloaded
    from qrack_tpu.serve.scheduler import Job, Scheduler

    s = Scheduler(max_depth=8, queue_budget_s=10.0,
                  batch_window_s=0.0, max_batch=1)
    s.set_brownout(1, shed_band=0, retry_in_s=0.25)
    assert s.brownout_level() == 1
    with pytest.raises(Overloaded) as ei:
        s.submit(Job(None, "admin", priority=0))
    assert ei.value.retry_in_s == 0.25
    assert ei.value.level == 1 and ei.value.band == 0
    s.submit(Job(None, "admin", priority=1))     # above the band: admitted
    s.set_brownout(3)
    with pytest.raises(Overloaded) as ei:
        s.submit(Job(None, "admin", priority=5))  # level 3 refuses all
    assert ei.value.level == 3 and ei.value.band is None
    s.set_brownout(0)
    s.submit(Job(None, "admin", priority=0))
    assert s.depth() == 2


def test_router_brownout_quantizes_borderline_dense(monkeypatch):
    """Level 2's rung: an auto-routed circuit that would take the full
    f32 dense stack lands on the compressed turboquant tier instead
    while brownout is active — pinned modes are never overridden."""
    from qrack_tpu.models.algorithms import quantum_volume_qcircuit
    from qrack_tpu.route import router as router_mod

    monkeypatch.delenv("QRACK_ROUTE", raising=False)
    circ = quantum_volume_qcircuit(12, rng=QrackRandom(11))
    base = router_mod.decide(circ, 12)
    assert base.stack == "dense" and base.reason == "cost"
    router_mod.set_brownout(True)
    try:
        d = router_mod.decide(circ, 12)
        assert d.stack == "turboquant" and d.reason == "brownout"
        monkeypatch.setenv("QRACK_ROUTE", "dense")   # tenant's explicit pin
        assert router_mod.decide(circ, 12).stack == "dense"
    finally:
        router_mod.set_brownout(False)
    assert router_mod.brownout_active() is False


def test_frontdoor_brownout_ladder_order():
    """The ladder's front-door rungs, strictly ordered: level 1 sheds
    only at/below the band, level 2 adds nothing at the front door
    (quantized routing is worker-side), level 3 refuses everything —
    always BEFORE tag mint/routing, so a refusal provably never
    executed."""
    from qrack_tpu.serve import Overloaded

    submitted = []

    class _Client:
        def submit(self, sid, circuit, tag=None, priority=0):
            submitted.append(priority)
            return True, {"ok": True}

    class _BrownoutSup(_StubSup):
        state = None

        def brownout(self):
            return self.state

    sup = _BrownoutSup(_Client())
    front = FleetFrontDoor(sup, route_timeout_s=5.0)

    sup.state = {"level": 1, "shed_band": 0, "retry_in_s": 0.5}
    with pytest.raises(Overloaded) as ei:
        front.apply("s1", _bell(), priority=0)
    assert ei.value.level == 1 and ei.value.band == 0
    front.apply("s1", _bell(), priority=1)       # above the band
    sup.state = {"level": 2, "shed_band": 0, "retry_in_s": 0.5}
    front.apply("s1", _bell(), priority=1)       # level 2: still admitted
    sup.state = {"level": 3, "shed_band": 0, "retry_in_s": 1.0}
    with pytest.raises(Overloaded) as ei:
        front.apply("s1", _bell(), priority=1)   # level 3 refuses all
    assert ei.value.level == 3 and ei.value.retry_in_s == 1.0
    sup.state = None
    front.apply("s1", _bell(), priority=0)
    assert submitted == [1, 1, 0]


class _FakeScaleSup:
    """Synthetic pressure source for ladder-ordering units — a fleet
    pinned at n_max so capacity can never arrive."""

    def __init__(self, n=2):
        self.n = n
        self.backlog = 0.0
        self.levels = []

    def pressure(self):
        return {"n_live": self.n, "n_total": self.n,
                "backlog": self.backlog, "load": 0.0,
                "capacity": float(self.n),
                "queue_wait_p99_s": 0.0, "latency_p99_s": 0.0}

    def set_brownout(self, level, shed_band=0, retry_in_s=0.5):
        self.levels.append(level)

    def boot_worker(self, timeout_s=None):  # pragma: no cover — n_max
        raise AssertionError("scale-up attempted at n_max")


def test_autoscaler_ladder_escalates_and_calms_one_rung_at_a_time():
    cfg = AutoscaleConfig(n_min=1, n_max=2, up_ticks=2, ladder_ticks=2,
                          cooldown_s=0.0)
    a = Autoscaler(cfg)
    sup = _FakeScaleSup(n=2)
    sup.backlog = 100.0                  # way past up_backlog per worker
    for _ in range(10):
        a.tick(sup)
    assert a.level == 3
    assert sup.levels[:3] == [1, 2, 3]   # strictly ordered, no skips
    sup.backlog = 0.0
    for _ in range(10):
        a.tick(sup)
    assert a.level == 0
    assert sup.levels == [1, 2, 3, 2, 1, 0]  # symmetric de-escalation
    d = a.stats()["decisions"]
    for lv in (1, 2, 3):
        assert d.get(f"brownout.level{lv}", 0) >= 1


def test_autoscaler_closed_loop_scale_up_then_down(tmp_path, monkeypatch):
    """The tentpole end-to-end on a real fleet: synthetic backlog
    pressure drives the monitor-tick scaler to boot a real worker into
    the warm path; pressure clearing drains the pool back down through
    the zero-loss retire — both visible in the decision counters."""
    box = {"backlog": 0.0}
    with _mini_fleet(tmp_path, n=1, autoscale=AutoscaleConfig(
            n_min=1, n_max=2, up_ticks=2, down_ticks=3,
            cooldown_s=0.1, ladder_ticks=10_000,
            boot_timeout_s=120.0)) as sup:
        real_pressure = sup.pressure

        def fake_pressure():
            p = real_pressure()
            p["backlog"] = box["backlog"]
            p["queue_wait_p99_s"] = 0.0
            return p

        monkeypatch.setattr(sup, "pressure", fake_pressure)
        sup.start()
        assert sup.worker_names() == ["w0"]
        box["backlog"] = 50.0
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            if sup.worker_names() == ["w0", "w1"]:
                break
            time.sleep(0.1)
        assert sup.worker_names() == ["w0", "w1"], sup.stats()
        _wait_states(sup, {"healthy"}, timeout_s=60.0)

        box["backlog"] = 0.0
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            if len(sup.worker_names()) == 1:
                break
            time.sleep(0.1)
        assert len(sup.worker_names()) == 1, sup.stats()

        auto = sup.stats()["autoscale"]
        assert auto["n_peak"] == 2
        assert auto["decisions"].get("scale_up.backlog", 0) >= 1
        assert auto["decisions"].get("scale_down.idle", 0) >= 1


@pytest.mark.slow
def test_fleet_surge_soak_smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fleet_soak", os.path.join(os.path.dirname(__file__),
                                   "..", "scripts", "fleet_soak.py"))
    soak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(soak)
    results = [soak.run_surge_trial(t, seed=321) for t in range(2)]
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad
