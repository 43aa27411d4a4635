"""Resilience layer: fault grammar, watchdogged dispatch, circuit
breaker, and TPU→CPU failover with oracle-matching state.

Every test drives the programmatic fault API (resilience.faults.inject)
rather than QRACK_TPU_FAULTS, and restores the global resilience state
(fixture below) so the rest of the suite runs with the layer disabled —
the default off-path the <2% bench criterion is measured on.
"""

import time

import numpy as np
import pytest

from qrack_tpu import QEngineCPU, create_quantum_interface
from qrack_tpu import resilience as res
from qrack_tpu import telemetry as tele
from qrack_tpu.engines.hybrid import QHybrid
from qrack_tpu.resilience import faults
from qrack_tpu.utils.rng import QrackRandom


@pytest.fixture(autouse=True)
def _clean_resilience():
    faults.clear()
    res.reset_breaker()
    res.configure(max_retries=2, backoff_s=0.0, timeout_s=0.0)
    yield
    faults.clear()
    res.reset_breaker()
    res.configure()  # re-read env (defaults)
    res.disable()
    tele.disable()
    tele.reset()


# ---------------------------------------------------------------------------
# fault grammar
# ---------------------------------------------------------------------------

def test_fault_spec_grammar():
    s = faults.parse_spec("tpu.compile:raise:3")
    assert (s.site, s.kind, s.after_n, s.times) == ("tpu.compile", "raise", 3, 1)
    s = faults.parse_spec("pager.exchange:timeout:0+4")
    assert (s.after_n, s.times) == (0, 4)
    s = faults.parse_spec("*:device-loss:2+")
    assert s.times is None  # persistent
    s = faults.parse_spec("device_get:nan-poison:1:42")
    assert s.seed == 42
    with pytest.raises(ValueError):
        faults.parse_spec("just-a-site")
    with pytest.raises(ValueError):
        faults.parse_spec("site:unknown-kind:0")


def test_fault_spec_matching_and_firing():
    s = faults.FaultSpec(site="compile", kind="raise", after_n=2, times=2)
    assert s.matches("tpu.compile") and s.matches("compile")
    assert not s.matches("tpu.device_get")
    fires = [s.should_fire() for _ in range(6)]
    # 2 pass through, 2 fire, then healed
    assert fires == [False, False, True, True, False, False]
    wild = faults.FaultSpec(site="*", kind="raise")
    assert wild.matches("anything.at.all")


def test_fault_env_grammar_loads():
    n = faults.load_env("tpu.compile:raise:0,pager.exchange:hang:2+")
    assert n == 2
    assert [s.kind for s in faults.specs()] == ["raise", "hang"]
    faults.load_env("")
    assert not faults.specs()


def test_seeded_fault_is_deterministic():
    s1 = faults.FaultSpec(site="*", kind="raise", times=None, seed=7)
    s2 = faults.FaultSpec(site="*", kind="raise", times=None, seed=7)
    seq1 = [s1.should_fire() for _ in range(20)]
    seq2 = [s2.should_fire() for _ in range(20)]
    assert seq1 == seq2                      # same seed, same stream
    assert 0 < sum(seq1) < 20                # p=1/2: fires some, not all


# ---------------------------------------------------------------------------
# guarded dispatch: retry, backoff, give-up
# ---------------------------------------------------------------------------

def test_transient_fault_recovers_via_retry():
    res.enable()
    faults.inject("x.dispatch", "raise", after_n=0, times=1)
    calls = []
    out = res.call_guarded("x.dispatch", lambda: calls.append(1) or 42)
    assert out == 42 and len(calls) == 1  # fault fired pre-call, retry ran fn


def test_persistent_fault_gives_up_with_cause():
    res.enable()
    res.configure(max_retries=2)
    faults.inject("x.dispatch", "device-loss", after_n=0, times=None)
    with pytest.raises(res.DispatchGiveUp) as ei:
        res.call_guarded("x.dispatch", lambda: 42)
    # device-loss is non-retryable: exactly one attempt, cause preserved
    assert isinstance(ei.value.cause, res.DeviceLost)
    assert faults.specs()[0].fired == 1


def test_retry_count_matches_max_retries():
    res.enable()
    res.configure(max_retries=3)
    faults.inject("x.dispatch", "raise", after_n=0, times=None)
    with pytest.raises(res.DispatchGiveUp):
        res.call_guarded("x.dispatch", lambda: 42)
    assert faults.specs()[0].fired == 4  # 1 attempt + 3 retries


def test_retry_telemetry_counters():
    tele.enable()
    res.enable()
    res.configure(max_retries=2)
    faults.inject("x.dispatch", "raise", after_n=0, times=2)
    assert res.call_guarded("x.dispatch", lambda: 7) == 7
    c = tele.snapshot()["counters"]
    assert c.get("resilience.failure.x.dispatch") == 2
    assert c.get("resilience.fault.x.dispatch.raise") == 2


def test_injected_hang_is_caught_by_watchdog():
    res.enable()
    res.configure(max_retries=0, timeout_s=0.1)
    faults.inject("x.dispatch", "hang", after_n=0, times=None)
    t0 = time.perf_counter()
    with pytest.raises(res.DispatchGiveUp) as ei:
        res.call_guarded("x.dispatch", lambda: 42)
    assert isinstance(ei.value.cause, res.DispatchTimeout)
    assert time.perf_counter() - t0 < 5.0  # watchdog, not the stub's nap


def test_watchdog_times_out_real_slow_fn():
    res.enable()
    res.configure(max_retries=0, timeout_s=0.05)

    def slow():
        time.sleep(2.0)
        return "too late"

    with pytest.raises(res.DispatchGiveUp) as ei:
        res.call_guarded("x.dispatch", slow)
    assert isinstance(ei.value.cause, res.DispatchTimeout)


def test_validate_finite_catches_nan_output():
    res.enable()
    res.configure(max_retries=0, validate=True)
    bad = np.array([1.0, np.nan])
    with pytest.raises(res.DispatchGiveUp) as ei:
        res.call_guarded("x.dispatch", lambda: bad)
    assert isinstance(ei.value.cause, res.NaNPoisoned)
    res.configure(validate=False)
    assert res.call_guarded("x.dispatch", lambda: bad) is bad


def test_guarded_program_disabled_is_passthrough():
    prog = res.instrument_dispatch("x.dispatch", lambda a: a * 2)
    res.disable()
    faults.inject("x.dispatch", "raise", after_n=0, times=None)  # re-enables
    res.disable()
    assert prog(21) == 42  # disabled: fault never consulted


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

def _fake_clock():
    t = [0.0]
    return t, (lambda: t[0])


def test_breaker_full_state_machine():
    t, clock = _fake_clock()
    br = res.CircuitBreaker(threshold=3, cooldown_s=10.0, clock=clock)
    for _ in range(2):
        br.record_failure("s")
    assert br.state == "closed"
    br.record_failure("s")
    assert br.state == "open" and br.trips == 1
    with pytest.raises(res.BreakerOpen):
        br.allow("s")
    t[0] = 10.1
    br.allow("s")  # cooldown elapsed: half-open probe allowed
    assert br.state == "half_open"
    br.record_failure("s")  # probe failed: re-open immediately
    assert br.state == "open" and br.trips == 2
    t[0] = 20.2
    br.allow("s")
    br.record_success()
    assert br.state == "closed" and br.consecutive_failures == 0


def test_breaker_success_resets_consecutive_count():
    br = res.CircuitBreaker(threshold=2, cooldown_s=10.0)
    br.record_failure("s")
    br.record_success()
    br.record_failure("s")
    assert br.state == "closed"  # never 2 consecutive


def test_breaker_trip_stops_dispatch_until_half_open():
    """Acceptance: an open breaker provably stops TPU dispatch — fn is
    never invoked while open, and runs again after the cooldown."""
    t, clock = _fake_clock()
    res.reset_breaker(res.CircuitBreaker(threshold=2, cooldown_s=30.0,
                                         clock=clock))
    res.enable()
    res.configure(max_retries=0)
    faults.inject("x.dispatch", "raise", after_n=0, times=2)
    calls = []
    for _ in range(2):
        with pytest.raises(res.DispatchGiveUp):
            res.call_guarded("x.dispatch", lambda: calls.append(1))
    assert res.get_breaker().state == "open" and not calls
    # while open: BreakerOpen without touching fn (fault already healed,
    # so any invocation WOULD succeed — proving the breaker is the gate)
    with pytest.raises(res.BreakerOpen):
        res.call_guarded("x.dispatch", lambda: calls.append(1))
    assert not calls
    t[0] = 30.1  # cooldown elapsed: half-open probe runs and closes
    assert res.call_guarded("x.dispatch", lambda: calls.append(1) or 9) == 9
    assert calls and res.get_breaker().state == "closed"


def test_breaker_events_in_telemetry():
    tele.enable()
    t, clock = _fake_clock()
    br = res.reset_breaker(res.CircuitBreaker(threshold=1, cooldown_s=5.0,
                                              clock=clock))
    br.record_failure("s")
    with pytest.raises(res.BreakerOpen):
        br.allow("s")
    t[0] = 5.1
    br.allow("s")
    br.record_success()
    names = [e["name"] for e in tele.snapshot()["events"]]
    assert "resilience.breaker.trip" in names
    assert "resilience.breaker.half_open" in names
    assert "resilience.breaker.close" in names
    assert tele.snapshot()["counters"]["resilience.breaker.rejected"] == 1


# ---------------------------------------------------------------------------
# failover: the circuit completes with oracle-matching state
# ---------------------------------------------------------------------------

N = 5


def _apply_prefix(e):
    e.H(0)
    e.CNOT(0, 1)
    e.T(1)
    e.RY(0.7, 2)


def _apply_suffix(e):
    e.CZ(1, 2)
    e.H(3)
    e.INC(3, 0, 3)


def _oracle_state():
    o = QEngineCPU(N, rng=QrackRandom(3), rand_global_phase=False)
    _apply_prefix(o)
    _apply_suffix(o)
    return np.asarray(o.GetQuantumState())


def _assert_oracle_match(engine):
    with faults.suspended():
        got = np.asarray(engine.GetQuantumState())
    want = _oracle_state()
    f = abs(np.vdot(want, got)) ** 2
    assert f > 1 - 1e-6, f


# (site, kind) matrix: persistent faults that must end in failover (or
# transparent retry for the transient rows) with identical results
_MATRIX = [
    ("tpu.compile", "raise"),
    ("tpu.compile", "device-loss"),
    ("tpu.compile", "timeout"),
    ("tpu.device_get", "raise"),
    ("tpu.device_get", "nan-poison"),
    ("compile", "device-loss"),  # bare category
]


@pytest.mark.parametrize("site,kind", _MATRIX,
                         ids=[f"{s}-{k}" for s, k in _MATRIX])
def test_tpu_failover_matrix_matches_oracle(site, kind):
    res.enable()
    q = create_quantum_interface("tpu", N, rng=QrackRandom(3),
                                 rand_global_phase=False)
    _apply_prefix(q)
    faults.inject(site, kind, after_n=0, times=None)
    _apply_suffix(q)        # compile-site rows fail over here...
    q.GetAmplitude(0)       # ...device_get rows on this guarded read
    assert type(q.engine).__name__ == "QEngineCPU"
    _assert_oracle_match(q)


@pytest.mark.parametrize("site,kind", [("pager.exchange", "raise"),
                                       ("pager.dispatch", "device-loss"),
                                       ("pager.device_get", "raise")])
def test_pager_failover_matrix_matches_oracle(site, kind, monkeypatch):
    # pin per-gate dispatch: this matrix targets the per-gate sites
    # (pager.exchange only exists there — fused windows run their
    # ppermutes inside tpu.fuse.flush, covered by test_fusion.py)
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "1")
    res.enable()
    q = create_quantum_interface("pager", N, n_pages=4, rng=QrackRandom(3),
                                 rand_global_phase=False)
    _apply_prefix(q)
    faults.inject(site, kind, after_n=0, times=None)
    _apply_suffix(q)
    q.GetAmplitude(0)  # device_get rows fail over on this guarded read
    name = type(q.engine).__name__
    if site == "pager.exchange":
        # elastic landing: shrinking localizes every qubit, the exchange
        # site vanishes, the pager keeps serving ON the mesh — and since
        # `raise` is not a device-down signal, the boundary probe has
        # already grown it back to the construction page count
        assert name == "QPager"
        assert q.engine.n_pages == 4 and not q.engine.elastic_degraded
    else:
        # dispatch/device_get faults follow the shrunk pager (the site
        # exists at every page count), so the chain exits the mesh
        assert name in ("QEngineTPU", "QEngineCPU")
    _assert_oracle_match(q)


def test_transient_fault_is_invisible_midcircuit():
    res.enable()
    q = create_quantum_interface("tpu", N, rng=QrackRandom(3),
                                 rand_global_phase=False)
    _apply_prefix(q)
    faults.inject("tpu.compile", "raise", after_n=0, times=1)  # one blip
    _apply_suffix(q)
    assert type(q.engine).__name__ == "QEngineTPU"  # no failover
    _assert_oracle_match(q)


def test_hybrid_fails_over_in_place_and_stays_pinned():
    res.enable()
    h = QHybrid(N, tpu_threshold_qubits=2, rng=QrackRandom(3),
                rand_global_phase=False)
    _apply_prefix(h)
    faults.inject("tpu.compile", "raise", after_n=0, times=None)
    _apply_suffix(h)
    assert h._failed_over == "cpu"
    assert type(h._engine).__name__ == "QEngineCPU"
    _assert_oracle_match(h)
    # the ceiling sticks: ops keep running on CPU with the fault armed
    h.X(4)
    h.X(4)
    _assert_oracle_match(h)


def test_hybrid_construction_failover():
    res.enable()
    faults.inject("discover", "device-loss", after_n=0, times=None)
    h = QHybrid(N, tpu_threshold_qubits=2, device_id=0)
    assert h._failed_over == "cpu"
    assert type(h._engine).__name__ == "QEngineCPU"


def test_resilient_engine_build_construction_failover():
    res.enable()
    faults.inject("discover", "device-loss", after_n=0, times=None)
    q = create_quantum_interface("tpu", N, device_id=0)
    assert type(q.engine).__name__ == "QEngineCPU"
    q.H(0)
    assert abs(q.Prob(0) - 0.5) < 1e-6


def test_failover_emits_telemetry():
    tele.enable()
    res.enable()
    q = create_quantum_interface("tpu", N)
    faults.inject("tpu.compile", "raise", after_n=0, times=None)
    q.H(0)      # queues in the lazy gate window — no dispatch yet
    q.Prob(0)   # read boundary flushes; the compile fault fires HERE
    snap = tele.snapshot()
    assert snap["counters"].get("resilience.failovers", 0) >= 1
    assert any(e["name"].startswith("resilience.failover.")
               for e in snap["events"])


def test_wide_pager_failover_exhausts_chain_loudly():
    """When every fallback is unavailable (breaker open blocks the TPU
    hop, CPU cap below the width), failover must raise the constructor's
    error — not wedge, not silently truncate the ket."""
    from qrack_tpu.config import get_config, set_config

    old_cap = get_config().max_cpu_qubits
    set_config(max_cpu_qubits=4)
    try:
        res.enable()
        q = create_quantum_interface("pager", 6, n_pages=4)
        br = res.get_breaker()
        for _ in range(br.threshold):
            br.record_failure("pager.dispatch")  # trip: blocks TPU hop too
        with pytest.raises(MemoryError):
            q.H(0)     # queues lazily; the dispatch (and the loud
            q.Prob(0)  # chain-exhausted failure) surfaces at the read
    finally:
        set_config(max_cpu_qubits=old_cap)


# ---------------------------------------------------------------------------
# elastic re-paging: shrink on loss, serve degraded, grow on recovery
# (docs/ELASTICITY.md)
# ---------------------------------------------------------------------------

def test_flap_spec_grammar_and_device_down():
    s = faults.parse_spec("pager.dispatch:flap:2+3")
    assert (s.site, s.kind, s.after_n, s.times) == ("pager.dispatch",
                                                    "flap", 2, 3)
    with pytest.raises(ValueError):
        faults.parse_spec("pager.dispatch:flapp:0")
    faults.inject("pager.dispatch", "flap", after_n=1, times=2)
    assert not faults.device_down("pager.dispatch")  # window not open yet
    faults.check("pager.dispatch")                   # call 1 passes through
    assert faults.device_down("pager.dispatch")      # window open
    assert not faults.device_down("tpu.compile")     # other sites healthy
    for _ in range(2):
        with pytest.raises(res.DeviceLost):
            faults.check("pager.dispatch")
    assert not faults.device_down("pager.dispatch")  # flap healed itself
    faults.inject("tpu.dispatch", "device-loss", after_n=0, times=None)
    assert faults.device_down()              # any armed loss, any site
    with faults.suspended():
        assert not faults.device_down()      # snapshots must stand still


def test_pager_shrink_expand_roundtrip():
    """Structural round trip: shrink while the flap window is open, the
    probe refuses to grow until it heals, then one boundary restores the
    construction page count — and the amplitudes survive both repages."""
    tele.enable()
    res.enable()
    q = create_quantum_interface("pager", N, n_pages=4, rng=QrackRandom(3),
                                 rand_global_phase=False)
    _apply_prefix(q)
    pager = q.engine
    faults.inject("pager.dispatch", "flap", after_n=0, times=2)
    assert faults.device_down("pager.dispatch")
    pager.shrink_pages()
    assert pager.n_pages == 2 and pager.elastic_degraded
    assert not pager.maybe_reexpand()        # loss window still open
    assert pager.n_pages == 2
    for _ in range(2):                       # consume the flap: recovery
        with pytest.raises(res.DeviceLost):
            faults.check("pager.dispatch")
    assert pager.maybe_reexpand()
    assert pager.n_pages == 4 and not pager.elastic_degraded
    _apply_suffix(q)
    _assert_oracle_match(q)
    c = tele.snapshot()["counters"]
    assert c.get("elastic.repage.shrink") == 1
    assert c.get("elastic.repage.expand") == 1


def _rcs_ops():
    """Deterministic RCS-style brickwork: random single-qubit phase/H
    layers + CZ entanglers (no measurement — rng streams must stay
    uncoupled from the oracle's)."""
    gen = np.random.Generator(np.random.PCG64(7))
    ops = []
    for _ in range(4):
        for qb in range(N):
            ops.append((("T", "H", "S")[int(gen.integers(0, 3))], (qb,)))
        a = int(gen.integers(0, N))
        ops.append(("CZ", (a, (a + 1) % N)))
    return ops


def _fuzz_ops():
    """A slice of the API-fuzzer vocabulary (test_fuzz_api.py) minus
    measuring ops, so oracle and pager stay stream-independent."""
    gen = np.random.Generator(np.random.PCG64(11))
    q = lambda: int(gen.integers(0, N))
    ops = []
    for _ in range(16):
        kind = int(gen.integers(0, 6))
        if kind == 0:
            ops.append((("X", "Y", "Z", "H", "S", "T")[q()], (q(),)))
        elif kind == 1:
            ops.append((("RX", "RY", "RZ")[kind % 3],
                        (float(gen.uniform(0, 6.28)), q())))
        elif kind == 2:
            a = q()
            ops.append((("CNOT", "CZ", "Swap", "ISwap")[a % 4],
                        (a, (a + 1 + q() % (N - 1)) % N)))
        elif kind == 3:
            s = int(gen.integers(0, N - 1))
            ops.append(("INC", (int(gen.integers(0, 8)), s,
                                int(gen.integers(1, N - s + 1)))))
        elif kind == 4:
            ops.append(("XMask", (int(gen.integers(1, 1 << N)),)))
        else:
            ops.append(("ZMask", (int(gen.integers(1, 1 << N)),)))
    return ops


_ELASTIC_CIRCUITS = {
    "qft": lambda: ([("H", (0,)), ("CNOT", (0, 1)), ("RY", (0.7, 2))]
                    + [("QFT", (0, N))]),
    "rcs": _rcs_ops,
    "fuzz": _fuzz_ops,
}


@pytest.mark.parametrize("window", [1, 32])
@pytest.mark.parametrize("circ", sorted(_ELASTIC_CIRCUITS))
def test_pager_shrink_midcircuit_matrix(circ, window, monkeypatch):
    """A flap mid-circuit (fused window mid-flight included) shrinks the
    pager, the job finishes degraded ON the mesh, the next boundary
    grows it back — and the final state matches the CPU oracle."""
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(window))
    tele.enable()
    res.enable()
    ops = _ELASTIC_CIRCUITS[circ]()
    cut = len(ops) // 2
    q = create_quantum_interface("pager", N, n_pages=4, rng=QrackRandom(3),
                                 rand_global_phase=False)
    for name, args in ops[:cut]:
        getattr(q, name)(*args)
    # one DeviceLost at whatever guarded site fires next, then recovery
    faults.inject("*", "flap", after_n=0, times=1)
    for name, args in ops[cut:]:
        getattr(q, name)(*args)
    q.GetAmplitude(0)   # read boundary: flush + (for device_get) failover
    q.Prob(0)           # post-recovery boundary: the probe grows back
    c = tele.snapshot()["counters"]
    assert c.get("elastic.repage.shrink", 0) >= 1, (circ, window)
    assert type(q.engine).__name__ == "QPager"
    assert q.engine.n_pages == 4 and not q.engine.elastic_degraded
    with faults.suspended():
        got = np.asarray(q.GetQuantumState())
    o = QEngineCPU(N, rng=QrackRandom(3), rand_global_phase=False)
    for name, args in ops:
        getattr(o, name)(*args)
    want = np.asarray(o.GetQuantumState())
    f = abs(np.vdot(want, got)) ** 2
    assert f > 1 - 1e-6, (circ, window, f)


def test_pager_staircase_descends_through_shrink():
    """A PERSISTENT device loss re-fires on the shrunk pager, so the
    chain keeps descending — 4 → 2 → 1 pages — before exiting the mesh,
    and the final state still matches the oracle."""
    tele.enable()
    res.enable()
    q = create_quantum_interface("pager", N, n_pages=4, rng=QrackRandom(3),
                                 rand_global_phase=False)
    _apply_prefix(q)
    faults.inject("pager.dispatch", "device-loss", after_n=0, times=None)
    _apply_suffix(q)
    q.GetAmplitude(0)
    c = tele.snapshot()["counters"]
    assert c.get("elastic.repage.shrink", 0) >= 2     # 4→2 then 2→1
    assert type(q.engine).__name__ in ("QEngineTPU", "QEngineCPU")
    _assert_oracle_match(q)


def test_hybrid_unpins_after_device_recovery():
    """Regression for the stay-down asymmetry: a pinned CPU ceiling must
    lift at the next call boundary once the device-loss heals, not
    persist until process restart."""
    res.enable()
    h = QHybrid(N, tpu_threshold_qubits=2, rng=QrackRandom(3),
                rand_global_phase=False)
    _apply_prefix(h)
    faults.inject("tpu.compile", "device-loss", after_n=0, times=None)
    _apply_suffix(h)
    assert h._failed_over == "cpu"
    assert type(h._engine).__name__ == "QEngineCPU"
    faults.clear()          # the device comes back
    h.X(4)                  # boundary: probe passes, ceiling lifts
    assert h._failed_over is None
    assert type(h._engine).__name__ == "QEngineTPU"
    h.X(4)                  # undo so the oracle circuit is unchanged
    _assert_oracle_match(h)


# ---------------------------------------------------------------------------
# probe library
# ---------------------------------------------------------------------------

def test_probe_roundtrip_ok():
    r = res.run_probe(timeout_s=120.0)
    assert r.ok and not r.timed_out and "PROBE_OK" in r.output


def test_probe_timeout_sigterm_first():
    import sys

    # a child that ignores nothing: SIGTERM must end it inside the grace
    r = res.run_probe(timeout_s=0.3, term_grace_s=10.0,
                      python=sys.executable,
                      extra_env={"QRACK_PROBE_TEST_SLEEP": "1"})
    # the real payload may or may not finish in 0.3s on a loaded VM —
    # only the invariants matter: bounded return, coherent flags
    assert r.duration_s < 60.0
    if r.timed_out:
        assert not r.ok and not r.killed  # SIGTERM sufficed


# ---------------------------------------------------------------------------
# cluster init validation (satellite)
# ---------------------------------------------------------------------------

def test_init_cluster_rejects_partial_config(monkeypatch):
    from qrack_tpu.parallel import cluster

    monkeypatch.setattr(cluster, "_INITIALIZED", False)
    monkeypatch.setattr(cluster, "_INIT_ARGS", None)
    with pytest.raises(ValueError, match="num_processes"):
        cluster.init_cluster(coordinator_address="127.0.0.1:9999")
    with pytest.raises(ValueError, match="coordinator"):
        cluster.init_cluster(num_processes=2, process_id=0)
    monkeypatch.setenv("QRACK_NUM_PROCESSES", "2")
    with pytest.raises(ValueError, match="process_id"):
        cluster.init_cluster(coordinator_address="127.0.0.1:9999")


def test_init_cluster_repeat_semantics(monkeypatch):
    from qrack_tpu.parallel import cluster

    args = ("127.0.0.1:9999", 2, 0, None)
    monkeypatch.setattr(cluster, "_INITIALIZED", True)
    monkeypatch.setattr(cluster, "_INIT_ARGS", args)
    # identical repeat: idempotent no-op
    cluster.init_cluster(coordinator_address="127.0.0.1:9999",
                         num_processes=2, process_id=0)
    # different args: explicit error, not silent ignore
    with pytest.raises(RuntimeError, match="different arguments"):
        cluster.init_cluster(coordinator_address="10.0.0.1:1234",
                             num_processes=4, process_id=1)


# ---------------------------------------------------------------------------
# randomized soak (short slice; the full O(100) run is
# scripts/fault_soak.py)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fault_soak_smoke():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "fault_soak", os.path.join(os.path.dirname(__file__),
                                   "..", "scripts", "fault_soak.py"))
    soak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(soak)
    results = [soak.run_trial(t, seed=123) for t in range(9)]
    bad = [r for r in results if not r["ok"]]
    assert not bad, bad
