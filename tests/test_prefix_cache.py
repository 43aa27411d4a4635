"""Prefix-sharing COW ket cache (serve/prefix_cache.py): admission
split, COW donation guard, eviction/spill/fault-in, corruption
containment, recovery warm-up, kill-switch parity, and the telemetry
report section.

The service-level tests drive the real QrackService admission path on
the planes-holding "tpu" stack (jax on whatever backend the suite
pins): tenant 1 misses, tenant 2 (min_refs=2) materializes + inserts at
the provably-shared boundary, tenant 3+ hit and pay only the suffix —
and every served state is checked against a from-|0…0⟩ CPU oracle.
"""

import glob
import importlib.util
import os
import pathlib

import numpy as np
import pytest

from qrack_tpu import QEngineCPU
from qrack_tpu import matrices as mat
from qrack_tpu import resilience as res
from qrack_tpu import telemetry as tele
from qrack_tpu.engines.tpu import planes_pinned
from qrack_tpu.factory import create_quantum_interface
from qrack_tpu.layers.qcircuit import QCircuit
from qrack_tpu.resilience import faults
from qrack_tpu.resilience.breaker import CircuitBreaker
from qrack_tpu.serve import QrackService, batcher
from qrack_tpu.serve.prefix_cache import PrefixCache, fingerprint_host
from qrack_tpu.utils.rng import QrackRandom

W = 6


@pytest.fixture(autouse=True)
def _clean_serve():
    faults.clear()
    res.reset_breaker()
    res.configure(max_retries=2, backoff_s=0.0, timeout_s=0.0)
    batcher.clear_programs()
    tele.enable()
    tele.reset()
    yield
    faults.clear()
    res.reset_breaker()
    res.configure()
    res.disable()
    tele.disable()
    tele.reset()
    batcher.clear_programs()


def _svc(**kw) -> QrackService:
    kw.setdefault("batch_window_ms", 5.0)
    kw.setdefault("queue_budget_ms", 60_000.0)
    kw.setdefault("tick_s", 0.02)
    return QrackService(**kw)


def _fidelity(a, b) -> float:
    a, b = np.asarray(a).ravel(), np.asarray(b).ravel()
    return abs(np.vdot(a, b)) ** 2 / (np.vdot(a, a).real
                                      * np.vdot(b, b).real)


def _ry(theta: float) -> np.ndarray:
    c, s = np.cos(theta / 2.0), np.sin(theta / 2.0)
    return np.array([[c, -s], [s, c]], dtype=np.complex128)


def _ring(circ: QCircuit, width: int = W) -> None:
    for q in range(width - 1):
        circ.append_ctrl((q,), q + 1, mat.X2, 1)


def _prep(width: int = W, seed: int = 7) -> QCircuit:
    circ = QCircuit()
    rng = np.random.default_rng(seed)
    for q in range(width):
        circ.append_1q(q, mat.H2)
    for _ in range(2):
        _ring(circ, width)
        for q in range(width):
            circ.append_1q(q, _ry(rng.uniform(0.0, 2.0 * np.pi)))
    return circ


def _tenant(tail_seed: int, width: int = W, prep_seed: int = 7) -> QCircuit:
    """Shared prep + per-tenant tail; the tail's leading CX ring is the
    merge barrier that keeps the shared gates byte-stable (see
    tests/test_prefix_digest.py)."""
    circ = _prep(width, prep_seed)
    _ring(circ, width)
    rng = np.random.default_rng(tail_seed)
    for q in range(width):
        circ.append_1q(q, _ry(rng.uniform(0.0, 2.0 * np.pi)))
    return circ


def _shared_k(width: int = W) -> int:
    return len(_prep(width).gates) + (width - 1)


def _oracle_state(circ: QCircuit, width: int = W, seed: int = 0):
    eng = QEngineCPU(width, rng=QrackRandom(seed), rand_global_phase=False)
    circ.Run(eng)
    return eng.GetQuantumState()


def _planes_ket(planes) -> np.ndarray:
    import jax

    host = np.asarray(jax.device_get(planes), dtype=np.float64)
    return host[0] + 1j * host[1]


# ---------------------------------------------------------------------------
# cache unit level: plan / insert / hit / acquire
# ---------------------------------------------------------------------------

def test_plan_miss_then_popular_insert_then_hit():
    cache = PrefixCache(min_refs=2, min_gates=4)
    k = _shared_k()
    assert cache.plan(_tenant(1), W) is None          # first miss
    kind, depth, digest = cache.plan(_tenant(2), W)   # popular miss
    assert (kind, depth) == ("insert", k)
    assert digest == _tenant(3).prefix_digest(k)
    # materialize gates[:k] on a planes engine and admit it
    pre, _suf = _tenant(2).split_at(k)
    eng = create_quantum_interface("tpu", W)
    pre.Run(eng)
    entry = cache.insert(digest, W, "dense", k, eng.device_planes)
    assert entry is not None and planes_pinned(entry.planes)
    kind2, depth2, got = cache.plan(_tenant(3), W)
    assert (kind2, depth2) == ("hit", k) and got is entry
    assert _fidelity(_planes_ket(cache.acquire(entry)),
                     _oracle_state(pre)) > 1 - 1e-6
    assert cache.stats()["entries"] == 1
    snap = tele.snapshot()["counters"]
    assert snap["serve.prefix.hit"] == 1
    assert snap["serve.prefix.hit_depth"] == k
    assert snap["serve.prefix.miss"] == 2


def test_insert_rejects_invalid_norm():
    import jax.numpy as jnp

    cache = PrefixCache(min_refs=1, min_gates=4)
    eng = create_quantum_interface("tpu", W)
    _prep().Run(eng)
    bad = jnp.asarray(1.5) * eng.device_planes   # norm off by >2e-2
    assert cache.insert("d" * 40, W, "dense", 8, bad) is None
    assert cache.stats()["entries"] == 0
    assert tele.snapshot()["counters"]["serve.prefix.corrupt"] == 1


def test_evict_spills_and_faults_back_in_verified(tmp_path):
    from qrack_tpu.checkpoint.store import CheckpointStore

    store = CheckpointStore(str(tmp_path))
    plane_bytes = 2 * (2 ** W) * 4   # (2, 2^W) f32
    cache = PrefixCache(max_bytes=plane_bytes + 8, store=store,
                        min_refs=1, min_gates=4)
    pre_a, _ = _tenant(1).split_at(_shared_k())
    pre_b, _ = _tenant(1, prep_seed=8).split_at(_shared_k())
    planes = []
    for pre in (pre_a, pre_b):
        eng = create_quantum_interface("tpu", W)
        pre.Run(eng)
        planes.append(eng.device_planes)
    e_a = cache.insert(pre_a.structure_digest(), W, "dense",
                       len(pre_a.gates), planes[0])
    e_b = cache.insert(pre_b.structure_digest(), W, "dense",
                       len(pre_b.gates), planes[1])
    # budget fits ONE resident plane: admitting b spilled a
    assert e_b.planes is not None
    assert e_a.planes is None and e_a.spilled
    got = cache.acquire(e_a)                     # transparent fault-in
    assert got is not None
    assert _fidelity(_planes_ket(got), _oracle_state(pre_a)) > 1 - 1e-6
    cnt = tele.snapshot()["counters"]
    assert cnt["serve.prefix.spill"] >= 1
    assert cnt["serve.prefix.faultin"] == 1


def test_corrupted_spill_is_evicted_never_served(tmp_path):
    from qrack_tpu.checkpoint.store import CheckpointStore

    store = CheckpointStore(str(tmp_path))
    cache = PrefixCache(store=store, min_refs=1, min_gates=4)
    pre, _ = _tenant(1).split_at(_shared_k())
    eng = create_quantum_interface("tpu", W)
    pre.Run(eng)
    entry = cache.insert(pre.structure_digest(), W, "dense",
                         len(pre.gates), eng.device_planes)
    cache.evict_all(spill=True)
    assert entry.planes is None
    files = glob.glob(str(tmp_path / "**" / "*"), recursive=True)
    target = [f for f in files
              if os.path.isfile(f) and "prefix" in f.lower()]
    assert target, files
    with open(target[0], "r+b") as fh:          # flip bytes mid-file
        fh.seek(os.path.getsize(target[0]) // 2)
        fh.write(b"\xff" * 16)
    assert cache.acquire(entry) is None          # detected, not served
    assert cache.stats()["entries"] == 0         # evicted on the spot
    later = cache.plan(_tenant(2), W)            # and never served twice:
    assert later is None or later[0] == "insert"  # inserted anew, never a hit
    cnt = tele.snapshot()["counters"]
    assert cnt.get("serve.prefix.corrupt", 0) \
        + cnt.get("serve.prefix.lost", 0) >= 1


# ---------------------------------------------------------------------------
# service level: admission split end-to-end on the real executor
# ---------------------------------------------------------------------------

def test_service_share_miss_insert_hit_oracle_exact():
    with _svc(engine_layers="tpu") as svc:
        assert svc.prefix_cache is not None      # default-on
        states = {}
        for t in range(4):
            sid = svc.create_session(W, seed=t, rand_global_phase=False)
            svc.submit(sid, _tenant(t)).result(60)
            states[t] = svc.get_state(sid, timeout=60)
        pstats = svc.stats()["prefix_cache"]
        assert pstats["entries"] == 1
        assert pstats["hits"] >= 2               # tenants 2 and 3
    for t in range(4):
        assert _fidelity(_oracle_state(_tenant(t)), states[t]) > 1 - 1e-6
    cnt = tele.snapshot()["counters"]
    assert cnt["serve.prefix.miss"] == 2
    assert cnt["serve.prefix.insert"] == 1
    assert cnt["serve.prefix.hit"] == 2
    assert cnt["serve.prefix.hit_depth"] == 2 * _shared_k()


def test_nonpristine_session_never_splits():
    """Only a freshly-created |0…0⟩ session may seed from the cache —
    a second submit on the same session must run its circuit in full."""
    with _svc(engine_layers="tpu") as svc:
        for t in range(2):                       # populate: miss+insert
            sid = svc.create_session(W, seed=t, rand_global_phase=False)
            svc.submit(sid, _tenant(t)).result(60)
        sid = svc.create_session(W, seed=9, rand_global_phase=False)
        svc.submit(sid, _tenant(9)).result(60)   # pristine: hits
        hits_before = tele.snapshot()["counters"]["serve.prefix.hit"]
        svc.submit(sid, _tenant(10)).result(60)  # NOT pristine any more
        state = svc.get_state(sid, timeout=60)
        assert tele.snapshot()["counters"]["serve.prefix.hit"] \
            == hits_before
    oracle = QEngineCPU(W, rng=QrackRandom(9), rand_global_phase=False)
    _tenant(9).Run(oracle)
    _tenant(10).Run(oracle)
    assert _fidelity(oracle.GetQuantumState(), state) > 1 - 1e-6


def test_cache_hit_failover_rollback_keeps_entry_bit_identical():
    """Donation-guard regression: a cache hit whose dispatch fails at
    the honest sync must roll the session back and replay WITHOUT ever
    donating (or mutating) the cached buffer all tenants share."""
    import jax

    res.reset_breaker(CircuitBreaker(threshold=100, cooldown_s=0.0))
    with _svc(engine_layers="tpu") as svc:
        for t in range(2):                       # populate the cache
            sid = svc.create_session(W, seed=t, rand_global_phase=False)
            svc.submit(sid, _tenant(t)).result(60)
        entry = next(iter(svc.prefix_cache._entries.values()))
        want = entry.fingerprint
        faults.inject("serve.device_get", "device-loss", times=1)
        sid = svc.create_session(W, seed=5, rand_global_phase=False)
        svc.submit(sid, _tenant(5)).result(60)   # hit -> fail -> replay
        state = svc.get_state(sid, timeout=60)
        assert entry.planes is not None
        host = np.asarray(jax.device_get(entry.planes))
        assert fingerprint_host(host) == want    # bit-identical
        assert planes_pinned(entry.planes)
    assert _fidelity(_oracle_state(_tenant(5)), state) > 1 - 1e-6


def test_materialize_amp_corrupt_detected_never_admitted():
    """The prefix.materialize fault site corrupts the WOULD-BE cached
    copy: validation rejects it, nothing is admitted, every tenant's
    own result stays oracle-exact (satellite of the integrity soak)."""
    faults.inject("prefix.materialize", "amp-corrupt", times=None)
    with _svc(engine_layers="tpu") as svc:
        states = {}
        for t in range(3):
            sid = svc.create_session(W, seed=t, rand_global_phase=False)
            svc.submit(sid, _tenant(t)).result(60)
            states[t] = svc.get_state(sid, timeout=60)
        assert svc.stats()["prefix_cache"]["entries"] == 0
    for t in range(3):
        assert _fidelity(_oracle_state(_tenant(t)), states[t]) > 1 - 1e-6
    cnt = tele.snapshot()["counters"]
    assert cnt["serve.prefix.corrupt"] >= 1
    assert cnt.get("serve.prefix.hit", 0) == 0


def test_prefix_kill_switch_restores_pre_cache_behavior(monkeypatch):
    monkeypatch.setenv("QRACK_SERVE_PREFIX", "0")
    with _svc(engine_layers="tpu") as svc:
        assert svc.prefix_cache is None
        assert "prefix_cache" not in svc.stats()
        states = {}
        for t in range(3):
            sid = svc.create_session(W, seed=t, rand_global_phase=False)
            svc.submit(sid, _tenant(t)).result(60)
            states[t] = svc.get_state(sid, timeout=60)
    for t in range(3):
        assert _fidelity(_oracle_state(_tenant(t)), states[t]) > 1 - 1e-6
    cnt = tele.snapshot()["counters"]
    assert not any(k.startswith("serve.prefix.") for k in cnt)


def test_recover_rebuilds_service_with_warm_prefix_cache(tmp_path):
    """Checkpoint/recover round-trip: close() spills the cache to the
    store's prefix tier; a recovered service adopts the spill, the
    first same-prep tenant faults it back in (verified) and hits."""
    ck = str(tmp_path / "ck")
    with _svc(engine_layers="tpu", checkpoint_dir=ck) as svc:
        for t in range(3):
            sid = svc.create_session(W, seed=t, rand_global_phase=False)
            svc.submit(sid, _tenant(t)).result(60)
        assert svc.stats()["prefix_cache"]["entries"] == 1
    tele.reset()
    with _svc(engine_layers="tpu", checkpoint_dir=ck,
              recover=True) as svc2:
        pstats = svc2.stats()["prefix_cache"]
        assert pstats["entries"] == 1 and pstats["spilled"] == 1
        sid = svc2.create_session(W, seed=7, rand_global_phase=False)
        svc2.submit(sid, _tenant(7)).result(60)
        state = svc2.get_state(sid, timeout=60)
        assert svc2.stats()["prefix_cache"]["resident"] == 1
    assert _fidelity(_oracle_state(_tenant(7)), state) > 1 - 1e-6
    cnt = tele.snapshot()["counters"]
    assert cnt["serve.prefix.faultin"] == 1
    assert cnt["serve.prefix.hit"] == 1


# ---------------------------------------------------------------------------
# telemetry report: the == prefix == section
# ---------------------------------------------------------------------------

def test_telemetry_report_prefix_section(tmp_path, capsys):
    tele.inc("serve.prefix.hit", 6)
    tele.inc("serve.prefix.miss", 2)
    tele.inc("serve.prefix.hit_depth", 60)
    tele.inc("serve.prefix.insert", 1)
    tele.inc("serve.prefix.evict", 1)
    tele.inc("serve.prefix.spill", 1)
    tele.gauge("serve.prefix.bytes", 4096)
    tele.inc("serve.batch.dispatches", 3)        # keep serve section real
    out = tmp_path / "t.jsonl"
    tele.write_jsonl(str(out))
    tele.reset()

    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "telemetry_report.py")
    spec = importlib.util.spec_from_file_location("telemetry_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rep = mod.report(mod.load(str(out), aggregate=False), top=5)
    pf = rep["prefix"]
    assert pf["serve.prefix.hit"] == 6
    assert pf["hit_rate"] == 0.75
    assert pf["mean_hit_depth"] == 10.0
    assert pf["serve.prefix.bytes"] == 4096
    assert not any(k.startswith("serve.prefix.") for k in rep["serve"])
    assert mod.main([str(out)]) == 0
    assert "== prefix ==" in capsys.readouterr().out
