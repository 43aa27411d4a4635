"""Roofline ledger, the shared formula and the peak table
(telemetry/roofline.py, telemetry/sentinel.py).

The ledger is the hardware-truth plane: every guarded dispatch site
reports the HBM bytes it planned to move, devget-honest walls turn
those into implied-bandwidth samples, and anything faster than the
device-class peak is structurally impossible (dispatch ack) — counted in
`roofline.honesty.clamped` and kept out of the gauges.  Byte math is pinned against
the same exact-accounting oracles the pager/turboquant tests use.
"""

import numpy as np
import pytest

from qrack_tpu import telemetry as tele
from qrack_tpu.telemetry import export, roofline, sentinel


@pytest.fixture(autouse=True)
def _clean_tele():
    roofline._reset_fingerprint_cache()
    tele.reset()
    yield
    tele.disable()
    tele.reset()
    roofline._reset_fingerprint_cache()


# ---------------------------------------------------------------------------
# one formula, one peak table
# ---------------------------------------------------------------------------

def test_shared_formula_and_peak_table(monkeypatch):
    assert sentinel.implied_gbps(1e9, 1.0) == 1.0
    assert sentinel.implied_gbps(2e9, 0.5) == 4.0
    # one full sweep: 2 planes * 2^w amps * esize, read + write
    assert sentinel.plane_pass_bytes(20) == 2 * (1 << 20) * 4 * 2
    assert sentinel.plane_pass_bytes(20, esize=2) == 2 * (1 << 20) * 2 * 2
    assert sentinel.peak_gbps("TPU v5 lite") == 819.0
    assert sentinel.peak_gbps("tpu_v5e") == 819.0
    assert sentinel.peak_gbps("TPU v4") == 1228.0
    assert sentinel.peak_gbps("TPU v5p") == 2765.0
    # cpu/unknown quote their fraction of the accelerator roofline
    assert sentinel.peak_gbps("cpu") == 819.0
    assert sentinel.peak_gbps(None) == 819.0
    monkeypatch.setenv("QRACK_TPU_PEAK_GBPS", "100")
    assert sentinel.peak_gbps("TPU v4") == 100.0


def test_honest_sample_enters_hist_and_gauges():
    tele.enable()
    sample = roofline.record("unit.ok", 100e9, 1.0, width=20)
    assert not sample["clamped"]
    assert sample["implied_hbm_gbps"] == 100.0
    assert sample["hbm_peak_gbps"] == 819.0
    assert abs(sample["hbm_roofline_frac"] - 100 / 819.0) < 1e-3
    snap = tele.snapshot(include_events=False)
    assert snap["counters"]["roofline.unit.ok.dispatches"] == 1
    assert snap["counters"]["roofline.unit.ok.planned_bytes"] == 100e9
    assert "roofline.unit.ok.implied_hbm_gbps" in snap["hists"]
    assert abs(snap["gauges"]["roofline.unit.ok.peak_frac"]
               - 100 / 819.0) < 1e-3
    # per-width facet gauge
    assert "roofline.unit.ok.w20.peak_frac" in snap["gauges"]


def test_dispatch_ack_sample_clamped_and_kept_out_of_gauges():
    tele.enable()
    # 5 TB in 1 s: 5000 GB/s implied, ~6x the v5e peak — the dispatch-ack
    # signature (dispatch acked, completion never timed)
    sample = roofline.record("unit.clamp", 5000e9, 1.0, width=20)
    assert sample["clamped"]
    snap = tele.snapshot(include_events=False)
    assert snap["counters"]["roofline.honesty.clamped"] == 1
    assert snap["counters"]["roofline.unit.clamp.clamped"] == 1
    # excluded from the achieved-bandwidth distribution and gauges
    assert "roofline.unit.clamp.implied_hbm_gbps" not in snap["hists"]
    assert "roofline.unit.clamp.peak_frac" not in snap["gauges"]
    assert "roofline.unit.clamp.w20.peak_frac" not in snap["gauges"]


def test_clamp_threshold_tracks_env_peak(monkeypatch):
    monkeypatch.setenv("QRACK_TPU_PEAK_GBPS", "10")
    roofline._reset_fingerprint_cache()
    tele.enable()
    sample = roofline.record("unit.envpeak", 50e9, 1.0)
    assert sample["hbm_peak_gbps"] == 10.0
    assert sample["clamped"]


def test_record_computes_sample_even_when_disabled():
    # a script runs with telemetry off by default: the ledger must still
    # hand back the numbers for its JSON line without touching counters
    sample = roofline.record("unit.off", 100e9, 1.0)
    assert sample["implied_hbm_gbps"] == 100.0
    assert tele.snapshot(include_events=False)["counters"] == {}


# ---------------------------------------------------------------------------
# byte-math pins against the exact-accounting oracles
# ---------------------------------------------------------------------------

def test_tq_sweep_bytes_pin():
    """roofline.tq.sweep.planned_bytes == tq.sweeps * resident bytes:
    every counted decompress/recompress pass moves the full compressed
    residency (same raw-array accounting as tq.resident.bytes)."""
    from qrack_tpu.engines.turboquant import QEngineTurboQuant

    tele.enable()
    eng = QEngineTurboQuant(8, bits=8)
    for q in range(8):
        eng.H(q)
        eng.RZ(0.3, q)
    _ = eng.GetQuantumState()
    c = tele.snapshot(include_events=False)["counters"]
    sweeps = c["tq.sweeps"]
    assert sweeps > 0
    assert c["roofline.tq.sweep.planned_bytes"] == \
        sweeps * eng.resident_bytes()


def test_pager_exchange_bytes_pin():
    """The ledger's pager.exchange accounting IS the collective byte
    math: every byte counted in exchange.pager.bytes (remap prologues,
    global 2x2 exchanges) lands in the roofline ledger too."""
    from qrack_tpu.parallel.pager import QPager

    tele.enable()
    p = QPager(10)
    for q in range(10):
        p.H(q)
        for j in range(q):
            p.MCPhase([j], 1.0, np.exp(1j * 0.1), q)
    _ = p.GetQuantumState()
    c = tele.snapshot(include_events=False)["counters"]
    assert c["exchange.pager.bytes"] > 0
    assert c["roofline.pager.exchange.planned_bytes"] == \
        c["exchange.pager.bytes"]


def test_w26_iqft_collective_bytes_model():
    """Pure-arithmetic pin of the batched-collective byte model the
    pager feeds the ledger: a w26 IQFT epilogue remapping k=4 paged
    qubits in one batched all-to-all moves (1 - 2^-4) * nb — the same
    number test_remap.py::test_w26_iqft_accounting_batched_collective
    measures from the live counters."""
    from qrack_tpu.ops import sharded as shb

    w, g = 26, 4
    L = w - g
    nb = 2 * (1 << w) * 4  # two f32 planes
    swaps = [(q, L + q) for q in range(g)]  # mixed local<->paged pairs
    frac = shb.exchange_cost(L, g, swaps)
    assert abs(frac - (1 - 2 ** -g)) < 1e-12
    assert frac * nb == (1 - 2 ** -4) * nb


def test_fuse_flush_bytes_pin():
    """Dense-engine window flushes note sweeps * plane_pass_bytes."""
    from qrack_tpu.engines.tpu import QEngineTPU

    tele.enable()
    eng = QEngineTPU(8)
    for q in range(8):
        eng.H(q)
        eng.RZ(0.4, q)
    _ = eng.GetQuantumState()
    c = tele.snapshot(include_events=False)["counters"]
    sweeps = c.get("fuse.kernel.sweeps", 0) + c.get("fuse.xla.sweeps", 0)
    assert sweeps > 0
    assert c["roofline.tpu.fuse.flush.planned_bytes"] == \
        sweeps * sentinel.plane_pass_bytes(8)


def test_serve_dispatch_records_roofline():
    from qrack_tpu.models.qft import qft_qcircuit
    from qrack_tpu.serve import QrackService

    tele.enable()
    # plane-backed engines only: the batched submit-then-sync path is
    # the guarded serve.dispatch site (CPU engines run as singletons)
    with QrackService(engine_layers="tpu", batch_window_ms=2.0,
                      tick_s=0.02) as svc:
        sid = svc.create_session(6, seed=7)
        svc.apply(sid, qft_qcircuit(6), timeout=60)
    snap = tele.snapshot(include_events=False)
    assert snap["counters"]["roofline.serve.dispatch.dispatches"] >= 1
    assert snap["counters"]["roofline.serve.dispatch.planned_bytes"] > 0
    assert "roofline.serve.dispatch.implied_hbm_gbps" in snap["hists"]


def test_is_clamped_reads_device_class():
    assert sentinel.is_clamped({"implied_hbm_gbps": 5000.0})
    assert not sentinel.is_clamped({"implied_hbm_gbps": 2.1})
    assert not sentinel.is_clamped({"metric": "x"})  # no bandwidth field
    assert sentinel.is_clamped({"implied_codes_gbps": 900.0})
    # a line measured on a bigger device class keeps its own peak
    assert not sentinel.is_clamped(
        {"implied_hbm_gbps": 2000.0,
         "device_class": {"kind": "tpu v5p", "peak_gbps": 2765.0}})


# ---------------------------------------------------------------------------
# device-class fingerprint persistence (next to xla_cache)
# ---------------------------------------------------------------------------

def test_fingerprint_persist_and_load(tmp_path, monkeypatch):
    monkeypatch.setenv("QRACK_TPU_DEVICE_KIND", "tpu_v5e")
    roofline._reset_fingerprint_cache()
    fp = roofline.device_class(refresh=True)
    assert fp["kind"] == "tpu_v5e"
    assert fp["peak_gbps"] == 819.0
    path = roofline.persist_fingerprint(str(tmp_path))
    assert path == str(tmp_path / "device_class.json")
    loaded = roofline.load_fingerprint(str(tmp_path))
    assert loaded["kind"] == "tpu_v5e"
    assert loaded["peak_gbps"] == 819.0
    # an unknown restart never clobbers a known persisted kind
    monkeypatch.delenv("QRACK_TPU_DEVICE_KIND")
    monkeypatch.setattr(roofline, "device_class",
                        lambda *a, **k: {"kind": "unknown", "platform": "",
                                         "hbm_bytes": None,
                                         "peak_gbps": 819.0})
    roofline.persist_fingerprint(str(tmp_path))
    assert roofline.load_fingerprint(str(tmp_path))["kind"] == "tpu_v5e"


def test_service_persists_fingerprint(tmp_path, monkeypatch):
    monkeypatch.setenv("QRACK_TPU_DEVICE_KIND", "tpu_v5e")
    roofline._reset_fingerprint_cache()
    from qrack_tpu.serve import QrackService

    with QrackService(engine_layers="cpu",
                      checkpoint_dir=str(tmp_path)) as svc:
        sid = svc.create_session(4, seed=1)
        svc.destroy_session(sid)
    fp = roofline.load_fingerprint(str(tmp_path))
    assert fp is not None and fp["kind"] == "tpu_v5e"


# ---------------------------------------------------------------------------
# Perfetto counter tracks on the merged trace
# ---------------------------------------------------------------------------

def test_roofline_gauges_export_as_counter_tracks():
    tele.enable()
    roofline.record("unit.trace", 100e9, 1.0, width=20)
    trace = export.chrome_trace()
    cs = [e for e in trace["traceEvents"]
          if e["ph"] == "C" and e["name"] == "roofline.unit.trace.peak_frac"]
    assert cs and abs(cs[0]["args"]["value"] - 100 / 819.0) < 1e-3
    # local_trace_source carries gauges, so the merged fleet trace gets
    # one roofline counter track per source
    src = export.local_trace_source("w0")
    assert "roofline.unit.trace.peak_frac" in src["gauges"]
    merged = export.merged_chrome_trace([src])
    cs = [e for e in merged["traceEvents"]
          if e["ph"] == "C" and e["name"] == "roofline.unit.trace.peak_frac"]
    assert len(cs) == 1 and cs[0]["pid"] == 1
