"""Summarize a telemetry snapshot JSONL (QRACK_TPU_TELEMETRY_OUT).

Each line of the input is one qrack_tpu.telemetry.snapshot() dict
(docs/OBSERVABILITY.md); a long campaign appends many.  By default the
LAST line is reported — pass --all to aggregate every line (counters
sum; spans merge).  Sections:

  * top gate counters (gate.<engine>.<kind>.w<width>), grouped and raw
  * compile-cache traffic: hit/miss/eviction per cache, miss ratio
  * fusion: gate-window queue/flush/drop traffic per engine, sweeps
    saved vs gates queued (saved_ratio); mean flushed window length
    rides the spans section (fuse.<engine>.window_len); kernel lowering
    rates ride the same section — fuse.kernel.hit_rate (kernel windows
    over all multi-op windows), fuse.kernel.sweeps_per_window /
    ops_per_sweep (HBM passes the kernel actually paid), and
    fuse.kernel.fallback_rate with per-reason fuse.kernel.fallback.*
    counters (docs/PERFORMANCE.md)
  * exchange traffic: pager/ICI event counts and bytes
  * remap: placement-planner traffic — windows planned, swap pairs
    issued by kind, windows that needed no remap (docs/PERFORMANCE.md)
  * autoscale: the fleet control loop — decisions by reason
    (fleet.autoscale.decision.*), scale-up/down/failed counts, boot
    latency percentiles (fleet.autoscale.spawn_s), the brownout
    ladder's refusal counters and their share of admissions
    (serve.brownout.*), current/peak pool size — docs/FLEET.md
  * serving: jobs admitted/shed/expired/completed, batch occupancy
    (batched jobs per dispatch), queue-depth / latency gauges, and
    pipeline health — overlap_ratio (staged batches per dispatch) and
    join_rate (in-flight joins per batched job) — docs/SERVING.md
  * routing: decisions and executed jobs per stack with per-stack hit
    rates, mis-routes and escalations, live residency gauges
    (route.residency.<stack>) — docs/ROUTING.md
  * compression: the routable TurboQuant tier — resident codes+scales
    bytes vs the f32 dense equivalent (compression_ratio), counted
    decompress/recompress sweeps vs the single-pass fused-window
    savings (sweeps_saved_share, ops_per_window), and drift replay
    repairs vs giveups on the quantized rung — docs/PERFORMANCE.md
  * lightcone: the buffered-circuit rung (docs/LIGHTCONE.md) — cone-
    width percentiles (the register the reads actually built vs the
    declared width), the share of buffered gates each read elided,
    cone-cache hit rate, and which ladder rung served the cone reads
    (lightcone.reads.<stack> shares)
  * checkpoint: save/restore counts + bytes, spill-store footprint,
    warm-start programs recorded/prewarmed, recovery-lease traffic
  * elasticity: repage shrink/expand traffic, failed expansions,
    hybrid un-pins; the current page count rides the gauges section
    (elastic.pages) — docs/ELASTICITY.md
  * integrity: invariant violations, replay repairs vs giveups,
    quarantine strikes/devices/repages, canary verification traffic;
    the live quarantine size rides the gauges section
    (integrity.quarantined) — docs/INTEGRITY.md
  * layer events (qunit/stabilizer/qbdt/hybrid/factory escalations)
  * spans: count, total, mean

Fleet mode (``--fleet``) reads the supervisor's fleet JSONL
(FleetSupervisor.metrics / QRACK_FLEET_TELEMETRY_OUT) instead: the
latest merged ``kind: fleet`` record (fleet-wide counters, histograms,
SLO gauges, per-incarnation summaries) plus every ``kind: postmortem``
black-box record — the postmortem section prints what each dead
worker was doing when it died.

The SLO section reads the log-bucket histograms behind observe()
(telemetry/histogram.py): p50/p95/p99 per distribution, not min/max.

A missing or empty input is a one-line message + exit 2, never a
traceback (campaigns glob for files that may not exist yet).

Usage: python scripts/telemetry_report.py tele.jsonl [--all] [--top N]
       python scripts/telemetry_report.py tele.jsonl --json
       python scripts/telemetry_report.py fleet_telemetry.jsonl --fleet
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from qrack_tpu.telemetry import Histogram, merge_snapshots  # noqa: E402


def _read_lines(path: str) -> list:
    recs = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    recs.append(json.loads(line))
    except OSError as e:
        print(f"telemetry_report: cannot read {path}: {e.strerror}",
              file=sys.stderr)
        raise SystemExit(2)
    if not recs:
        print(f"telemetry_report: no snapshot lines in {path}",
              file=sys.stderr)
        raise SystemExit(2)
    return recs


def load(path: str, aggregate: bool) -> dict:
    snaps = _read_lines(path)
    if not aggregate:
        return snaps[-1]
    merged = merge_snapshots(snaps)
    merged["lines"] = len(snaps)
    # postmortems ride along when a fleet journal is fed through --all
    posts = [p for s in snaps for p in (s.get("postmortems") or [])]
    if posts:
        merged["postmortems"] = posts
    return merged


def load_fleet(path: str) -> dict:
    """Latest merged fleet record + the union of every postmortem seen
    anywhere in the journal (deduped per worker incarnation)."""
    recs = _read_lines(path)
    fleets = [r for r in recs if r.get("kind") == "fleet"]
    snap = dict(fleets[-1]) if fleets else {}
    posts = list(snap.get("postmortems") or [])
    seen = {(p.get("worker"), p.get("pid")) for p in posts}
    for r in recs:
        cand = [r] if r.get("kind") == "postmortem" \
            else (r.get("postmortems") or [])
        for p in cand:
            key = (p.get("worker"), p.get("pid"))
            if key not in seen:
                posts.append(p)
                seen.add(key)
    if not snap and not posts:
        print(f"telemetry_report: no fleet records in {path}",
              file=sys.stderr)
        raise SystemExit(2)
    snap["postmortems"] = posts
    return snap


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} TiB"


def report(snap: dict, top: int) -> dict:
    counters = snap.get("counters", {})
    gates = {k: v for k, v in counters.items() if k.startswith("gate.")}
    out = {
        "top_gates": sorted(gates.items(), key=lambda kv: -kv[1])[:top],
        "gates_total": sum(gates.values()),
        "compile": {},
        "fusion": {},
        "exchange": {},
        "remap": {},
        "serve": {},
        "prefix": {},
        "route": {},
        "compression": {},
        "noise": {},
        "lightcone": {},
        "roofline": {},
        "checkpoint": {},
        "elastic": {},
        "integrity": {},
        "fleet": {},
        "autoscale": {},
        "gauges": snap.get("gauges", {}),
        "layer_events": {},
        "spans": snap.get("spans", {}),
        "slo": {},
        "workers": snap.get("workers", {}),
        "postmortems": snap.get("postmortems", []),
    }
    # SLO section: percentiles from the observe() histograms — the
    # quantiles the gauges publish, recomputed here so --all aggregation
    # (which merges hists) reports merged percentiles too
    for name, d in sorted((snap.get("hists") or {}).items()):
        if name.startswith("roofline."):
            continue  # GB/s distributions, not latencies — == roofline ==
        if name.startswith("lightcone."):
            continue  # cone-width distribution, not a latency — its
            #           percentiles print in == lightcone ==
        h = Histogram.from_dict(d)
        if not h.count:
            continue
        out["slo"][name] = {
            "count": h.count, "mean_s": h.mean, "min_s": h.min,
            "p50_s": h.percentile(50), "p95_s": h.percentile(95),
            "p99_s": h.percentile(99), "max_s": h.max,
        }
    for k, v in counters.items():
        if k.startswith("compile."):
            # compile.<cache>.<hit|miss|eviction|call> — cache names may
            # themselves be dotted (compile.tpu.apply_2x2.miss)
            cache, _, kind = k[len("compile."):].rpartition(".")
            out["compile"].setdefault(cache, {})[kind] = v
        elif k.startswith("fuse."):
            out["fusion"][k] = v
        elif k.startswith("exchange."):
            out["exchange"][k] = v
        elif k.startswith("remap."):
            out["remap"][k] = v
        elif k.startswith("serve."):
            out["serve"][k] = v
        elif k.startswith("route."):
            out["route"][k] = v
        elif k.startswith("noise."):
            out["noise"][k] = v
        elif k.startswith("lightcone."):
            out["lightcone"][k] = v
        elif k.startswith("checkpoint."):
            out["checkpoint"][k] = v
        elif k.startswith("elastic."):
            out["elastic"][k] = v
        elif k.startswith("integrity."):
            out["integrity"][k] = v
        elif k.startswith("fleet."):
            out["fleet"][k] = v
        elif k.startswith("roofline."):
            out["roofline"][k] = v
        elif k.split(".")[0] in ("qunit", "qunitmulti", "stabilizer",
                                 "qbdt", "hybrid", "factory", "engine",
                                 "cluster", "resilience"):
            out["layer_events"][k] = v
    for cache, kinds in out["compile"].items():
        total = kinds.get("hit", 0) + kinds.get("miss", 0)
        if total:
            kinds["miss_ratio"] = round(kinds.get("miss", 0) / total, 4)
    # share of remap traffic that rode batched exchange collectives
    # (1.0 = every prologue batched, 0 = pair-at-a-time / collective off)
    rb = out["exchange"].get("exchange.pager.remap", 0)
    if rb:
        cb = out["exchange"].get("exchange.pager.collective_bytes", 0)
        out["remap"]["remap.pager.collective_share"] = round(cb / rb, 4)
    for k in [k for k in out["fusion"] if k.endswith(".gates")]:
        eng = k[len("fuse."):-len(".gates")]
        gates = out["fusion"][k]
        if gates:
            out["fusion"][f"fuse.{eng}.saved_ratio"] = round(
                out["fusion"].get(f"fuse.{eng}.sweeps_saved", 0) / gates, 4)
    # kernel lowering: how many multi-op windows took the Pallas kernel,
    # the HBM sweeps each paid, and why the rest fell back to the chain
    kw = out["fusion"].get("fuse.kernel.windows", 0)
    xw = out["fusion"].get("fuse.xla.windows", 0)
    if kw + xw:
        out["fusion"]["fuse.kernel.hit_rate"] = round(kw / (kw + xw), 4)
    if kw:
        ks = out["fusion"].get("fuse.kernel.sweeps", 0)
        out["fusion"]["fuse.kernel.sweeps_per_window"] = round(ks / kw, 3)
        if ks:
            out["fusion"]["fuse.kernel.ops_per_sweep"] = round(
                out["fusion"].get("fuse.kernel.ops", 0) / ks, 3)
    fallbacks = sum(v for k, v in out["fusion"].items()
                    if k.startswith("fuse.kernel.fallback."))
    if fallbacks + kw:
        out["fusion"]["fuse.kernel.fallback_rate"] = round(
            fallbacks / (fallbacks + kw), 4)
    dispatches = out["serve"].get("serve.batch.dispatches", 0)
    if dispatches:
        out["serve"]["batch_occupancy"] = round(
            out["serve"].get("serve.batch.jobs", 0) / dispatches, 3)
        # pipeline health: fraction of dispatch cycles that had the next
        # batch staged under the in-flight one, and fraction of batched
        # jobs that joined a staged batch instead of waiting a cycle
        out["serve"]["overlap_ratio"] = round(
            out["serve"].get("serve.overlap.staged", 0) / dispatches, 4)
    batch_jobs = out["serve"].get("serve.batch.jobs", 0)
    if batch_jobs:
        out["serve"]["join_rate"] = round(
            out["serve"].get("serve.overlap.join.jobs", 0) / batch_jobs, 4)
    # prefix cache: the shared-state-prep COW tier (serve/prefix_cache.py,
    # docs/SERVING.md) — hit economics (rate + mean depth of skipped
    # gates), lifecycle counters, and the resident-bytes gauge
    pf = out["prefix"]
    for k in list(out["serve"]):
        if k.startswith("serve.prefix."):
            pf[k] = out["serve"].pop(k)
    pf_hit = pf.get("serve.prefix.hit", 0)
    pf_miss = pf.get("serve.prefix.miss", 0)
    if pf_hit + pf_miss:
        pf["hit_rate"] = round(pf_hit / (pf_hit + pf_miss), 4)
    if pf_hit:
        # hit_depth accumulates the skipped prefix length per hit, so
        # the mean is gates-not-executed per cache hit
        pf["mean_hit_depth"] = round(
            pf.get("serve.prefix.hit_depth", 0) / pf_hit, 2)
    pf_bytes = snap.get("gauges", {}).get("serve.prefix.bytes")
    if pf and pf_bytes is not None:
        pf["serve.prefix.bytes"] = pf_bytes
    # per-stack hit rates: fraction of routed jobs each stack executed
    routed_jobs = sum(v for k, v in out["route"].items()
                      if k.startswith("route.jobs."))
    if routed_jobs:
        for k in [k for k in out["route"] if k.startswith("route.jobs.")]:
            stack = k[len("route.jobs."):]
            out["route"][f"hit_rate.{stack}"] = round(
                out["route"][k] / routed_jobs, 4)
    # compression: the TurboQuant tier's footprint and sweep economics —
    # resident codes+scales vs the f32 dense equivalent, how many
    # decompress/recompress passes the single-pass windows avoided, and
    # whether drift replays had to repair (or give up on) the rung
    comp = {k: v for k, v in counters.items() if k.startswith("tq.")}
    gauges = snap.get("gauges", {})
    res_b = gauges.get("tq.resident.bytes", 0)
    dense_b = gauges.get("tq.resident.dense_equiv_bytes", 0)
    if res_b:
        comp["tq.resident.bytes"] = res_b
        comp["tq.resident.dense_equiv_bytes"] = dense_b
        if dense_b:
            comp["compression_ratio"] = round(dense_b / res_b, 3)
    saved = counters.get("fuse.tq.sweeps_saved", 0)
    sweeps = comp.get("tq.sweeps", 0)
    if sweeps or saved:
        comp["fuse.tq.sweeps_saved"] = saved
        comp["sweeps_saved_share"] = round(saved / max(sweeps + saved, 1), 4)
    windows = counters.get("fuse.tq.windows", 0)
    if windows:
        comp["ops_per_window"] = round(
            counters.get("fuse.tq.ops", 0) / windows, 3)
    if comp:
        for k in ("integrity.replay.repaired", "integrity.replay.giveup"):
            if counters.get(k):
                comp[k] = counters[k]
    out["compression"] = comp
    # noise: the Monte-Carlo trajectory engine (docs/NOISE.md) — batch
    # geometry (trajectories per batch, HBM chunk rate), the devget-
    # honest trajectories/s gauge, and the single-trace proof
    # (compile.noise.window miss_ratio lives in == compile caches ==)
    nz = out["noise"]
    batches = nz.get("noise.traj.batches", 0)
    if batches:
        nz["trajectories_per_batch"] = round(
            nz.get("noise.traj.trajectories", 0) / batches, 2)
        nz["chunk_rate"] = round(
            nz.get("noise.traj.chunked", 0) / batches, 4)
    for g in ("noise.traj.rate", "noise.traj.chunk_size"):
        if g in gauges:
            nz[g] = gauges[g]
    # lightcone: the buffered-circuit rung — cone-width percentiles
    # (the register each read actually built), the share of buffered
    # gates the cone slicing elided, cone-cache hit rate, and the
    # ladder rung mix that served the cone reads (docs/LIGHTCONE.md)
    lc = out["lightcone"]
    cw = (snap.get("hists") or {}).get("lightcone.cone_width")
    if cw:
        h = Histogram.from_dict(cw)
        if h.count:
            lc["cone_width"] = {
                "count": h.count, "p50": round(h.percentile(50), 1),
                "p95": round(h.percentile(95), 1),
                "max": round(h.max, 1)}
    cone_gates = lc.get("lightcone.gates.cone", 0)
    elided = lc.get("lightcone.gates.elided", 0)
    if cone_gates + elided:
        lc["elided_share"] = round(elided / (cone_gates + elided), 4)
    hits = lc.get("lightcone.cache.hit", 0)
    misses = lc.get("lightcone.cache.miss", 0)
    if hits + misses:
        lc["cache_hit_rate"] = round(hits / (hits + misses), 4)
    lc_reads = lc.get("lightcone.reads", 0)
    if lc_reads:
        for k in [k for k in lc if k.startswith("lightcone.reads.")]:
            lc[f"rung_share.{k[len('lightcone.reads.'):]}"] = round(
                lc[k] / lc_reads, 4)
    # roofline: achieved bandwidth per guarded dispatch site — GB/s
    # percentiles from the implied-bandwidth histograms (merged hists
    # under --all/--fleet report merged percentiles, same as SLO),
    # peak-fraction gauges and clamped-sample counts (the roofline.*
    # counters collected above)
    for name, d in sorted((snap.get("hists") or {}).items()):
        if not name.startswith("roofline."):
            continue
        h = Histogram.from_dict(d)
        if not h.count:
            continue
        out["roofline"][name] = {
            "count": h.count,
            "p50_gbps": round(h.percentile(50), 2),
            "p99_gbps": round(h.percentile(99), 2),
            "max_gbps": round(h.max, 2),
        }
    for name, v in gauges.items():
        if name.startswith("roofline.") and name not in out["roofline"]:
            out["roofline"][name] = v
    # autoscale: the fleet control loop's decision mix, the brownout
    # ladder's refusal counters (+ their share of everything that asked
    # for admission), boot latency percentiles, and pool size
    asc = {}
    for k in list(out["fleet"]):
        if k.startswith("fleet.autoscale."):
            asc[k[len("fleet.autoscale."):]] = out["fleet"].pop(k)
    shed = counters.get("serve.brownout.shed", 0)
    refused = counters.get("serve.brownout.overloaded", 0)
    quantized = counters.get("serve.brownout.quantized", 0)
    if shed or refused or quantized:
        asc["brownout.shed"] = shed
        asc["brownout.overloaded"] = refused
        asc["brownout.quantized"] = quantized
        denom = shed + refused + counters.get("serve.jobs.admitted", 0)
        if denom:
            asc["brownout_share"] = round((shed + refused) / denom, 4)
    spawn = (snap.get("hists") or {}).get("fleet.autoscale.spawn_s")
    if spawn:
        h = Histogram.from_dict(spawn)
        if h.count:
            asc["spawn_s"] = {
                "count": h.count, "p50_s": round(h.percentile(50), 3),
                "p99_s": round(h.percentile(99), 3),
                "max_s": round(h.max, 3)}
    for g in ("fleet.autoscale.n_workers", "fleet.autoscale.n_peak",
              "fleet.autoscale.backlog"):
        if g in gauges:
            asc[g[len("fleet.autoscale."):]] = gauges[g]
    out["autoscale"] = asc
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="snapshot JSONL (QRACK_TPU_TELEMETRY_OUT)")
    ap.add_argument("--all", action="store_true",
                    help="aggregate every line instead of taking the last")
    ap.add_argument("--fleet", action="store_true",
                    help="input is a supervisor fleet JSONL "
                         "(FleetSupervisor.metrics): report the latest "
                         "merged record + every postmortem")
    ap.add_argument("--top", type=int, default=10,
                    help="gate counters to show (default 10)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object")
    args = ap.parse_args(argv)

    snap = load_fleet(args.path) if args.fleet \
        else load(args.path, args.all)
    rep = report(snap, args.top)
    if args.json:
        print(json.dumps(rep, indent=1, sort_keys=True))
        return 0

    print(f"== top gates (of {rep['gates_total']:.0f} total dispatches) ==")
    for name, v in rep["top_gates"]:
        print(f"  {name:<40s} {v:>12.0f}")
    print("== compile caches ==")
    for cache, kinds in sorted(rep["compile"].items()):
        parts = " ".join(f"{k}={v}" for k, v in sorted(kinds.items()))
        print(f"  {cache:<40s} {parts}")
    if rep["fusion"]:
        print("== fusion ==")
        for name, v in sorted(rep["fusion"].items()):
            print(f"  {name:<40s} {v:>12.3f}")
    print("== exchange ==")
    for name, v in sorted(rep["exchange"].items()):
        shown = _fmt_bytes(v) if name.endswith("bytes") else f"{v:.0f}"
        print(f"  {name:<40s} {shown:>12s}")
    if rep["remap"]:
        print("== remap ==")
        for name, v in sorted(rep["remap"].items()):
            shown = f"{v:.0f}" if float(v).is_integer() else f"{v:.3f}"
            print(f"  {name:<40s} {shown:>12s}")
    if rep["serve"]:
        print("== serve ==")
        for name, v in sorted(rep["serve"].items()):
            print(f"  {name:<40s} {v:>12.3f}")
    if rep["prefix"]:
        print("== prefix ==")
        for name, v in sorted(rep["prefix"].items()):
            if name.endswith("bytes"):
                shown = _fmt_bytes(v)
            elif float(v).is_integer():
                shown = f"{v:.0f}"
            else:
                shown = f"{v:.4f}"
            print(f"  {name:<40s} {shown:>12s}")
    if rep["route"]:
        print("== routing ==")
        for name, v in sorted(rep["route"].items()):
            print(f"  {name:<40s} {v:>12.3f}")
    if rep["noise"]:
        print("== noise ==")
        for name, v in sorted(rep["noise"].items()):
            shown = f"{v:.0f}" if float(v).is_integer() else f"{v:.3f}"
            print(f"  {name:<40s} {shown:>12s}")
    if rep["compression"]:
        print("== compression ==")
        for name, v in sorted(rep["compression"].items()):
            if name.endswith("bytes"):
                shown = _fmt_bytes(v)
            elif float(v).is_integer():
                shown = f"{v:.0f}"
            else:
                shown = f"{v:.4f}"
            print(f"  {name:<40s} {shown:>12s}")
    if rep["lightcone"]:
        print("== lightcone ==")
        for name, v in sorted(rep["lightcone"].items()):
            if isinstance(v, dict):
                print(f"  {name:<40s} n={v['count']:<6d} "
                      f"p50={v['p50']:.1f} p95={v['p95']:.1f} "
                      f"max={v['max']:.1f} qubits")
            else:
                shown = f"{v:.0f}" if float(v).is_integer() else f"{v:.4f}"
                print(f"  {name:<40s} {shown:>12s}")
    if rep["roofline"]:
        print("== roofline ==")
        for name, v in sorted(rep["roofline"].items()):
            if isinstance(v, dict):
                print(f"  {name:<48s} n={v['count']:<6d} "
                      f"p50={v['p50_gbps']:.2f}GB/s "
                      f"p99={v['p99_gbps']:.2f}GB/s "
                      f"max={v['max_gbps']:.2f}GB/s")
            elif name.endswith("bytes"):
                print(f"  {name:<48s} {_fmt_bytes(v):>12s}")
            else:
                shown = f"{v:.0f}" if float(v).is_integer() else f"{v:.4f}"
                print(f"  {name:<48s} {shown:>12s}")
    if rep["checkpoint"]:
        print("== checkpoint ==")
        for name, v in sorted(rep["checkpoint"].items()):
            shown = _fmt_bytes(v) if name.endswith("bytes") else f"{v:.0f}"
            print(f"  {name:<40s} {shown:>12s}")
    if rep["elastic"]:
        print("== elasticity ==")
        for name, v in sorted(rep["elastic"].items()):
            print(f"  {name:<40s} {v:>12.0f}")
    if rep["integrity"]:
        print("== integrity ==")
        for name, v in sorted(rep["integrity"].items()):
            print(f"  {name:<40s} {v:>12.0f}")
    if rep["fleet"]:
        print("== fleet ==")
        for name, v in sorted(rep["fleet"].items()):
            print(f"  {name:<40s} {v:>12.0f}")
    if rep["autoscale"]:
        print("== autoscale ==")
        for name, v in sorted(rep["autoscale"].items()):
            if isinstance(v, dict):
                print(f"  {name:<40s} n={v['count']:<5d} "
                      f"p50={v['p50_s']:.3f}s p99={v['p99_s']:.3f}s "
                      f"max={v['max_s']:.3f}s")
            else:
                shown = f"{v:.0f}" if float(v).is_integer() else f"{v:.4f}"
                print(f"  {name:<40s} {shown:>12s}")
    if rep["gauges"]:
        print("== gauges ==")
        for name, v in sorted(rep["gauges"].items()):
            if name.startswith("roofline."):
                continue  # shown in == roofline ==
            print(f"  {name:<40s} {v:>12.6g}")
    print("== layer events ==")
    for name, v in sorted(rep["layer_events"].items()):
        print(f"  {name:<40s} {v:>12.0f}")
    if rep["spans"]:
        print("== spans ==")
        for name, agg in sorted(rep["spans"].items()):
            mean = agg["total_s"] / max(agg["count"], 1)
            print(f"  {name:<32s} n={agg['count']:<6d} "
                  f"total={agg['total_s']:.6f}s mean={mean:.6f}s")
    if rep["slo"]:
        print("== SLO (histogram percentiles) ==")
        for name, s in sorted(rep["slo"].items()):
            print(f"  {name:<36s} n={s['count']:<7d} "
                  f"p50={s['p50_s'] * 1e3:.3f}ms "
                  f"p95={s['p95_s'] * 1e3:.3f}ms "
                  f"p99={s['p99_s'] * 1e3:.3f}ms "
                  f"max={s['max_s'] * 1e3:.3f}ms")
    if rep["workers"]:
        print("== fleet workers (per incarnation) ==")
        for key, s in sorted(rep["workers"].items()):
            lat = s.get("serve.latency") or {}
            extra = ""
            if lat:
                extra = (f" lat_p50={lat['p50'] * 1e3:.3f}ms"
                         f" lat_p99={lat['p99'] * 1e3:.3f}ms")
            print(f"  {key:<24s} jobs={s.get('jobs_completed', 0):.0f}"
                  f"{extra}")
    if rep["postmortems"]:
        print("== postmortems (what the worker was doing when it died) ==")
        for post in rep["postmortems"]:
            print(f"  -- {post.get('worker')} pid={post.get('pid')} "
                  f"reason={post.get('reason')} --")
            for e in post.get("last_events") or []:
                extra = " ".join(
                    f"{k}={v}" for k, v in sorted(e.items())
                    if k not in ("name", "t_s"))
                print(f"    [{e.get('t_s', 0):10.3f}s] "
                      f"{e.get('name'):<28s} {extra}")
            for s in (post.get("last_spans") or [])[-5:]:
                print(f"    span {s.get('name'):<26s} "
                      f"ts={s.get('ts_s', 0):.3f}s "
                      f"dur={s.get('dur_s', 0) * 1e3:.3f}ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
