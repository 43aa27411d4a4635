"""Serving throughput/latency bench: scheduler+batcher vs the library
path, devget-honest end to end.

The LIBRARY baseline models N independent callers the way they really
hit the library: each request builds its OWN QCircuit object and its
own engine, runs RunFused, and completes with a device->host read.
The fused-program jit cache is per-circuit-OBJECT, so every caller
pays its own trace+compile — that is the "N users running the same
circuit pay N full dispatch round-trips" cost the serving subsystem
exists to collapse.

The SERVE path keeps N long-lived sessions; each round every session
submits a FRESH circuit object (tenants build their own circuits too)
and the digest-keyed batch ProgramCache recognizes them as the same
program, vmaps the N kets into one stacked dispatch, and completes all
N handles after one one-element device_get of the batched output.

Also reported, for honesty: the WARM single-object sequential baseline
(one pre-traced circuit run N times).  On the CPU backend batching
does NOT beat that number — same FLOPs, bigger cache footprint — the
serving win is compile + dispatch-round-trip amortization across
tenants, not per-gate arithmetic.  docs/SERVING.md records both.

LOADGEN mode (--loadgen, docs/SERVING.md): an open/closed-loop load
generator over O(1000) synthetic tenants (mixed circuit shapes, fixed
seed) for the continuous-batching pipeline's A/B.  Closed loop
(default) keeps --lg-concurrency requests in flight — each completion
immediately triggers that client's next submit — which is the
arrival-limited regime where batches stay PARTIAL and the serial
executor pays the full batch window per batch while the pipelined
executor hides it behind device execution.  Open loop (--lg-mode
open) submits at a fixed-seed Poisson --lg-rate instead and measures
the latency distribution at that offered load.  Every run spawns an
automatic QRACK_SERVE_PIPELINE=0 child with identical parameters and
seed; the headline is pipelined-vs-serial steady-state throughput
(acceptance: >= 1.5x with p99 latency no worse).  Percentiles come
from the shared telemetry Histogram helpers; a warmup pass of the
same traffic precedes the timed pass so batch-size compiles land
outside the measurement in both modes.

MIXED-TRAFFIC mode (--mixed, docs/ROUTING.md): one routed service
(engine_layers="route") hosts three tenant classes at once — Clifford-
heavy GHZ tenants, dense quantum-volume tenants, and shallow-QAOA
tenants — and the same traffic replays with QRACK_ROUTE=dense forced.
Per-class walls are timed class-phased within the shared service (every
session stays resident across the whole round), completion stays
devget-honest (the executor's sync step), and the headline is the
routed-vs-forced speedup on the Clifford class, measured at a
dense-feasible width so the forced baseline can exist at all.  A w100
Clifford tenant additionally rides the routed phase only: past the
dense cap there IS no forced baseline — that impossibility is the
routing subsystem's reason to exist.

SHALLOW mode (--shallow, docs/LIGHTCONE.md): a w50+ depth-4 local-
observable tenant class (shallow RY+CZ brickwork, models/algorithms.py
brickwork_qcircuit) rides ONE routed service next to dense w22 QFT
tenants.  The wide tenants' width is past every state-holding rung, but
their observables' past cones are ~6 qubits, so the router takes the
lightcone rung: gates buffer host-side and the completion read executes
a cone-width sub-circuit through the dense ladder.  After the timed
rounds a probe session checks the served expectation against the
analytic marginal sin^2(theta_q/2) — oracle-exact, not approximate.
The same wide submission then replays with QRACK_ROUTE=dense forced:
admission refuses it with the typed MisrouteError at submit() — there
is no forced-dense baseline wall for this class, and that refusal IS
the baseline the lightcone rung replaces (the dense w22 tenants still
serve under the same pin, so the refusal is width-specific).

NOISY mode (--noisy, docs/NOISE.md): one noisy-trajectory tenant —
noisy-RCS circuits under a depolarizing model, B=256 trajectories per
submission through QrackService.submit_trajectories (ONE vmapped
dispatch per window, exactly one trace across all rounds) — plus an
automatic child process measuring the sequential per-trajectory QNoisy
fallback at identical (key, trajectory_id) counters.  The headline is
the trajectories/s ratio (acceptance: >= 5x batched); docs/SERVING.md
and docs/NOISE.md record the measured ratio.

PREFIX mode (--prefix, docs/SERVING.md): the prefix-sharing COW ket
cache's loadgen.  Each round creates FRESH pristine sessions (only
those may seed from the cache); --px-share of them replay ONE shared
state-prep (H wall + --px-layers x (CX ring + seeded RY layer)) and
differ only in a short per-tenant tail, the rest get unique preps and
can never share.  A 2-tenant warmup populates the cache (min_refs=2:
miss, then miss+insert at the provably shared boundary), the timed
pass measures submit->result jobs/s devget-honestly, and a CPU oracle
re-runs verified sessions' FULL circuits from |0…0> so cached-seeded
results are checked end to end.  Every run spawns an automatic
QRACK_SERVE_PREFIX=0 child — byte-identical traffic down the pre-cache
admission path (acceptance: >= 3x jobs/s at oracle-equal fidelity).
The --px-solo arm (internal) runs ONE arm for the tpu_campaign.sh
prefix_cache_w22 / prefix_cache_w22_off single-client stage pair.

Usage:
    python scripts/serve_bench.py [--width 16] [--jobs 8] [--rounds 4]
                                  [--layers tpu] [--window-ms 50] [--json]
    python scripts/serve_bench.py --noisy [--noisy-width 14]
                                  [--noisy-traj 256] [--noisy-depth 4]
    python scripts/serve_bench.py --mixed [--clifford-width 20]
                                  [--qaoa-width 12] [--wide-width 100]
    python scripts/serve_bench.py --shallow [--shallow-width 50]
                                  [--shallow-jobs 4] [--shallow-dense-width 22]
    python scripts/serve_bench.py --loadgen [--tenants 1000]
                                  [--lg-requests 2000] [--lg-mode closed]
                                  [--lg-concurrency 40] [--lg-rate 400]
    python scripts/serve_bench.py --prefix [--px-width 18]
                                  [--px-tenants 20] [--px-rounds 3]
                                  [--px-layers 8] [--px-share 0.8]

Exit 0 when the acceptance bar holds (default: cold AND steady-state
serve rounds < 0.6x the sequential library wall; --mixed: routed
Clifford class >= 10x faster than dense-forced; --shallow: wide tenant
auto-routes to lightcone, probe expectations analytic-exact, forced
dense refuses with MisrouteError; --loadgen: pipelined throughput >=
1.5x the serial A/B child with p99 no worse; --prefix: cache-on >= 3x
the cache-off child's jobs/s at oracle-equal fidelity), 1 otherwise.
"""

import argparse
import json
import math
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from qrack_tpu.utils.platform import pin_host_cpu  # noqa: E402

pin_host_cpu(8)

import numpy as np  # noqa: E402

from qrack_tpu import telemetry as tele  # noqa: E402
from qrack_tpu.factory import create_quantum_interface  # noqa: E402
from qrack_tpu.models.qft import qft_qcircuit  # noqa: E402
from qrack_tpu.serve import QrackService  # noqa: E402
from qrack_tpu.serve.session import planes_engine  # noqa: E402


def _devget_read(engine) -> None:
    """Honest completion: a real one-element device->host read (a dispatch
    acks dispatch on block_until_ready; only device_get is proof)."""
    import jax

    core = planes_engine(engine)
    if core is not None:
        np.asarray(jax.device_get(core.device_planes[:1, :1]))
    else:
        engine.Prob(0)


def _pctl(vals, q):
    if not vals:
        return None
    return tele.Histogram.of(vals).percentile(q)


def measure_library_cold(width, jobs, layers, **engine_kwargs):
    """N sequential fresh-caller requests: own circuit object (own jit
    cache), own engine, RunFused, devget."""
    t0 = time.perf_counter()
    for _ in range(jobs):
        circ = qft_qcircuit(width)
        eng = create_quantum_interface(layers, width, **engine_kwargs)
        circ.RunFused(eng)
        _devget_read(eng)
    return time.perf_counter() - t0


def measure_library_warm(width, jobs, layers, **engine_kwargs):
    """N sequential requests sharing ONE pre-traced circuit object —
    the best case the plain library offers a single caller."""
    circ = qft_qcircuit(width)
    engines = [create_quantum_interface(layers, width, **engine_kwargs)
               for _ in range(jobs)]
    circ.RunFused(engines[0])  # trace+compile outside the timed region
    _devget_read(engines[0])
    t0 = time.perf_counter()
    for eng in engines:
        circ.RunFused(eng)
        _devget_read(eng)
    return time.perf_counter() - t0


def measure_serve(width, jobs, rounds, layers, window_ms, **engine_kwargs):
    """`rounds` rounds of `jobs` concurrent fresh-circuit submissions
    through the scheduler.  Round 0 is cold (pays the one shared batch
    compile); later rounds are steady state."""
    svc = QrackService(engine_layers=layers, max_depth=4 * jobs + 8,
                       batch_window_ms=window_ms, max_batch=jobs,
                       queue_budget_ms=120_000.0, **engine_kwargs)
    walls, handles_steady = [], []
    try:
        sids = [svc.create_session(width, seed=i) for i in range(jobs)]
        for r in range(rounds):
            circs = [qft_qcircuit(width) for _ in sids]
            t0 = time.perf_counter()
            handles = [svc.submit(sid, c) for sid, c in zip(sids, circs)]
            for h in handles:
                h.result(timeout=600)
            walls.append(time.perf_counter() - t0)
            if r > 0:
                handles_steady.extend(handles)
    finally:
        svc.close()
    return walls, handles_steady


def _measure_mixed_phase(args, mode):
    """One full mixed-traffic run with QRACK_ROUTE pinned to `mode`
    ("auto" = routing on, "dense" = forced).  Returns per-class wall
    lists (one entry per round; round 0 is cold) plus, in auto mode,
    walls for the w100 Clifford tenant no forced baseline can serve."""
    from qrack_tpu.models.algorithms import (ghz_qcircuit, qaoa_qcircuit,
                                             quantum_volume_qcircuit)
    from qrack_tpu.utils.rng import QrackRandom

    prev = os.environ.get("QRACK_ROUTE")
    os.environ["QRACK_ROUTE"] = mode
    walls = {"clifford": [], "dense": [], "qaoa": [], "wide": []}
    try:
        svc = QrackService(engine_layers="route",
                           max_depth=8 * args.jobs + 16,
                           batch_window_ms=args.window_ms,
                           max_batch=args.jobs,
                           queue_budget_ms=600_000.0)
        try:
            tenants = {
                "clifford": ([svc.create_session(args.clifford_width, seed=i)
                              for i in range(args.jobs)],
                             lambda: ghz_qcircuit(args.clifford_width)),
                # fresh circuit OBJECT per submission, same content
                # every round and phase (fixed seed): steady rounds are
                # warm in BOTH phases, so routed-vs-forced is fair
                "dense": ([svc.create_session(args.width, seed=100 + i)
                           for i in range(args.jobs)],
                          lambda: quantum_volume_qcircuit(
                              args.width, rng=QrackRandom(17))),
                "qaoa": ([svc.create_session(args.qaoa_width, seed=200 + i)
                          for i in range(args.jobs)],
                         lambda: qaoa_qcircuit(args.qaoa_width, p=1)),
            }
            if mode == "auto" and args.wide_width:
                tenants["wide"] = (
                    [svc.create_session(args.wide_width, seed=300)],
                    lambda: ghz_qcircuit(args.wide_width))
            for _ in range(args.rounds):
                for cls, (sids, make) in tenants.items():
                    circs = [make() for _ in sids]
                    t0 = time.perf_counter()
                    handles = [svc.submit(sid, c)
                               for sid, c in zip(sids, circs)]
                    for h in handles:
                        h.result(timeout=600)
                    walls[cls].append(time.perf_counter() - t0)
        finally:
            svc.close()
    finally:
        if prev is None:
            os.environ.pop("QRACK_ROUTE", None)
        else:
            os.environ["QRACK_ROUTE"] = prev
    return walls


def _lg_mix():
    """The loadgen's tenant classes: (label, width, circuit factory).
    Four distinct shape buckets (structure digests differ) with batched
    execution walls (19-37 ms at bucket 16 on this box) at least as
    large as the batch window, so an in-flight batch's compute is long
    enough to hide the next batch's staging window behind — the overlap
    the A/B resolves.  Smaller circuits finish before the window does
    and both modes pay window + compute sequentially.  Factories are
    deterministic — every submission of a class carries identical
    content, so the digest-keyed ProgramCache batches them."""
    from qrack_tpu.models.algorithms import (qaoa_qcircuit,
                                             quantum_volume_qcircuit)
    from qrack_tpu.utils.rng import QrackRandom

    return [
        ("qft13", 13, lambda: qft_qcircuit(13)),
        ("qft14", 14, lambda: qft_qcircuit(14)),
        ("qaoa13", 13, lambda: qaoa_qcircuit(13, p=2)),
        ("qv12", 12, lambda: quantum_volume_qcircuit(
            12, rng=QrackRandom(17))),
    ]


def _lg_precompile(mix, max_batch: int) -> None:
    """Compile every (class, batch-size bucket) program before traffic
    starts — the prewarm discipline (checkpoint/warmstart.py), inlined:
    the steady-state A/B must measure dispatch overlap, not whichever
    mode happened to hit more cold 1-2s jit compiles.  Runs on the
    caller thread while the executor is idle (jax is in-process on the
    CPU backend here; nothing else is dispatching)."""
    import jax.numpy as jnp

    from qrack_tpu.config import get_config
    from qrack_tpu.serve import batcher as _batcher

    dtype = get_config().device_real_dtype()
    pad_on = os.environ.get("QRACK_SERVE_BATCH_PAD", "1") != "0"
    if pad_on:  # occupancies 1..max_batch land on pow2 buckets
        sizes, b = [], 1
        while b < _batcher._bucket(max_batch):
            sizes.append(b)
            b <<= 1
        sizes.append(b)
    else:
        sizes = list(range(1, max_batch + 1))
    for _, w, make in mix:
        circ = make()
        for bsz in sizes:
            fn = _batcher.batch_program(circ, w, bsz)
            plane = (jnp.zeros((2, 1 << w), dtype=dtype)
                     .at[0, 0].set(1.0))
            _batcher.sync_scalar(fn([plane] * bsz))


def measure_loadgen(args, pipeline: bool) -> dict:
    """One loadgen run in THIS process: warmup pass + timed pass of the
    same fixed-seed traffic against a service built with the given
    dispatch mode.  Returns the raw per-run metrics dict."""
    tele.enable()
    tele.reset()
    # Dozens of generator threads waking at each batch settle starve
    # the dispatch-owner thread under the default 5 ms GIL slice: each
    # release point in the dispatch stage hands the core away for up to
    # 5 ms x waiters, stretching ~8 ms of host work past the batch's
    # whole device execution and leaving the pipeline nothing to
    # overlap.  A sub-ms slice keeps the owner hot in BOTH A/B modes
    # (set identically here and in the serial child).
    sys.setswitchinterval(5e-4)
    mix = _lg_mix()
    rng = np.random.default_rng(args.lg_seed)
    total = args.lg_warmup + args.lg_requests
    tenant_class = rng.integers(0, len(mix), size=args.tenants)
    order = rng.integers(0, args.tenants, size=total)
    svc = QrackService(engine_layers=args.layers,
                       max_depth=total + args.tenants + 64,
                       batch_window_ms=args.lg_window_ms,
                       max_batch=args.lg_batch,
                       queue_budget_ms=600_000.0, tick_s=0.05,
                       pipeline=pipeline)
    failed = [0]
    fail_lock = threading.Lock()
    try:
        sids = [svc.create_session(mix[tenant_class[i]][1], seed=10_000 + i)
                for i in range(args.tenants)]
        # fresh circuit OBJECT per request (tenants build their own),
        # constructed before the timed loop so generator threads do no
        # build work while the executor shares this one core
        circs = [mix[tenant_class[t]][2]() for t in order]
        _lg_precompile(mix, args.lg_batch)

        def _one(i, handles, base):
            try:
                h = svc.submit(sids[order[i]], circs[i])
                handles[i - base] = h
                h.result(600)
            except Exception:  # noqa: BLE001 — count, keep generating
                with fail_lock:
                    failed[0] += 1

        def phase(lo, hi):
            handles = [None] * (hi - lo)
            if args.lg_mode == "closed":
                it = iter(range(lo, hi))
                lock = threading.Lock()

                def worker():
                    while True:
                        with lock:
                            i = next(it, None)
                        if i is None:
                            return
                        _one(i, handles, lo)

                ts = [threading.Thread(target=worker, daemon=True)
                      for _ in range(args.lg_concurrency)]
                t0 = time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
            else:  # open loop: fixed-seed Poisson arrivals
                gaps = rng.exponential(1.0 / args.lg_rate, size=hi - lo)
                t0 = time.perf_counter()
                due = t0
                for k, i in enumerate(range(lo, hi)):
                    due += gaps[k]
                    now = time.perf_counter()
                    if due > now:
                        time.sleep(due - now)
                    try:
                        handles[i - lo] = svc.submit(sids[order[i]],
                                                     circs[i])
                    except Exception:  # noqa: BLE001
                        failed[0] += 1
                for h in handles:
                    if h is not None:
                        try:
                            h.result(600)
                        except Exception:  # noqa: BLE001
                            failed[0] += 1
            return handles, time.perf_counter() - t0

        phase(0, args.lg_warmup)   # warms batch-size compiles, both modes
        failed[0] = 0
        tele.reset()
        handles, wall = phase(args.lg_warmup, total)
    finally:
        svc.close()
    lats = [h.latency_s for h in handles
            if h is not None and h.latency_s is not None]
    q_waits = [h.queue_wait_s for h in handles
               if h is not None and h.queue_wait_s is not None]
    snap = tele.snapshot()
    cnt = snap["counters"]
    dispatches = cnt.get("serve.batch.dispatches", 0)
    batched = cnt.get("serve.batch.jobs", 0)
    completed = len(lats)
    return {
        "pipeline": bool(pipeline),
        "wall_s": round(wall, 6),
        "completed": completed, "failed": failed[0],
        "throughput_jobs_per_s": round(completed / wall, 2) if wall else 0,
        "latency_p50_s": _pctl(lats, 50), "latency_p99_s": _pctl(lats, 99),
        "queue_wait_p50_s": _pctl(q_waits, 50),
        "queue_wait_p99_s": _pctl(q_waits, 99),
        "dispatches": dispatches, "batch_jobs": batched,
        "batch_occupancy": round(batched / dispatches, 2) if dispatches
        else 0,
        "overlap_staged": cnt.get("serve.overlap.staged", 0),
        "join_jobs": cnt.get("serve.overlap.join.jobs", 0),
        "overlap_ratio": round(cnt.get("serve.overlap.staged", 0)
                               / dispatches, 3) if dispatches else 0,
        "join_rate": round(cnt.get("serve.overlap.join.jobs", 0)
                           / batched, 3) if batched else 0,
        "compile_misses_steady": cnt.get("compile.serve_batch.miss", 0),
    }


def _lg_child_args(args) -> list:
    """Re-invoke THIS script as the serial A/B child: same parameters,
    same seed, pipeline forced off."""
    return [sys.executable, os.path.abspath(__file__), "--loadgen",
            "--ab-child", "--json", "--lg-pipeline", "0",
            "--layers", args.layers,
            "--tenants", str(args.tenants),
            "--lg-requests", str(args.lg_requests),
            "--lg-warmup", str(args.lg_warmup),
            "--lg-mode", args.lg_mode,
            "--lg-concurrency", str(args.lg_concurrency),
            "--lg-rate", str(args.lg_rate),
            "--lg-window-ms", str(args.lg_window_ms),
            "--lg-batch", str(args.lg_batch),
            "--lg-seed", str(args.lg_seed)]


def run_loadgen(args) -> dict:
    """Pipelined run in-process, then the automatic serial A/B child
    (fresh process: its own jit caches, its own executor) with the
    identical fixed-seed traffic.  The comparison is steady-state
    throughput and tail latency of the SAME offered load."""
    res_pipe = measure_loadgen(args, pipeline=args.lg_pipeline != 0)
    env = dict(os.environ, QRACK_SERVE_PIPELINE="0")
    proc = subprocess.run(_lg_child_args(args), capture_output=True,
                          text=True, env=env, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError("serial A/B child failed:\n" + proc.stderr[-2000:])
    out = proc.stdout
    res_serial = json.loads(out[out.index("{"):])
    speedup = (res_pipe["throughput_jobs_per_s"]
               / max(res_serial["throughput_jobs_per_s"], 1e-9))
    # "no worse" with a 5% noise floor: on this shared 1-core VM two
    # runs of the same config jitter by a few percent
    p99_ok = (res_pipe["latency_p99_s"] is not None
              and res_serial["latency_p99_s"] is not None
              and res_pipe["latency_p99_s"]
              <= res_serial["latency_p99_s"] * 1.05)
    res = {
        "mode": "loadgen", "lg_mode": args.lg_mode,
        "tenants": args.tenants, "requests": args.lg_requests,
        "warmup": args.lg_warmup, "concurrency": args.lg_concurrency,
        "rate": args.lg_rate, "window_ms": args.lg_window_ms,
        "max_batch": args.lg_batch, "seed": args.lg_seed,
        "classes": [c[0] for c in _lg_mix()],
        "pipelined": res_pipe, "serial": res_serial,
        "speedup_throughput": round(speedup, 3),
        "p99_no_worse": bool(p99_ok),
        "pass_1p5x": bool(speedup >= 1.5 and p99_ok),
    }
    tele.gauge("serve.bench.loadgen_speedup", res["speedup_throughput"])
    tele.gauge("serve.bench.loadgen_jobs_per_s",
               res_pipe["throughput_jobs_per_s"])
    if res_pipe["latency_p99_s"] is not None:
        tele.gauge("serve.bench.loadgen_p99_s", res_pipe["latency_p99_s"])
    return res


def _px_circuit(width, prep_layers, prep_seed, tail_seed):
    """One tenant's full circuit: a deterministic state-prep block
    (H wall + prep_layers x (CX ring + seeded RY layer)) followed by a
    short per-tenant tail.  Tenants built with the SAME prep_seed share
    the prep gate-for-gate, so their prefix digests agree there.

    The tail starts with a CX ring on purpose: AppendGate merges a
    same-target uncontrolled gate into the previous gate's payload, so
    a rotation tail appended straight after the prep's rotation layer
    would MUTATE the shared gates and fork every tenant's digest.  The
    entangling ring is a merge barrier (and, being identical across
    tenants, extends the shared prefix by one ring — the divergence
    point is the seeded tail rotation layer)."""
    from qrack_tpu import matrices as mat
    from qrack_tpu.layers.qcircuit import QCircuit

    def ring(circ):
        for q in range(width - 1):
            circ.append_ctrl((q,), q + 1, mat.X2, 1)

    def ry_layer(circ, rng):
        for q in range(width):
            th = rng.uniform(0.0, 2.0 * np.pi)
            c, s = np.cos(th / 2.0), np.sin(th / 2.0)
            circ.append_1q(q, np.array([[c, -s], [s, c]],
                                       dtype=np.complex128))

    circ = QCircuit()
    rng = np.random.default_rng(prep_seed)
    for q in range(width):
        circ.append_1q(q, mat.H2)
    for _ in range(prep_layers):
        ring(circ)
        ry_layer(circ, rng)
    ring(circ)
    ry_layer(circ, np.random.default_rng(tail_seed))
    return circ


def _px_traffic(args):
    """(prep_seed, tail_seed) per job: px_share of each round's tenants
    replay the ONE shared prep (seed = lg_seed); the rest get a prep
    seed unique to (round, tenant) so they can never share — not even
    with their own earlier rounds."""
    n = args.px_tenants
    n_shared = max(1, int(round(n * args.px_share)))
    plan = []
    for r in range(args.px_rounds):
        for i in range(n):
            shared = i < n_shared
            prep = args.lg_seed if shared else 77_000 + 1000 * r + i
            plan.append((shared, prep, 88_000 + 1000 * r + i))
    return n_shared, plan


def measure_prefix(args) -> dict:
    """One prefix-bench arm in THIS process (the cache obeys
    QRACK_SERVE_PREFIX from the environment).  Untimed: session
    creation, circuit construction, a 2-tenant warmup that populates
    the cache (min_refs=2: miss, then miss+insert at the provably
    shared boundary), and the per-session CPU-oracle fidelity check.
    Timed: submit+result of every job on FRESH pristine sessions (only
    pristine sessions may split, so each round gets its own), closed by
    a devget read — dispatch acks on block_until_ready; only a
    device->host read is proof of completion."""
    tele.enable()
    tele.reset()
    sys.setswitchinterval(5e-4)
    n_shared, plan = _px_traffic(args)
    circs = [_px_circuit(args.px_width, args.px_layers, p, t)
             for _, p, t in plan]
    warm_circs = [_px_circuit(args.px_width, args.px_layers, args.lg_seed,
                              99_000 + i) for i in range(2)]
    # queue budget OFF: the whole timed pass queues at submit time and
    # the cache-off arm's full-circuit tail can sit queued for many
    # minutes on this 1-core VM — expiry would break the A/B symmetry
    svc = QrackService(engine_layers=args.layers,
                       max_depth=len(plan) + 64,
                       batch_window_ms=args.lg_window_ms,
                       max_batch=args.lg_batch,
                       queue_budget_ms=0.0, tick_s=0.05)
    cache_on = svc.prefix_cache is not None
    try:
        for i, c in enumerate(warm_circs):
            wsid = svc.create_session(args.px_width, seed=90_000 + i)
            svc.submit(wsid, c).result(3600)
        tele.reset()
        sids = [svc.create_session(args.px_width, seed=10_000 + j)
                for j in range(len(plan))]
        t0 = time.perf_counter()
        handles = [svc.submit(sid, c) for sid, c in zip(sids, circs)]
        for h in handles:
            h.result(3600)
        svc.call(sids[-1], _devget_read, mutates=False).result(3600)
        wall = time.perf_counter() - t0
        # untimed: CPU-oracle fidelity on round-0 sessions — the first
        # px_verify of each class (non-sharers AND the cache-served
        # sharers; 0 skips, for widths where the 2^w complex128 oracle
        # is minutes per session)
        verify = (list(range(n_shared, args.px_tenants))[:args.px_verify]
                  + list(range(min(args.px_verify, n_shared))))
        fids = []
        for j in verify:
            oracle = create_quantum_interface("cpu", args.px_width)
            circs[j].Run(oracle)
            ket = np.asarray(svc.get_state(sids[j]),
                             dtype=np.complex128).ravel()
            ref = np.asarray(oracle.GetQuantumState(),
                             dtype=np.complex128).ravel()
            fids.append(float(abs(np.vdot(ref, ket)) ** 2))
        pstats = svc.stats().get("prefix_cache")
    finally:
        svc.close()
    lats = [h.latency_s for h in handles if h.latency_s is not None]
    cnt = tele.snapshot()["counters"]
    hits = cnt.get("serve.prefix.hit", 0)
    misses = cnt.get("serve.prefix.miss", 0)
    completed = len(lats)
    return {
        "cache_on": bool(cache_on),
        "width": args.px_width, "tenants": args.px_tenants,
        "rounds": args.px_rounds, "shared_per_round": n_shared,
        "gates_full": len(circs[0].gates),
        "wall_s": round(wall, 6), "completed": completed,
        "throughput_jobs_per_s": round(completed / wall, 2) if wall else 0,
        "latency_p50_s": _pctl(lats, 50), "latency_p99_s": _pctl(lats, 99),
        "prefix_hits": hits, "prefix_misses": misses,
        "hit_rate": round(hits / (hits + misses), 3) if hits + misses
        else 0.0,
        "mean_hit_depth": round(cnt.get("serve.prefix.hit_depth", 0)
                                / hits, 1) if hits else 0.0,
        "verified_sessions": len(fids),
        "min_fidelity": round(min(fids), 9) if fids else None,
        "cache_stats": pstats,
    }


def _px_child_args(args) -> list:
    """Re-invoke THIS script as the cache-off A/B child: identical
    fixed-seed traffic, QRACK_SERVE_PREFIX=0 in the child env."""
    return [sys.executable, os.path.abspath(__file__), "--prefix",
            "--ab-child", "--json",
            "--layers", args.layers,
            "--px-width", str(args.px_width),
            "--px-tenants", str(args.px_tenants),
            "--px-rounds", str(args.px_rounds),
            "--px-layers", str(args.px_layers),
            "--px-share", str(args.px_share),
            "--px-verify", str(args.px_verify),
            "--lg-window-ms", str(args.lg_window_ms),
            "--lg-batch", str(args.lg_batch),
            "--lg-seed", str(args.lg_seed)]


def run_prefix(args) -> dict:
    """Cache-on run in-process, then the automatic cache-off A/B child
    (fresh process, QRACK_SERVE_PREFIX=0: byte-for-byte the pre-cache
    admission path) with the identical fixed-seed traffic.  Acceptance:
    >=3x jobs/s at equal per-session fidelity (both arms CPU-oracle
    verified against the SAME full circuits)."""
    os.environ.pop("QRACK_SERVE_PREFIX", None)  # on-arm: default-on
    res_on = measure_prefix(args)
    env = dict(os.environ, QRACK_SERVE_PREFIX="0")
    proc = subprocess.run(_px_child_args(args), capture_output=True,
                          text=True, env=env, timeout=7200)
    if proc.returncode != 0:
        raise RuntimeError("cache-off A/B child failed:\n"
                           + proc.stderr[-2000:])
    out = proc.stdout
    res_off = json.loads(out[out.index("{"):])
    speedup = (res_on["throughput_jobs_per_s"]
               / max(res_off["throughput_jobs_per_s"], 1e-9))
    # equal fidelity: both arms sit at the f32-vs-f64 accumulation
    # floor for ~O(400) gates; the cache must not move it
    fid_floor = 1.0 - 5e-4
    fid_ok = (res_on["min_fidelity"] is not None
              and res_off["min_fidelity"] is not None
              and res_on["min_fidelity"] >= fid_floor
              and res_off["min_fidelity"] >= fid_floor)
    res = {
        "mode": "prefix", "width": args.px_width,
        "tenants": args.px_tenants, "rounds": args.px_rounds,
        "share": args.px_share, "prep_layers": args.px_layers,
        "seed": args.lg_seed, "cache_on": res_on, "cache_off": res_off,
        "speedup_jobs_per_s": round(speedup, 3),
        "fidelity_ok": bool(fid_ok),
        "pass_3x": bool(speedup >= 3.0 and fid_ok
                        and res_on["prefix_hits"] > 0),
    }
    tele.gauge("serve.bench.prefix_speedup", res["speedup_jobs_per_s"])
    tele.gauge("serve.bench.prefix_jobs_per_s",
               res_on["throughput_jobs_per_s"])
    if res_on["latency_p99_s"] is not None:
        tele.gauge("serve.bench.prefix_p99_s", res_on["latency_p99_s"])
    return res


def run_mixed(args) -> dict:
    tele.enable()
    tele.reset()
    routed = _measure_mixed_phase(args, "auto")
    snap = tele.snapshot()
    route_jobs = {k[len("route.jobs."):]: v
                  for k, v in snap["counters"].items()
                  if k.startswith("route.jobs.")}
    tele.reset()
    forced = _measure_mixed_phase(args, "dense")

    def steady(ws):
        tail = ws[1:] or ws
        return float(np.median(tail)) if tail else None

    res = {
        "mode": "mixed",
        "jobs_per_class": args.jobs, "rounds": args.rounds,
        "clifford_width": args.clifford_width, "dense_width": args.width,
        "qaoa_width": args.qaoa_width, "wide_width": args.wide_width,
        "routed_jobs_by_stack": route_jobs,
        "misroutes": snap["counters"].get("route.misroutes", 0),
    }
    for cls in ("clifford", "dense", "qaoa"):
        r, f = steady(routed[cls]), steady(forced[cls])
        res[f"routed_{cls}_steady_wall_s"] = round(r, 6)
        res[f"forced_{cls}_steady_wall_s"] = round(f, 6)
        res[f"{cls}_jobs_per_s_routed"] = round(args.jobs / r, 2)
        res[f"{cls}_jobs_per_s_forced"] = round(args.jobs / f, 2)
        res[f"{cls}_speedup_vs_forced"] = round(f / r, 2)
    if routed["wide"]:
        w = steady(routed["wide"])
        res["wide_clifford_steady_wall_s"] = round(w, 6)
        res["wide_clifford_jobs_per_s"] = round(1.0 / w, 2)
        res["wide_clifford_forced"] = "unservable (width past dense cap)"
    for k in ("clifford_speedup_vs_forced", "dense_speedup_vs_forced",
              "qaoa_speedup_vs_forced"):
        tele.gauge(f"route.bench.{k}", res[k])
    res["pass_10x_clifford"] = bool(res["clifford_speedup_vs_forced"] >= 10.0)
    return res


def _measure_shallow_routed(args):
    """The routed phase of --shallow: wide brickwork tenants and dense
    QFT tenants share one routed service.  Per-class walls are timed
    class-phased like --mixed; every completion is devget-honest (for
    the lightcone-routed sessions the executor's sync read IS a local
    observable driven through a cone-width engine).  After the timed
    rounds a FRESH probe session submits one brickwork circuit and
    reads Prob(q) at sampled qubits through svc.call — those must match
    the analytic marginal sin^2(theta_q/2) exactly (the probe is fresh
    because the timed tenants stack one circuit per round, so only the
    first round's state has the single-circuit analytic form)."""
    from qrack_tpu.models.algorithms import (brickwork_qcircuit,
                                             brickwork_theta)

    walls = {"shallow": [], "dense": []}
    svc = QrackService(engine_layers="route",
                       max_depth=8 * args.shallow_jobs + 16,
                       batch_window_ms=args.window_ms,
                       max_batch=args.shallow_jobs,
                       queue_budget_ms=600_000.0)
    try:
        tenants = {
            "shallow": ([svc.create_session(args.shallow_width, seed=i)
                         for i in range(args.shallow_jobs)],
                        lambda: brickwork_qcircuit(args.shallow_width)),
            "dense": ([svc.create_session(args.shallow_dense_width,
                                          seed=100 + i)
                       for i in range(args.shallow_jobs)],
                      lambda: qft_qcircuit(args.shallow_dense_width)),
        }
        for _ in range(args.rounds):
            for cls, (sids, make) in tenants.items():
                circs = [make() for _ in sids]
                t0 = time.perf_counter()
                handles = [svc.submit(sid, c)
                           for sid, c in zip(sids, circs)]
                for h in handles:
                    h.result(timeout=600)
                walls[cls].append(time.perf_counter() - t0)

        # analytic-exactness probe: local expectations served through
        # the shared dispatch owner, checked against sin^2(theta_q/2)
        psid = svc.create_session(args.shallow_width, seed=999)
        svc.submit(psid, brickwork_qcircuit(args.shallow_width)).result(600)
        qs = sorted({0, 1, args.shallow_width // 2,
                     args.shallow_width - 1})
        probe = []
        for q in qs:
            served = svc.call(
                psid, lambda e, q=q: e.Prob(q), mutates=False).result(600)
            exact = math.sin(brickwork_theta(q) / 2.0) ** 2
            probe.append({"qubit": q, "served": served, "analytic": exact,
                          "abs_err": abs(served - exact)})
    finally:
        svc.close()
    return walls, probe


def _measure_shallow_refusal(args) -> dict:
    """The forced-dense baseline for the wide tenant: there isn't one.
    With QRACK_ROUTE=dense pinned, admission must refuse the SAME
    brickwork submission with the typed MisrouteError at submit(),
    while a dense-feasible w22 tenant still serves under the pin —
    the refusal is the width's, not the deployment's."""
    from qrack_tpu.models.algorithms import brickwork_qcircuit
    from qrack_tpu.route import MisrouteError

    prev = os.environ.get("QRACK_ROUTE")
    os.environ["QRACK_ROUTE"] = "dense"
    out = {"refused": False, "error": None, "dense_w22_served": False}
    try:
        svc = QrackService(engine_layers="route",
                           queue_budget_ms=600_000.0)
        try:
            wsid = svc.create_session(args.shallow_width, seed=0)
            try:
                svc.submit(wsid, brickwork_qcircuit(args.shallow_width))
            except MisrouteError as e:
                out["refused"] = True
                out["error"] = f"{type(e).__name__}: {e}"
            dsid = svc.create_session(args.shallow_dense_width, seed=1)
            h = svc.submit(dsid, qft_qcircuit(args.shallow_dense_width))
            h.result(timeout=600)
            out["dense_w22_served"] = True
        finally:
            svc.close()
    finally:
        if prev is None:
            os.environ.pop("QRACK_ROUTE", None)
        else:
            os.environ["QRACK_ROUTE"] = prev
    return out


def run_shallow(args) -> dict:
    tele.enable()
    tele.reset()
    routed, probe = _measure_shallow_routed(args)
    snap = tele.snapshot()
    cnt = snap["counters"]
    route_jobs = {k[len("route.jobs."):]: v
                  for k, v in cnt.items() if k.startswith("route.jobs.")}
    refusal = _measure_shallow_refusal(args)

    def steady(ws):
        tail = ws[1:] or ws
        return float(np.median(tail)) if tail else None

    max_err = max(p["abs_err"] for p in probe)
    res = {
        "mode": "shallow",
        "shallow_width": args.shallow_width,
        "dense_width": args.shallow_dense_width,
        "jobs_per_class": args.shallow_jobs, "rounds": args.rounds,
        "routed_jobs_by_stack": route_jobs,
        "lightcone_reads": cnt.get("lightcone.reads", 0),
        "probe": probe, "probe_max_abs_err": max_err,
        "forced_dense": refusal,
    }
    for cls in ("shallow", "dense"):
        w = steady(routed[cls])
        res[f"routed_{cls}_steady_wall_s"] = round(w, 6)
        res[f"{cls}_jobs_per_s"] = round(args.shallow_jobs / w, 2)
    tele.gauge("serve.bench.shallow_jobs_per_s", res["shallow_jobs_per_s"])
    tele.gauge("serve.bench.shallow_probe_err", max_err)
    res["pass_shallow"] = bool(
        route_jobs.get("lightcone", 0) >= args.shallow_jobs
        and max_err < 1e-6
        and refusal["refused"] and refusal["dense_w22_served"])
    return res


def measure_noisy_sequential(args) -> dict:
    """The sequential-trajectory fallback: the SAME trajectory engine,
    the SAME (key, trajectory_id) counters, but one trajectory per
    dispatch — what a caller gets without the batched axis.  Runs in
    the A/B child process.  Completion stays devget-honest (every
    ``run_trajectories`` call devgets its outputs in
    TrajectoryJob.step); trajectory 0 runs once untimed first so the
    single batch-1 trace lands outside the wall, mirroring the batched
    side's steady-round measurement."""
    from qrack_tpu.models.rcs import rcs_qcircuit
    from qrack_tpu.noise import NoiseModel, depolarizing, run_trajectories

    circuit = rcs_qcircuit(args.noisy_width, args.noisy_depth, seed=7)
    model = NoiseModel(default=depolarizing(args.noisy_lam))
    run_trajectories(circuit, model, 1, width=args.noisy_width, key=7,
                     trajectory_ids=[0])  # warm the batch-1 program
    t0 = time.perf_counter()
    for tid in range(args.noisy_traj):
        run_trajectories(circuit, model, 1, width=args.noisy_width,
                         key=7, trajectory_ids=[tid])
    wall = time.perf_counter() - t0
    return {"sequential": True, "wall_s": round(wall, 6),
            "traj_per_s": round(args.noisy_traj / wall, 3) if wall else 0}


def run_noisy(args) -> dict:
    """Noisy-trajectory tenant class (docs/NOISE.md): noisy-RCS circuits
    under a depolarizing model, B trajectories per submission, through
    QrackService.submit_trajectories — ONE vmapped dispatch per window,
    devget-honest completion inside TrajectoryJob.step.  Round 0 pays
    the single structure-keyed trace; steady rounds must be compile
    hits (the JSON records compile.noise counters so "exactly 1 trace"
    is checkable from the output).  An automatic child process then
    measures the sequential per-trajectory fallback at identical
    (key, trajectory_id) counters; the headline is the trajectories/s
    ratio (acceptance: >= 5x batched)."""
    from qrack_tpu.models.rcs import rcs_qcircuit
    from qrack_tpu.noise import NoiseModel, depolarizing

    tele.enable()
    tele.reset()
    model = NoiseModel(default=depolarizing(args.noisy_lam))
    svc = QrackService(engine_layers=args.layers,
                       queue_budget_ms=600_000.0)
    walls = []
    try:
        sid = svc.create_session(args.noisy_width, seed=0)
        for _ in range(args.noisy_rounds):
            # fresh circuit OBJECT per round (tenants build their own);
            # the trajectory ProgramCache keys on structure, not object
            circ = rcs_qcircuit(args.noisy_width, args.noisy_depth, seed=7)
            t0 = time.perf_counter()
            h = svc.submit_trajectories(sid, circ, model, args.noisy_traj,
                                        key=7)
            h.result(timeout=600)
            walls.append(time.perf_counter() - t0)
    finally:
        svc.close()
    snap = tele.snapshot()["counters"]
    steady = float(np.median(walls[1:] or walls))
    batched_rate = args.noisy_traj / steady if steady else 0.0

    # sequential A/B child: fresh process, CPU-pinned like _run_child's
    # cpu children (ROADMAP C1: wrong on the chip, where the parent
    # holds the device and the two sides then run on different backends)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--noisy",
         "--seq-child", "--json",
         "--noisy-width", str(args.noisy_width),
         "--noisy-traj", str(args.noisy_traj),
         "--noisy-depth", str(args.noisy_depth),
         "--noisy-lam", str(args.noisy_lam)],
        capture_output=True, text=True, env=env, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError("sequential A/B child failed:\n"
                           + proc.stderr[-2000:])
    out = proc.stdout
    seq = json.loads(out[out.index("{"):])
    speedup = batched_rate / max(seq["traj_per_s"], 1e-9)
    window_misses = snap.get("compile.noise.window.miss", 0)
    res = {
        "mode": "noisy",
        "width": args.noisy_width, "trajectories": args.noisy_traj,
        "depth": args.noisy_depth, "lam": args.noisy_lam,
        "rounds": args.noisy_rounds, "layers": args.layers,
        "batched_cold_wall_s": round(walls[0], 6),
        "batched_steady_wall_s": round(steady, 6),
        "traj_per_s_batched": round(batched_rate, 3),
        "sequential_wall_s": seq["wall_s"],
        "traj_per_s_sequential": seq["traj_per_s"],
        "speedup_trajectories": round(speedup, 3),
        "compile_noise_misses": snap.get("compile.noise.miss", 0),
        "compile_noise_hits": snap.get("compile.noise.hit", 0),
        "compile_noise_window_misses": window_misses,
        "chunks": snap.get("noise.traj.chunks", 0),
        # all rounds, all windows, ONE trace of the vmapped program
        "single_trace": bool(window_misses == 1),
        "pass_5x": bool(speedup >= 5.0),
    }
    tele.gauge("serve.bench.noisy_traj_per_s", res["traj_per_s_batched"])
    tele.gauge("serve.bench.noisy_speedup", res["speedup_trajectories"])
    return res


def run(args) -> dict:
    tele.enable()
    tele.reset()
    kw = {}
    lib_cold = measure_library_cold(args.width, args.jobs, args.layers, **kw)
    lib_warm = measure_library_warm(args.width, args.jobs, args.layers, **kw)
    walls, handles = measure_serve(args.width, args.jobs, args.rounds,
                                   args.layers, args.window_ms, **kw)
    serve_cold = walls[0]
    steady = walls[1:] or walls
    serve_steady = float(np.median(steady))

    q_waits = [h.queue_wait_s for h in handles if h.queue_wait_s is not None]
    execs = [h.execute_s for h in handles if h.execute_s is not None]
    lats = [h.latency_s for h in handles if h.latency_s is not None]
    snap = tele.snapshot()
    dispatches = snap["counters"].get("serve.batch.dispatches", 0)
    batched = snap["counters"].get("serve.batch.jobs", 0)

    res = {
        "width": args.width, "jobs": args.jobs, "rounds": args.rounds,
        "layers": args.layers, "batch_window_ms": args.window_ms,
        "lib_cold_wall_s": round(lib_cold, 6),
        "lib_warm_wall_s": round(lib_warm, 6),
        "serve_cold_wall_s": round(serve_cold, 6),
        "serve_steady_wall_s": round(serve_steady, 6),
        "ratio_cold_vs_lib": round(serve_cold / lib_cold, 4),
        "ratio_steady_vs_lib": round(serve_steady / lib_cold, 4),
        "ratio_steady_vs_warm_lib": round(serve_steady / lib_warm, 4),
        "jobs_per_s_steady": round(args.jobs / serve_steady, 2),
        "queue_wait_p50_s": _pctl(q_waits, 50),
        "queue_wait_p99_s": _pctl(q_waits, 99),
        "execute_p50_s": _pctl(execs, 50),
        "execute_p99_s": _pctl(execs, 99),
        "latency_p50_s": _pctl(lats, 50),
        "latency_p99_s": _pctl(lats, 99),
        "batch_occupancy": round(batched / dispatches, 3) if dispatches else 0,
        "compile_misses": snap["counters"].get("compile.serve_batch.miss", 0),
        "compile_hits": snap["counters"].get("compile.serve_batch.hit", 0),
    }
    # into serve.* telemetry so the atexit JSONL (QRACK_TPU_TELEMETRY_OUT)
    # and scripts/telemetry_report.py carry the bench verdict
    tele.gauge("serve.bench.jobs_per_s", res["jobs_per_s_steady"])
    tele.gauge("serve.bench.ratio_steady_vs_lib", res["ratio_steady_vs_lib"])
    for key in ("queue_wait_p50_s", "queue_wait_p99_s", "latency_p50_s",
                "latency_p99_s", "execute_p50_s", "execute_p99_s"):
        if res[key] is not None:
            tele.gauge(f"serve.bench.{key}", res[key])
    res["pass_0p6x"] = bool(res["ratio_cold_vs_lib"] < 0.6
                            and res["ratio_steady_vs_lib"] < 0.6)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--jobs", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=4,
                    help="serve rounds; round 0 is the cold round")
    ap.add_argument("--layers", default="tpu",
                    help="engine stack (default tpu = plane-holding dense "
                         "engine on whatever backend jax selects)")
    ap.add_argument("--window-ms", type=float, default=50.0)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--mixed", action="store_true",
                    help="mixed-traffic routing bench: Clifford + dense "
                         "QV + shallow-QAOA tenants in ONE routed "
                         "service, vs the same traffic QRACK_ROUTE="
                         "dense-forced (docs/ROUTING.md)")
    ap.add_argument("--clifford-width", type=int, default=20,
                    help="Clifford tenant width — dense-FEASIBLE so the "
                         "forced baseline exists (default 20)")
    ap.add_argument("--qaoa-width", type=int, default=12)
    ap.add_argument("--wide-width", type=int, default=100,
                    help="extra routed-only Clifford tenant width (no "
                         "forced baseline possible; 0 disables)")
    ap.add_argument("--shallow", action="store_true",
                    help="lightcone tenant bench: w50+ depth-4 local-"
                         "observable brickwork tenants next to dense "
                         "w22 QFT tenants in ONE routed service, with "
                         "an analytic-exactness probe and the forced-"
                         "dense MisrouteError refusal baseline "
                         "(docs/LIGHTCONE.md)")
    ap.add_argument("--shallow-width", type=int, default=50,
                    help="wide tenant width — past every state-holding "
                         "rung, so only the lightcone rung serves it "
                         "(default 50)")
    ap.add_argument("--shallow-jobs", type=int, default=4,
                    help="sessions per class in --shallow (default 4)")
    ap.add_argument("--shallow-dense-width", type=int, default=22,
                    help="dense-feasible neighbor tenant width "
                         "(default 22)")
    ap.add_argument("--noisy", action="store_true",
                    help="noisy-trajectory tenant class: noisy-RCS "
                         "under a depolarizing model, B trajectories "
                         "per submission via submit_trajectories, with "
                         "an automatic sequential per-trajectory A/B "
                         "child (docs/NOISE.md, docs/SERVING.md)")
    ap.add_argument("--seq-child", action="store_true",
                    help=argparse.SUPPRESS)  # internal: sequential A/B
    ap.add_argument("--noisy-width", type=int, default=14)
    ap.add_argument("--noisy-traj", type=int, default=256,
                    help="trajectories per batch (default 256)")
    ap.add_argument("--noisy-depth", type=int, default=4)
    ap.add_argument("--noisy-lam", type=float, default=0.02,
                    help="depolarizing parameter")
    ap.add_argument("--noisy-rounds", type=int, default=3,
                    help="batched rounds; round 0 pays the one trace")
    ap.add_argument("--loadgen", action="store_true",
                    help="open/closed-loop load generator over O(1000) "
                         "tenants with an automatic QRACK_SERVE_"
                         "PIPELINE=0 A/B child (docs/SERVING.md)")
    ap.add_argument("--ab-child", action="store_true",
                    help=argparse.SUPPRESS)  # internal: one run, JSON out
    ap.add_argument("--lg-pipeline", type=int, default=1,
                    help=argparse.SUPPRESS)  # internal: child forces 0
    ap.add_argument("--tenants", type=int, default=1000)
    ap.add_argument("--lg-requests", type=int, default=2000,
                    help="timed-pass requests (default 2000)")
    ap.add_argument("--lg-warmup", type=int, default=400,
                    help="warmup-pass requests, untimed (default 400)")
    ap.add_argument("--lg-mode", choices=("closed", "open"),
                    default="closed",
                    help="closed: --lg-concurrency clients resubmit on "
                         "completion; open: Poisson --lg-rate arrivals")
    ap.add_argument("--lg-concurrency", type=int, default=40,
                    help="closed-loop in-flight clients; default keeps "
                         "per-class demand (~concurrency/4) in the "
                         "16-lane bucket, where batch compute is "
                         "comparable to the window and partial batches "
                         "leave the serial mode paying it in full")
    ap.add_argument("--lg-rate", type=float, default=400.0,
                    help="open-loop offered arrivals/s")
    ap.add_argument("--lg-window-ms", type=float, default=30.0,
                    help="batch window for the loadgen service — sized "
                         "near the batched execution wall so overlap "
                         "is what the A/B resolves")
    ap.add_argument("--lg-batch", type=int, default=32,
                    help="service max_batch — sized ABOVE per-class "
                         "concurrent demand so batches stay partial "
                         "and the serial mode pays the full window")
    ap.add_argument("--lg-seed", type=int, default=42)
    ap.add_argument("--prefix", action="store_true",
                    help="prefix-sharing COW ket-cache bench: tenants "
                         "replaying one shared state-prep vs unique-"
                         "prep tenants, with an automatic QRACK_SERVE_"
                         "PREFIX=0 A/B child (docs/SERVING.md)")
    ap.add_argument("--px-width", type=int, default=18)
    ap.add_argument("--px-tenants", type=int, default=20,
                    help="fresh sessions per round (default 20)")
    ap.add_argument("--px-rounds", type=int, default=3,
                    help="timed rounds; every round uses fresh "
                         "pristine sessions (default 3)")
    ap.add_argument("--px-layers", type=int, default=8,
                    help="state-prep depth: H wall + N x (CX ring + "
                         "RY layer) (default 8)")
    ap.add_argument("--px-share", type=float, default=0.8,
                    help="fraction of tenants replaying the shared "
                         "prep (default 0.8)")
    ap.add_argument("--px-verify", type=int, default=4,
                    help="sessions CPU-oracle verified per class per "
                         "arm; 0 skips the oracle (default 4)")
    ap.add_argument("--px-solo", action="store_true",
                    help=argparse.SUPPRESS)  # internal: one-arm stage
    args = ap.parse_args(argv)

    if args.seq_child:
        print(json.dumps(measure_noisy_sequential(args), sort_keys=True))
        return 0
    if args.noisy:
        res = run_noisy(args)
        if args.json:
            print(json.dumps(res, indent=1, sort_keys=True))
        else:
            print(f"noisy trajectories w={res['width']} "
                  f"B={res['trajectories']} depth={res['depth']} "
                  f"lam={res['lam']} (devget-honest)")
            print(f"  batched : cold {res['batched_cold_wall_s'] * 1e3:9.1f}"
                  f" ms, steady {res['batched_steady_wall_s'] * 1e3:9.1f} ms"
                  f" -> {res['traj_per_s_batched']:9.1f} traj/s")
            print(f"  sequential fallback: {res['sequential_wall_s'] * 1e3:9.1f}"
                  f" ms -> {res['traj_per_s_sequential']:9.1f} traj/s")
            print(f"  speedup {res['speedup_trajectories']:.2f}x | "
                  f"compile miss={res['compile_noise_misses']:.0f} "
                  f"hit={res['compile_noise_hits']:.0f} "
                  f"traces={res['compile_noise_window_misses']:.0f} "
                  f"(single_trace={res['single_trace']})")
            print(f"  acceptance (>=5x trajectories/s): "
                  f"{'PASS' if res['pass_5x'] else 'FAIL'}")
        return 0 if res["pass_5x"] else 1
    if args.prefix:
        if args.ab_child:
            print(json.dumps(measure_prefix(args), sort_keys=True))
            return 0
        if args.px_solo:
            # single-arm campaign stage: ONE jax process, cache state
            # taken from QRACK_SERVE_PREFIX (the tpu_campaign.sh pair
            # runs this twice, on then off — docs/TPU_EVIDENCE.md)
            r = measure_prefix(args)
            suffix = "" if r["cache_on"] else "_off"
            ok = (r["completed"] == args.px_tenants * args.px_rounds
                  and (not r["cache_on"] or r["prefix_hits"] > 0)
                  and (r["min_fidelity"] is None
                       or r["min_fidelity"] >= 1.0 - 5e-4))
            print(json.dumps({
                "metric": f"prefix_cache_w{args.px_width}_serve{suffix}",
                "value": r["throughput_jobs_per_s"], "unit": "jobs/s",
                "completed": r["completed"],
                "latency_p99_s": r["latency_p99_s"],
                "hit_rate": r["hit_rate"],
                "mean_hit_depth": r["mean_hit_depth"],
                "min_fidelity": r["min_fidelity"]}))
            if ok:
                print("PREFIX_SERVE_SOLO_OK")
            return 0 if ok else 1
        res = run_prefix(args)
        if args.json:
            print(json.dumps(res, indent=1, sort_keys=True))
        else:
            on, off = res["cache_on"], res["cache_off"]
            print(f"prefix cache w={res['width']}: {res['tenants']} "
                  f"tenants x {res['rounds']} rounds, share "
                  f"{res['share']:.0%}, prep {res['prep_layers']} layers "
                  f"({on['gates_full']} gates full) (devget-honest)")
            for label, r in (("cache on ", on), ("cache off", off)):
                fid = (f"{r['min_fidelity']:.7f}"
                       if r["min_fidelity"] is not None else "n/a")
                print(f"  {label}: {r['throughput_jobs_per_s']:8.1f} "
                      f"jobs/s | p50 {r['latency_p50_s'] * 1e3:7.1f} ms "
                      f"p99 {r['latency_p99_s'] * 1e3:7.1f} ms | "
                      f"min fidelity {fid} "
                      f"({r['verified_sessions']} oracled)")
            print(f"  hits {on['prefix_hits']:.0f} "
                  f"(rate {on['hit_rate']:.2f}, mean depth "
                  f"{on['mean_hit_depth']:.1f} gates) | "
                  f"misses {on['prefix_misses']:.0f}")
            print(f"  speedup {res['speedup_jobs_per_s']:.2f}x, fidelity "
                  f"{'equal' if res['fidelity_ok'] else 'DEGRADED'}")
            print(f"  acceptance (>=3x jobs/s, oracle fidelity intact): "
                  f"{'PASS' if res['pass_3x'] else 'FAIL'}")
        # campaign evidence: one flat metric line + the OK marker
        print(json.dumps({
            "metric": f"prefix_cache_w{res['width']}_serve",
            "value": res["cache_on"]["throughput_jobs_per_s"],
            "unit": "jobs/s",
            "speedup_vs_cache_off": res["speedup_jobs_per_s"],
            "cache_off_jobs_per_s":
                res["cache_off"]["throughput_jobs_per_s"],
            "mean_hit_depth": res["cache_on"]["mean_hit_depth"],
            "hit_rate": res["cache_on"]["hit_rate"],
            "min_fidelity": res["cache_on"]["min_fidelity"]}))
        if res["pass_3x"]:
            print("PREFIX_SERVE_OK")
        return 0 if res["pass_3x"] else 1

    if args.ab_child:
        res = measure_loadgen(args, pipeline=args.lg_pipeline != 0)
        print(json.dumps(res, sort_keys=True))
        return 0
    if args.loadgen:
        res = run_loadgen(args)
        if args.json:
            print(json.dumps(res, indent=1, sort_keys=True))
        else:
            p, s = res["pipelined"], res["serial"]
            print(f"loadgen {res['lg_mode']} loop: {res['tenants']} tenants"
                  f" x {res['requests']} requests, classes "
                  f"{'/'.join(res['classes'])}, window "
                  f"{res['window_ms']}ms, max_batch {res['max_batch']}"
                  + (f", concurrency {res['concurrency']}"
                     if res["lg_mode"] == "closed"
                     else f", rate {res['rate']}/s"))
            for label, r in (("pipelined", p), ("serial   ", s)):
                print(f"  {label}: {r['throughput_jobs_per_s']:8.1f} jobs/s"
                      f" | p50 {r['latency_p50_s'] * 1e3:7.1f} ms"
                      f" p99 {r['latency_p99_s'] * 1e3:7.1f} ms"
                      f" | occupancy {r['batch_occupancy']:5.2f}"
                      f" | overlap {r['overlap_ratio']:.2f}"
                      f" join {r['join_rate']:.2f}"
                      f" | failed {r['failed']}")
            print(f"  speedup {res['speedup_throughput']:.2f}x, p99 "
                  f"{'no worse' if res['p99_no_worse'] else 'WORSE'}")
            print(f"  acceptance (>=1.5x, p99 no worse): "
                  f"{'PASS' if res['pass_1p5x'] else 'FAIL'}")
        return 0 if res["pass_1p5x"] else 1

    if args.shallow:
        res = run_shallow(args)
        if args.json:
            print(json.dumps(res, indent=1, sort_keys=True))
        else:
            print(f"shallow traffic x{res['jobs_per_class']}/class, "
                  f"{res['rounds']} rounds (devget-honest; steady = "
                  f"median of post-cold rounds)")
            print(f"  shallow w{res['shallow_width']:<3d} routed "
                  f"{res['routed_shallow_steady_wall_s'] * 1e3:9.1f} ms "
                  f"({res['shallow_jobs_per_s']:>8.2f} jobs/s) | "
                  f"forced dense: "
                  f"{'refused (' + res['forced_dense']['error'] + ')' if res['forced_dense']['refused'] else 'NOT REFUSED'}")
            print(f"  dense   w{res['dense_width']:<3d} routed "
                  f"{res['routed_dense_steady_wall_s'] * 1e3:9.1f} ms "
                  f"({res['dense_jobs_per_s']:>8.2f} jobs/s) | "
                  f"forced dense: "
                  f"{'served' if res['forced_dense']['dense_w22_served'] else 'FAILED'}")
            print(f"  probe max |served - sin^2(theta/2)| = "
                  f"{res['probe_max_abs_err']:.2e} over qubits "
                  f"{[p['qubit'] for p in res['probe']]}")
            print(f"  routed jobs by stack: {res['routed_jobs_by_stack']} "
                  f"| lightcone reads: {res['lightcone_reads']:.0f}")
            print(f"  acceptance (lightcone-routed, analytic-exact, "
                  f"forced-dense refused): "
                  f"{'PASS' if res['pass_shallow'] else 'FAIL'}")
        # campaign evidence: one flat metric line + the OK marker
        # (one ^{"metric" line and an _OK$ marker)
        print(json.dumps({
            "metric": f"lightcone_w{res['shallow_width']}_serve",
            "value": res["shallow_jobs_per_s"], "unit": "jobs/s",
            "probe_max_abs_err": res["probe_max_abs_err"],
            "forced_dense_refused": res["forced_dense"]["refused"],
            "routed_jobs_by_stack": res["routed_jobs_by_stack"]}))
        if res["pass_shallow"]:
            print("LIGHTCONE_SHALLOW_OK")
        return 0 if res["pass_shallow"] else 1

    if args.mixed:
        res = run_mixed(args)
        if args.json:
            print(json.dumps(res, indent=1, sort_keys=True))
        else:
            print(f"mixed traffic x{args.jobs}/class, {args.rounds} rounds "
                  f"(devget-honest; steady = median of post-cold rounds)")
            for cls, w in (("clifford", args.clifford_width),
                           ("dense", args.width),
                           ("qaoa", args.qaoa_width)):
                print(f"  {cls:<9s} w{w:<3d} routed "
                      f"{res[f'routed_{cls}_steady_wall_s'] * 1e3:9.1f} ms "
                      f"({res[f'{cls}_jobs_per_s_routed']:>8.2f} jobs/s) | "
                      f"forced dense "
                      f"{res[f'forced_{cls}_steady_wall_s'] * 1e3:9.1f} ms "
                      f"-> {res[f'{cls}_speedup_vs_forced']:.2f}x")
            if "wide_clifford_steady_wall_s" in res:
                print(f"  clifford  w{args.wide_width:<3d} routed "
                      f"{res['wide_clifford_steady_wall_s'] * 1e3:9.1f} ms "
                      f"| forced dense: {res['wide_clifford_forced']}")
            print(f"  routed jobs by stack: {res['routed_jobs_by_stack']} "
                  f"(misroutes={res['misroutes']:.0f})")
            print(f"  acceptance (clifford >=10x vs forced): "
                  f"{'PASS' if res['pass_10x_clifford'] else 'FAIL'}")
        return 0 if res["pass_10x_clifford"] else 1

    res = run(args)
    if args.json:
        print(json.dumps(res, indent=1, sort_keys=True))
    else:
        print(f"w={res['width']} jobs={res['jobs']} layers={res['layers']} "
              f"(devget-honest)")
        print(f"  library, fresh caller x{res['jobs']} (each pays its own "
              f"compile): {res['lib_cold_wall_s'] * 1e3:9.1f} ms")
        print(f"  library, warm shared program x{res['jobs']}:"
              f"              {res['lib_warm_wall_s'] * 1e3:9.1f} ms")
        print(f"  serve cold round   (incl. one shared batch compile): "
              f"{res['serve_cold_wall_s'] * 1e3:9.1f} ms")
        print(f"  serve steady round (median of {res['rounds'] - 1}):"
              f"           {res['serve_steady_wall_s'] * 1e3:9.1f} ms")
        print(f"  ratio vs library: cold {res['ratio_cold_vs_lib']:.3f}x, "
              f"steady {res['ratio_steady_vs_lib']:.3f}x "
              f"(vs warm-lib {res['ratio_steady_vs_warm_lib']:.3f}x)")
        print(f"  throughput {res['jobs_per_s_steady']} jobs/s | "
              f"queue p50/p99 {res['queue_wait_p50_s'] * 1e3:.1f}/"
              f"{res['queue_wait_p99_s'] * 1e3:.1f} ms | "
              f"latency p50/p99 {res['latency_p50_s'] * 1e3:.1f}/"
              f"{res['latency_p99_s'] * 1e3:.1f} ms")
        print(f"  batch occupancy {res['batch_occupancy']} "
              f"(compile miss={res['compile_misses']:.0f} "
              f"hit={res['compile_hits']:.0f})")
        print(f"  acceptance (<0.6x library): "
              f"{'PASS' if res['pass_0p6x'] else 'FAIL'}")
    return 0 if res["pass_0p6x"] else 1


if __name__ == "__main__":
    sys.exit(main())
