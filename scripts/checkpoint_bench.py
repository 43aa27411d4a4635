"""Checkpoint bench: spill/restore walls + cold vs warm serve start.

Two measurements (docs/CHECKPOINT.md):

* **Spill / restore walls** — save_state / load_state(into=...) of a
  dense engine at several widths, devget-honest on the restore side (a
  real device->host read after the planes land, because
  block_until_ready on a remote-attached device acks dispatch, not completion).

* **Warm-start time-to-first-result** — the acceptance measurement.
  The same 8-tenant QFT serve workload runs in two FRESH subprocesses
  sharing one checkpoint dir: the cold child populates the persistent
  XLA compile cache + program manifest, the warm child starts with
  prewarm=True and replays them.  TTFR is the first-request latency —
  submit of the first batch to its first completed handle — because
  that is the cost warm start exists to move OFF the request path: the
  cold service traces + compiles the batch program under the first
  tenant's job, the warm one did it before taking traffic (and the
  persistent XLA cache made the prewarm compile itself a disk read).
  The full process-entry walls (imports, service construction, prewarm)
  are reported alongside so the shifted cost stays visible.
  Acceptance: warm TTFR at least --min-speedup (default 2.0) times
  faster than cold.

Usage:
    python scripts/checkpoint_bench.py [--width 16] [--jobs 8]
                                       [--min-speedup 2.0] [--json]
    (self-invokes with --child; exit 0 when the speedup bar holds)
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from qrack_tpu.utils.platform import pin_host_cpu  # noqa: E402

pin_host_cpu(8)

import numpy as np  # noqa: E402


def _devget_read(engine) -> None:
    import jax

    from qrack_tpu.serve.session import planes_engine

    core = planes_engine(engine)
    if core is not None:
        np.asarray(jax.device_get(core.device_planes[:1, :1]))
    else:
        engine.Prob(0)


# -- child: one fresh serving process, TTFR from process entry ----------


def child_main(args) -> int:
    t0 = time.perf_counter()  # timing starts at child entry: restart cost
    from qrack_tpu.models.qft import qft_qcircuit
    from qrack_tpu.serve import QrackService

    svc = QrackService(engine_layers="tpu", checkpoint_dir=args.ckdir,
                       prewarm=args.warm, max_depth=4 * args.jobs + 8,
                       batch_window_ms=1000.0, max_batch=args.jobs,
                       queue_budget_ms=600_000.0)
    try:
        sids = [svc.create_session(args.width, seed=i)
                for i in range(args.jobs)]
        # built once, outside the timed window: submits must all land
        # inside the batch window so every run dispatches ONE batch of
        # --jobs (per-submit circuit construction + WAL fsync stagger
        # arrivals; the window closes early once the batch fills, so a
        # wide window costs nothing here)
        circ = qft_qcircuit(args.width)
        t_ready = time.perf_counter()  # service up, prewarm (if any) done
        handles = [svc.submit(sid, circ) for sid in sids]
        first = None
        for h in handles:
            h.result(timeout=600)
            if first is None:
                first = time.perf_counter()
        t_all = time.perf_counter()
    finally:
        from qrack_tpu.serve import batcher as _batcher
        programs = _batcher.stats()
        svc.close()
    print(json.dumps({
        "ttfr_s": round(first - t_ready, 6),
        "setup_s": round(t_ready - t0, 6),
        "entry_to_first_s": round(first - t0, 6),
        "round_wall_s": round(t_all - t_ready, 6),
        "programs": programs,
    }))
    return 0


# -- parent ------------------------------------------------------------


def _run_child(args, ckdir: str, warm: bool) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--ckdir", ckdir, "--width", str(args.width),
           "--jobs", str(args.jobs)]
    if warm:
        cmd.append("--warm")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-4000:])
        raise RuntimeError(f"child (warm={warm}) failed rc={r.returncode}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def measure_spill_restore(widths) -> list:
    from qrack_tpu.checkpoint import load_state, save_state
    from qrack_tpu.factory import create_quantum_interface
    from qrack_tpu.utils.rng import QrackRandom

    out = []
    for w in widths:
        eng = create_quantum_interface("tpu", w, rng=QrackRandom(11))
        # a Haar-random ket: incompressible, so the npz-deflate walls
        # below measure real throughput (a |0..0> QFT state deflates to
        # almost nothing and would flatter the MB/s numbers)
        rng = np.random.Generator(np.random.PCG64(11))
        ket = rng.standard_normal(1 << w) + 1j * rng.standard_normal(1 << w)
        eng.SetQuantumState(ket / np.linalg.norm(ket))
        _devget_read(eng)
        path = os.path.join(tempfile.mkdtemp(prefix="qckpt-bench-"),
                            f"w{w}.qckpt")
        t0 = time.perf_counter()
        save_state(eng, path)
        t_save = time.perf_counter() - t0
        fresh = create_quantum_interface("tpu", w, rng=QrackRandom(12))
        t0 = time.perf_counter()
        restored = load_state(path, into=fresh)
        _devget_read(restored)  # honest: planes are ON device again
        t_restore = time.perf_counter() - t0
        nbytes = os.path.getsize(path)
        out.append({"width": w, "bytes": nbytes,
                    "save_s": round(t_save, 6),
                    "restore_s": round(t_restore, 6),
                    "save_mb_s": round(nbytes / t_save / 1e6, 1),
                    "restore_mb_s": round(nbytes / t_restore / 1e6, 1)})
        shutil.rmtree(os.path.dirname(path), ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--jobs", type=int, default=8)
    ap.add_argument("--spill-widths", default="12,16,18",
                    help="comma-separated widths for the wall table")
    ap.add_argument("--min-speedup", type=float, default=2.0)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--ckdir", help=argparse.SUPPRESS)
    ap.add_argument("--warm", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child:
        return child_main(args)

    walls = measure_spill_restore(
        [int(w) for w in args.spill_widths.split(",") if w])

    ckdir = tempfile.mkdtemp(prefix="qckpt-warmstart-")
    try:
        cold = _run_child(args, ckdir, warm=False)
        warm = _run_child(args, ckdir, warm=True)
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
    speedup = cold["ttfr_s"] / warm["ttfr_s"] if warm["ttfr_s"] > 0 else 0.0
    res = {
        "width": args.width, "jobs": args.jobs,
        "spill_restore": walls,
        "cold": cold, "warm": warm,
        "ttfr_speedup": round(speedup, 3),
        "min_speedup": args.min_speedup,
        "pass": bool(speedup >= args.min_speedup),
    }
    # mirror the verdict into telemetry so the atexit JSONL
    # (QRACK_TPU_TELEMETRY_OUT) and scripts/telemetry_report.py carry it
    from qrack_tpu import telemetry as tele
    tele.gauge("checkpoint.bench.ttfr_speedup", res["ttfr_speedup"])
    tele.gauge("checkpoint.bench.cold_ttfr_s", cold["ttfr_s"])
    tele.gauge("checkpoint.bench.warm_ttfr_s", warm["ttfr_s"])
    for row in walls:
        tele.gauge(f"checkpoint.bench.save_mb_s.w{row['width']}",
                   row["save_mb_s"])
        tele.gauge(f"checkpoint.bench.restore_mb_s.w{row['width']}",
                   row["restore_mb_s"])
    if args.json:
        print(json.dumps(res, indent=1, sort_keys=True))
    else:
        print("== spill/restore walls (devget-honest restore) ==")
        for row in walls:
            print(f"  w{row['width']:<3d} {row['bytes'] / 1e6:8.2f} MB   "
                  f"save {row['save_s'] * 1e3:8.1f} ms "
                  f"({row['save_mb_s']:7.1f} MB/s)   "
                  f"restore {row['restore_s'] * 1e3:8.1f} ms "
                  f"({row['restore_mb_s']:7.1f} MB/s)")
        print(f"== warm start: {args.jobs}-tenant w{args.width} QFT, fresh "
              f"process each ==")
        for name, c in (("cold", cold), ("warm", warm)):
            print(f"  {name} TTFR {c['ttfr_s'] * 1e3:9.1f} ms  "
                  f"(setup {c['setup_s'] * 1e3:.1f} ms, "
                  f"entry->first {c['entry_to_first_s'] * 1e3:.1f} ms, "
                  f"round {c['round_wall_s'] * 1e3:.1f} ms)")
        print(f"  speedup {speedup:.2f}x  (bar >= {args.min_speedup:.1f}x): "
              f"{'PASS' if res['pass'] else 'FAIL'}")
    return 0 if res["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
