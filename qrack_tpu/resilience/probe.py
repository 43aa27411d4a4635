"""TPU health probe.

Probe logic exists ONCE, here.  Two halves:

* **child** (:func:`probe_payload` / ``--child``): imports jax, lists
  devices, runs a small elementwise op and a 512x512 matmul, prints
  ``PROBE_OK``.  This is the half that can hang forever on a hung
  backend, so it runs in a subprocess, never in the caller.
* **parent** (:func:`run_probe` / ``--watchdog``): spawns the child
  (this file, by path — the child never imports the qrack_tpu package,
  keeping its startup minimal and its hang surface exactly the backend
  init being probed), waits ``timeout_s``, then escalates SIGTERM →
  (``term_grace_s``) → SIGKILL → bounded wait.  SIGTERM first: a
  SIGKILLed client can leave the device claimed, and the next
  process then fails to take it.

This module is deliberately stdlib-only at import time so the child
(`python resilience/probe.py --child`) starts in milliseconds and a
watchdog parent can always import it.

The parent half records `resilience.probe.ok/fail` counters and a
`resilience.probe` span when qrack_tpu telemetry is importable and
enabled (best-effort: the probe itself must never depend on it).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import List, Optional

PROBE_OK_SENTINEL = "PROBE_OK"

DEFAULT_TIMEOUT_S = 120.0
DEFAULT_TERM_GRACE_S = 15.0
_KILL_WAIT_S = 10.0  # bounded wait after SIGKILL; never block forever


# ---------------------------------------------------------------------------
# child half: the hang-prone payload
# ---------------------------------------------------------------------------

def probe_payload(matmul_dim: int = 512) -> None:
    """Backend init + tiny compute + real matmul, stdout line-buffered.
    Run ONLY under a watchdog (run_probe or an external `timeout`)."""
    t0 = time.time()
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    print(f"PROBE devices={devs}", flush=True)
    x = jnp.arange(16, dtype=jnp.float32)
    y = (x * 2.0 + 1.0).block_until_ready()
    print(f"PROBE small_op_ok sum={float(y.sum())} t={time.time()-t0:.2f}s",
          flush=True)
    a = jnp.ones((matmul_dim, matmul_dim), dtype=jnp.float32)
    b = (a @ a).block_until_ready()
    print(f"PROBE matmul_ok val={float(b[0,0])} t={time.time()-t0:.2f}s",
          flush=True)
    print(PROBE_OK_SENTINEL, flush=True)


def child_main() -> int:
    probe_payload()
    return 0


# ---------------------------------------------------------------------------
# parent half: SIGTERM-first subprocess watchdog
# ---------------------------------------------------------------------------

@dataclass
class ProbeResult:
    ok: bool
    returncode: Optional[int]
    duration_s: float
    timed_out: bool = False
    killed: bool = False          # needed SIGKILL after the TERM grace
    output: str = ""
    command: List[str] = field(default_factory=list)

    def __bool__(self) -> bool:
        return self.ok


def _tele():
    """Best-effort telemetry handle; None when unavailable (standalone
    execution, or qrack_tpu not importable)."""
    try:
        from qrack_tpu import telemetry

        return telemetry if telemetry._ENABLED else None
    except Exception:
        return None


def reap_child(proc, term_grace_s: float = DEFAULT_TERM_GRACE_S,
               kill_wait_s: float = _KILL_WAIT_S,
               wait=None) -> "ReapResult":
    """SIGTERM-first child reaping with a bounded SIGKILL escalation.

    The one escalation ladder every parent in the tree uses (probe
    watchdog here; the fleet supervisor for worker shutdown): SIGTERM →
    wait ``term_grace_s`` → SIGKILL → wait ``kill_wait_s`` → abandon.
    A child that ignores SIGTERM therefore cannot leak past its
    watchdog, and an unkillable (D-state) child never blocks the
    caller unboundedly.

    `wait` overrides how each bounded wait happens — it is called as
    ``wait(timeout_s)`` and must raise :class:`subprocess.TimeoutExpired`
    on expiry (run_probe passes a ``communicate`` closure so pipe
    output keeps draining during the grace windows); default is
    ``proc.wait``.  Never raises."""
    if wait is None:
        wait = proc.wait
    killed = abandoned = False
    try:
        proc.terminate()  # SIGTERM first: avoid server-side half-claims
    except OSError:
        pass  # already gone
    try:
        wait(term_grace_s)
    except subprocess.TimeoutExpired:
        killed = True
        try:
            proc.kill()
        except OSError:
            pass
        try:
            wait(kill_wait_s)
        except subprocess.TimeoutExpired:
            abandoned = True  # unkillable child; abandon, stay bounded
    return ReapResult(killed=killed, abandoned=abandoned,
                      returncode=proc.returncode)


@dataclass
class ReapResult:
    killed: bool                   # needed SIGKILL after the TERM grace
    abandoned: bool                # survived even SIGKILL's bounded wait
    returncode: Optional[int]


def run_probe(timeout_s: float = DEFAULT_TIMEOUT_S,
              term_grace_s: float = DEFAULT_TERM_GRACE_S,
              python: Optional[str] = None,
              extra_env: Optional[dict] = None) -> ProbeResult:
    """Spawn the probe child and watchdog it: SIGTERM at `timeout_s`,
    SIGKILL `term_grace_s` later, bounded wait after that.  Never
    hangs the caller, never raises on an unhealthy backend — inspect
    the returned :class:`ProbeResult`."""
    cmd = [python or sys.executable, os.path.abspath(__file__), "--child"]
    env = dict(os.environ)
    if extra_env:
        env.update(extra_env)
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    timed_out = killed = False
    out = ""
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        timed_out = True
        collected = []

        def drain(t):
            collected[:] = [proc.communicate(timeout=t)[0]]

        reaped = reap_child(proc, term_grace_s=term_grace_s,
                            kill_wait_s=_KILL_WAIT_S, wait=drain)
        killed = reaped.killed
        out = "" if reaped.abandoned else (collected[0] if collected else "")
    duration = time.perf_counter() - t0
    ok = (not timed_out and proc.returncode == 0
          and PROBE_OK_SENTINEL in (out or ""))
    res = ProbeResult(ok=ok, returncode=proc.returncode, duration_s=duration,
                      timed_out=timed_out, killed=killed, output=out or "",
                      command=cmd)
    tele = _tele()
    if tele is not None:
        tele.event("resilience.probe.ok" if ok else "resilience.probe.fail",
                   duration_s=duration, timed_out=timed_out, killed=killed)
    return res


_PROBE_CACHE: Optional[ProbeResult] = None


def ensure_backend(timeout_s: float = DEFAULT_TIMEOUT_S,
                   refresh: bool = False) -> ProbeResult:
    """Once-per-process gate for in-process backend init: probe the
    backend from a subprocess first, so a hung backend is detected by a
    killable child instead of hanging the caller's jax.devices().
    Wired behind QRACK_TPU_PROBE_FIRST=1 (engines/tpu.py discover)."""
    global _PROBE_CACHE
    if refresh or _PROBE_CACHE is None:
        _PROBE_CACHE = run_probe(timeout_s=timeout_s)
    return _PROBE_CACHE


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--child", action="store_true",
                      help="run the payload directly (no watchdog; the "
                           "caller must bound it)")
    mode.add_argument("--watchdog", action="store_true",
                      help="spawn the payload in a SIGTERM-first "
                           "watchdogged subprocess")
    ap.add_argument("--timeout", type=float, default=DEFAULT_TIMEOUT_S)
    ap.add_argument("--term-grace", type=float, default=DEFAULT_TERM_GRACE_S)
    args = ap.parse_args(argv)
    if args.watchdog:
        res = run_probe(timeout_s=args.timeout, term_grace_s=args.term_grace)
        sys.stdout.write(res.output)
        if res.timed_out:
            print(f"PROBE_TIMEOUT after {args.timeout}s"
                  + (" (SIGKILL needed)" if res.killed else " (SIGTERM)"),
                  flush=True)
        return 0 if res.ok else 1
    # default (and --child): the payload itself
    return child_main()


if __name__ == "__main__":
    sys.exit(main())
