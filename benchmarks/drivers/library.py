"""Traffic kind ``library``: one caller in a closed loop.  The next
application starts when the read of the last returns.

The traffic file gives ``warmup_applications`` (whole applications
before the window, each on data of its own, so that every program the
window will use has run) and ``traced_seconds`` (how much of the window
a traced run profiles).
"""

import time


def run(env):
    """Drive one run; returns what the metric readers read."""
    family, plan, spans, checks = env.family, env.plan, env.spans, env.checks
    q = env.make_engine()
    for k in range(env.traffic["warmup_applications"]):
        family.warmup(q, plan, k, spans, checks)
    env.engine_on_device(q)
    # a whole application: asked once per traced run, not in every run
    barrier = _barrier_probe(q, family, plan, spans) if env.trace else None
    spans.clear()

    seconds = env.seconds
    if env.trace:
        seconds = min(seconds, env.traffic["traced_seconds"])
    family.start(q, plan, spans)
    q.GetAmplitude(0)  # nothing of set-up is left in the queue
    setup_seconds = env.since_start() - checks.untimed_seconds
    compiles_before = env.compiles.mark()
    counters_open = env.counters()

    circuit_seconds, reads = [], []
    cpu_seconds = []  # the caller's own CPU time in each application
    failed = 0
    with env.window():
        t_open = t = time.perf_counter()
        cpu = time.thread_time()
        i = 0
        while t - t_open < seconds:
            with spans("application"):
                try:
                    family.enqueue(q, plan, i, spans)
                    with spans("completion_read"):
                        reads.append(q.GetAmplitude(family.read_index(plan, i)))
                except Exception as e:  # an operation failed: count it
                    env.say(application=i, raised=repr(e))
                    reads.append(None)
                    failed += 1
            now = time.perf_counter()
            circuit_seconds.append(now - t)
            t = now
            cpu_seconds.append(time.thread_time() - cpu)
            cpu = time.thread_time()
            i += 1
        window_seconds = t - t_open
    compiles_after = env.compiles.mark()
    counters = {k: v - counters_open.get(k, 0)
                for k, v in env.counters().items()}
    peak = env.peak_bytes()

    # once the window has closed: every read that has a closed form
    failed += _check_reads(family, plan, reads, checks)
    family.final_check(q, plan, i - 1, spans, checks)
    return {
        "attempted": i, "failed": failed,
        "circuit_seconds": circuit_seconds, "window_seconds": window_seconds,
        "setup_seconds": setup_seconds, "peak_bytes_after_window": peak,
        "compiles_before_window": compiles_before,
        "window_compiles": compiles_after[0] - compiles_before[0],
        "window_counters": counters, "host_spans": spans.recorded,
        "caller_cpu_seconds": cpu_seconds,
        "barrier": barrier,
    }


def _barrier_probe(q, family, plan, spans):
    """Is ``block_until_ready`` a completion barrier on this chip?  Queue
    one more application, wait on its planes, then time the read: if the
    wait was complete the read has nothing left to wait for."""
    family.enqueue(q, plan, 0, spans)
    planes = q._state  # the read of the property flushes the last window
    t0 = time.perf_counter()
    planes.block_until_ready()
    t1 = time.perf_counter()
    q.GetAmplitude(family.read_index(plan, 0))
    t2 = time.perf_counter()
    return {"block_until_ready_seconds": t1 - t0,
            "read_after_it_seconds": t2 - t1}


def _check_reads(family, plan, reads, checks):
    """Compare each application's read with its closed form, where the
    family has one; any read has to be an amplitude at all."""
    import numpy as np

    got, want, wrong = [], [], 0
    for i, amp in enumerate(reads):
        if amp is None:
            continue
        if not (np.isfinite(amp.real) and np.isfinite(amp.imag)
                and abs(amp) <= 1.0 + 1e-3):
            wrong += 1
            continue
        exact = family.expected(plan, i)
        if exact is not None:
            got.append(amp)
            want.append(exact)
    checks.require("window_reads_are_amplitudes", wrong == 0,
                   f"{wrong} of {len(reads)}")
    if got:
        err = np.abs(np.asarray(got) - np.asarray(want)) / np.abs(want)
        wrong += int(np.sum(err > checks.limits["amplitude_rel_err"]))
        checks.amplitudes("window_read_amplitudes", got, want)
    return wrong
