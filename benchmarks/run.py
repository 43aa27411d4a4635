#!/usr/bin/env python3
"""One process, one cell, one run:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and with
``--trace 1`` ``breakdown``).  Every earlier line is a JSON object of
its own: the device, each number compared beside its limit, sample
counts.  Without a TPU the run ends non-zero before any work and prints
no result.  ``--rehearse-cpu`` drives the same code on the CPU at the
configuration's small width, the kernel under the Pallas interpreter;
it ends non-zero and is never ``correct``: a rehearsal is not a run.
"""

import time

T_START = time.perf_counter()

import argparse
import contextlib
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]


class Env:
    """What a traffic driver is handed."""

    def __init__(self, cell, args, jax, rehearsal):
        import harness

        self.cell, self.jax, self.rehearsal = cell, jax, rehearsal
        self.traffic = cell.traffic
        self.family = cell.family
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.seed = args.seed
        self.width = cell.config["rehearse_qubit_count" if rehearsal
                                 else "qubit_count"]
        self.pages = cell.config.get("pages", 1)
        params = dict(cell.config["circuit"],
                      warmup_applications=cell.traffic["warmup_applications"])
        self.plan = cell.family.Plan(self.width, params, args.seed)
        self.checks = harness.Checks(cell.config["limits"])
        self.compiles = harness.Compiles(jax)
        annotate = jax.profiler.TraceAnnotation if self.trace else None
        self.spans = harness.Spans(annotate)
        self.say = harness.say
        self.trace_dir = os.path.join(ROOT, "bench_out", "trace", cell.name)

    def since_start(self):
        return time.perf_counter() - T_START

    def make_engine(self):
        """The engine the configuration names: its ``engine`` gives the
        class the stack has to end in and the factory's keyword arguments."""
        from qrack_tpu import create_quantum_interface, resilience
        from qrack_tpu.utils.rng import QrackRandom

        engine = self.cell.config["engine"]
        self.checks.require("resilience_off", not resilience._ACTIVE)
        # QrackRandom takes 32 bits; the plan, not the engine, uses the seed
        q = create_quantum_interface(
            self.cell.config["stack"], self.width,
            rng=QrackRandom(self.seed & 0x7FFFFFFF), rand_global_phase=False,
            **engine["kwargs"])
        self.checks.require(f"engine_is_{engine['class']}",
                            type(q).__name__ == engine["class"],
                            type(q).__name__)
        return q

    def engine_on_device(self, q):
        """float32 planes, divided evenly over exactly the cell's chips:
        one page a chip where the configuration pages the ket."""
        planes, chips = q._state, self.cell.chips
        on = planes.devices()
        shard = planes.sharding.shard_shape(planes.shape)
        self.checks.require(
            "planes_on_device_float32",
            on == set(self.jax.devices()[:chips])
            and shard[-1] * chips == planes.shape[-1]
            and getattr(q, "n_pages", 1) == self.pages == chips
            and str(planes.dtype) == "float32",
            f"{on} {planes.dtype} shard {shard} of {planes.shape}, "
            f"{getattr(q, 'n_pages', 1)} page(s)")

    def counters(self):
        """The program's counters, which only a traced run switches on."""
        if not self.trace:
            return {}
        from qrack_tpu import telemetry

        return dict(telemetry.snapshot(include_events=False)["counters"])

    def peak_bytes(self):
        import harness

        return harness.peak_bytes(self.jax) or 0

    @contextlib.contextmanager
    def window(self):
        if not self.trace:
            yield
            return
        import shutil
        import tracing

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        with tracing.capture(self.jax, self.trace_dir):
            yield


def _slowest(result):
    """The window's slowest application and where the caller spent it: a
    stall shows as wall time in a span without CPU time of the caller."""
    times = result["circuit_seconds"]
    i = times.index(max(times))
    spans = {k: result["host_spans"][k][i] * 1e3
             for k in ("gate_calls", "completion_read")}
    return {"index": i, "ms": times[i] * 1e3,
            "caller_cpu_ms": result["caller_cpu_seconds"][i] * 1e3,
            "spans_ms": spans}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny width on the CPU; never correct, exits 3")
    return ap.parse_args(argv)


def execute(args):
    """One run.  Returns (exit code, result line or None, checks or None)."""
    import harness
    import reference

    cell = harness.Cell(args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["QRACK_TPU_FUSE_KERNEL"] = "on"  # interpreter, not a speed
        # as many host devices as the cell has chips; JAX reads the flag
        # when its backend starts, so a process that has one keeps it
        flag = "--xla_force_host_platform_device_count="
        flags = os.environ.get("XLA_FLAGS", "").split()
        have = max([int(f[len(flag):]) for f in flags if f.startswith(flag)]
                   or [1])
        if have < cell.chips:
            os.environ["XLA_FLAGS"] = " ".join(
                flags + [flag + str(cell.chips)])

    import jax

    devices = jax.devices()
    on_chip = devices[0].platform == "tpu" and len(devices) >= cell.chips
    if not on_chip and not args.rehearse_cpu:
        print(f"benchmarks/run.py: {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 2, None, None
    rehearsal = not on_chip
    if rehearsal and len(devices) < cell.chips:
        print(f"benchmarks/run.py: a rehearsal of {cell.name} needs "
              f"{cell.chips} host devices; this process's JAX started with "
              f"{len(devices)}", file=sys.stderr)
        return 2, None, None
    cache_dir = harness.compile_cache_dir(jax)
    peaks = harness.load_json("peaks.json")
    kind = devices[0].device_kind
    if not rehearsal and kind not in peaks:
        print(f"benchmarks/run.py: no peaks for device kind {kind!r} in "
              "benchmarks/peaks.json", file=sys.stderr)
        return 2, None, None
    harness.say(workload=cell.name, seed=args.seed, seconds=args.seconds,
                trace=args.trace, rehearsal=rehearsal, compile_cache=cache_dir,
                jax=jax.__version__, device=harness.device_dict(jax))

    env = Env(cell, args, jax, rehearsal)
    with env.checks.untimed():
        worst = harness.self_check(cell.family, env.plan.params, reference,
                                   12, args.seed)
        env.checks.require("closed_form_is_the_reference_at_w12",
                           worst < 1e-12, worst)
    if env.trace:
        from qrack_tpu import telemetry

        telemetry.enable()
    result = cell.driver.run(env)

    ms = [s * 1e3 for s in result["circuit_seconds"]]
    # the tail and the rate are printed, not judged: too few applications
    # fit a window for a percentile, and the machine stalls (PERF.md)
    harness.say(samples=len(ms), rehearsal=rehearsal,
                window_seconds=result["window_seconds"],
                circuit_ms_min=min(ms), circuit_ms_max=max(ms),
                circuit_ms_p90=statistics.quantiles(ms, n=10, method="inclusive")[-1]
                if len(ms) > 1 else ms[0],
                circuits_per_s=len(ms) / result["window_seconds"],
                slowest_application=_slowest(result),
                window_compiles=result["window_compiles"],
                programs_built=env.compiles.count,
                compile_seconds=env.compiles.seconds,
                persistent_cache_misses=env.compiles.cache_misses,
                compare_seconds_before_window=env.checks.untimed_seconds,
                total_seconds=env.since_start(), barrier=result["barrier"])
    context = dict(result, cell=cell, peaks=peaks.get(kind), width=env.width,
                   pages=env.pages, rehearsal=rehearsal)
    device = harness.device_dict(jax)
    if not env.trace:
        metrics = harness.read_metrics("end_to_end", cell, context)
    else:
        import tracing

        trace = tracing.load(env.trace_dir) if not rehearsal else None
        context.update(trace=trace)
        # a reader's time is one chip's: the planes' sum over their number
        env.checks.require(
            "device_planes_in_trace_equal_chips",
            rehearsal or trace.chips == cell.chips,
            "rehearsal" if rehearsal else sorted(trace.devices))
        env.checks.require(
            "no_cpu_backend_fallback", rehearsal or
            "fuse.kernel.fallback.cpu_backend" not in env.counters())
        metrics = harness.read_metrics("per_layer", cell, context)
        if trace is not None:
            device.update(busy_s=trace.busy_s(), window_s=trace.window_s())
    for name, m in metrics.items():
        if (name.endswith("_roofline") or "mfu" in name) and m["value"] > 100:
            env.checks.require(f"{name}_at_most_100", False, m["value"])
    correct = env.checks.correct and not rehearsal
    if rehearsal:  # a CPU number is never written under a metric's name
        harness.say(rehearsed_metrics=sorted(metrics))
        metrics = {}
    harness.say(checks_passed=env.checks.correct,
                failed_checks=env.checks.failures)
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if env.trace and context.get("trace") is not None:
        line["breakdown"] = {"device_ops": context["trace"].top_ops(),
                             "idle_gaps": context["trace"].idle_gaps()}
    return (3 if rehearsal else 0), line, env.checks


def main():
    code, line, _ = execute(parse())
    if line is not None:
        print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
