"""What every cell's run shares: finding a cell's files by the names in
BENCHMARK.json, the device and compile bookkeeping, host spans, the
comparisons that decide ``correct``, and the call of each metric's reader.

Nothing here knows a cell, a configuration, a circuit family, a traffic
mix or a per-layer metric by name: each is a file found by its name.
"""

import contextlib
import importlib.util
import json
import os
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def say(**fields):
    """One JSON line of standard output, before the result line."""
    print(json.dumps(fields), flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(directory, name):
    """``benchmarks/<directory>/<name>.py`` as a module; names may hold dots."""
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with everything its names lead to."""

    def __init__(self, name):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.benchmark = json.load(f)
        found = [w for w in self.benchmark["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.entry = found[0]
        self.name = name
        self.chips = self.entry["chips"]
        config = [c for c in self.benchmark["configs"]
                  if c["name"] == self.entry["config"]][0]
        with open(os.path.join(ROOT, config["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        self.family = load_module("circuits", self.config["family"])
        self.driver = load_module("drivers", self.traffic["kind"])

    def metrics(self, group):
        """The metrics of ``group`` that this cell reports: all that list
        no ``workloads``, and those that list this cell."""
        return [m for m in self.benchmark[group]
                if self.name in m.get("workloads", [self.name])]


class Compiles:
    """Backend compiles as JAX reports them.  Against a warm persistent
    cache the event still fires, with the load time as its duration."""

    def __init__(self, jax):
        self.count = 0
        self.seconds = 0.0
        self.cache_misses = 0  # programs the persistent cache did not hold
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == CACHE_MISS_EVENT:
            self.cache_misses += 1

    def mark(self):
        return self.count, self.seconds


def compile_cache_dir(jax):
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says, else
    the fixed ``<checkout>/.xla_cache`` (the path is part of the key).

    A Mosaic kernel is serialized into its program with the Python call
    stack of every operation as its location, so the cache's key changes
    with any line number on the way to the first call: the program's
    telemetry switched on is enough (PERF.md, PR 27).  Locations without
    the stack make a traced run find what an untraced run compiled."""
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    if jax.devices()[0].platform != "tpu":
        return None  # a rehearsal keeps no programs
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(ROOT, ".xla_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def peak_bytes(jax):
    """The allocator's high-water mark on the fullest chip; None where
    the backend keeps none (the CPU)."""
    stats = [d.memory_stats() for d in jax.devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats if s]
    return max(peaks) if peaks else None


def device_dict(jax):
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes(jax)}


class Spans:
    """Host spans of the benchmark's own, around the calls into each
    layer.  Always on the host clock; in a traced run also written into
    the profiler's trace, so that they sit on the device's clock too."""

    def __init__(self, annotate=None):
        self._annotate = annotate
        self.recorded = {}

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter()
        if self._annotate is None:
            yield
        else:
            with self._annotate("bench." + name):
                yield
        self.recorded.setdefault(name, []).append(time.perf_counter() - t0)

    def clear(self):
        self.recorded = {}


class Checks:
    """Every number compared, beside its limit.  ``correct`` is their
    conjunction."""

    def __init__(self, limits):
        self.limits = limits
        self.records = []
        self.untimed_seconds = 0.0

    @contextlib.contextmanager
    def untimed(self):
        """Comparison work before the window: not part of set-up."""
        t0 = time.perf_counter()
        yield
        self.untimed_seconds += time.perf_counter() - t0

    def compare(self, name, value, limit_key):
        limit = self.limits[limit_key]
        ok = bool(np.isfinite(value) and value <= limit)
        self.records.append({"check": name, "value": float(value),
                             "limit": limit, "limit_key": limit_key, "ok": ok})
        say(**self.records[-1])
        return ok

    def require(self, name, ok, detail=""):
        """A comparison that is exact: it holds or it does not."""
        self.records.append({"check": name, "value": 0.0 if ok else 1.0,
                             "limit": 0, "ok": bool(ok), "detail": str(detail)})
        say(**self.records[-1])
        return ok

    def amplitudes(self, name, got, want):
        got, want = np.asarray(got), np.asarray(want)
        err = float(np.max(np.abs(got - want) / np.abs(want)))
        return self.compare(name, err, "amplitude_rel_err")

    def norm_drift(self, name, q, steps):
        """The norm of the ket the engine holds after ``steps``
        applications, by a reduction of the benchmark's own (float32
        planes, float32 sum).  Rounding moves it a little every step, so
        it is held to a drift per step."""
        import jax
        import jax.numpy as jnp

        norm = float(jax.jit(lambda planes: jnp.sum(planes * planes))(q._state))
        self.compare(name + ".norm_drift_per_step",
                     abs(norm - 1.0) / max(steps, 1), "norm_drift_per_step")

    @property
    def correct(self):
        return bool(self.records) and all(r["ok"] for r in self.records)

    @property
    def failures(self):
        return [r["check"] for r in self.records if not r["ok"]]


def bit_positions(q):
    """The bit position, in the planes the engine holds, of each logical
    qubit: the engine's placement table where it keeps one (the pager's
    ``_qmap``), else the identity.  Read it after the planes: a read of
    ``q._state`` flushes the last window, which may move the table.  The
    table is read, never undone: ``_unmap()`` is an exchange of the
    program's own and would change what the next step runs."""
    table = getattr(q, "_qmap", None)
    return list(range(q.qubit_count)) if table is None else list(table)


def self_check(family, params, reference, width, seed):
    """The family's closed form against the plain reference, every
    amplitude, at a width the host holds: no device program."""
    rng = np.random.default_rng(seed)
    x = int(rng.integers(1, 1 << width))
    state = reference.run(width, family.gates(width, params), x)
    want = np.array([family.amplitude(width, params, x, y)
                     for y in range(1 << width)])
    return float(np.max(np.abs(state - want)))


def read_metrics(group, cell, context):
    """Every metric of ``group`` that this cell reports, each taken by
    the reader file of its own name; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for metric in cell.metrics(group):
        value = load_module(group, metric["name"]).read(context)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out
