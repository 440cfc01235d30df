"""Shared machinery of the chip benchmark: files found by name, seeds,
spans on the profiler's clock, compile counting, the result line.

Nothing here knows a configuration, a traffic mix or a metric: those are
files under ``configs/``, ``workloads/``, ``loops/`` and ``metrics/``,
found by the names ``BENCHMARK.json`` gives them.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH))
WORK = os.path.join(BENCH, ".work")            # checkpoints, traces (ignored)
CACHE = os.path.join(BENCH, ".cache", "jax")   # persistent compile cache


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """Import a file of the benchmark by path (names may hold dots)."""
    path = os.path.join(BENCH, *parts)
    name = "chipbench_" + "_".join(parts).replace(".", "_").replace("/", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def use_compile_cache(path: str = CACHE) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the cache key: a moving directory never hits)."""
    import jax

    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def seed_key(seed: int, salt: int = 0):
    """A PRNG key from any whole number (seeds may pass 2**32)."""
    import jax

    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 62) & 0x7FFFFFFF)
    return jax.random.fold_in(key, salt)


class Spans:
    """Bench spans around the calls into each layer.  Each is kept in
    memory (host clock) and emitted as a ``jax.profiler.TraceAnnotation``
    so a device trace can attribute idle gaps to it."""

    def __init__(self):
        self.items: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str, **args):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                t1 = time.perf_counter()
                with self._lock:
                    self.items.append({"name": name, "t0": t0, "t1": t1,
                                       **args})

    def named(self, name: str) -> List[Dict[str, Any]]:
        return [s for s in self.items if s["name"] == name]


class Compiles:
    """Counts backend compilations (cache hits included: a hit still
    loads a program) with their host times, from JAX's monitoring
    events."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.times: List[float] = []
        self._on = False

    def install(self) -> None:
        import jax

        def listener(event, duration, **_):
            if self._on and event in self.EVENTS:
                self.times.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(listener)
        self._on = True

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


def write_counters() -> int:
    """Bytes this process has passed to write(2) so far (all threads)."""
    with open("/proc/self/io") as f:
        for line in f:
            if line.startswith("wchar:"):
                return int(line.split()[1])
    raise OSError("no wchar line in /proc/self/io")


def device_info(devices) -> Dict[str, Any]:
    import jax

    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


def find_chips(chips: int):
    """The chips the cell asks for; raises unless JAX sees enough TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"{chips} chips asked, {len(devs)} found")
    return devs[:chips]


class Run:
    """What a cell's loop gets: the cell, its files, the seed and the window,
    spans, compile counts, and a place to put what the metric readers
    read."""

    def __init__(self, cell: Dict[str, Any], spec: Dict[str, Any], seed: int,
                 seconds: float, trace: bool, devices, work: str = WORK):
        self.cell = cell
        self.spec = spec
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.workload = load_json("workloads", cell["name"] + ".json")
        cfg = next(c for c in spec["configs"] if c["name"] == cell["config"])
        with open(os.path.join(ROOT, cfg["file"])) as f:
            self.config = json.load(f)
        self.work = os.path.join(work, cell["name"])
        self.spans = Spans()
        self.compiles = Compiles()
        self.read: Dict[str, Any] = {}      # what metrics/*.py read
        self.t_start = time.perf_counter()
        self.trace_dir = os.path.join(self.work, "trace")

    def span(self, name: str, **args):
        return self.spans.span(name, **args)

    @contextlib.contextmanager
    def profiled(self):
        """Profile the enclosed part of the run when tracing.  Yields a
        context manager that marks the traced window inside it: the loop
        opens it once the profiler has settled (after a first step), so the
        tracer's start-up is not read as device idle time."""
        import jax

        if not self.trace:
            yield contextlib.nullcontext
            return
        import trace_reduce

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # Python calls are not traced
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            yield lambda: self.span(trace_reduce.WINDOW_SPAN)
        finally:
            jax.profiler.stop_trace()


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                checks: Dict[str, Dict[str, float]],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted), "failed": int(failed),
                           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks          # last: each number compared, its limit
    return json.dumps(out)


def judge(checks: Dict[str, Dict[str, float]]) -> bool:
    """Every number compared lies within its limit (a missing or non-finite
    number fails)."""
    import math

    ok = True
    for c in checks.values():
        v = c.get("value")
        if v is None or not math.isfinite(v) or v > c["limit"]:
            ok = False
    return ok
