"""The benchmark harness: finds a cell's files by name, runs it once.

A cell (``bench/workloads/<cell>.json``) names its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<mix>.json``) and the driver that runs the mix against
the program (``bench/drivers/<driver>.py``).  Which metrics the cell
reports comes from ``BENCHMARK.json``; each per-layer metric is read by
``bench/metrics/<metric>.py`` and each kernel's operations and bytes come
from ``bench/costs/<kernel>.py``.  Adding a cell, configuration, mix or
metric adds files; no file here changes.

A driver module provides::

    setup(ctx) -> state            build and warm up the timed path
    window(ctx, state) -> rec      the measured window
    release(ctx, state)            drop the program's device state
    check(ctx, state, rec) -> {number: value}
                                   compare what the window produced with
                                   the plain reference
    end_to_end(ctx, rec) -> {metric: value}

``rec`` is a ``Record``: the window's counts and spans, and in a traced
run the reduced device trace.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import resource
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec"))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(*parts)
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One cell's files, resolved by name, with optional overrides (tests
    run the same cell at a size the CPU can hold)."""

    def __init__(self, name: str, overrides: dict | None = None):
        bench = load_json(ROOT, "BENCHMARK.json")
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name, self.chips = name, int(entry["chips"])
        self.spec = load_json(BENCH, "workloads", f"{name}.json")
        self.cfg = load_json(BENCH, "configs", f"{entry['config']}.json")
        self.traffic = load_json(BENCH, "traffic", f"{entry['traffic']}.json")
        for part, extra in (overrides or {}).items():
            target = getattr(self, part)
            target.update(extra)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _applies(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
        self.driver = load_module(BENCH, "drivers",
                                  f"{self.spec['driver']}.py")


class Record(dict):
    """What a window leaves for the metric readers.

    Keys a driver fills: ``window_s``; ``spans`` (the engine's spans, a
    traced run only); ``launches`` ({kernel: (launches, shape dict)});
    ``mac_ops`` (the MAC operations the completed work needs).  The
    harness adds ``trace`` (``trace_reduce.Reduced``) and ``peaks``.
    """

    def roofline(self, kernel: str):
        """Share (%) of the kernel's roofline, or None when the trace has
        no such kernel: the larger of (operations / int8 peak) and
        (bytes / HBM bandwidth) of the launches the trace holds, over
        their device time."""
        t = self.get("trace")
        if t is None or kernel not in self.get("launches", {}):
            return None
        busy, n_events = t.kernels.get(kernel, (0.0, 0))
        if n_events == 0 or busy <= 0.0:
            return None
        cost = load_module(BENCH, "costs", f"{kernel}.py")
        shape = self["launches"][kernel][1]
        t_ops = n_events * cost.ops(shape) / self["peaks"]["int8_ops"]
        t_bytes = (n_events * cost.nbytes(shape)
                   / self["peaks"]["hbm_bytes_per_s"])
        self.setdefault("bounds", {})[kernel] = (
            "compute" if t_ops >= t_bytes else "memory")
        return 100.0 * max(t_ops, t_bytes) / busy

    def mfu(self):
        """Share (%) of the int8 peak that the window's MAC work needs."""
        if not self.get("mac_ops"):
            return None
        return 100.0 * self["mac_ops"] / (self["window_s"]
                                          * self["peaks"]["int8_ops"])

    def idle_share(self):
        t = self.get("trace")
        if t is None or t.window_s <= 0:
            return None
        return 100.0 * (1.0 - t.busy_s / t.window_s)


class Ctx:
    """What a driver sees: the cell, the run's arguments, the program."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.cell, self.seed, self.seconds, self.trace = (
            cell, int(seed), float(seconds), bool(trace))
        self.cfg, self.traffic, self.spec = cell.cfg, cell.traffic, cell.spec
        self.limits = cell.spec["limits"]
        if os.path.join(ROOT, "src") not in sys.path:
            sys.path.insert(0, os.path.join(ROOT, "src"))

    def span(self, name: str):
        """A span of the benchmark's own, in the profiler's trace."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def setup_jax() -> None:
    """The persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else a fixed directory in the checkout; every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_chip: bool) -> tuple:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    d = devs[0]
    return devs[:chips], {"platform": d.platform, "kind": d.device_kind,
                          "count": chips}


def peaks_for(kind: str, require_chip: bool) -> dict:
    table = load_json(BENCH, "peaks.json")["kinds"]
    if kind in table:
        return table[kind]
    if require_chip:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return {"bf16_flops": float("nan"), "int8_ops": float("nan"),
            "hbm_bytes_per_s": float("nan")}


class CompileCounter:
    """Counts, while armed, what builds a program: tracing to a jaxpr,
    lowering, backend compilation and loads from the persistent cache.
    The window should see none of them."""

    def __init__(self):
        import jax
        self.armed, self.count, self.seconds = False, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1
            self.seconds += duration


class HostWatch:
    """What the host did in the window besides the work: garbage-collector
    pauses, CPU time, context switches and major page faults.  Printed on
    an earlier line, so a run that reads far off shows whether the
    benchmark's process stalled."""

    def __init__(self):
        self.pauses, self._t = [], None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        self._r0 = resource.getrusage(resource.RUSAGE_SELF)
        self._w0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        r0, r1 = self._r0, resource.getrusage(resource.RUSAGE_SELF)
        pauses = [p for _, p in self.pauses]
        self.line = (
            f"host_window wall_s={time.perf_counter() - self._w0:.3f} "
            f"cpu_s={r1.ru_utime + r1.ru_stime - r0.ru_utime - r0.ru_stime:.3f}"
            f" nivcsw={r1.ru_nivcsw - r0.ru_nivcsw}"
            f" majflt={r1.ru_majflt - r0.ru_majflt}"
            f" gc_runs={len(pauses)}"
            f" gc_full={sum(1 for g, _ in self.pauses if g == 2)}"
            f" gc_max_s={max(pauses, default=0.0):.4f}"
            f" gc_total_s={sum(pauses):.4f}")
        return False


def memory_peak(devs) -> int | None:
    peaks = []
    for d in devs:
        try:
            stats = d.memory_stats()
        except Exception:   # backends without memory stats (the CPU)
            stats = None
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float | None = None, require_chip: bool = True,
        overrides: dict | None = None, log=print) -> dict:
    """Run one cell once; returns the result object (the last line)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(workload, overrides)
    setup_jax()
    import jax
    devs, device = device_info(cell.chips, require_chip)
    peaks = peaks_for(device["kind"], require_chip)
    ctx = Ctx(cell, seed, seconds, trace)
    drv = cell.driver
    counter = CompileCounter()
    t_drv = time.perf_counter()
    state = drv.setup(ctx)
    setup_s = time.perf_counter() - t_start
    log(f"setup_phases start_and_jax_init_s={t_drv - t_start:.3f} "
        f"driver_s={setup_s - (t_drv - t_start):.3f}")

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            # host spans and device operations; no Python call tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
        counter.armed = True
        with HostWatch() as host:
            rec = drv.window(ctx, state)
        counter.armed = False
        if trace:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"stop_trace_s={time.perf_counter() - t0:.3f}")
        rec["peaks"] = peaks
        device["memory_peak_bytes"] = memory_peak(devs)
        log(f"compiles_in_window={counter.count} "
            f"compile_s_in_window={counter.seconds:.3f}")
        log(host.line)
        if rec.get("info"):
            log(rec["info"])
        if rec.get("longest_call"):
            log("longest_call wall_s={:.4f} cpu_s={:.4f} at_s={:.3f}".format(
                *rec["longest_call"]))
        if trace:
            from bench import trace_reduce
            t0 = time.perf_counter()
            patterns = {k: load_module(BENCH, "costs", f"{k}.py").MATCH
                        for k in rec.get("launches", {})}
            rec["trace"] = trace_reduce.reduce_dir(
                tmp, patterns, frozenset(rec.get("span_names", ())),
                {k: n for k, (n, _) in rec.get("launches", {}).items()})
            log(f"trace_reduce_s={time.perf_counter() - t0:.3f} "
                f"trace_truncated={rec['trace'].truncated} "
                f"traced_window_s={rec['trace'].window_s:.3f}")
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    drv.release(ctx, state)
    gc.collect()
    checks = drv.check(ctx, state, rec)
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    metrics = {}
    if not trace:
        values = drv.end_to_end(ctx, rec)
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            reader = load_module(BENCH, "metrics", f"{m['name']}.py")
            v = reader.read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        t = rec["trace"]
        device["busy_s"], device["window_s"] = t.busy_s, t.window_s
    out = {"correct": bool(correct), "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": rec["trace"].top_ops,
                            "idle_gaps": rec["trace"].idle_gaps}
        if rec.get("bounds"):
            log("roofline_bounds=" + json.dumps(rec["bounds"]))
    out["checks"] = checks
    return out
