"""One run of one benchmark cell: set-up, a measured window, the check.

Everything that belongs to a cell is found by name: the cell's entry in
`BENCHMARK.json` and its file `workloads/<cell>.json` (configuration,
driver, traffic, correctness limits), the configuration's file
`configs/<config>.json`, the generator it names
`generators/<generator>.py` (`chipbench.matrices`), the driver
`drivers/<driver>.py` (set-up, one call of the closed loop, the
end-to-end numbers, the comparison with the plain reference) and one
reader `metrics/<metric>.py` per per-layer metric.  A new cell, traffic
mix, matrix family or metric is a new file.

A run:

1. turns on JAX's persistent compilation cache
   (`repro.compile_cache.enable_compile_cache`, every program cached) and
   refuses any platform but a TPU, or fewer chips than the cell asks for;
2. runs the driver's set-up: generation from `--seed`, factorization,
   transform and schedule, staging, compile, warm-up; `setup_s` runs from
   process start to the window's start;
3. calls the driver in a closed loop with one caller until `--seconds`
   have passed; with `--trace 1` the first `trace_calls` calls run under
   the profiler inside a `chipbench.window` span, each in a
   `chipbench.solve` span, with the program's own spans on the same clock;
4. reads the chips' peak memory, frees the program's state, and compares
   the answers with the plain reference (`chipbench.reference`);
5. prints the set-up split, compile counts and the window on earlier
   lines, each compared number beside its limit as the last lines of
   standard error, and the result as the last line of standard output.

Backend compiles are counted from JAX's monitoring events, apart for
set-up and window: the window should hold none.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

from chipbench import load_module

__all__ = ["BENCH_DIR", "Cell", "load_cell", "percentile", "jsonable",
           "run", "run_control", "main"]

BENCH_DIR = Path(__file__).resolve().parent
BENCHMARK_FILE = BENCH_DIR.parent / "BENCHMARK.json"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_writes"}
REQUIRED_PLATFORM = "tpu"
WINDOW_SPAN = "chipbench.window"
CALL_SPAN = "chipbench.solve"


def percentile(samples, q: float) -> float:
    """Nearest-rank percentile (the formula of `repro.obs.metrics`)."""
    if not samples:
        return float("nan")
    s = sorted(samples)
    rank = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return float(s[rank])


class Cell:
    """One cell's files, found by name (module doc)."""

    def __init__(self, name: str, bench: dict, workload: dict,
                 config: dict):
        self.name = name
        self.bench = bench
        self.workload = workload
        self.config = config
        self.chips = workload["chips"]
        self.driver = load_module(
            BENCH_DIR / "drivers" / f"{workload['driver']}.py")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", ())
                          or ("workloads" not in m and m["moves"] in moved)]

    def reader(self, metric: str):
        return load_module(BENCH_DIR / "metrics" / f"{metric}.py").read


def load_cell(name: str) -> Cell:
    """The cell's files."""
    bench = json.loads(BENCHMARK_FILE.read_text())
    if name not in {w["name"] for w in bench["workloads"]}:
        raise KeyError(f"no workload {name!r} in {BENCHMARK_FILE.name}")
    workload = json.loads(
        (BENCH_DIR / "workloads" / f"{name}.json").read_text())
    config = json.loads((BENCH_DIR / "configs"
                         / f"{workload['config']}.json").read_text())
    return Cell(name, bench, workload, config)


class Phases:
    """Named set-up durations, in order."""

    def __init__(self):
        self.seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) \
                + time.perf_counter() - t0


class Compiles:
    """Compiles, counted from JAX's monitoring events: every compile or
    load of a program from the persistent cache (`count`, `seconds`), and
    how many of them the cache served (`cache_hits`) or took a new entry
    for (`cache_writes`)."""

    def __init__(self):
        import jax
        self.counts = {"count": 0, "seconds": 0.0, "cache_hits": 0,
                       "cache_writes": 0}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.counts["count"] += 1
                self.counts["seconds"] += duration

    def _on_event(self, event, **_kw):
        key = CACHE_EVENTS.get(event)
        if key is not None:
            with self._lock:
                self.counts[key] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self.counts)

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_time)
        jax.monitoring.unregister_event_listener(self._on_event)


def jsonable(v):
    """JSON-safe number: a reading that is not finite is written as text."""
    if isinstance(v, float) and not math.isfinite(v):
        return str(v)
    return v


def _start_trace(trace_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # keep the host cost of tracing low
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _window(cell: Cell, st, seconds: float, trace_calls: int, tracer,
            trace_dir: str | None) -> tuple:
    """The closed loop: (call latencies in s, elapsed s, seconds spent
    stopping the profiler)."""
    import jax
    drv = cell.driver
    latencies = []
    tracing = trace_dir is not None
    annotation = None
    if tracing:
        _start_trace(trace_dir)
        annotation = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        annotation.__enter__()
    clock = time.perf_counter
    t0 = clock()
    i = 0
    stop_s = 0.0
    while True:
        t1 = clock()
        if tracer is not None:
            with tracer.span(CALL_SPAN):
                drv.call(st, i)
        else:
            drv.call(st, i)
        t2 = clock()
        latencies.append(t2 - t1)
        i += 1
        if tracing and i >= trace_calls:
            annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
            stop_s = clock() - t2
            t2 = clock()
        if t2 - t0 >= seconds:
            break
    if tracing:
        annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        stop_s = clock() - t2
    return latencies, t2 - t0, stop_s


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def _enable_compile_cache() -> str:
    """JAX's persistent compilation cache in the program's fixed directory
    (`repro.compile_cache`), holding every program however quick to
    compile; returns the directory."""
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def _devices(cell: Cell):
    """Start JAX with the compilation cache; (devices, device record,
    cache directory), or None when the platform is not
    `REQUIRED_PLATFORM` or there are fewer chips than the cell asks for
    (the reason goes to standard error)."""
    cache_dir = _enable_compile_cache()
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != REQUIRED_PLATFORM:
        print(f"chipbench: no TPU ({device}); this benchmark runs only on "
              "the chip", file=sys.stderr)
        return None
    if device["count"] < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, found "
              f"{device['count']}", file=sys.stderr)
        return None
    return devices, device, cache_dir


def run_control(cell_name: str, seed: int, calls: int):
    """The cell's control: its set-up, then `calls` answers made by the
    lower-precision control in the program's place (the driver's
    `control`), compared as a run compares them.  Returns (correct,
    [(name, value, limit), ...]) or None when the platform is refused."""
    cell = load_cell(cell_name)
    if _devices(cell) is None:
        return None
    st = cell.driver.setup(cell.config, cell.workload["traffic"],
                           seed % (1 << 63), Phases())
    for i in range(calls):
        cell.driver.call(st, i, control=True)
    cell.driver.release(st)
    gc.collect()
    checks, failed = cell.driver.check(st)
    checks = list(checks) + [("failed_calls", failed, 0)]
    return all(v <= lim for _, v, lim in checks), checks


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_process: float | None = None):
    """One run of one cell (module doc).  Returns the result dict, or None
    when the platform is refused (nothing is printed on standard output
    then)."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = load_cell(cell_name)
    traffic = cell.workload["traffic"]
    phases = Phases()
    with phases("jax_init"):
        found = _devices(cell)
    if found is None:
        return None
    devices, device, cache_dir = found
    compiles = Compiles()
    seed = seed % (1 << 63)
    c0 = compiles.snapshot()
    st = cell.driver.setup(cell.config, traffic, seed, phases)
    setup_s = time.perf_counter() - t_process
    c_setup = compiles.snapshot()
    print("chipbench: setup " + json.dumps({
        "cell": cell_name, "seed": seed, "setup_s": setup_s,
        "phases_s": phases.seconds, "compiles": compiles.since(c0),
        "compile_cache": cache_dir}), flush=True)

    tracer = None
    trace_dir = None
    if trace:
        from repro import obs
        tracer = obs.enable(annotate_jax=True)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        latencies, elapsed, trace_stop_s = _window(
            cell, st, seconds, traffic["trace_calls"], tracer, trace_dir)
        in_window = compiles.since(c_setup)
        memory_peak = _peak_bytes(devices[:cell.chips])
        print("chipbench: window " + json.dumps({
            "calls": len(latencies), "elapsed_s": elapsed,
            "compiles": in_window, "trace_stop_s": trace_stop_s,
            "counters": {k: v[:64] if isinstance(v, list) else v
                         for k, v in cell.driver.counters(st).items()}}),
            flush=True)
        if in_window["count"]:
            print(f"chipbench: WARNING {in_window['count']} compile(s) "
                  "inside the measured window", flush=True)
        result_device = {**device, "memory_peak_bytes": memory_peak}
        breakdown = None
        if trace:
            metrics, red = _per_layer(cell, st, tracer, trace_dir,
                                      device["kind"])
            result_device.update(busy_s=red["busy_s"],
                                 window_s=red["window_s"])
            breakdown = {"device_ops": red["ops"][:10],
                         "idle_gaps": red["gaps"][:10]}
            print("chipbench: trace " + json.dumps({
                k: red[k] for k in ("window_s", "busy_s", "idle_share",
                                    "devices", "calls", "reduce_s")}),
                flush=True)
        else:
            values = cell.driver.end_to_end(st, latencies, elapsed)
            values["setup_s"] = setup_s
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
    finally:
        compiles.close()
        if trace:
            from repro import obs
            obs.disable()
            shutil.rmtree(trace_dir, ignore_errors=True)
    cell.driver.release(st)
    gc.collect()
    checks, failed = cell.driver.check(st)
    checks = list(checks) + [("failed_calls", failed, 0)]
    correct = bool(latencies) and all(v <= lim for _, v, lim in checks)
    for name, v, lim in checks:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr,
              flush=True)
    result = {"correct": correct, "attempted": len(latencies),
              "failed": int(failed), "metrics": metrics,
              "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": jsonable(v), "limit": lim}
                        for name, v, lim in checks}
    print(json.dumps(result), flush=True)
    return result


def _per_layer(cell: Cell, st, tracer, trace_dir: str,
               kind: str) -> tuple:
    """Per-layer metrics of a traced run: (metrics, trace reduction)."""
    from chipbench import trace_reduce, work
    spans = tracer.spans()
    names = {WINDOW_SPAN, CALL_SPAN} | {s.name for s in spans}
    t0 = time.perf_counter()
    red = trace_reduce.reduce(trace_reduce.load(trace_dir, names))
    red["reduce_s"] = time.perf_counter() - t0
    counters = dict(cell.driver.counters(st))
    if "iterations" in counters:
        counters["traced_iterations"] = sum(
            counters["iterations"][:red["calls"]])
    peak = work.peaks(kind) if cell.per_layer and red["busy_s"] > 0 \
        else None
    ctx = {"trace": red, "counters": counters, "peak": peak,
           "spans": [(s.name, s.attrs) for s in spans],
           "work": cell.driver.work(st)}
    metrics = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, red


def main(argv=None, t_process: float | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 t_process=t_process)
    return 1 if result is None else 0
