"""One run of one benchmark cell, driven by data.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
harness finds everything else by name:

- ``bench/configs/<config>.json``: the deployment, and the ``driver`` and
  ``reference`` it uses;
- ``bench/traffic/<traffic>.json``: a mix's parameters and the
  ``generator`` (``bench/gen/<generator>.py``) that turns them into an
  open-loop schedule;
- ``bench/drivers/<driver>.py``: the system under test behind one
  ``submit``;
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(cell)``
  returning a number or None (nothing to read: the metric is left out).

A run builds and warms the system (``setup_s``), then offers every request
at the moment it is due, for ``seconds``; each latency runs from that due
moment.  After the window it waits for every request (a minute past the
close at most), reads the device's memory peak, frees the program's
state, and recomputes the checked outputs with the plain reference.  With
``trace`` on, the program's tracer records the whole window and a
``jax.profiler`` session the part ``TRACE_LEAD_S`` after its start.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DRAIN_S = 60.0          # how long past the close a late answer may come
TRACE_LEAD_S = 1.0      # profiler start, from the window start
TRACE_MAX_S = 4.0       # longest profiled span
TRACER_EVENTS_PER_REQUEST = 64   # ring size: no event of a window dropped


class CellError(RuntimeError):
    """The cell cannot run here; nothing is printed on standard output."""


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise CellError(f"no such file: {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise CellError(f"no such file: {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


class Cell:
    """Everything one run knows: what the drivers and the metric readers
    read.  Fields after ``records`` are filled once the window closed."""

    def __init__(self, name: str, seed: int, seconds: float, trace: bool,
                 root: str = ROOT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        wl = [w for w in self.bench["workloads"] if w["name"] == name]
        if not wl:
            raise CellError(f"no workload {name!r} in BENCHMARK.json")
        self.workload = wl[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        conf = [c for c in self.bench["configs"]
                if c["name"] == self.workload["config"]]
        if not conf:
            raise CellError(f"no config {self.workload['config']!r}")
        self.config = load_json(os.path.join(root, conf[0]["file"]))
        self.traffic = load_json(os.path.join(
            root, "bench", "traffic", self.workload["traffic"] + ".json"))
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace_on = bool(trace)
        self.requests: list = []
        self.tracer = None
        self.peaks: Optional[dict] = None
        # filled by the run
        self.setup_s: Optional[float] = None
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.records: list = []
        self.counters_open: dict = {}
        self.counters_end: dict = {}
        self.lateness: list = []
        self.compiles_in_window = 0
        self.events: list = []
        self.trace = None            # bench.trace.Trace of the profiled part

    def bench_path(self, *parts) -> str:
        return os.path.join(self.root, "bench", *parts)

    def metrics(self) -> list:
        """The metric entries this run reports, in ``BENCHMARK.json``
        order: end-to-end without tracing, per-layer with it."""
        group = "per_layer" if self.trace_on else "end_to_end"
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def n_done(self) -> int:
        return sum(r["t_done"] is not None for r in self.records)


class CompileCounter:
    """Counts jit traces and executable loads (compiles and persistent
    cache hits alike) while ``on``."""

    def __init__(self):
        from jax._src import dispatch

        self.events = (dispatch.JAXPR_TRACE_EVENT,
                       dispatch.BACKEND_COMPILE_EVENT)
        self.on = False
        self.n = 0

    def __call__(self, event, duration_secs, **kwargs):
        if self.on and event in self.events:
            self.n += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        self.on = True
        return self

    def __exit__(self, *exc):
        import jax

        self.on = False
        jax.monitoring.unregister_event_duration_listener(self)


def check_devices(cell: Cell):
    """The platform this run may measure on: a TPU with the chips the
    cell asks for, and its peaks from ``bench/peaks.json``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise CellError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < cell.chips:
        raise CellError(f"the cell needs {cell.chips} chips; found "
                        f"{len(devs)}")
    peaks = load_json(cell.bench_path("peaks.json"))["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise CellError(f"device kind {kind!r} is not in bench/peaks.json")
    cell.peaks = peaks[kind]


def open_loop(cell: Cell, driver):
    """Offer each request at its due moment, from ``cell.t_open``; return
    once the window has closed.  ``cell.lateness`` keeps how late each
    offer was."""
    from jax.profiler import TraceAnnotation

    for req in cell.requests:
        due = cell.t_open + req["due_s"]
        while True:
            left = due - time.perf_counter()
            if left <= 0:
                break
            time.sleep(min(left, 0.0005) if left < 0.002 else left - 0.0015)
        if cell.trace_on:
            with TraceAnnotation("bench.submit"):
                driver.submit(req, due)
        else:
            driver.submit(req, due)
        cell.lateness.append(time.perf_counter() - due)
    left = cell.t_close - time.perf_counter()
    if left > 0:
        time.sleep(left)


class Profiler(threading.Thread):
    """A ``jax.profiler`` session over ``[lead, lead + span)`` of the
    window, with the Python tracer off; the span is wrapped in the
    ``bench.trace.WINDOW`` annotation."""

    def __init__(self, cell: Cell, log_dir: str):
        super().__init__(name="bench-profiler", daemon=True)
        self.cell, self.log_dir = cell, log_dir
        self.lead = min(TRACE_LEAD_S, 0.2 * cell.seconds)
        self.span = min(TRACE_MAX_S, 0.5 * cell.seconds)
        self.error: Optional[BaseException] = None

    def run(self):
        import jax

        from bench.trace import WINDOW

        try:
            wait = self.cell.t_open + self.lead - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(WINDOW):
                    time.sleep(self.span)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — reported by the harness
            self.error = e


def device_report(cell: Cell) -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:cell.chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def read_metrics(cell: Cell) -> dict:
    out = {}
    for m in cell.metrics():
        reader = load_module(cell.bench_path("metrics", m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(cell)
        if v is None or not math.isfinite(v):
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(name: str, seed: int, seconds: float, trace: bool,
        t_process: float, root: str = ROOT, require_tpu: bool = True,
        log=None) -> dict:
    """Run one cell and return its result line (a dict)."""
    log = log or (lambda msg: print(f"[bench] {msg}", file=sys.stderr,
                                    flush=True))
    cell = Cell(name, seed, seconds, trace, root=root)
    import jax

    if require_tpu:
        check_devices(cell)
    d0 = jax.devices()[0]
    print(f"[bench] platform {d0.platform} device_kind {d0.device_kind} "
          f"count {len(jax.devices())}", flush=True)
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")

    gen = load_module(cell.bench_path("gen", cell.traffic["generator"]
                                      + ".py"),
                      "bench_gen_" + cell.traffic["generator"])
    cell.requests = gen.generate(cell.traffic, cell.seed, cell.seconds)
    if cell.trace_on:
        from repro.obs.tracer import Tracer

        cell.tracer = Tracer(capacity=max(
            Tracer.DEFAULT_CAPACITY,
            TRACER_EVENTS_PER_REQUEST * len(cell.requests)))
    drv = load_module(cell.bench_path("drivers", cell.config["driver"]
                                      + ".py"),
                      "bench_driver_" + cell.config["driver"])
    driver = drv.Driver(cell)
    driver.setup()
    cell.setup_s = time.perf_counter() - t_process
    log(f"setup {cell.setup_s:.3f} s; {len(cell.requests)} requests due in "
        f"{cell.seconds:g} s")

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with CompileCounter() as compiles:
            cell.counters_open = driver.counters()
            if cell.tracer is not None:
                cell.tracer.clear()
            prof = Profiler(cell, log_dir) if trace else None
            cell.t_open = time.perf_counter()
            cell.t_close = cell.t_open + cell.seconds
            if prof is not None:
                prof.start()
            open_loop(cell, driver)
            driver.wait(cell.t_close + DRAIN_S)
            if prof is not None:
                prof.join()
                if prof.error is not None:
                    raise prof.error
        cell.compiles_in_window = compiles.n
        cell.counters_end = driver.counters()
        cell.records = driver.records()
        answers = driver.answers()
        device = device_report(cell)
        if cell.tracer is not None:
            cell.events = cell.tracer.events()
            if cell.tracer.dropped:
                log(f"WARNING tracer dropped {cell.tracer.dropped} events")
        if trace:
            from bench.trace import Trace

            cell.trace = Trace.from_dir(log_dir)
    finally:
        driver.close()
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)

    from bench.stats import percentile

    late = [x * 1e3 for x in cell.lateness]
    log(f"generator lateness ms: p50 {percentile(late, 50)} p95 "
        f"{percentile(late, 95)} max {max(late, default=None)}; compiles "
        f"in the window {cell.compiles_in_window}")

    metrics = read_metrics(cell)
    checks = driver.check(answers)
    unfinished = len(cell.records) - cell.n_done()
    checks["requests_unfinished"] = [unfinished, 0]
    correct = all(v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": len(cell.records),
              "failed": unfinished, "metrics": metrics, "device": device}
    if trace:
        result["device"]["busy_s"] = cell.trace.busy_s()
        result["device"]["window_s"] = cell.trace.window_s
        result["breakdown"] = {"device_ops": cell.trace.top_ops(10),
                               "idle_gaps": cell.trace.idle_gaps(10)}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} {v!r} limit {lim!r} "
            f"{'ok' if v <= lim else 'FAILED'}")
    return result
