"""The general harness: finds a cell's configuration, mix, driver and
metric readers by name, checks the device, runs the driver, and prints the
result line.  It holds nothing particular to any cell.

Layout it reads (``<bench>`` is the directory holding this file):
  BENCHMARK.json                   cells, configurations and metrics
  <bench>/configs/<config>.json    a configuration ("file" in BENCHMARK.json)
  <bench>/mixes/<traffic>.json     a traffic mix; its "driver" names ...
  <bench>/drivers/<driver>.py      ... the code that runs it: run(Cell)
  <bench>/metrics/<metric>.py      read(facts) -> value, or None
  <bench>/peaks.json               the chip's peaks, keyed by device_kind
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class HarnessError(RuntimeError):
    """A run that cannot give a result: no chip, an unknown device, a
    compile inside the window."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find(search, kind, name, suffix):
    """The file ``<dir>/<kind>/<name><suffix>`` in the first of ``search``
    that has it."""
    for d in search:
        p = os.path.join(d, kind, name + suffix)
        if os.path.exists(p):
            return p
    raise HarnessError(f"no {kind[:-1]} named {name!r} under {search}")


def load_module(path):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything a driver needs: the cell's entry, its configuration and
    mix (parsed files), the run's seed, window and trace flag, and the
    device facts."""

    name: str
    workload: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    device: dict
    peaks: dict
    search: list


@dataclasses.dataclass
class Outcome:
    """What a driver hands back.  ``e2e`` holds the end-to-end metrics by
    name; ``facts`` what the metric readers read; ``checks`` the numbers
    compared for ``correct`` as (name, value, limit)."""

    e2e: dict
    facts: dict
    attempted: int
    failed: int
    checks: list
    breakdown: dict = None

    @property
    def correct(self):
        return all(v <= lim for _, v, lim in self.checks) and bool(self.checks)


def cell_spec(spec, workload):
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise HarnessError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
    w = cells[workload]
    cfgs = {c["name"]: c for c in spec["configs"]}
    return w, cfgs[w["config"]]


def check_device(platform, kind, count, chips, peaks):
    """Refuse a run off a TPU, on fewer chips than the cell asks, or on a
    device kind that the peaks table does not hold."""
    if platform != "tpu":
        raise HarnessError(f"no TPU: JAX found platform {platform!r}")
    if count < chips:
        raise HarnessError(f"the cell needs {chips} chips, found {count}")
    if kind not in peaks["devices"]:
        raise HarnessError(f"device kind {kind!r} has no peaks in "
                           f"peaks.json")


def device_facts(chips, peaks, require_tpu=True):
    """Platform, kind and count as JAX reports them, checked by
    :func:`check_device` (skipped only by the tests' smoke runs)."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu:
        check_device(d.platform, d.device_kind, len(devs), chips, peaks)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def chip_peaks(cell):
    """(peak bf16 FLOP/s, peak HBM bytes/s) of the run's device."""
    p = cell.peaks["devices"][cell.device["kind"]]
    return p["bf16_flops"], p["hbm_bytes_per_s"]


def memory_peak_bytes(chips):
    import jax
    stats = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return max(stats)


class CompileCounter:
    """Counts JAX's trace, lowering and compile events in this process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1


@contextlib.contextmanager
def window(counter):
    """The measured window: fails the run if anything compiles in it."""
    before = counter.count
    yield
    if counter.count != before:
        raise HarnessError(f"{counter.count - before} compile events inside "
                           f"the measured window")


WINDOW_SPAN = "bench.window"


class Tracer:
    """The profiler around the measured window of a ``--trace 1`` run.
    Spans: ``span(name)`` marks what the host does (``bench.*``)."""

    def __init__(self, enabled):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if enabled else None
        self.trace = None
        self.window_ns = None

    def span(self, name):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def around(self):
        if not self.enabled:
            yield
            return
        import jax
        jax.profiler.start_trace(self.dir)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    def load(self):
        """Reduce the written trace; the window is the ``bench.window``
        span on the trace's own clock."""
        from bench import trace as T
        paths = [os.path.join(dp, f) for dp, _, fs in os.walk(self.dir)
                 for f in fs if f.endswith(".xplane.pb")]
        if not paths:
            raise HarnessError("the profiler wrote no trace")
        tr = T.load(paths[0])
        win = [s for s in tr.spans if s[0] == WINDOW_SPAN]
        if not win:
            raise HarnessError("the trace holds no window span")
        _, start, dur = win[0]
        tr.spans = [s for s in tr.spans if s[0] != WINDOW_SPAN]
        self.trace, self.window_ns = tr, (start, start + dur)
        shutil.rmtree(self.dir, ignore_errors=True)
        return tr


def trace_facts(tracer):
    """The device facts every traced cell reports."""
    from bench import trace as T
    tr, win = tracer.trace, tracer.window_ns
    busy = T.busy_s(tr, win)
    window_s = (win[1] - win[0]) / 1e9
    device = {"busy_s": busy, "window_s": window_s}
    breakdown = {"device_ops": T.top_ops(tr), "idle_gaps": T.idle_gaps(tr, win)}
    return device, breakdown


def applies(metric, workload, e2e_names):
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def read_metrics(spec, workload, facts, search):
    """The cell's per-layer metrics, each from its reader; a reader that
    finds nothing to read leaves its metric out."""
    names = {m["name"] for m in spec["end_to_end"]
             if "workloads" not in m or workload in m["workloads"]}
    metrics = {}
    for m in spec["per_layer"]:
        if not applies(m, workload, names):
            continue
        reader = load_module(find(search, "metrics", m["name"], ".py"))
        v = reader.read(facts)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics


def run_cell(root, workload, seed, seconds, trace, *, search=None,
             require_tpu=True, compile_cache=True, t_start=None):
    """One run of one cell of ``<root>/BENCHMARK.json``; returns the result
    object (printed by :func:`emit`).  ``search`` lists the directories
    searched for mixes, drivers and metrics (``<root>/bench`` alone by
    default).  ``require_tpu=False`` is for the tests alone: the command
    line always requires the chip."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    search = search or [os.path.join(root, "bench")]
    w, c = cell_spec(spec, workload)
    config = load_json(os.path.join(root, c["file"]))
    mix = load_json(find(search, "mixes", w["traffic"], ".json"))
    peaks = load_json(find(search, "", "peaks", ".json"))
    device = device_facts(w["chips"], peaks, require_tpu)
    if compile_cache:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell = Cell(name=workload, workload=w, config=config, mix=mix, seed=seed,
                seconds=seconds, trace=trace, device=device, peaks=peaks,
                search=search)
    driver = load_module(find(search, "drivers", mix["driver"], ".py"))
    out = driver.run(cell, t_start)
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if trace:
        metrics = read_metrics(spec, workload, out.facts, search)
    else:
        metrics = {m["name"]: {"value": out.e2e[m["name"]], "unit": m["unit"]}
                   for m in e2e if m["name"] in out.e2e}
    device = dict(device, memory_peak_bytes=out.facts["memory_peak_bytes"])
    if trace:
        device.update(out.facts["trace_device"])
    res = {"correct": out.correct, "attempted": out.attempted,
           "failed": out.failed, "metrics": metrics, "device": device}
    if trace and out.breakdown:
        res["breakdown"] = out.breakdown
    res["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in out.checks}
    return res


def emit(res):
    """The numbers compared, last on stderr; the result, last on stdout."""
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
