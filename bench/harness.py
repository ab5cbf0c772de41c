"""The harness: runs one cell of ``BENCHMARK.json`` once and prints one
result line.

Everything that belongs to one cell is found by name:

* ``BENCHMARK.json`` names the cell's configuration (its ``file``), its
  traffic and its chips, and lists the metrics;
* ``bench/traffic/<traffic>.json`` names the driver and holds the mix's
  parameters;
* ``bench/drivers/<driver>.py`` runs that kind of cell: ``setup``,
  ``window``, ``traced`` and ``check``;
* ``bench/limits/<cell>.json`` holds the limit of each number compared;
* ``bench/metrics/<metric>.py`` reads one per-layer metric from what the
  run recorded, or returns None when there is nothing to read.

A run: set-up (timed as ``setup_s`` from process start), the measured
window, with ``--trace 1`` a short traced stretch in a run of its own
kind, the device memory peak, and last the comparison with the plain
reference, which decides ``correct``.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"
COMPILE_CACHE = ROOT / ".jax_cache"


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Run:
    """What one run of one cell records; handed to the driver."""

    def __init__(self, spec: dict, cell: dict, seed: int, seconds: float,
                 bench_dir: Path):
        self.spec, self.cell = spec, cell
        self.root = bench_dir.parent
        self.name = cell["name"]
        self.seed, self.seconds = seed, seconds
        self.chips = int(cell["chips"])
        cfg_entry = next(c for c in spec["configs"]
                         if c["name"] == cell["config"])
        self.config = _load_json(bench_dir.parent / cfg_entry["file"])
        self.traffic = _load_json(bench_dir / "traffic" /
                                  f"{cell['traffic']}.json")
        self.limits = _load_json(bench_dir / "limits" / f"{self.name}.json")
        self.devices: list = []
        self.e2e: dict[str, float] = {}          # end-to-end metrics
        self.readings: dict = {}                 # what metric readers read
        self.spans: dict[str, list[float]] = defaultdict(list)
        self.checks: list[tuple[str, float, float]] = []
        self.attempted = self.failed = 0
        self.compiles = 0
        self.trace_summary: dict | None = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: recorded in memory and, while tracing, in the
        profiler's trace as ``bench.<name>``."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.spans[name].append(time.perf_counter() - t0)

    def check(self, name: str, value: float, limit: float) -> None:
        """One number compared: it passes when ``value <= limit``."""
        self.checks.append((name, float(value), float(limit)))


def _load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _count_compiles(run: Run) -> None:
    """Count every executable built or loaded from the compile cache."""
    import jax
    from jax._src import dispatch

    def listener(event: str, _secs: float, **_kw) -> None:
        if event == dispatch.BACKEND_COMPILE_EVENT:
            run.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(listener)


def find_devices(chips: int, require_tpu: bool) -> list:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform "
                       f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices


def use_compile_cache(root: Path, chips: int = 1) -> None:
    """JAX's persistent cache at one fixed path inside the checkout;
    every program is cached, so only a cell's first run compiles.

    A cell on more than one chip caches nothing and compiles in every
    run's set-up: a two-chip program read back from a persistent cache
    written on another host has halted a TPU v5e core, so the program
    keeps the cache off for multi-device processes
    (``launch.mesh.use_compile_cache``) and the benchmark does the same."""
    import jax
    if chips > 1:
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        return
    jax.config.update("jax_compilation_cache_dir",
                      str(root / COMPILE_CACHE.name))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def applies(metric: dict, cell: dict, spec: dict) -> bool:
    """Whether ``metric`` is reported in ``cell``: listed there, or, with
    no ``workloads`` key, wherever the metric it moves is reported."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    moved = next((m for m in spec["end_to_end"]
                  if m["name"] == metric.get("moves")), None)
    if moved is None:                       # an end-to-end metric itself
        return True
    return applies(moved, cell, spec)


def read_metric(name: str, readings: dict, bench_dir: Path = BENCH):
    """Run the reader ``bench/metrics/<name>.py`` on ``readings``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(readings)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, bench_json: Path = ROOT / "BENCHMARK.json",
             require_tpu: bool = True) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    bench_dir = bench_json.parent / "bench"
    spec = _load_json(bench_json)
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no cell {name!r} in {bench_json}")
    run = Run(spec, cell, seed, seconds, bench_dir)
    run.devices = find_devices(run.chips, require_tpu)
    use_compile_cache(run.root, run.chips)
    _count_compiles(run)
    if str(bench_dir) not in sys.path:
        sys.path.insert(0, str(bench_dir.parent))
    driver = importlib.import_module(f"bench.drivers.{run.traffic['driver']}")

    state = driver.setup(run)
    run.e2e["setup_s"] = time.perf_counter() - t_start
    before = run.compiles
    driver.window(run, state)
    window_compiles = run.compiles - before
    if trace:
        run.trace_summary = _traced(run, driver, state)
    used = run.devices[:run.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    driver.check(run, state)
    run.check("window_compiles", window_compiles, 0)
    correct = all(v <= lim for _, v, lim in run.checks)

    readings = dict(run.readings, trace=run.trace_summary,
                    device_kind=used[0].device_kind, chips=run.chips,
                    spans=dict(run.spans), config=run.config,
                    traffic=run.traffic)
    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in spec[kind]:
        if not applies(m, cell, spec):
            continue
        value = run.e2e.get(m["name"]) if not trace else \
            read_metric(m["name"], readings, bench_dir)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = used[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(run.devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace_summary is not None:
        t = run.trace_summary
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = {n: {"value": v, "limit": lim}
                     for n, v, lim in run.checks}
    return out


def _traced(run: Run, driver, state) -> dict:
    """A short stretch of the cell's work under the profiler, reduced
    with the benchmark's own code."""
    import jax

    from bench.lib import trace as tr
    log_dir = run.root / OUT.name / "trace" / run.name
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir)
    jax.profiler.start_trace(str(log_dir))
    try:
        with run.span("traced"):
            driver.traced(run, state)
    finally:
        jax.profiler.stop_trace()
    events = tr.load_events(tr.find_xplane(str(log_dir)),
                            cpu_ops=run.devices[0].platform == "cpu")
    return tr.reduce_trace(events, tr.span_window(events, "bench.traced"))


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except NoDevice as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    for n, c in out["checks"].items():
        print(f"check {n} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0
