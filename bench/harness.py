"""Shared machinery of one benchmark run: finding a cell's files, the chip
check, the compile cache, compile accounting, host spans, the per-layer
metric readers, and the result line."""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# a fixed directory inside the checkout: JAX keys cache entries by it
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for: no result."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def load_workload(name: str) -> dict:
    return load_json(BENCH, "workloads", f"{name}.json")


def load_config(name: str) -> dict:
    return load_json(BENCH, "configs", f"{name}.json")


def family(config: dict):
    return importlib.import_module(f"bench.models.{config['family']}")


def kind_module(workload: dict):
    return importlib.import_module(f"bench.kinds.{workload['kind']}")


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read`` function."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- device ----------------------------------------------------------------------


def chip_devices(chips: int):
    """The cell's chips.  Raises ``NoChip`` without a TPU or with fewer
    chips than asked for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"first device is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def enable_cache() -> None:
    """JAX's persistent compilation cache, for every program however quick
    to compile, so that only a cell's first run compiles: in
    ``$JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads it), else
    in the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Seconds and count of backend compilations (a compile, or a load
    from the persistent cache) since the process started listening."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


def span(name: str):
    """A host span in the profiler's trace (nearly free when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced(log_dir: str):
    """Profile the block; the ``bench.window`` span bounds what the trace
    reduction reads."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with span("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()


# -- one run ------------------------------------------------------------------------


@dataclass
class Run:
    """What a kind's driver gets, and fills in."""

    name: str
    workload: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float  # perf_counter at process start
    devices: list
    peaks: Optional[dict]
    compile_clock: CompileClock
    out_dir: str
    fault: Optional[str] = None
    # filled by the driver
    setup_s: float = 0.0
    compile_s: float = 0.0
    end_to_end: Dict[str, float] = field(default_factory=dict)
    counters: Dict[str, Any] = field(default_factory=dict)
    checks: List[Dict[str, Any]] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: Optional[int] = None
    window_compiles: int = 0
    trace_data: Any = None
    notes: List[str] = field(default_factory=list)

    def mark_window_start(self) -> float:
        t = time.perf_counter()
        self.setup_s = t - self.t_start
        self.compile_s = self.compile_clock.seconds
        self._window_compiles0 = self.compile_clock.count
        return t

    def mark_window_end(self) -> None:
        self.window_compiles = self.compile_clock.count - self._window_compiles0

    def read_memory_peak(self) -> None:
        """The fullest chip's peak: buffers in use plus, where the chip
        keeps it apart, the space reserved for the programs' scratch."""
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            self.notes.append(f"memory_stats device {d.id}: {stats}")
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"])
                             + int(stats.get("peak_bytes_reserved", 0)))
        self.memory_peak_bytes = max(peaks) if peaks else None

    def check(self, name: str, value: float, limit: float) -> None:
        """A number compared with its reference: at most ``limit``."""
        self.checks.append({"name": name, "value": float(value), "limit": float(limit)})

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            c["value"] <= c["limit"] for c in self.checks)


def applicable(spec: dict, section: str, cell: str, reported: List[str]) -> List[dict]:
    """Entries of ``spec[section]`` that this cell reports: those listing
    the cell under ``workloads``, or, without that key, those (per-layer)
    whose end-to-end metric the cell reports, or (end-to-end) all."""
    out = []
    for m in spec[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, t_start: float, *,
             workload: Optional[dict] = None, config: Optional[dict] = None,
             spec: Optional[dict] = None, require_chip: bool = True,
             fault: Optional[str] = None, out_dir: Optional[str] = None) -> Run:
    """Run one cell once.  ``require_chip=False`` and ``fault`` are for
    the tests alone: they rehearse a run on any backend, and break the
    timed path underneath."""
    import jax

    from bench.peaks import peaks

    workload = workload or load_workload(name)
    config = config or load_config(workload["config"])
    if require_chip:
        devices = chip_devices(workload["chips"])
        pk = peaks(devices[0].device_kind)
        enable_cache()
    else:
        devices = jax.devices()[: workload["chips"]]
        pk = None
    run = Run(name=name, workload=workload, config=config, seed=seed,
              seconds=seconds, trace=trace, t_start=t_start, devices=devices,
              peaks=pk, compile_clock=CompileClock(),
              out_dir=out_dir or os.path.join(ROOT, ".bench_out", name, str(seed)),
              fault=fault)
    kind_module(workload).run(run)
    return run


def result(run: Run, spec: dict) -> dict:
    """The result line's object, in the order the contract gives."""
    e2e = applicable(spec, "end_to_end", run.name, [])
    e2e_names = [m["name"] for m in e2e]
    metrics: Dict[str, Dict[str, Any]] = {}
    if not run.trace:
        values = dict(run.end_to_end, setup_s=run.setup_s)
        for m in e2e:
            if m["name"] not in values:
                raise KeyError(f"cell {run.name} produced no {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in applicable(spec, "per_layer", run.name, e2e_names):
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = run.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(run.devices), "memory_peak_bytes": run.memory_peak_bytes}
    out: Dict[str, Any] = {"correct": run.correct, "attempted": run.attempted,
                           "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace and run.trace_data is not None:
        t = run.trace_data
        device["busy_s"] = t.mean_busy_s()
        device["window_s"] = t.window_s
        out["breakdown"] = {"device_ops": t.top_ops(10), "idle_gaps": t.idle_gaps(10)}
    if run.window_compiles:
        out["window_compiles"] = run.window_compiles
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in run.checks}
    return out


def check_lines(run: Run) -> List[str]:
    return [f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}" for c in run.checks]


def print_result(run: Run, spec: dict) -> None:
    line = json.dumps(result(run, spec))
    for note in run.notes:
        print(note, file=sys.stderr)
    for text in check_lines(run):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
