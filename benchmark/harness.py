"""What every runner and `run.py` share: finding a cell's files by name,
the device check, the compile counter, percentiles, the traced window and
the evaluation of per-layer metrics.
"""
from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_SECONDS = 8.0      # a traced run traces this much of its window


class BenchmarkError(Exception):
    """A run that cannot give a result: no result line, exit code not 0."""


# -- files found by name ------------------------------------------------

def load_json(*parts: str, bench_dir: str = BENCH_DIR) -> dict:
    path = os.path.join(bench_dir, *parts)
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`: a runner, a program's builder, a
    reader, a work file or a reference, registered by nothing but its file
    name."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


# the keys by which a configuration names files, and where each file lies
NAMED_FILES = {"entry": "runners/{}.py", "program": "programs/{}.py",
               "step_work": "work/{}.py", "reference": "reference/{}.py",
               "published_as": "published/{}.json"}


def check_named_files(config: dict, bench_dir: str = BENCH_DIR) -> None:
    """BenchmarkError that names the key and the missing file, where the
    configuration names a file the benchmark does not have."""
    for key, where in NAMED_FILES.items():
        if key not in config:
            continue
        rel = where.format(config[key])
        if not os.path.isfile(os.path.join(bench_dir, rel)):
            raise BenchmarkError(
                f"configuration {config.get('name')!r} names "
                f"\"{key}\": {config[key]!r}, and there is no "
                f"benchmark/{rel}")


@dataclass
class Cell:
    """One entry of `workloads` with its files loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    manifest: dict

    @classmethod
    def find(cls, workload: str, root: str = ROOT,
             bench_dir: str = BENCH_DIR) -> "Cell":
        manifest = load_manifest(root)
        entry = next((w for w in manifest["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            raise BenchmarkError(
                f"no workload {workload!r} in BENCHMARK.json (has: "
                f"{[w['name'] for w in manifest['workloads']]})")
        cfg = next(c for c in manifest["configs"]
                   if c["name"] == entry["config"])
        with open(os.path.join(root, cfg["file"])) as f:
            config = json.load(f)
        traffic = load_json("traffic", entry["traffic"] + ".json",
                            bench_dir=bench_dir)
        limits = load_json("limits", workload + ".json", bench_dir=bench_dir)
        return cls(workload, int(entry["chips"]), config, traffic, limits,
                   manifest)

    def end_to_end_names(self) -> List[str]:
        return [m["name"] for m in self.manifest["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer_names(self) -> List[str]:
        return [m["name"] for m in self.manifest["per_layer"]
                if self.name in m.get("workloads", [self.name])]


# -- the device ---------------------------------------------------------

def setup_compile_cache() -> str:
    """JAX's persistent compilation cache: where the environment says, else
    at a fixed path inside the checkout. Every program is kept, however
    quick its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_tpu(chips: int):
    """The devices, or BenchmarkError naming what is missing. JAX may come
    up on the CPU with only a warning, so the platform is asserted."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchmarkError(
            f"no TPU: jax.devices()[0].platform is "
            f"{devices[0].platform!r}; the benchmark runs only on the chip")
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell needs {chips} chip(s), JAX found {len(devices)}")
    return devices[:chips]


def load_peak(device_kind: str) -> dict:
    peaks = load_json("peaks.json")
    if device_kind not in peaks:
        raise BenchmarkError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"(has: {sorted(peaks)}); add it with its source")
    return peaks[device_kind]


def memory_peak_bytes(devices) -> Optional[int]:
    """`peak_bytes_in_use` of the fullest device (None off the chip)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


# -- the window ----------------------------------------------------------

class CompileCounter:
    """Counts programs lowered or compiled while `open` is set: there must
    be none inside a measured window."""

    def __init__(self):
        import jax

        self.open = False
        self.events: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **_kw):
        if self.open and "/jax/core/compile/" in name \
                and "jaxpr_trace" not in name:
            self.events.append(name)

    def close(self) -> List[str]:
        """Stop counting and take the listener off again; the events."""
        from jax._src import monitoring

        self.open = False
        unregister = getattr(
            monitoring, "_unregister_event_duration_listener_by_callback",
            None)
        if unregister is not None:
            unregister(self._on)
        return self.events


def annotate(name: str):
    """A host span on the profiler's clock (`bench.<name>`)."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


class Tracer:
    """Traces the first TRACE_SECONDS of a window into a fixed directory
    inside the checkout, and reads it back as a `reduce_trace.Trace`."""

    def __init__(self, enabled: bool, workload: str):
        self.enabled = enabled
        self.dir = os.path.join(ROOT, ".bench_trace", workload)
        self.running = False
        self.started_at = None

    def start(self):
        if not self.enabled:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.running = True
        self.started_at = time.perf_counter()

    def due(self) -> bool:
        return self.running and \
            time.perf_counter() - self.started_at >= TRACE_SECONDS

    def stop(self):
        if self.running:
            import jax

            jax.profiler.stop_trace()
            self.running = False

    def load(self):
        from . import reduce_trace

        try:
            return reduce_trace.load_xplane(
                reduce_trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation between order
    statistics (numpy's default), of all the values given."""
    v = sorted(values)
    if not v:
        raise BenchmarkError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


# -- results -------------------------------------------------------------

@dataclass
class Compared:
    """One number of the comparison that decides `correct`, beside its
    limit. Correct while value <= limit."""
    value: float
    limit: float
    where: str = ""             # e.g. the leaf that reads worst

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)       # NaN is not ok


@dataclass
class RunResult:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    compared: Dict[str, Compared]
    stats: Dict[str, Any] = field(default_factory=dict)
    trace: Any = None                      # reduce_trace.Trace of a traced run
    memory_peak_bytes: Optional[int] = None
    compiles_in_window: List[str] = field(default_factory=list)

    @property
    def correct(self) -> bool:
        return (bool(self.compared) and self.failed == 0
                and not self.compiles_in_window
                and all(c.ok for c in self.compared.values()))


@dataclass
class ReadContext:
    """What a reader sees."""
    trace: Any
    window: Any
    model: dict
    traffic: dict
    peak: dict
    chips: int
    stats: dict
    load: Any = load_module


def per_layer_metrics(cell: Cell, result: RunResult, peak: dict,
                      bench_dir: str = BENCH_DIR) -> Dict[str, dict]:
    """{metric: {"value", "unit"}} for the cell's per-layer metrics whose
    reader found something to read."""
    from . import reduce_trace

    ctx = ReadContext(result.trace, reduce_trace.window_of(result.trace),
                      cell.config, cell.traffic, peak, cell.chips,
                      result.stats)
    out = {}
    for name in cell.per_layer_names():
        spec = load_json("layer_metrics", name + ".json",
                         bench_dir=bench_dir)
        value = load_module("readers", spec["reader"]).read(
            spec.get("params", {}), ctx)
        if value is not None:
            out[name] = {"value": value, "unit": spec["unit"]}
    return out
