"""Profiler (reference: python/paddle/profiler/profiler.py:346 + the C++
layered tracers in paddle/fluid/platform/profiler/).

TPU-native mapping (SURVEY.md §5): device-side tracing is jax.profiler
(XPlane -> TensorBoard/perfetto, the CUPTI analog); host spans are
RecordEvent instrumentation aggregated into a summary table. Both run under
one Profiler orchestrator with the reference's scheduler-state API."""
from __future__ import annotations

import contextlib
import enum
import glob
import os
import re
import tempfile
import threading
import time
from collections import defaultdict
from typing import Callable, Optional

import jax

from . import metrics

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "export_protobuf",
           "load_profiler_result", "SummaryView", "metrics",
           "host_tracing_active", "tracing", "scopes", "digest",
           "aggregate",
           "timeline", "slo", "headroom", "TraceContext"]


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SummaryView(enum.Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


def make_scheduler(closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        cycle = closed + ready + record
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


class _HostEventCollector(threading.local):
    def __init__(self):
        self.events = []
        self.active = False


_collector = _HostEventCollector()


def host_tracing_active() -> bool:
    """True while a Profiler is collecting host spans — instrumented hot
    paths check this before opening per-event RecordEvent spans so the
    always-on cost is one attribute read."""
    return _collector.active


class RecordEvent:
    """Host instrumentation span (reference: platform/profiler RecordEvent).

    One span path, one clock: besides the host-event list of an active
    `Profiler`, the span enters a `jax.profiler.TraceAnnotation` of the
    same name, so it lands in the xplane of whatever device trace is
    running (a `Profiler`'s, `jax.profiler.trace`, a profiler server's)
    beside the device's operations. With no trace running the annotation
    is a flag test inside the profiler library. `traced` says, once the
    span has ended, whether a trace was recording from its start to its
    end: whether the xplane holds it whole."""

    __slots__ = ("name", "begin", "traced", "_annotation")

    def __init__(self, name: str, event_type=None):
        self.name = name
        self.begin = None
        self.traced = False
        self._annotation = None

    def __enter__(self):
        self.traced = jax.profiler.TraceAnnotation.is_enabled()
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.begin = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def end(self):
        if self.begin is None:
            return
        end = time.perf_counter()
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        self.traced = self.traced \
            and jax.profiler.TraceAnnotation.is_enabled()
        if _collector.active:
            _collector.events.append((self.name, self.begin, end))
        self.begin = None


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    def handler(prof):
        prof._export_dir = dir_name
        prof.export(os.path.join(
            dir_name, (worker_name or "worker") + ".json"))
    return handler


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    return export_chrome_tracing(dir_name, worker_name)


def load_profiler_result(filename: str):
    import json

    with open(filename) as f:
        return json.load(f)


class Profiler:
    """Orchestrator with scheduler states. Device tracing = jax.profiler
    (XPlane); host spans = RecordEvent collection."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self.scheduler = make_scheduler(closed=max(lo, 0), ready=0,
                                            record=hi - lo, repeat=1)
        else:
            self.scheduler = scheduler or (
                lambda step: ProfilerState.RECORD)
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self._jax_tracing = False
        self._trace_dir = None
        self._trace_started = 0.0
        self._step_times = []
        self._last_step_t = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def _jax_start(self):
        """Start the device trace. A failure raises: a session that
        silently records no xplane reports nothing in its ModelView."""
        if not self._jax_tracing and not self.timer_only:
            self._trace_dir = os.environ.get("PT_PROFILE_DIR") \
                or os.path.join(tempfile.gettempdir(), "paddle_tpu_profile")
            self._trace_started = time.time() - 1.0   # mtime's grain
            jax.profiler.start_trace(self._trace_dir)
            self._jax_tracing = True

    def _jax_stop(self):
        if self._jax_tracing:
            self._jax_tracing = False
            jax.profiler.stop_trace()

    def start(self):
        _collector.active = True
        _collector.events = []
        self.state = self.scheduler(self.step_num)
        if self.state in (ProfilerState.RECORD,
                          ProfilerState.RECORD_AND_RETURN):
            self._jax_start()
        self._last_step_t = time.perf_counter()

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append((now - self._last_step_t, num_samples))
        self._last_step_t = now
        self.step_num += 1
        new_state = self.scheduler(self.step_num)
        if new_state != self.state:
            if new_state in (ProfilerState.RECORD,
                             ProfilerState.RECORD_AND_RETURN):
                self._jax_start()
            elif self.state in (ProfilerState.RECORD,
                                ProfilerState.RECORD_AND_RETURN):
                self._jax_stop()
                if self.on_trace_ready:
                    self.on_trace_ready(self)
            self.state = new_state

    def stop(self):
        self._jax_stop()
        _collector.active = False
        if self.on_trace_ready and self.state in (
                ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self.on_trace_ready(self)

    def export(self, path: str, format: str = "json"):
        """Export host spans as chrome-trace; XPlane files live in the
        jax.profiler trace dir."""
        import json

        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        events = []
        for name, b, e in _collector.events:
            events.append({
                "name": name, "ph": "X", "pid": 0, "tid": 0,
                "ts": b * 1e6, "dur": (e - b) * 1e6,
            })
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "xplane_dir": self._trace_dir}, f)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        """The host-span table; with `views=SummaryView.ModelView` (or a
        list holding it) the device time by pass and block instead, read
        from the xplane this session wrote."""
        if views is not None and SummaryView.ModelView in (
                views if isinstance(views, (list, tuple)) else [views]):
            table = self.model_view()
            print(table)
            return table
        agg = defaultdict(lambda: [0.0, 0])
        for name, b, e in _collector.events:
            agg[name][0] += (e - b) * 1e3
            agg[name][1] += 1
        lines = [f"{'Name':<40} {'Calls':>8} {'Total(ms)':>12} {'Avg(ms)':>12}"]
        for name, (total, calls) in sorted(agg.items(),
                                           key=lambda kv: -kv[1][0]):
            lines.append(
                f"{name:<40} {calls:>8} {total:>12.3f} "
                f"{total / max(calls, 1):>12.3f}")
        table = "\n".join(lines)
        print(table)
        return table

    def model_view(self) -> str:
        """Device time by pass (forward / recompute / backward /
        optimizer) and block (`scopes.scope` names) of every program
        `profiler.scopes` can still compile, from the newest xplane under
        this session's trace directory: the reference profiler's
        ModelView. The device's events are joined with each program's
        compiled text by instruction name (`scopes.device_time_by_phase`);
        where the trace says which program ran when (a TPU's "XLA Modules"
        line) a program is given only its own events."""
        paths = [] if self._trace_dir is None else sorted(
            (p for p in glob.glob(os.path.join(
                self._trace_dir, "**", "*.xplane.pb"), recursive=True)
             if os.path.getmtime(p) >= self._trace_started),
            key=os.path.getmtime)
        if not paths:
            return "ModelView: this session wrote no device trace"
        ops, modules = _device_events(paths[-1])
        lines = [f"{'Program / pass / block':<44} {'ms':>12} {'share':>8}"]
        by_name = defaultdict(list)
        for prog in scopes.live_programs():
            by_name[prog.name].append(prog)
        for program, progs in by_name.items():
            runs = [(s, e) for n, s, e in modules
                    if n.startswith(f"jit_{program}(")]
            mine = [ev for ev in ops
                    if any(s <= ev[1] and ev[2] <= e for s, e in runs)] \
                if runs else ops
            # several programs of one name (an engine each, a token
            # length each): one map of what they agree on
            seconds, found, inherited = scopes.device_time_by_phase(
                mine, scopes.merge_phases(p.phases() for p in progs))
            total = sum(seconds.values())
            if found < 0.05 or total <= 0.0:
                continue        # this program did not run in the trace
            lines.append(f"{program:<44} {total * 1e3:>12.3f} "
                         f"{'found ' + format(found, '.1%'):>8}")
            by_pass = defaultdict(float)
            for phase, t in seconds.items():
                by_pass[phase if isinstance(phase, str) else phase[0]] += t
            for name in (*scopes.PASSES, scopes.UNATTRIBUTED):
                if name not in by_pass:
                    continue
                lines.append(f"  {name:<42} {by_pass[name] * 1e3:>12.3f} "
                             f"{by_pass[name] / total:>8.1%}")
                blocks = sorted(((ph[1], t) for ph, t in seconds.items()
                                 if not isinstance(ph, str)
                                 and ph[0] == name), key=lambda kv: -kv[1])
                lines += [f"    {blk:<40} {t * 1e3:>12.3f} "
                          f"{t / total:>8.1%}" for blk, t in blocks]
            lines.append(f"  {'of which phase inherited, not named':<42} "
                         f"{inherited * total * 1e3:>12.3f} "
                         f"{inherited:>8.1%}")
        if len(lines) == 1:
            return "ModelView: no registered program ran in the trace"
        return "\n".join(lines)

    # throughput timer (reference: profiler/timer.py benchmark hooks)
    def step_info(self, unit="samples"):
        if not self._step_times:
            return "no steps recorded"
        import numpy as np

        times = np.asarray([t for t, _ in self._step_times[-20:]])
        ips = None
        samples = [n for _, n in self._step_times[-20:] if n]
        if samples:
            ips = np.asarray(samples) / times[-len(samples):]
        msg = f"avg step: {times.mean() * 1e3:.2f} ms"
        if ips is not None:
            msg += f", ips: {ips.mean():.1f} {unit}/s"
        return msg


_CONTAINER = re.compile(r"(^|\s)(while|conditional|call)(\.\d+)?(\(|$)")


def _device_events(xplane_path: str):
    """(ops, modules) of the first device in a profiler trace, each a list
    of (name, start_s, end_s). A TPU plane has an "XLA Ops" line, whose
    events are named by their HLO instruction's text, and an "XLA Modules"
    line that says which program ran when. The CPU backend has neither:
    its thunks are host events named by the instruction alone, on XLA's
    own threads, and `modules` is empty. Operations that only contain
    others (`while`, `conditional`, `call`) are left out."""
    data = jax.profiler.ProfileData.from_file(xplane_path)

    def events(line):
        return [(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9) for e in line.events
                if not _CONTAINER.search(e.name)]

    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            by_name = {line.name: line for line in plane.lines}
            if "XLA Ops" in by_name:
                return (events(by_name["XLA Ops"]),
                        events(by_name["XLA Modules"])
                        if "XLA Modules" in by_name else [])
    ops = []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("tf_XLA"):
                    ops += [ev for ev in events(line)
                            if " " not in ev[0] and ":" not in ev[0]]
    return ops, []


# fleet observability plane — imported last: tracing layers TraceContext
# propagation on RecordEvent (above), aggregate ships registry snapshots
# across processes, digest is the mergeable quantile sketch both use.
from . import digest           # noqa: E402
from . import scopes           # noqa: E402
from . import tracing          # noqa: E402
from . import aggregate        # noqa: E402
# the SLO engine (ISSUE 16): timeline = the time dimension over the
# registry, slo = objectives/attainment/burn alerts over gateway
# outcomes, headroom = the AutoScaler advisory interface
from . import timeline         # noqa: E402
from . import slo              # noqa: E402
from . import headroom         # noqa: E402
from .tracing import TraceContext  # noqa: E402
from .aggregate import FleetAggregator  # noqa: E402
from .timeline import Timeline, load_spill  # noqa: E402
from .slo import SLOAlert, SLOObjective, SLOTracker  # noqa: E402
from .headroom import ScaleAdvice, ScaleAdvisor  # noqa: E402
