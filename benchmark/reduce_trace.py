"""From a profiler trace to numbers: the one reduction every PR shares.

A `Trace` is what the readers and the breakdown work on: for each device
the operations that ran on it, and the benchmark's own host annotations,
all as (name, start_s, end_s) on one clock. `load_xplane` fills one from
the `.xplane.pb` the JAX profiler writes; tests build one by hand.

Reductions: the union of busy intervals, the idle share, a kernel's summed
time by name prefix, the time collectives run with no compute beside them,
and the attribution of idle gaps to the annotation the host was inside.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]            # name, start_s, end_s

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# an op is a collective by the name XLA gives it (with or without -start /
# -done halves of an asynchronous pair)
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")


@dataclass
class Trace:
    """device_ops holds leaf operations only: a `while` is left out, the
    operations of its body are in."""
    device_ops: Dict[int, List[Event]] = field(default_factory=dict)
    annotations: List[Event] = field(default_factory=list)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Points of the disjoint sorted `a` not in the disjoint sorted `b`."""
    out = []
    for lo, hi in a:
        cur = lo
        for s, e in b:
            if e <= cur:
                continue
            if s >= hi:
                break
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


_KIND = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
# operations that only contain others (their bodies' operations are events
# of their own on the same line)
CONTAINER_KINDS = ("while", "conditional", "call")


def op_kind(name: str) -> str:
    """The HLO opcode of a device event. On the chip an event's name is the
    instruction's text, `%fusion.4 = bf16[8,128]{1,0} fusion(...), ...`;
    a name without that form is its own kind."""
    rhs = name.split(" = ", 1)
    if len(rhs) == 2:
        m = _KIND.search(" " + rhs[1])
        if m:
            return m.group(1)
    return name


def short_name(name: str) -> str:
    """`%fusion fusion bf16[8,128]` for the instruction text above."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    out_type = re.sub(r"\{[^}]*\}", "",
                      rhs.split(" " + op_kind(name) + "(")[0])
    # the same instruction of another layer differs only by its number
    lhs = re.sub(r"\.\d+$", "", lhs)
    return f"{lhs} {op_kind(name)} {out_type}"[:120]


def is_container(name: str) -> bool:
    return op_kind(name) in CONTAINER_KINDS


def is_collective(name: str) -> bool:
    kind = op_kind(name)
    return any(w in kind for w in COLLECTIVE_WORDS)


def window_of(trace: Trace, annotation: Optional[str] = None) -> Interval:
    """The traced window: from the first to the last annotation of the
    given name (or of any), else the span of the device operations."""
    spans = [(s, e) for n, s, e in trace.annotations
             if annotation is None or n == annotation]
    if not spans:
        spans = [(s, e) for ops in trace.device_ops.values()
                 for _, s, e in ops]
    if not spans:
        raise ValueError("empty trace: no annotation, no device operation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy(trace: Trace, device: int, window: Interval) -> List[Interval]:
    return clip(union((s, e) for _, s, e in trace.device_ops[device]),
                *window)


def busy_seconds(trace: Trace, window: Interval) -> float:
    """Seconds with an operation on the device, averaged over devices."""
    per = [length(busy(trace, d, window)) for d in trace.device_ops]
    return sum(per) / len(per)


def idle_share(trace: Trace, window: Interval) -> float:
    """1 - busy/window on the device that idles most."""
    span = window[1] - window[0]
    return max(1.0 - length(busy(trace, d, window)) / span
               for d in trace.device_ops)


def kernel_events(trace: Trace, match: Callable[[str], bool],
                  window: Interval, device: Optional[int] = None):
    """Device events that `match(name)` accepts (a name prefix, or a work
    module's reading of the instruction text), wholly inside the window,
    on one device (the first, unless told)."""
    if device is None:
        device = min(trace.device_ops)
    return [(n, s, e) for n, s, e in trace.device_ops[device]
            if s >= window[0] and e <= window[1] and match(n)]


def exposed_collective_share(trace: Trace, window: Interval) -> float:
    """Share of the window in which a collective runs on a device and no
    other operation does; the device where that share is largest."""
    span = window[1] - window[0]
    worst = 0.0
    for ops in trace.device_ops.values():
        coll = clip(union((s, e) for n, s, e in ops if is_collective(n)),
                    *window)
        comp = clip(union((s, e) for n, s, e in ops
                          if not is_collective(n)), *window)
        worst = max(worst, length(subtract(coll, comp)) / span)
    return worst


def top_ops(trace: Trace, window: Interval, n: int = 10):
    """[[name, seconds]] of the device operations that took most time,
    averaged over devices."""
    total: Dict[str, float] = {}
    for ops in trace.device_ops.values():
        for name, s, e in ops:
            lo, hi = max(s, window[0]), min(e, window[1])
            if hi > lo:
                name = short_name(name)
                total[name] = total.get(name, 0.0) + (hi - lo)
    k = max(len(trace.device_ops), 1)
    return [[name, t / k] for name, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps_by_annotation(trace: Trace, window: Interval, n: int = 5):
    """[[annotation, seconds]]: the idle time of the device that idles
    most, split by the annotation the host was inside (the innermost, i.e.
    the latest started, that covers the moment; "(none)" outside all)."""
    device = max(trace.device_ops, key=lambda d: -length(
        busy(trace, d, window)))
    gaps = subtract([window], busy(trace, device, window))
    ann = sorted(trace.annotations, key=lambda a: a[1])
    starts = [a[1] for a in ann]
    total: Dict[str, float] = {}
    for lo, hi in gaps:
        # cut the gap at every annotation boundary inside it
        cuts = sorted({lo, hi} | {t for _, s, e in ann for t in (s, e)
                                  if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            name = "(none)"
            for j in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
                if ann[j][2] >= mid:
                    name = ann[j][0]
                    break
            total[name] = total.get(name, 0.0) + (b - a)
    return [[name, t] for name, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: Trace, window: Interval) -> dict:
    return {"device_ops": top_ops(trace, window, 10),
            "idle_gaps": idle_gaps_by_annotation(trace, window, 5)}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, annotation_prefix: str = "bench.") -> Trace:
    """Read the profiler's file with nothing but JAX. Device operations are
    the events of each TPU plane's "XLA Ops" line; annotations are the host
    events whose name starts with `annotation_prefix`."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            tail = plane.name[len(DEVICE_PLANE_PREFIX):].split()[0]
            dev = int(tail)
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    trace.device_ops[dev] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events if not is_container(e.name)]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(annotation_prefix):
                        trace.annotations.append(
                            (e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    if not trace.device_ops:
        raise ValueError(f"{path}: no TPU plane with an "
                         f"{DEVICE_OPS_LINE!r} line")
    trace.annotations.sort(key=lambda a: a[1])
    return trace
