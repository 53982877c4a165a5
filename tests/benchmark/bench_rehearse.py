"""Shared by the benchmark's tests: drive a runner on the CPU at a tiny
configuration, entering BELOW run.py's device check (as
tests/test_chip_smoke.py does for chip_smoke.py). The tiny cells live in
`data/`: the same kinds of file the real cells have, at toy widths."""
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic_trace(stats_spans=8):
    """A hand-built trace standing in for the profiler's on the CPU: one
    device busy 90% of each of a few engine/trainer steps."""
    from benchmark import reduce_trace as rt

    ops, ann = [], []
    for i in range(stats_spans):
        t = 0.1 * i
        ann.append(("bench.trainer_step", t, t + 0.01))
        ann.append(("bench.engine_step", t, t + 0.1))
        ops.append(("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
                    t + 0.01, t + 0.1))
    ann.append(("bench.sync", 0.1 * stats_spans, 0.1 * stats_spans + 0.01))
    return rt.Trace(device_ops={0: ops}, annotations=sorted(
        ann, key=lambda a: a[1]))


def stand_in_tracer(monkeypatch):
    """The profiler's trace stood in for by `synthetic_trace()` (the CPU
    has no TPU plane), the traced seconds cut to 0.3."""
    from benchmark import harness

    monkeypatch.setattr(harness.Tracer, "start", lambda self: setattr(
        self, "running", self.enabled) or setattr(
            self, "started_at", time.perf_counter()))
    monkeypatch.setattr(harness.Tracer, "stop",
                        lambda self: setattr(self, "running", False))
    monkeypatch.setattr(harness.Tracer, "load",
                        lambda self: synthetic_trace())
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.3)


def rehearse(workload, seconds=1.0, seed=2 ** 31 + 5, trace=False,
             root=DATA):
    """(cell, RunResult, result line) of one run of a tiny cell of the
    tree at `root`."""
    import jax

    from benchmark import harness
    from benchmark import run as run_mod

    cell = harness.Cell.find(workload, root=root, bench_dir=root)
    devices = jax.devices()[:cell.chips]
    runner = harness.load_module("runners", cell.config["entry"])
    result = runner.run(cell, seed, seconds, trace, devices,
                        time.perf_counter())
    peak = harness.load_json("peaks.json")["TPU v5 lite"]
    line = run_mod.result_line(cell, result, devices, trace, peak)
    return cell, result, line
