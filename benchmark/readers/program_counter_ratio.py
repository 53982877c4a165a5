"""A ratio of the program's own counters: the sum of the counters named in
`params["num"]` over the sum of those in `params["den"]`, times
`params.get("scale", 1)`, from `paddle_tpu.profiler.metrics.snapshot()`
.

WHOLE RUN, not the window: a counter counts since the process started, so
the ratio covers warm-up, ramp, window and drain, and moves with their
lengths. A reader cannot take the difference over the window until the
runner hands it the counters at the window's two ends (PERF.md section 7);
each metric's file says so under `covers`.

None where a counter it names does not exist (a program from before the
counter) or the denominator is 0.
"""
from __future__ import annotations


def read(params: dict, ctx):
    try:
        from paddle_tpu.profiler import metrics
    except ImportError:
        return None
    counters = metrics.snapshot()["counters"]
    names = list(params["num"]) + list(params["den"])
    if any(n not in counters for n in names):
        return None
    den = sum(counters[n] for n in params["den"])
    if den <= 0:
        return None
    return params.get("scale", 1) * sum(counters[n] for n in params["num"]) \
        / den
