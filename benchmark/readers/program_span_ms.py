"""The median duration, in milliseconds, of the program's own spans named
`params["span"]` inside the traced seconds, read from the program's span
ring (`paddle_tpu.profiler.tracing.ring_spans()`).

The benchmark's trace reader keeps only `bench.*` annotations, so the
program's spans reach a reader through the ring, on the host's clock. A
ring span that ran wholly while a device trace recorded carries `traced`:
those are the spans of the traced window every other per-layer metric
covers (the runners start and stop the trace between steps), and the only
ones read here; warm-up, ramp and drain are left out. The ring keeps the
newest 32,768 spans (since PR 26); a 40-second run of the serve cell
writes about 10,000.

None where the ring holds no such span (a program from before the span, or
before the mark).
"""
from __future__ import annotations

from ..harness import percentile


def read(params: dict, ctx):
    try:
        from paddle_tpu.profiler import tracing
    except ImportError:
        return None
    durations = [s["dur"] * 1e3 for s in tracing.ring_spans()
                 if s["name"] == params["span"] and s.get("traced")]
    if not durations:
        return None
    return percentile(durations, 50)
