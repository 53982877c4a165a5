"""Host time inside a span: for each annotation `params["annotation"]` in
the traced window, its wall time minus the time the device was busy inside
it; the mean over the spans, in milliseconds."""
from __future__ import annotations

from .. import reduce_trace as rt


def read(params: dict, ctx):
    spans = [(s, e) for n, s, e in ctx.trace.annotations
             if n == params["annotation"]
             and s >= ctx.window[0] and e <= ctx.window[1]]
    if not spans:
        return None
    device = min(ctx.trace.device_ops)
    host = [(e - s) - rt.length(rt.busy(ctx.trace, device, (s, e)))
            for s, e in spans]
    return 1e3 * sum(host) / len(host)
