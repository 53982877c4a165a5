"""A kernel's share of its roofline, from the device trace.

For each of the kernel's events in the traced window: the least time the
chip could take for what the call needs, max(operations / peak FLOP/s,
HBM bytes / peak bytes/s), from the shapes in the event by the work file
`params["work"]`. The share is the sum of those least times over the sum
of the events' device durations, in percent.
"""
from __future__ import annotations

from .. import reduce_trace as rt


def read(params: dict, ctx):
    work = ctx.load("work", params["work"]).work
    wp = params.get("work_params", {})
    events = rt.kernel_events(ctx.trace,
                              lambda name: work(name, wp) is not None,
                              ctx.window)
    spent = sum(e - s for _, s, e in events)
    if not events or spent <= 0:
        return None
    least = 0.0
    for name, _, _ in events:
        flops, nbytes = work(name, wp)
        least += max(flops / ctx.peak["bf16_flops_per_s"],
                     nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / spent
