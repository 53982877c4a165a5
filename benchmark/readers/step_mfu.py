"""The whole step's share of the chips' peak: the operations the traced
work needs (the work file that the cell's configuration names under
`step_work`, from the runner's counts of what the traced steps processed)
over time x chips x peak FLOP/s, in percent: one metric an entry point,
each architecture counted by its own operations.
`params["time"]` is "window" (the traced window) or the name of an
annotation (the summed wall time inside those spans)."""
from __future__ import annotations


def read(params: dict, ctx):
    stats = ctx.stats.get("traced_work")
    if not stats:
        return None
    flops = ctx.load("work", ctx.model["step_work"]).flops(ctx.model, stats)
    if params["time"] == "window":
        seconds = ctx.window[1] - ctx.window[0]
    else:
        seconds = sum(e - s for n, s, e in ctx.trace.annotations
                      if n == params["time"]
                      and s >= ctx.window[0] and e <= ctx.window[1])
    if flops <= 0 or seconds <= 0:
        return None
    return 100.0 * flops / (seconds * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
