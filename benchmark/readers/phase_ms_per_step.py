"""Device time of one phase of the compiled step, a step, in milliseconds.

The device events of the traced window are joined, by HLO instruction name,
with the map the program itself keeps of its compiled text
(`paddle_tpu.profiler.scopes.device_time_by_phase`: each instruction's
pass, forward / recompute / backward / optimizer, and the `pt.<block>`
scope it was traced under). The seconds of `params["pass"]` and/or
`params["block"]` (either may be left out: every pass, every block) are
divided by the steps the trace holds: the runner's count of traced tokens
over batch x sequence (it syncs before it stops the trace, so the window
holds whole steps).

None, and the metric is left out, where the program keeps no such map (a
program from before the scopes), where the instruction of under 99% of the
events' time is in the program's text (another program ran in the window),
or where under 95% of it carries a pass of its own: time with no
`op_name`, and time whose phase the program's map only inherited from a
callee or an operand (a guess), both count against that limit.
"""
from __future__ import annotations

MIN_FOUND = 0.99
MIN_WITH_PASS = 0.95


def read(params: dict, ctx):
    try:
        from paddle_tpu.profiler import scopes
    except ImportError:
        return None
    work = ctx.stats.get("traced_work")
    if not work or not ctx.trace.device_ops:
        return None
    steps = work["tokens"] / (ctx.traffic["batch"] * ctx.traffic["seq"])
    events = [ev for ev in ctx.trace.device_ops[min(ctx.trace.device_ops)]
              if ev[1] >= ctx.window[0] and ev[2] <= ctx.window[1]]
    joined = scopes.device_time_by_phase(events, params["program"])
    if joined is None or steps <= 0:
        return None
    seconds, found, inherited = joined
    total = sum(seconds.values())
    if total <= 0 or found < MIN_FOUND:
        return None
    named = 1 - seconds.get(scopes.UNATTRIBUTED, 0.0) / total - inherited
    if named < MIN_WITH_PASS:
        return None
    want = sum(t for phase, t in seconds.items()
               if phase != scopes.UNATTRIBUTED
               and params.get("pass") in (None, phase[0])
               and params.get("block") in (None, phase[1]))
    return 1e3 * want / steps
