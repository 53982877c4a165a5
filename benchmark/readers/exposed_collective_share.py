"""Share of the traced window in which a collective runs on a device and
no other operation does, in percent. Nothing on one chip, and nothing
where the trace holds no collective."""
from __future__ import annotations

from .. import reduce_trace as rt


def read(params: dict, ctx):
    if len(ctx.trace.device_ops) < 2:
        return None
    if not any(rt.is_collective(n) for ops in ctx.trace.device_ops.values()
               for n, _, _ in ops):
        return None
    return 100.0 * rt.exposed_collective_share(ctx.trace, ctx.window)
