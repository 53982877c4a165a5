"""Share of the traced window with no operation on the device (the device
that idles most), in percent."""
from __future__ import annotations

from .. import reduce_trace as rt


def read(params: dict, ctx):
    return 100.0 * rt.idle_share(ctx.trace, ctx.window)
