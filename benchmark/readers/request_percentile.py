"""A percentile over the window's requests of a per-request reading the
runner kept (`params["stat"]`: "queue_wait_ms", from the time a request was
due to the first step that scheduled it, by the runner's clock against the
engine's `sched_t0`; "ttft_ms", from due to the first token), in
milliseconds."""
from __future__ import annotations

from ..harness import percentile


def read(params: dict, ctx):
    values = ctx.stats.get(params["stat"])
    if not values:
        return None
    return percentile(values, params["percentile"])
