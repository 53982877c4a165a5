"""RMSNorm over rows of width H (`ops/pallas/rms_norm.py`, forward).

Needs: read x [R, H] and the scale [H], write y [R, H] (those of them
that live in HBM: see `hlo_text.hbm_bytes`); about 4 operations
an element (square, add, multiply by the inverse root, multiply by the
scale). Memory-bound on any chip.
"""
from __future__ import annotations

from .hlo_text import hbm_bytes, pallas_call


def work(event_name: str, params: dict):
    """(flops, bytes) of one call, or None where the event is no rms-norm
    kernel: a tpu_custom_call of (x [R, H], scale [H]) -> y [R, H]."""
    call = pallas_call(event_name)
    if call is None:
        return None
    outs, ins = call
    if len(outs) != 1 or len(ins) != 2:
        return None
    (x, w), y = ins, outs[0]
    if len(x[1]) != 2 or w[1] != x[1][1:] or y[1] != x[1]:
        return None
    rows, width = x[1]
    return 4.0 * rows * width, hbm_bytes([x, w, y])
