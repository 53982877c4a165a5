"""Flash attention over [B*H, S, D] (`ops/pallas/flash_attention.py`).

One unit u = B*H * S^2 * D operations is one S x S x D matmul a head over
the causal half. What each kernel needs for what it returns:
  forward  (q, k, v) -> (o, lse):          S = QK^T, O = PV            2u
  backward (.., do) -> (dk, dv):           S, dP = dO V^T, dV, dK      4u
  backward (.., do) -> dq:                 S, dP, dQ                   3u
Bytes: each operand and result that lives in HBM once (the float32 row
statistics too). `params["causal"]` false doubles the units.
K and V arrive already repeated to the query heads (`jnp.repeat` in
`models/llama.py:_block`), so that is what the kernel has to read.
"""
from __future__ import annotations

from .hlo_text import hbm_bytes, pallas_call


def work(event_name: str, params: dict):
    """(flops, bytes) of one call, or None where the event is no flash
    attention kernel."""
    call = pallas_call(event_name)
    if call is None:
        return None
    outs, ins = call
    rank3 = [s for s in ins if len(s[1]) == 3 and s[0] != "f32"]
    if len(rank3) < 3:
        return None
    bh, s, d = rank3[0][1]
    big = [x for x in rank3 if x[1] == (bh, s, d)]
    full = [o for o in outs if o[1] == (bh, s, d)]
    if len(big) == 3 and len(full) == 1 and len(outs) == 2:
        matmuls = 2                                   # forward: o, lse
    elif len(big) >= 4 and len(full) == 2 and len(outs) == 2:
        matmuls = 4                                   # dk, dv
    elif len(big) >= 4 and len(full) == 1 and len(outs) == 1:
        matmuls = 3                                   # dq
    else:
        return None
    unit = 2.0 * bh * s * s * d * (0.5 if params.get("causal", True) else 1)
    return matmuls * unit, hbm_bytes(ins + outs)
