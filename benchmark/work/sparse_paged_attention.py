"""Paged attention over selected pages
(`ops/pallas/sparse_paged_attention.py`).

A call serves one block-sparse layer's step. What it must move is not in
its shapes (the page stacks are the whole pool, `memory_space=ANY`): the
program writes into its step span how many page SLABS the step's walk must
read at the least, one KV head's `[block, D]` of K and of V each: the
chosen pages of each (row, KV head) of one token past `dense_len`, and each
other row's cached pages once (`MiniCPMSalaSpec.walked_slabs`; a chunk's
query blocks in fact each fetch their row's pages again, which is what the
share then shows). The reader `kernel_roofline_rows` prices each traced
step's calls by it: slabs x 2 x block x D x itemsize bytes, and 4 x D
operations a key a query head of the KV head's group (one query). The
step's own queries, keys and output are left out: the least, so the share
cannot pass 100.
"""
from __future__ import annotations

from .hlo_text import DTYPE_BYTES, pallas_call


def work(event_name: str, params: dict, rows=None):
    """(flops, bytes) of one call whose walk reads `rows` slabs, or None
    where the event is not this kernel: a tpu_custom_call named
    `sparse_paged_attention` whose operands hold two equal rank-5 page
    stacks `[L, pages, HKV, block, D]`."""
    if "sparse_paged_attention" not in event_name.split("=", 1)[0]:
        return None
    call = pallas_call(event_name)
    if call is None:
        return None
    outs, ins = call
    stacks = [s for s in ins if len(s[1]) == 5]
    masks = [s for s in ins if s[0] == "f32" and len(s[1]) == 3]
    if len(stacks) != 2 or stacks[0][:2] != stacks[1][:2] or not masks \
            or len(outs[0][1]) != 3 or stacks[0][0] not in ("bf16", "f32"):
        return None
    dtype, (_, _, _, block, d) = stacks[0][:2]
    # query heads a KV head: the output is [HKV, tokens x group, D], the
    # page mask [HKV, tokens, pages]
    group = max(1, outs[0][1][1] // masks[0][1][1])
    slabs = float(rows or 0)
    return (4.0 * d * block * group * slabs,
            2.0 * block * d * DTYPE_BYTES[dtype] * slabs)
