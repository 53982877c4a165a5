"""Paged attention — one Pallas TPU kernel over the serving engine's cache.

Reference analog: block_multi_head_attention.cu's decode/append kernels
(python surface incubate/nn/functional/block_multihead_attention): each
batch row attends its OWN pages, read in place through the block table and
bounded by the row's length. The jnp formulation it replaces gathered every
row's `max_blocks` pages into a dense `[B, HKV, max_seq, D]` view and then
copied that view once more for each token (`[T, HKV, max_seq, D]`), with a
softmax over `max_seq` padded positions.

Layout, as the engine keeps it: caches `[L, num_blocks, HKV, block, D]`
(`layer_idx` static to the caller, a run-time scalar to the kernel) or
`[num_blocks, HKV, block, D]`; a page is one
contiguous `[HKV, block, D]` slab, fetched whole by one DMA. Queries are
the step's packed tokens `[T, HQ, D]`; `cu_seqlens_q` says which tokens
are which row's, `start[b]` the cache position of the row's first token
this step, `block_tables[b]` its pages. All three are run-time scalars
(scalar prefetch), so one compiled program serves every content of a
`(T, B, max_blocks)` shape.

The kernel reads the caches as they were BEFORE this step's tokens were
written, and takes this step's keys and values from the packed `k`, `v`
themselves: a token's keys are two sets, its row's cached positions
`0 .. start - 1`, in the row's pages, and the row's tokens of this step up
to itself, in the pack. So nothing orders a layer's attention behind that
layer's page write: `block_multihead_attention` runs the write
(`kv_page_write.py`, the stacks aliased in and out) AFTER this kernel has
read them, and a stack threaded through a model's layers is only ever
touched by Mosaic calls, in the plain layout. (While the write was XLA's
scatter, XLA kept the scattered stack in a layout of its own, block and head
swapped, and copied both whole stacks in and out of it every step: ISSUE 30.)

The grid walks the packed token axis in blocks of `q_block` tokens. A
block holds tokens of one row (a prefill chunk) or of many (decode rows of
one token each). First the pack: key blocks of the packed axis, each query
masked to `[its row's first token, itself]`. Then the pages: the kernel
loops over the rows that own a token of the block and, for each, over that
row's pages up to `start`, `pages` pages a step, double-buffered. The
`G = HQ // HKV` query heads of a KV head share its keys: their
`q_block * G` query rows go through the MXU together. A token belongs to
one row, so the block keeps ONE online-softmax state (float32 `m`, `l`,
`acc`); keys of another row, or past a token's own position, are masked to
-inf. A row with no tokens this step costs nothing, a row with nothing
cached (the engine's padding row) walks no page, and a token that no row
owns reads 0.

Operand precision is the reference's: cache-dtype operands into the MXU,
float32 accumulation, float32 softmax.

A row here attends every page it owns. Where a model chooses, on the device
in the same step, which of its pages each query attends (block-sparse
attention), the sibling `sparse_paged_attention.py` walks the pages listed
for each (row, KV head), or all of a row's pages under a mask a token;
`paged_attention_ref` takes that mask as `page_mask`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import kernels_enabled, note_reference_dispatch
from ...profiler.scopes import scope
from .flash_attention import _MASK_MIN

__all__ = ["paged_attention", "paged_attention_ref", "use_kernel",
           "note_traced_call",
           "traced_kernel_calls"]

_LANES = 128
_KV_BLOCK = 128              # keys a step of either walk
_QUERY_ROWS = 64             # MXU rows a query block aims at (q_block * G)
_NO_ROW = 2 ** 30            # first-token bound of a token no row owns
_traced_kernel_calls = 0


def note_traced_call() -> None:
    """A trace took a kernel over the pages: this one, or its sibling over
    selected pages (`sparse_paged_attention.py`)."""
    global _traced_kernel_calls
    _traced_kernel_calls += 1


def traced_kernel_calls() -> int:
    """How many times a trace took the kernel so far. A caller that wraps
    a trace (the serving engine's step programs) reads it before and after
    to learn whether its program holds the kernel."""
    return _traced_kernel_calls


def _sublane_tile(dtype) -> int:
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _q_block(group: int) -> int:
    """Tokens a query block: the smallest of 8..64 whose `q_block * G`
    query rows fill `_QUERY_ROWS` MXU rows and tile bf16's 16 sublanes."""
    for qb in (8, 16, 32, 64):
        if qb * group >= _QUERY_ROWS and (qb * group) % 16 == 0:
            return qb
    return 64


def use_kernel(q, key_cache, quant=False) -> bool:
    """Whether the kernel runs, from what the code can see: kernels on, a
    floating cache of the queries' dtype (an int8 page needs its scales),
    a head that fills the 128 lanes, a page that tiles the dtype's
    sublanes. With kernels on, a no is counted as a reference dispatch."""
    if not kernels_enabled():
        return False
    hkv, bs, d = key_cache.shape[-3:]
    if (not quant and key_cache.dtype == q.dtype
            and q.dtype in (jnp.bfloat16, jnp.float32)
            and d % _LANES == 0 and bs % _sublane_tile(q.dtype) == 0
            and q.shape[1] % hkv == 0):
        return True
    note_reference_dispatch("paged_attention")
    return False


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, start_ref, cu_ref, first_ref, layer_ref,  # prefetch
                  q_ref, lb_ref, k_ref, v_ref, kc_ref, vc_ref, o_ref,
                  kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
                  *, scale, group, q_block, rows, max_blocks):
    hkv, qrows, d = q_ref.shape
    layer = layer_ref[0]
    pages, bs = kbuf.shape[1], kbuf.shape[3]
    kv_block = pages * bs
    i = pl.program_id(0)
    lo = i * q_block
    hi = lo + q_block

    @pl.when(i == 0)
    def _():
        # a slot the walk does not fill is masked, not skipped: what it
        # holds must be finite (0 x NaN in P.V is NaN)
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    m_ref[...] = jnp.full_like(m_ref, _MASK_MIN)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # query row r of the block is token lo + r // G (head g = r % G)
    tok = lo + jax.lax.broadcasted_iota(jnp.int32, (qrows, 1), 0) // group

    def attend(h, k, v, valid):
        """One KV head's query rows against one block of keys: the online
        softmax step. `m` stays finite (it starts at _MASK_MIN), so a row
        with every key masked gives exp(-inf) = 0, never exp(0)."""
        s = jax.lax.dot_general(
            q_ref[h], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, -jnp.inf)
        m_prev = m_ref[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[h][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = jnp.broadcast_to(m_new, (qrows, _LANES))
        l_ref[h] = jnp.broadcast_to(l_new, (qrows, _LANES))

    # -- this step's tokens: the pack, from the first token of the first
    # row of the block up to the block's own last token
    lb = lb_ref[...]                                    # [qrows, 1]
    first = first_ref[i]

    def pack_block(j, carry):
        at = pl.multiple_of(j * _KV_BLOCK, _KV_BLOCK)
        cols = at + jax.lax.broadcasted_iota(jnp.int32, (1, _KV_BLOCK), 1)
        valid = (cols >= lb) & (cols <= tok)
        for h in range(hkv):
            attend(h, k_ref[h, pl.ds(at, _KV_BLOCK), :],
                   v_ref[h, pl.ds(at, _KV_BLOCK), :], valid)
        return carry

    jax.lax.fori_loop(cu_ref[jnp.minimum(first, rows)] // _KV_BLOCK,
                      (hi - 1) // _KV_BLOCK + 1, pack_block, 0)

    # -- what each row had cached: its pages up to `start`
    def page_copies(b, j, slot, n_pages, wait):
        for p in range(pages):
            idx = j * pages + p

            @pl.when(idx < n_pages)
            def _():
                page = bt_ref[b * max_blocks + idx]
                for c, (cache, buf) in enumerate(((kc_ref, kbuf),
                                                  (vc_ref, vbuf))):
                    cp = pltpu.make_async_copy(
                        cache.at[layer, page], buf.at[slot, p],
                        sem.at[c, slot])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    def one_row(b):
        q_lo, q_hi, start = cu_ref[b], cu_ref[b + 1], start_ref[b]
        # nothing to walk unless the row has a token in this block
        n_keys = jnp.where(jnp.minimum(hi, q_hi) > jnp.maximum(lo, q_lo),
                           start, 0)
        n_pages = pl.cdiv(n_keys, bs)
        n_blk = pl.cdiv(n_keys, kv_block)
        # last cached position a query row may see; -1: not this row's
        limit = jnp.where((tok >= q_lo) & (tok < q_hi), start - 1, -1)

        @pl.when(n_blk > 0)
        def _():
            page_copies(b, 0, 0, n_pages, wait=False)

            def one_block(j, carry):
                slot = j % 2

                @pl.when(j + 1 < n_blk)
                def _():
                    page_copies(b, j + 1, 1 - slot, n_pages, wait=False)

                page_copies(b, j, slot, n_pages, wait=True)
                cols = j * kv_block + jax.lax.broadcasted_iota(
                    jnp.int32, (1, kv_block), 1)
                valid = cols <= limit                   # [qrows, kv_block]
                for h in range(hkv):
                    attend(h, kbuf[slot, :, h].reshape(kv_block, d),
                           vbuf[slot, :, h].reshape(kv_block, d), valid)
                return carry

            jax.lax.fori_loop(0, n_blk, one_block, 0)

        return b + 1

    # rows that own a token of this block: from the first whose tokens end
    # past `lo` while they begin before `hi` (cu_ref has rows + 1 entries)
    jax.lax.while_loop(
        lambda b: (b < rows) & (cu_ref[jnp.minimum(b, rows)] < hi),
        one_row, first)

    for h in range(hkv):
        l = jnp.maximum(l_ref[h][:, :1], 1e-30)
        o_ref[h] = (acc_ref[h] / l).astype(o_ref.dtype)


def paged_attention(q, k, v, key_cache, value_cache, block_tables, start,
                    cu_seqlens_q, *, layer_idx=None):
    """Causal attention of this step's packed tokens over their rows'
    cached pages and the step's own keys: the kernel (`use_kernel` says
    whether it applies).

    q `[T, HQ, D]`, k / v `[T, HKV, D]` (after rope, in the cache's
    dtype); key_cache / value_cache `[L, num_blocks, HKV, block, D]` with
    a static `layer_idx`, or `[num_blocks, HKV, block, D]`, as they are
    BEFORE this step's tokens are written; block_tables
    `[B, max_blocks]`; start `[B]` the cache position of each row's first
    token this step; cu_seqlens_q `[B + 1]`. Token `t` of row `b` sees the
    row's cached positions `0 .. start[b] - 1` and the packed tokens
    `cu_seqlens_q[b] .. t`. Returns `[T, HQ, D]`."""
    note_traced_call()
    if layer_idx is None:
        key_cache, value_cache = key_cache[None], value_cache[None]
        layer_idx = 0
    # the layer is a run-time scalar to the kernel, and the call a jitted
    # function: a model's layers share ONE trace and one lowering of the
    # kernel (about a second of host time a layer otherwise, every start)
    return _paged_call(q, k, v, key_cache, value_cache, block_tables, start,
                       cu_seqlens_q, jnp.full((1,), layer_idx, jnp.int32),
                       interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_call(q, k, v, key_cache, value_cache, block_tables, start,
                cu_seqlens_q, layer, *, interpret):
    t, hq, d = q.shape
    hkv, bs = key_cache.shape[2], key_cache.shape[3]
    rows, max_blocks = block_tables.shape
    group = hq // hkv
    q_block = _q_block(group)
    qrows = q_block * group
    n_q = pl.cdiv(t, q_block)
    t_pad = n_q * q_block
    tk_pad = pl.cdiv(t, _KV_BLOCK) * _KV_BLOCK
    pages = max(1, _KV_BLOCK // bs)

    def by_head(x, n, heads):
        """[T, heads * g, D] -> [heads, n * g, D]: token-major rows."""
        g = x.shape[1] // heads
        return jnp.pad(x, ((0, n - t), (0, 0), (0, 0))) \
            .reshape(n, heads, g, d).transpose(1, 0, 2, 3) \
            .reshape(heads, n * g, d)

    cu = cu_seqlens_q.astype(jnp.int32)
    # first row whose tokens end past each block's first token
    first = jnp.sum(cu[None, 1:] <= (jnp.arange(n_q, dtype=jnp.int32)
                                     * q_block)[:, None], axis=1,
                    dtype=jnp.int32)
    # each query row's first packed key: its row's first token
    tok = jnp.arange(t_pad, dtype=jnp.int32)
    t2b = jnp.sum(cu[None, 1:] <= tok[:, None], axis=1)
    lb = jnp.where(t2b < rows, cu[jnp.minimum(t2b, rows - 1)], _NO_ROW)
    lb = jnp.repeat(lb, group)[:, None].astype(jnp.int32)
    isz = q.dtype.itemsize
    vmem = (4 * hkv * qrows * d * isz                  # q, o: two buffers
            + 2 * hkv * tk_pad * d * isz               # k, v of the pack
            + 4 * pages * hkv * bs * d * isz           # pages: two slots
            + hkv * qrows * (2 * _LANES + d) * 4)      # m, l, acc
    # the pack's keys stay where they are for the whole grid: one buffer
    whole, once = (lambda i, *_: (0, 0, 0)), pl.Buffered(1)
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=1.0 / math.sqrt(d),
            group=group, q_block=q_block, rows=rows, max_blocks=max_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_q,),
            in_specs=[
                pl.BlockSpec((hkv, qrows, d), lambda i, *_: (0, i, 0)),
                pl.BlockSpec((qrows, 1), lambda i, *_: (i, 0)),
                pl.BlockSpec((hkv, tk_pad, d), whole, once),
                pl.BlockSpec((hkv, tk_pad, d), whole, once),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((hkv, qrows, d), lambda i, *_: (0, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, hkv, bs, d), key_cache.dtype),
                pltpu.VMEM((2, pages, hkv, bs, d), value_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv, qrows, _LANES), jnp.float32),
                pltpu.VMEM((hkv, qrows, _LANES), jnp.float32),
                pltpu.VMEM((hkv, qrows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((hkv, t_pad * group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(2 * vmem + (8 << 20), 100 << 20))),
        name="paged_attention", metadata={"kernel": "paged_attention"},
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32), start.astype(jnp.int32),
      cu, first, layer, by_head(q, t_pad, hkv), lb, by_head(k, tk_pad, hkv),
      by_head(v, tk_pad, hkv), key_cache, value_cache)
    return out.reshape(hkv, t_pad, group, d).transpose(1, 0, 2, 3) \
        .reshape(t_pad, hq, d)[:t]


# ---------------------------------------------------------------------------
# the gathered reference (kernels off, int8 pages, shapes that do not tile)
# ---------------------------------------------------------------------------

def paged_attention_ref(q, key_cache, value_cache, block_tables, start,
                        cu_seqlens_q, *, layer_idx=None, k_scales=None,
                        v_scales=None, page_mask=None):
    """Each row's pages gathered whole into a dense `[B, HKV, max_seq, D]`
    view, each token given its row's view, softmax over `max_seq`.
    `page_mask [T, HKV, max_blocks]` bool, where given, says which of its
    row's pages each token attends (`sparse_paged_attention.py`)."""
    t, hq, d = q.shape
    hkv, bs = key_cache.shape[-3], key_cache.shape[-2]
    rows, max_blocks = block_tables.shape
    max_seq = max_blocks * bs
    li = () if layer_idx is None else (layer_idx,)
    tok = jnp.arange(t)
    t2b = jnp.searchsorted(cu_seqlens_q[1:], tok, side="right")
    pos = start[t2b] + tok - cu_seqlens_q[t2b]
    # gather WHOLE pages ([B, MB] indices, contiguous slabs) instead of
    # per-(row, pos) strided element slices
    with scope("kv_gather"):
        kd = key_cache[li + (block_tables,)].transpose(0, 2, 1, 3, 4) \
            .reshape(rows, hkv, max_seq, d)              # [B, HKV, S, D]
        vd = value_cache[li + (block_tables,)].transpose(0, 2, 1, 3, 4) \
            .reshape(rows, hkv, max_seq, d)
        if k_scales is not None:
            # dequant the gathered view: int8 pages * per-slot scales
            # (cache HBM traffic already halved at this point)
            ksd = k_scales[li + (block_tables,)].transpose(0, 2, 1, 3) \
                .reshape(rows, hkv, max_seq)[..., None]  # [B, HKV, S, 1]
            vsd = v_scales[li + (block_tables,)].transpose(0, 2, 1, 3) \
                .reshape(rows, hkv, max_seq)[..., None]
            kd = (kd.astype(jnp.float32) * ksd).astype(q.dtype)
            vd = (vd.astype(jnp.float32) * vsd).astype(q.dtype)
        kt = kd[t2b]                                     # each token's row
    qg = q.reshape(t, hkv, hq // hkv, d)
    # MXU dots take the low-precision operands directly with f32
    # ACCUMULATION (preferred_element_type) — operand .astype(f32) casts
    # materialized an f32 copy of every gathered KV view
    logits = jnp.einsum("tkgd,tksd->tkgs", qg, kt,
                        preferred_element_type=jnp.float32) \
        / jnp.sqrt(jnp.float32(d))
    valid = jnp.arange(max_seq)[None, :] <= pos[:, None]     # [T, S]
    valid = valid[:, None, None, :]
    if page_mask is not None:
        valid = valid & jnp.repeat(page_mask, bs, axis=-1)[:, :, None, :]
    logits = jnp.where(valid, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    with scope("kv_gather"):
        vt = vd[t2b]
    out = jnp.einsum("tkgs,tksd->tkgd", probs.astype(q.dtype), vt,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype).reshape(t, hq, d)
