"""`sparse_paged_attention` as PR 34 left it (kernel and call, verbatim but
for the imports): every row walked in query blocks of `_q_block(G)` tokens,
a row that is not listed under a mask built a block. Kept so that
`tests/test_minicpm_sala.py` can hold the LIST walk of the kernel that
followed it to this one's bits on the same machine; nothing else uses it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.flash_attention import _MASK_MIN
from paddle_tpu.ops.pallas.paged_attention import (_KV_BLOCK, _LANES, _NO_ROW,
                                                  _q_block)

_FAR = 2 ** 29               # a page no position reaches


def _sparse_kernel(bt_ref, start_ref, cu_ref, first_ref, layer_ref,
                   listed_ref, nsel_ref, sel_ref,                # prefetch
                   q_ref, lb_ref, k_ref, v_ref, pm_ref, kc_ref, vc_ref,
                   o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
                   *, scale, group, q_block, rows, max_blocks, max_sel):
    hkv, qrows, d = q_ref.shape
    layer = layer_ref[0]
    pages, bs = kbuf.shape[1], kbuf.shape[2]
    kv_block = pages * bs
    i = pl.program_id(0)
    lo = i * q_block
    hi = lo + q_block

    @pl.when(i == 0)
    def _():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    m_ref[...] = jnp.full_like(m_ref, _MASK_MIN)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    tok = lo + jax.lax.broadcasted_iota(jnp.int32, (qrows, 1), 0) // group
    # query row r is token r // G of the block: [qrows, q_block] one-hot
    row_of = (jax.lax.broadcasted_iota(jnp.int32, (qrows, q_block), 0)
              // group == jax.lax.broadcasted_iota(
                  jnp.int32, (qrows, q_block), 1)).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, kv_block), 1)

    def attend(h, k, v, valid):
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

    # -- this step's tokens: the pack
    lb = lb_ref[...]
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

    # -- what each row had cached: its chosen pages, or all of them masked
    def walk(b, h, limit, n_pages, listed):
        """KV head `h` of row `b` over `n_pages` pages: those of its list
        (`listed`, static) or pages 0 .. n_pages - 1 under the mask."""
        n_blk = pl.cdiv(n_pages, pages)
        base = (b * hkv + h) * max_sel

        def logical(idx):
            return sel_ref[base + jnp.minimum(idx, max_sel - 1)] \
                if listed else idx

        def copies(j, slot, wait):
            for p in range(pages):
                idx = j * pages + p

                @pl.when(idx < n_pages)
                def _():
                    page = bt_ref[b * max_blocks + logical(idx)]
                    for c, (cache, buf) in enumerate(((kc_ref, kbuf),
                                                      (vc_ref, vbuf))):
                        cp = pltpu.make_async_copy(
                            cache.at[layer, page, h], buf.at[slot, p],
                            sem.at[c, slot])
                        if wait:
                            cp.wait()
                        else:
                            cp.start()

        @pl.when(n_blk > 0)
        def _():
            copies(0, 0, wait=False)

            def one_block(j, carry):
                slot = j % 2

                @pl.when(j + 1 < n_blk)
                def _():
                    copies(j + 1, 1 - slot, wait=False)

                copies(j, slot, wait=True)
                # each slot's positions: its logical page's, or none
                cols = jnp.full((1, kv_block), _FAR, jnp.int32)
                for p in range(pages):
                    idx = j * pages + p
                    at = jnp.where(idx < n_pages, logical(idx) * bs, _FAR)
                    cols = jnp.where(col // bs == p, at + col % bs, cols)
                valid = cols <= limit                   # [qrows, kv_block]
                if not listed:
                    # the mask's 128 pages that hold this block's, a token
                    # a sublane; spread to the block's query rows
                    idx0 = j * pages
                    at = pl.multiple_of(idx0 // _LANES * _LANES, _LANES)
                    chunk = jnp.dot(row_of, pm_ref[h, :, pl.ds(at, _LANES)],
                                    preferred_element_type=jnp.float32)
                    keep = jnp.zeros((qrows, kv_block), jnp.float32)
                    for p in range(pages):
                        bit = jnp.sum(
                            jnp.where(lane == (idx0 + p) % _LANES, chunk,
                                      0.0), axis=-1, keepdims=True)
                        keep = jnp.where(col // bs == p, bit, keep)
                    valid = valid & (keep > 0.5)
                attend(h, kbuf[slot].reshape(kv_block, d),
                       vbuf[slot].reshape(kv_block, d), valid)
                return carry

            jax.lax.fori_loop(0, n_blk, one_block, 0)

    def one_row(b):
        q_lo, q_hi, start = cu_ref[b], cu_ref[b + 1], start_ref[b]
        has = jnp.minimum(hi, q_hi) > jnp.maximum(lo, q_lo)
        limit = jnp.where((tok >= q_lo) & (tok < q_hi), start - 1, -1)
        by_list = listed_ref[b] == 1
        for h in range(hkv):
            @pl.when(has & by_list)
            def _():
                walk(b, h, limit, nsel_ref[b * hkv + h], True)

            @pl.when(has & jnp.logical_not(by_list))
            def _():
                walk(b, h, limit, pl.cdiv(start, bs), False)

        return b + 1

    jax.lax.while_loop(
        lambda b: (b < rows) & (cu_ref[jnp.minimum(b, rows)] < hi),
        one_row, first)

    for h in range(hkv):
        l = jnp.maximum(l_ref[h][:, :1], 1e-30)
        o_ref[h] = (acc_ref[h] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sparse_call(q, k, v, key_cache, value_cache, block_tables, start,
                 cu_seqlens_q, listed, sel, n_sel, page_mask, layer, *,
                 interpret):
    t, hq, d = q.shape
    hkv, bs = key_cache.shape[2], key_cache.shape[3]
    rows, max_blocks = block_tables.shape
    max_sel = sel.shape[-1]
    group = hq // hkv
    q_block = _q_block(group)
    qrows = q_block * group
    n_q = pl.cdiv(t, q_block)
    t_pad = n_q * q_block
    tk_pad = pl.cdiv(t, _KV_BLOCK) * _KV_BLOCK
    pages = max(1, _KV_BLOCK // bs)
    mb_pad = pl.cdiv(max_blocks, _LANES) * _LANES

    def by_head(x, n, heads):
        g = x.shape[1] // heads
        return jnp.pad(x, ((0, n - t), (0, 0), (0, 0))) \
            .reshape(n, heads, g, d).transpose(1, 0, 2, 3) \
            .reshape(heads, n * g, d)

    cu = cu_seqlens_q.astype(jnp.int32)
    first = jnp.sum(cu[None, 1:] <= (jnp.arange(n_q, dtype=jnp.int32)
                                     * q_block)[:, None], axis=1,
                    dtype=jnp.int32)
    tok = jnp.arange(t_pad, dtype=jnp.int32)
    t2b = jnp.sum(cu[None, 1:] <= tok[:, None], axis=1)
    lb = jnp.where(t2b < rows, cu[jnp.minimum(t2b, rows - 1)], _NO_ROW)
    lb = jnp.repeat(lb, group)[:, None].astype(jnp.int32)
    pm = jnp.pad(page_mask.astype(jnp.float32).transpose(1, 0, 2),
                 ((0, 0), (0, t_pad - t), (0, mb_pad - max_blocks)))
    isz = q.dtype.itemsize
    vmem = (4 * hkv * qrows * d * isz + 2 * hkv * tk_pad * d * isz
            + 4 * pages * bs * d * isz + 2 * hkv * q_block * mb_pad * 4
            + hkv * qrows * (2 * _LANES + d) * 4)
    whole, once = (lambda i, *_: (0, 0, 0)), pl.Buffered(1)
    out = pl.pallas_call(
        functools.partial(
            _sparse_kernel, scale=1.0 / math.sqrt(d), group=group,
            q_block=q_block, rows=rows, max_blocks=max_blocks,
            max_sel=max_sel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=(n_q,),
            in_specs=[
                pl.BlockSpec((hkv, qrows, d), lambda i, *_: (0, i, 0)),
                pl.BlockSpec((qrows, 1), lambda i, *_: (i, 0)),
                pl.BlockSpec((hkv, tk_pad, d), whole, once),
                pl.BlockSpec((hkv, tk_pad, d), whole, once),
                pl.BlockSpec((hkv, q_block, mb_pad),
                             lambda i, *_: (0, i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((hkv, qrows, d), lambda i, *_: (0, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bs, d), key_cache.dtype),
                pltpu.VMEM((2, pages, bs, d), value_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv, qrows, _LANES), jnp.float32),
                pltpu.VMEM((hkv, qrows, _LANES), jnp.float32),
                pltpu.VMEM((hkv, qrows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((hkv, t_pad * group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(2 * vmem + (8 << 20), 100 << 20))),
        name="sparse_paged_attention",
        metadata={"kernel": "sparse_paged_attention"},
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32), start.astype(jnp.int32),
      cu, first, layer, listed.astype(jnp.int32),
      n_sel.reshape(-1).astype(jnp.int32),
      sel.reshape(-1).astype(jnp.int32), by_head(q, t_pad, hkv), lb,
      by_head(k, tk_pad, hkv), by_head(v, tk_pad, hkv), pm, key_cache,
      value_cache)
    return out.reshape(hkv, t_pad, group, d).transpose(1, 0, 2, 3) \
        .reshape(t_pad, hq, d)[:t]
