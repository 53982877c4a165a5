"""Paged attention over SELECTED pages — the paged-attention kernel's walk
given, for each (row, KV head), the pages to read.

Reference analog: the block-sparse attention of InfLLM-v2 / MiniCPM4
(`models/minicpm_sala.py`): past `dense_len` positions a query attends the
first block, the blocks of its local window and the `topk` best blocks
between, chosen per KV head on the device in the same step. A selection
block is one page of the engine's cache.

What `paged_attention.py` says of the layout, the pack (this step's own
keys, taken from `k`, `v`, always attended causally within the row: a step
holds no more tokens than the local window) and the online softmax holds
here. The walk differs, and there are two, each with tiles of its own
inside the ONE call (which of them a row takes is read on the device from
`listed`):

- `listed[b]` = 1 (a row of ONE token past `dense_len`): the LIST walk. It
  reads the `n_sel[b, h]` logical pages `sel[b, h, :]`, no others, through
  the block table (scalar prefetch, so one lowering serves every layer and
  every selection), ONE KV HEAD'S SLAB a DMA (`[block, D]` of K and of V:
  two KV heads choose different pages), for a narrow query block of
  `_q_block(G)` tokens;
- `listed[b]` = 0 (a row with a chunk, or a row still under `dense_len`):
  the WIDE walk. It reads every cached page of the row, and `page_mask
  [T, HKV, max_blocks]` (1 = the token attends that page) masks what each
  query did not choose. A query tile is `_WIDE_TOKENS` tokens of the packed
  axis, a key tile `_WIDE_KEYS` keys: a page is fetched whole (every KV
  head's slab in one copy, as they lie together in the stack) once a query
  tile that holds a token of its row, and the mask's pages are spread to
  the tile's keys by one small product with a 0/1 expander.

The grid is the narrow blocks, then the wide tiles; a step of the grid that
holds no token of its kind (from scalar prefetch) leaves at once. Queries
and output lie whole in VMEM, in blocks of `_q_block(G)` tokens with the
heads of a KV head's group outermost in a block (row `(block, g, token)`):
a narrow block and a wide tile are then both runs of rows, and what is
known a token (bounds, masks) spreads to the query rows along a leading
axis. A token's output is written by the walk its row takes, 0 where no row
owns it.

`sparse_paged_attention` is the kernel; `paged_attention_ref(...,
page_mask=)` the gathered jnp formulation over the written caches, which
the CPU runs. `page_mask_of_lists` turns the lists of the listed rows into
rows of the mask, so that both see one selection. `wide_tiles` says how
many query tiles fetch a row's pages (the model counts its fetches by it).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import paged_attention as _pa
from .flash_attention import _MASK_MIN
from .paged_attention import _KV_BLOCK, _LANES, _NO_ROW, _q_block

__all__ = ["sparse_paged_attention", "page_mask_of_lists", "wide_tiles"]

_FAR = 2 ** 29               # a page no position reaches
_WIDE_TOKENS = 64            # tokens a query tile of the wide walk
_WIDE_KEYS = 1024            # keys a step of the wide walk
_LIST, _WIDE = 1, 2          # whose token: a listed row's, another row's


def page_mask_of_lists(page_mask, sel, n_sel, listed, first_tok):
    """`page_mask [T, HKV, MB]` with the row of each listed row's one token
    (`first_tok [B]`) replaced by its list `sel [B, HKV, S]` (the first
    `n_sel [B, HKV]` entries)."""
    t, hkv, mb = page_mask.shape
    b, _, s = sel.shape
    ok = (jnp.arange(s)[None, None, :] < n_sel[:, :, None]) \
        & listed.astype(bool)[:, None, None]
    rows = jnp.zeros((b, hkv, mb + 1), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(hkv)[None, :, None],
        jnp.where(ok, sel, mb)].set(True)[..., :mb]
    at = jnp.where(listed.astype(bool), first_tok, t)
    return page_mask.at[at].set(rows, mode="drop")


def wide_tiles(cu_seqlens_q, this):
    """Query tiles of the wide walk that hold a token of each row `[B]`:
    how often a row that is not listed has its cached pages fetched."""
    lo, hi = cu_seqlens_q[:-1], cu_seqlens_q[1:]
    return jnp.where(this > 0, (hi - 1) // _WIDE_TOKENS
                     - lo // _WIDE_TOKENS + 1, 0)


def _wide_pages(bs: int) -> int:
    """Pages a step of the wide walk: a power of two (a step's pages lie
    within one 128-lane piece of the mask) within `_WIDE_KEYS` keys."""
    p = 1
    while 2 * p * bs <= _WIDE_KEYS and 2 * p <= _LANES:
        p *= 2
    return p


def _sparse_kernel(bt_ref, start_ref, cu_ref, first_ref, work_ref, layer_ref,
                   listed_ref, nsel_ref, sel_ref,                # prefetch
                   q_ref, ti_ref, k_ref, v_ref, pm_ref, kc_ref, vc_ref,
                   o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
                   *, scale, group, q_block, n_q, rows, max_blocks, max_sel):
    hkv, _, d = q_ref.shape
    layer = layer_ref[0]
    wide_pages, bs = kbuf.shape[1], kbuf.shape[3]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        # a slot a walk does not fill is masked, not skipped: what it holds
        # must be finite; and a token no walk owns reads 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        o_ref[...] = jnp.zeros_like(o_ref)

    def by_rows(x):
        """`[tokens, c]`, known a token, to the tokens' query rows
        `[tokens * G, c]`: the group is a leading axis of a block."""
        n, c = x.shape
        return jnp.broadcast_to(
            x.reshape(n // q_block, 1, q_block, c),
            (n // q_block, group, q_block, c)).reshape(n * group, c)

    def begin(n):
        m_ref[:, :n] = jnp.full((hkv, n, _LANES), _MASK_MIN, jnp.float32)
        l_ref[:, :n] = jnp.zeros((hkv, n, _LANES), jnp.float32)
        acc_ref[:, :n] = jnp.zeros((hkv, n, d), jnp.float32)

    def scores(h, r0, n, k):
        return jax.lax.dot_general(
            q_ref[h, pl.ds(r0, n), :], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    def softmax_step(h, n, s, v, by_lane=False):
        """KV head `h`'s first `n` query rows take one block of keys with
        scores `s` (-inf where masked). `m` stays finite (it starts at
        _MASK_MIN), so a row with every key masked gives exp(-inf) = 0.
        `by_lane`: `l` keeps a partial sum a lane (the keys 128 apart), for
        `finish` to add up across the lanes once; a sum across the lanes
        every step costs the wide walk as much as its products."""
        m_prev = m_ref[h, :n][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        if by_lane:
            part = p[:, :_LANES]
            for c in range(_LANES, p.shape[1], _LANES):
                part = part + p[:, c:c + _LANES]
            l_ref[h, :n] = alpha * l_ref[h, :n] + part
        else:
            l_new = alpha * l_ref[h, :n][:, :1] \
                + jnp.sum(p, axis=-1, keepdims=True)
            l_ref[h, :n] = jnp.broadcast_to(l_new, (n, _LANES))
        acc_ref[h, :n] = acc_ref[h, :n] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h, :n] = jnp.broadcast_to(m_new, (n, _LANES))

    def finish(r0, n, mine, by_lane=False):
        """Write the rows `mine [n, 1]` marks; leave the others."""
        for h in range(hkv):
            l = jnp.sum(l_ref[h, :n], axis=-1, keepdims=True) if by_lane \
                else l_ref[h, :n][:, :1]
            out = acc_ref[h, :n] / jnp.maximum(l, 1e-30)
            was = o_ref[h, pl.ds(r0, n), :].astype(jnp.float32)
            o_ref[h, pl.ds(r0, n), :] = jnp.where(mine, out, was) \
                .astype(o_ref.dtype)

    def rows_from(first, hi, one_row):
        """`one_row(b)` for the rows that own a token before `hi`, from
        `first` on (cu_ref has rows + 1 entries)."""
        def body(b):
            one_row(b)
            return b + 1

        jax.lax.while_loop(
            lambda b: (b < rows) & (cu_ref[jnp.minimum(b, rows)] < hi),
            body, first)

    def walk_pages(n_pages, per_step, page_of, head, one_block):
        """`one_block(j, slot)` over `n_pages` pages, `per_step` a step,
        double-buffered: K and V of page `page_of(idx)` are copied to
        `[slot, p]` of the buffers, the next step's while this one is
        attended; every KV head's slab in one copy (`head` None), or the
        slab of `head` alone, to the place of head 0."""
        n_blk = pl.cdiv(n_pages, per_step)

        def copies(j, slot, wait):
            for p in range(per_step):
                idx = j * per_step + p

                @pl.when(idx < n_pages)
                def _():
                    page = page_of(idx)
                    for c, (cache, buf) in enumerate(((kc_ref, kbuf),
                                                      (vc_ref, vbuf))):
                        cp = pltpu.make_async_copy(
                            cache.at[layer, page] if head is None
                            else cache.at[layer, page, head],
                            buf.at[slot, p] if head is None
                            else buf.at[slot, p, 0], sem.at[c, slot])
                        if wait:
                            cp.wait()
                        else:
                            cp.start()

        @pl.when(n_blk > 0)
        def _():
            copies(0, 0, wait=False)

            def step(j, carry):
                slot = j % 2

                @pl.when(j + 1 < n_blk)
                def _():
                    copies(j + 1, 1 - slot, wait=False)

                copies(j, slot, wait=True)
                one_block(j, slot)
                return carry

            jax.lax.fori_loop(0, n_blk, step, 0)

    # -- the list walk: a narrow block that holds a listed row's token -----
    def narrow():
        qrows = q_block * group
        pages = max(1, _KV_BLOCK // bs)
        kv_block = pages * bs
        lo = i * q_block
        hi = lo + q_block
        r0 = pl.multiple_of(i * qrows, qrows)
        first = first_ref[i]
        begin(qrows)
        ti = by_rows(ti_ref[pl.ds(pl.multiple_of(lo, q_block), q_block), :])
        lb, mine = ti[:, :1], ti[:, 1:2] == _LIST
        tok = lo + jax.lax.broadcasted_iota(jnp.int32, (qrows, 1), 0) \
            % q_block
        col = jax.lax.broadcasted_iota(jnp.int32, (1, kv_block), 1)

        def attend(h, k, v, valid):
            softmax_step(h, qrows, jnp.where(valid, scores(h, r0, qrows, k),
                                             -jnp.inf), v)

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

        def walk(b, h, limit):
            """KV head `h` of row `b` over the pages of its list."""
            n_pages = nsel_ref[b * hkv + h]
            base = (b * hkv + h) * max_sel

            def logical(idx):
                return sel_ref[base + jnp.minimum(idx, max_sel - 1)]

            def one_block(j, slot):
                # each slot's positions: its logical page's, or none
                cols = jnp.full((1, kv_block), _FAR, jnp.int32)
                for p in range(pages):
                    idx = j * pages + p
                    at = jnp.where(idx < n_pages, logical(idx) * bs, _FAR)
                    cols = jnp.where(col // bs == p, at + col % bs, cols)
                attend(h, kbuf[slot, :pages, 0].reshape(kv_block, d),
                       vbuf[slot, :pages, 0].reshape(kv_block, d),
                       cols <= limit)

            walk_pages(n_pages, pages,
                       lambda idx: bt_ref[b * max_blocks + logical(idx)], h,
                       one_block)

        def one_row(b):
            q_lo, q_hi, start = cu_ref[b], cu_ref[b + 1], start_ref[b]
            has = jnp.minimum(hi, q_hi) > jnp.maximum(lo, q_lo)
            limit = jnp.where((tok >= q_lo) & (tok < q_hi), start - 1, -1)
            for h in range(hkv):
                @pl.when(has & (listed_ref[b] == 1))
                def _():
                    walk(b, h, limit)

        rows_from(first, hi, one_row)
        finish(r0, qrows, mine)

    # -- the wide walk: a tile that holds a token of any other row ---------
    def wide():
        w = i - n_q
        n_tok = _WIDE_TOKENS
        qrows = n_tok * group
        kv_block = wide_pages * bs
        lo = w * n_tok
        hi = lo + n_tok
        r0 = pl.multiple_of(w * qrows, qrows)
        first = first_ref[i]
        begin(qrows)
        at_tok = pl.ds(pl.multiple_of(lo, n_tok), n_tok)
        ti = ti_ref[at_tok, :]
        lb, mine = ti[:, :1], ti[:, 1:2] == _WIDE            # [n_tok, 1]
        tok = lo + jax.lax.broadcasted_iota(jnp.int32, (n_tok, 1), 0)

        def attend(h, k, v, valid):
            """`valid [n_tok, keys]`: what a token may see, every query
            head of it alike."""
            bias = by_rows(jnp.where(valid, 0.0, -jnp.inf))
            softmax_step(h, qrows, scores(h, r0, qrows, k) + bias, v,
                         by_lane=True)

        def pack_block(j, carry):
            at = pl.multiple_of(j * _KV_BLOCK, _KV_BLOCK)
            cols = at + jax.lax.broadcasted_iota(jnp.int32, (1, _KV_BLOCK), 1)
            valid = mine & (cols >= lb) & (cols <= tok)
            for h in range(hkv):
                attend(h, k_ref[h, pl.ds(at, _KV_BLOCK), :],
                       v_ref[h, pl.ds(at, _KV_BLOCK), :], valid)
            return carry

        jax.lax.fori_loop(cu_ref[jnp.minimum(first, rows)] // _KV_BLOCK,
                          (hi - 1) // _KV_BLOCK + 1, pack_block, 0)

        col = jax.lax.broadcasted_iota(jnp.int32, (1, kv_block), 1)
        # the pages of a step lie in one 128-lane piece of the mask, from
        # lane `first page % 128` on: `lane` is 0 where a piece's lane, less
        # the key's page within the step, is that first lane
        lane = jax.lax.broadcasted_iota(jnp.int32, (_LANES, kv_block), 0) \
            - jax.lax.broadcasted_iota(jnp.int32, (_LANES, kv_block), 1) // bs

        def walk(b, start, in_row):
            """Every cached page of row `b`, all KV heads' slabs of a page
            in one copy, `in_row [n_tok, 1]` its tokens."""
            def one_block(j, slot):
                idx0 = j * wide_pages
                seen = in_row & (idx0 * bs + col < start)
                piece = pl.ds(pl.multiple_of(idx0 // _LANES * _LANES,
                                             _LANES), _LANES)
                spread = (lane == idx0 % _LANES).astype(jnp.bfloat16)
                for h in range(hkv):
                    chosen = jnp.dot(
                        pm_ref[h, at_tok, piece].astype(jnp.bfloat16),
                        spread, preferred_element_type=jnp.float32)
                    attend(h, kbuf[slot, :, h].reshape(kv_block, d),
                           vbuf[slot, :, h].reshape(kv_block, d),
                           seen & (chosen > 0.5))

            walk_pages(pl.cdiv(start, bs), wide_pages,
                       lambda idx: bt_ref[b * max_blocks + idx], None,
                       one_block)

        def one_row(b):
            q_lo, q_hi, start = cu_ref[b], cu_ref[b + 1], start_ref[b]
            has = jnp.minimum(hi, q_hi) > jnp.maximum(lo, q_lo)

            @pl.when(has & (listed_ref[b] == 0) & (start > 0))
            def _():
                walk(b, start, (tok >= q_lo) & (tok < q_hi))

        rows_from(first, hi, one_row)
        finish(r0, qrows, by_rows(ti[:, 1:2]) == _WIDE, by_lane=True)

    @pl.when((work_ref[i] == 1) & (i < n_q))
    def _():
        narrow()

    @pl.when((work_ref[i] == 1) & (i >= n_q))
    def _():
        wide()


def sparse_paged_attention(q, k, v, key_cache, value_cache, block_tables,
                           start, cu_seqlens_q, listed, sel, n_sel,
                           page_mask, *, layer_idx):
    """`paged_attention`'s operands (stacked caches, a static `layer_idx`)
    and the selection: `listed [B]` int32, `sel [B, HKV, S]` logical pages,
    `n_sel [B, HKV]`, `page_mask [T, HKV, max_blocks]` bool (read for rows
    that are not listed). Returns `[T, HQ, D]`."""
    _pa.note_traced_call()
    return _sparse_call(q, k, v, key_cache, value_cache, block_tables,
                        start, cu_seqlens_q, listed, sel, n_sel, page_mask,
                        jnp.full((1,), layer_idx, jnp.int32),
                        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sparse_call(q, k, v, key_cache, value_cache, block_tables, start,
                 cu_seqlens_q, listed, sel, n_sel, page_mask, layer, *,
                 interpret):
    i32 = jnp.int32
    t, hq, d = q.shape
    hkv, bs = key_cache.shape[2], key_cache.shape[3]
    rows, max_blocks = block_tables.shape
    max_sel = sel.shape[-1]
    group = hq // hkv
    q_block = _q_block(group)
    n_w = pl.cdiv(t, _WIDE_TOKENS)
    t_pad = n_w * _WIDE_TOKENS
    n_q = t_pad // q_block
    tk_pad = pl.cdiv(t, _KV_BLOCK) * _KV_BLOCK
    wide_pages = _wide_pages(bs)
    wide_rows = _WIDE_TOKENS * group
    mb_pad = pl.cdiv(max_blocks, _LANES) * _LANES

    def by_head(x, n, qb):
        """[T, heads * g, D] -> [heads, n * g, D]: blocks of `qb` tokens,
        a block's rows (g, token)."""
        g = x.shape[1] // hkv
        return jnp.pad(x, ((0, n - t), (0, 0), (0, 0))) \
            .reshape(n // qb, qb, hkv, g, d).transpose(2, 0, 3, 1, 4) \
            .reshape(hkv, n * g, d)

    cu = cu_seqlens_q.astype(i32)
    listed = listed.astype(i32)
    # each token's row: its first packed key, and which walk writes it
    tok = jnp.arange(t_pad, dtype=i32)
    t2b = jnp.sum(cu[None, 1:] <= tok[:, None], axis=1)
    own = jnp.minimum(t2b, rows - 1)
    kind = jnp.where(t2b < rows, jnp.where(listed[own] == 1, _LIST, _WIDE), 0)
    ti = jnp.stack([jnp.where(t2b < rows, cu[own], _NO_ROW), kind],
                   axis=1).astype(i32)
    # the grid: narrow blocks, then wide tiles; each one's first token, the
    # first row whose tokens end past it, and whether it holds its kind
    lo = jnp.concatenate([jnp.arange(n_q, dtype=i32) * q_block,
                          jnp.arange(n_w, dtype=i32) * _WIDE_TOKENS])
    first = jnp.sum(cu[None, 1:] <= lo[:, None], axis=1, dtype=i32)
    work = jnp.concatenate([
        jnp.any(kind.reshape(n_q, q_block) == _LIST, axis=1),
        jnp.any(kind.reshape(n_w, _WIDE_TOKENS) == _WIDE, axis=1)]) \
        .astype(i32)
    pm = jnp.pad(page_mask.astype(jnp.float32).transpose(1, 0, 2),
                 ((0, 0), (0, t_pad - t), (0, mb_pad - max_blocks)))
    isz = q.dtype.itemsize
    vmem = (3 * hkv * t_pad * group * d * isz          # q; o: two buffers
            + 2 * hkv * tk_pad * d * isz               # k, v of the pack
            + hkv * t_pad * mb_pad * 4 + t_pad * _LANES * 4
            + 4 * wide_pages * hkv * bs * d * isz      # pages: two slots
            + hkv * wide_rows * (2 * _LANES + d) * 4   # m, l, acc
            + 6 * wide_rows * wide_pages * bs * 4)     # a step's scores
    # operands that stay where they are for the whole grid: one buffer
    once = pl.Buffered(1)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape), once)

    out = pl.pallas_call(
        functools.partial(
            _sparse_kernel, scale=1.0 / math.sqrt(d), group=group,
            q_block=q_block, n_q=n_q, rows=rows, max_blocks=max_blocks,
            max_sel=max_sel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(n_q + n_w,),
            in_specs=[
                whole((hkv, t_pad * group, d)),
                whole((t_pad, 2)),
                whole((hkv, tk_pad, d)),
                whole((hkv, tk_pad, d)),
                whole((hkv, t_pad, mb_pad)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((hkv, t_pad * group, d),
                                   lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, wide_pages, hkv, bs, d), key_cache.dtype),
                pltpu.VMEM((2, wide_pages, hkv, bs, d), value_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv, wide_rows, _LANES), jnp.float32),
                pltpu.VMEM((hkv, wide_rows, _LANES), jnp.float32),
                pltpu.VMEM((hkv, wide_rows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((hkv, t_pad * group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(vmem + (16 << 20), 100 << 20))),
        name="sparse_paged_attention",
        metadata={"kernel": "sparse_paged_attention"},
        interpret=interpret,
    )(block_tables.reshape(-1).astype(i32), start.astype(i32), cu, first,
      work, layer, listed, n_sel.reshape(-1).astype(i32),
      sel.reshape(-1).astype(i32), by_head(q, t_pad, q_block), ti,
      by_head(k, tk_pad, 1), by_head(v, tk_pad, 1), pm, key_cache,
      value_cache)
    return out.reshape(hkv, n_q, group, q_block, d).transpose(1, 3, 0, 2, 4) \
        .reshape(t_pad, hq, d)[:t]
