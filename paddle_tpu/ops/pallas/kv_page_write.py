"""KV page write — one Pallas TPU kernel that puts a step's keys and values
into the serving engine's page stacks where they lie.

Reference analog: block_multi_head_attention.cu's cache-write kernels
(python surface incubate/nn/functional/block_multihead_attention): each
token of the step stores its `[HKV, D]` key and value at slot `pos % block`
of page `block_tables[row, pos // block]`. As an XLA scatter
(`stack.at[layer, page, :, slot].set(k)`) that update is strided over the
head axis, so XLA lays the scatter's operand out with block and head
swapped; the jit boundary and the paged-attention kernel want the plain
layout, and the whole K stack and the whole V stack were copied once in and
once out, every step (four copies of 1.07e9 B at 16 layers x 1025 pages;
what they cost is in PERF.md section 6, PR 30).

Layout, as the engine keeps it: stacks `[L, num_blocks, HKV, block, D]`
(`layer_idx` static to the caller, a run-time scalar to the kernel) or
`[num_blocks, HKV, block, D]`, taken in the plain layout and returned
aliased (`input_output_aliases`): with the stacks donated to the step
program nothing is copied. `block_tables`, `start`, `cu_seqlens_q` and the
layer are run-time scalars (scalar prefetch), so one compiled program
serves every layer and every content of a `(T, B, max_blocks)` shape.

The unit of work is a page segment: one row's tokens of this step that
fall in one page. The kernel first lists the segments on the scalar core
(page, first packed token, first slot, count), then walks them: a page is
one contiguous `[HKV, block, D]` slab, fetched whole to VMEM by one DMA,
given the segment's rows and written back, K and V together,
double-buffered (a bf16 row is half a 32-bit sublane word, so a row cannot
be written alone). The rows are placed by a select against an iota mask
over the slots: every slot the segment does not own keeps its bits, and an
owned slot takes the token's bits (the pack reaches the kernel in float32,
which holds every bf16 value, so the rows move as whole 32-bit sublanes).

Where the caller says that its last row is the padding row (index `B - 1`,
`block_multihead_attention`'s `last_row_is_padding`: the engine's steps), that
row is skipped: its page is trash by definition, and it holds most of a
lightly loaded step's tokens. A row with no tokens this step moves nothing.

`kv_page_write` is the kernel; `kv_page_write_ref` the jnp formulation of
the same signature, the scatter, which runs wherever `use_kernel` says no:
kernels off, an int8 cache (written with its scales by the caller), shapes
that do not tile. The predicate is the paged-attention kernel's: the two
kernels apply to the same caches, and one count
(`pallas/reference_dispatch/paged_attention`) says when they do not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from .paged_attention import use_kernel

__all__ = ["kv_page_write", "kv_page_write_ref", "use_kernel",
           "traced_kernel_calls"]

_SUBLANES = 8
_traced_kernel_calls = 0


def traced_kernel_calls() -> int:
    """How many times a trace took the kernel so far (the serving engine
    reads it around the trace of a step program, as it reads
    `paged_attention.traced_kernel_calls`)."""
    return _traced_kernel_calls


def kv_page_write_ref(key_cache, value_cache, k, v, block_tables, start,
                      cu_seqlens_q, *, layer_idx=None,
                      last_row_is_padding=False):
    """The scatter: `cache.at[layer, page, :, slot].set(k)` for every packed
    token; token `i` of row `b` lies at cache position `start[b] + i`. A
    padding row's tokens go into its trash page, whatever
    `last_row_is_padding` says."""
    bs = key_cache.shape[-2]
    tok = jnp.arange(k.shape[0])
    t2b = jnp.searchsorted(cu_seqlens_q[1:], tok, side="right")
    pos = start[t2b] + tok - cu_seqlens_q[t2b]
    at = (() if layer_idx is None else (layer_idx,)) \
        + (block_tables[t2b, pos // bs], slice(None), pos % bs)
    return key_cache.at[at].set(k), value_cache.at[at].set(v)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _write_kernel(bt_ref, start_ref, cu_ref, layer_ref,          # prefetch
                  k_ref, v_ref, _kc_in, _vc_in, kc_ref, vc_ref,
                  seg_ref, kbuf, vbuf, kst, vst, rsem, wsem,
                  *, rows, max_blocks, max_segments):
    """`rows`: how many rows of the batch write (all, or all but the padding
    row)."""
    hkv, bs, d = kbuf.shape[1:]
    layer = layer_ref[0]

    # -- the segments, listed on the scalar core: each row's pages that take
    # a token this step
    def list_row(b, n_seg):
        t_lo, n = cu_ref[b], cu_ref[b + 1] - cu_ref[b]
        first = start_ref[b]
        p_lo = first // bs
        n_pages = jnp.where(n > 0, (first + n - 1) // bs - p_lo + 1, 0)
        n_pages = jnp.minimum(n_pages, max_segments - n_seg)

        def one_page(j, carry):
            at = n_seg + j
            lo = jnp.maximum(first, (p_lo + j) * bs)
            hi = jnp.minimum(first + n, (p_lo + j + 1) * bs)
            seg_ref[0, at] = bt_ref[b * max_blocks
                                    + jnp.minimum(p_lo + j, max_blocks - 1)]
            seg_ref[1, at] = t_lo + lo - first          # first packed token
            seg_ref[2, at] = lo - (p_lo + j) * bs       # first slot
            seg_ref[3, at] = hi - lo                    # tokens
            return carry

        jax.lax.fori_loop(0, n_pages, one_page, 0)
        return n_seg + n_pages

    n_seg = jax.lax.fori_loop(0, rows, list_row, 0)

    # -- the walk: page in, rows placed, page out; two slots
    def copies(i, slot, out):
        page = seg_ref[0, i]
        sem = wsem if out else rsem
        for c, (cache, buf) in enumerate(((kc_ref, kbuf), (vc_ref, vbuf))):
            hbm, vmem = cache.at[layer, page], buf.at[slot]
            yield pltpu.make_async_copy(vmem if out else hbm,
                                        hbm if out else vmem,
                                        sem.at[c, slot])

    def start_all(i, slot, out):
        for cp in copies(i, slot, out):
            cp.start()

    def wait_all(i, slot, out):
        for cp in copies(i, slot, out):
            cp.wait()

    @pl.when(n_seg > 0)
    def _():
        start_all(0, 0, out=False)

    at_slot = jax.lax.broadcasted_iota(jnp.int32, (1, bs, 1), 1)

    def one_segment(i, carry):
        slot = i % 2
        wait_all(i, slot, out=False)

        @pl.when(i + 1 < n_seg)
        def _():
            # the other slot is free once the page before this one is out
            @pl.when(i >= 1)
            def _():
                wait_all(i - 1, 1 - slot, out=True)

            start_all(i + 1, 1 - slot, out=False)

        t0, s0, n = seg_ref[1, i], seg_ref[2, i], seg_ref[3, i]

        def one_token(j, carry):
            kst[:, pl.ds(s0 + j, 1), :] = k_ref[:, pl.ds(t0 + j, 1), :]
            vst[:, pl.ds(s0 + j, 1), :] = v_ref[:, pl.ds(t0 + j, 1), :]
            return carry

        jax.lax.fori_loop(0, n, one_token, 0)
        own = (at_slot >= s0) & (at_slot < s0 + n)
        kbuf[slot] = jnp.where(own, kst[...].astype(kbuf.dtype), kbuf[slot])
        vbuf[slot] = jnp.where(own, vst[...].astype(vbuf.dtype), vbuf[slot])
        start_all(i, slot, out=True)
        return carry

    jax.lax.fori_loop(0, n_seg, one_segment, 0)

    # the last two pages out are still on their way
    @pl.when(n_seg >= 2)
    def _():
        wait_all(n_seg - 2, n_seg % 2, out=True)

    @pl.when(n_seg >= 1)
    def _():
        wait_all(n_seg - 1, (n_seg - 1) % 2, out=True)


@functools.partial(jax.jit, static_argnames=("skip_last_row", "interpret"))
def _write_call(key_cache, value_cache, k, v, block_tables, start,
                cu_seqlens_q, layer, *, skip_last_row, interpret):
    t, hkv, d = k.shape
    bs = key_cache.shape[3]
    rows, max_blocks = block_tables.shape
    rows -= int(skip_last_row)
    t_pad = pl.cdiv(t, _SUBLANES) * _SUBLANES
    # a segment holds a token at least, and a row's n tokens lie in at most
    # (n - 1) // block + 2 pages
    max_segments = max(1, min(t, t // bs + 2 * rows))

    def by_head(x):
        """[T, HKV, D] -> [HKV, T_pad, D] float32: a token a sublane."""
        return jnp.pad(x.astype(jnp.float32).transpose(1, 0, 2),
                       ((0, 0), (0, t_pad - t), (0, 0)))

    isz = key_cache.dtype.itemsize
    vmem = (2 * hkv * t_pad * d * 4                    # k, v of the pack
            + 2 * hkv * bs * d * (2 * isz + 4))        # pages, staged rows
    whole, once = (lambda i, *_: (0, 0, 0)), pl.Buffered(1)
    stack = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_write_kernel, rows=rows, max_blocks=max_blocks,
                          max_segments=max_segments),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((hkv, t_pad, d), whole, once),
                pl.BlockSpec((hkv, t_pad, d), whole, once),
                stack, stack,
            ],
            out_specs=[stack, stack],
            scratch_shapes=[
                pltpu.SMEM((4, max_segments), jnp.int32),
                pltpu.VMEM((2, hkv, bs, d), key_cache.dtype),
                pltpu.VMEM((2, hkv, bs, d), value_cache.dtype),
                pltpu.VMEM((hkv, bs, d), jnp.float32),
                pltpu.VMEM((hkv, bs, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=[jax.ShapeDtypeStruct(key_cache.shape, key_cache.dtype),
                   jax.ShapeDtypeStruct(value_cache.shape,
                                        value_cache.dtype)],
        # operands 6 and 7 (after the four prefetched scalars and the pack)
        # are the stacks: written where they lie
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(2 * vmem + (8 << 20), 100 << 20))),
        name="kv_page_write", metadata={"kernel": "kv_page_write"},
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32), start.astype(jnp.int32),
      cu_seqlens_q.astype(jnp.int32), layer, by_head(k), by_head(v),
      key_cache, value_cache)


def kv_page_write(key_cache, value_cache, k, v, block_tables, start,
                  cu_seqlens_q, *, layer_idx=None, last_row_is_padding=False):
    """This step's keys and values into their pages: the kernel (`use_kernel`
    says whether it applies).

    key_cache / value_cache `[L, num_blocks, HKV, block, D]` with a static
    `layer_idx`, or `[num_blocks, HKV, block, D]`; k / v `[T, HKV, D]` in
    the caches' dtype (after rope); block_tables `[B, max_blocks]`; start
    `[B]` the cache position of each row's first token this step;
    cu_seqlens_q `[B + 1]`. Token `i` of row `b` goes to slot
    `(start[b] + i) % block` of page `block_tables[b, (start[b] + i) //
    block]`; with `last_row_is_padding` the last row writes nothing. Returns
    the written caches, which ARE the given ones where the caller donated
    them."""
    global _traced_kernel_calls
    _traced_kernel_calls += 1
    stacked = layer_idx is not None
    if not stacked:
        key_cache, value_cache = key_cache[None], value_cache[None]
    # the layer is a run-time scalar to the kernel, and the call a jitted
    # function: a model's layers share ONE trace and one lowering
    kc, vc = _write_call(
        key_cache, value_cache, k, v, block_tables, start, cu_seqlens_q,
        jnp.full((1,), layer_idx if stacked else 0, jnp.int32),
        skip_last_row=bool(last_row_is_padding), interpret=_interpret())
    return (kc, vc) if stacked else (kc[0], vc[0])
