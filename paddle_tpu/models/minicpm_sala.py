"""MiniCPM-SALA at serving time: block-sparse attention layers beside
lightning (linear) attention layers, as a spec of layer kinds and pure
functions over them.

Reference analog: the `minicpm_sala` family's published forward
(`mixer_types`: one mixer a layer, then a SwiGLU MLP; `L` the PUBLISHED
depth also where fewer layers are held):

- embedding `E[id] * scale_emb`; a layer `h += (scale_depth / sqrt(L)) *
  Mixer(RMSNorm(h))`, `h += (scale_depth / sqrt(L)) * MLP(RMSNorm(h))`; head
  `W_head RMSNorm(h) / (hidden_size / dim_model_base)`, untied.
- `lightning-attn`: q, k, v as `lightning_nh` heads; RMSNorm a head on q and
  k; rope on q and k (HALF-SPLIT pairs, the whole head); `S_t = lambda_h
  S_{t-1} + k_t^T v_t`, `o_t = (q_t / sqrt(d)) S_t` with the fixed decay
  `lambda_h = exp(-2^(-8 (h + 1) / H))`; one RMSNorm over the concatenated
  heads, times `sigmoid(u W_z)`; `W_o`.
- `minicpm4` (InfLLM-v2, no rope): grouped-query attention with RMSNorm a
  head on q and k. A query with `n` keys (itself included): `n <=
  dense_len`: causal softmax over all. Else: compressed keys `Kc_j =
  mean(k[stride j .. stride j + kernel - 1])` of the windows that end at or
  before it; `p_h = softmax_j(q_h Kc_j / sqrt(d))`, summed over the query
  heads of a KV head; a block's score the max over the windows that overlap
  it; the query attends block 0 .. `init_blocks - 1`, the blocks that hold
  its last `window_size` positions and the `topk` best others, one causal
  softmax over their keys; times `sigmoid(u W_g)`; `W_o`.

What a request keeps between steps, by layer kind
(`inference/layer_states.py`): the block-sparse layers K/V PAGES, and beside
them a PAGE SIDE, the selector's cache: one compressed key `[HKV, D]` for
every `kernel_stride` positions, addressed by the block table, written by
the step when a window's last key arrives (a window may straddle two pages
and two steps: a ROW SLOT keeps each row's last `kernel_size - 1` keys);
the lightning layers a ROW SLOT, the float32 state `[H, D, D]`.
A selection block is one page: the configuration's `block_size` is the
sparse `block_size`. One compiled step serves the packed token axis. A
lightning row of one token goes through `ops/pallas/ssm_state_update.py`
at groups = heads, a row with a chunk through `models/chunk_scan.py` (both
shared with `models/nemotron_h.py`). A block-sparse row of one token past
`dense_len` is walked by `ops/pallas/sparse_paged_attention.py` over the
pages chosen for each (row, KV head) and no others; the tokens of a chunk
walk their row's pages with each query's selection as a mask.

Parameters are a flat `{name: array}` made in the served dtype.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..inference.layer_states import LayerStates, PageSide, RowState
from ..ops.pallas.rms_norm import rms_norm
from ..ops.pallas.sparse_paged_attention import (page_mask_of_lists,
                                                 wide_tiles)
from ..ops.pallas.ssm_state_update import ssm_state_update
from ..profiler.scopes import scope
from .chunk_scan import chunk_scan, rows_of

__all__ = ["MiniCPMSalaSpec", "MiniCPMSala", "init_params",
           "SPARSE_COUNTERS"]

SPARSE = "minicpm4"
LINEAR = "lightning-attn"

# what the step counts on the device, as one int32 vector in this order.
# The first three count (query, KV head) page visits of the queries past
# `dense_len`, summed over the block-sparse layers: the pages of each
# query's selection; the pages the walk reads for it (its list for a row of
# one token, all of its context for a token of a chunk); the pages of its
# context, which dense attention would read. The fourth counts, once a
# step, the rows whose queries all lie within `dense_len`. The last two
# count `[block, D]` K slabs, summed over the block-sparse layers: those the
# kernel's copies bring in (a listed row's chosen pages; any other row's
# cached pages, every KV head's, once a wide query tile that holds a token of
# the row) and the least a walk could (the same with each page once: what
# `MiniCPMSalaSpec.walked_slabs` tells the step span from positions alone).
# A chunk's query still computes against every page of its context under the
# mask, so `sparse_pages_walked` does not move with how often a page is
# fetched.
SPARSE_COUNTERS = ("serving/sparse_pages_selected",
                   "serving/sparse_pages_walked",
                   "serving/sparse_pages_context",
                   "serving/sparse_dense_rows",
                   "serving/sparse_slabs_fetched",
                   "serving/sparse_slabs_least")


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaSpec:
    """The sizes, under the published config's own key names; the sparse
    sizes under MiniCPM4's `sparse_config` names."""
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    mixer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    lightning_nh: int
    lightning_nkv: int
    lightning_head_dim: int
    rms_norm_eps: float
    rope_theta: float
    scale_emb: float
    scale_depth: float
    dim_model_base: int
    published_layers: int                 # L of scale_depth / sqrt(L)
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    chunk_size: int = 128                 # the scan's and the selector's
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, config: dict, **over):
        """From a dict that carries the published keys (others ignored);
        `over`: the sparse sizes, `published_layers`, `dtype`."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in names}
        kw.update(over)
        kw["mixer_types"] = tuple(kw["mixer_types"])
        kw.setdefault("published_layers", len(kw["mixer_types"]))
        return cls(**kw)

    def __post_init__(self):
        if set(self.mixer_types) - {SPARSE, LINEAR}:
            raise ValueError(f"mixer_types holds {SPARSE!r} and {LINEAR!r}, "
                             f"got {sorted(set(self.mixer_types))}")
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError("lightning attention is built at "
                             "lightning_nkv = lightning_nh")
        if self.block_size % self.kernel_stride \
                or self.kernel_size % self.kernel_stride:
            raise ValueError("block_size and kernel_size must be whole "
                             "numbers of kernel_stride")
        if self.window_size < self.kernel_size or self.dense_len \
                < self.window_size + self.init_blocks * self.block_size:
            raise ValueError(
                "the selection needs window_size >= kernel_size (every "
                "window over a block before the local ones has closed) and "
                "dense_len >= window_size + init_blocks * block_size (the "
                "first blocks and the local ones are apart)")

    def count(self, kind):
        return self.mixer_types.count(kind)

    @property
    def slopes(self):
        """The lightning heads' decay rates: lambda_h = exp(-slope_h)."""
        h = self.lightning_nh
        return tuple(2.0 ** (-8.0 * (i + 1) / h) for i in range(h))

    def param_shapes(self) -> Dict[str, tuple]:
        """{name: (shape, dtype)}: matrices in the served dtype, norm
        scales float32."""
        dt = jnp.dtype(self.dtype)
        f32 = jnp.float32
        h, f, d = self.hidden_size, self.intermediate_size, self.head_dim
        hq, hkv = self.num_attention_heads, self.num_key_value_heads
        lw = self.lightning_nh * self.lightning_head_dim
        per_kind = {
            SPARSE: {"qkv": ((h, (hq + 2 * hkv) * d), dt),
                     "q_norm": ((d,), f32), "k_norm": ((d,), f32),
                     "gate": ((h, hq * d), dt),
                     "o_proj": ((hq * d, h), dt)},
            LINEAR: {"qkv": ((h, 3 * lw), dt),
                     "q_norm": ((self.lightning_head_dim,), f32),
                     "k_norm": ((self.lightning_head_dim,), f32),
                     "out_norm": ((lw,), f32), "gate": ((h, lw), dt),
                     "o_proj": ((lw, h), dt)}}
        shapes = {"embed": ((self.vocab_size, h), dt),
                  "final_norm": ((h,), f32),
                  "head": ((h, self.vocab_size), dt)}
        for i, kind in enumerate(self.mixer_types):
            shapes[f"layers.{i}.norm"] = ((h,), f32)
            shapes[f"layers.{i}.mlp_norm"] = ((h,), f32)
            shapes[f"layers.{i}.gate_up"] = ((h, 2 * f), dt)
            shapes[f"layers.{i}.down"] = ((f, h), dt)
            for k, v in per_kind[kind].items():
                shapes[f"layers.{i}.{k}"] = v
        return shapes

    # -- what a step's walk reads, known from positions alone -------------
    def walked_slabs(self, rows):
        """One block-sparse layer's least page reads for a step that holds
        `rows` = [(start, chunk), ...], one KV head's slab of a page each:
        the list of a row of one token past `dense_len`, else the row's
        cached pages once."""
        n = 0
        for start, chunk in rows:
            if chunk == 1 and start + 1 > self.dense_len:
                n += self.selected_pages(start)
            else:
                n += -(-start // self.block_size)
        return n * self.num_key_value_heads

    def selected_pages(self, t):
        """Pages a query at position `t` past `dense_len` chooses, a KV
        head: the first, the local ones and the `topk` best between."""
        lo = (t - self.window_size + 1) // self.block_size
        return self.init_blocks + t // self.block_size - lo + 1 \
            + min(self.topk, lo - self.init_blocks)


def init_params(spec: MiniCPMSalaSpec, seed: int = 0, std: float = 0.02):
    """Random parameters, one jitted call, each leaf in its own dtype:
    matrices normal(0, std), every 1-D leaf one."""
    shapes = spec.param_shapes()

    def make(key):
        return {name: jnp.ones(shape, dt) if len(shape) == 1 else (
            std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)).astype(dt)
            for i, (name, (shape, dt)) in enumerate(sorted(shapes.items()))}

    return jax.jit(make)(jax.random.key(seed))


def _norm(x, w, eps):
    return rms_norm(x, w.astype(x.dtype), eps)


def _head_norm(x, w, eps):
    """RMSNorm over each head's channels: x `[T, H, D]`, w `[D]`."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + eps) * w).astype(x.dtype)


def _rope(x, pos, theta):
    """Rotary embedding on half-split pairs over the whole head: x
    `[T, H, D]` at positions `pos [T]`."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class MiniCPMSala:
    """The thin class `ServingEngine.from_model` takes: the spec, the
    parameters, what its layers keep for a request, and the step."""

    def __init__(self, spec: MiniCPMSalaSpec, params: Dict[str, jax.Array]):
        want = spec.param_shapes()
        if set(want) != set(params):
            raise ValueError("parameters do not match the spec: "
                             f"{sorted(set(want) ^ set(params))[:6]}")
        self.spec = spec
        self.params = dict(params)

    # -- what the engine asks --------------------------------------------
    def layer_states(self) -> LayerStates:
        s = self.spec
        n_s, n_l = s.count(SPARSE), s.count(LINEAR)
        hkv, d = s.num_key_value_heads, s.head_dim
        rows = ()
        if n_l:
            rows += (RowState("lin", n_l, (s.lightning_nh,
                                           s.lightning_head_dim,
                                           s.lightning_head_dim),
                              "float32"),)
        if n_s:
            rows += (RowState("ktail", n_s, (s.kernel_size - 1, hkv, d),
                              s.dtype),)
        return LayerStates(
            attention_layers=n_s, kv_heads=hkv, head_dim=d, row_states=rows,
            page_sides=(PageSide(
                "ckeys", n_s,
                (s.block_size // s.kernel_stride * hkv * d,), s.dtype),)
            if n_s else (),
            counters=SPARSE_COUNTERS if n_s else (),
            step_args=(lambda rows: {
                "sparse_pages_walked": s.walked_slabs(rows)})
            if n_s else None)

    def serving_params(self):
        return self.params

    # -- the step ----------------------------------------------------------
    def serving_step(self, p, tokens, enc, dec, this, cu, bt, kc, vc, *kept,
                     mode=None):
        """One engine step over the packed tokens `[T]` (the engine's
        contract): rows `[B + 1]`, the last the padding row; `kc`, `vc` the
        block-sparse layers' pages; `kept` = the lightning state and the
        key tails by slot (where the model has such layers), the compressed
        keys by page, then `slots [B + 1]`. Returns (last-token logits
        `[B + 1, V]`, kc, vc, *kept without slots, counts in the order of
        `SPARSE_COUNTERS`)."""
        if mode not in (None, "fresh_prefill"):
            raise ValueError(f"MiniCPMSala has no {mode!r} step")
        s = self.spec
        t = tokens.shape[0]
        if kc.shape[0] and (kc.shape[3] != s.block_size or t > min(
                s.window_size, s.dense_len)):
            raise ValueError(
                "a selection block is one page and a step's tokens lie "
                f"within the local window: block_size {s.block_size}, "
                f"window_size {s.window_size}; got pages of {kc.shape[3]} "
                f"and a step of {t} tokens")
        kept = list(kept)
        slots = kept.pop()
        lin = kept.pop(0) if s.count(LINEAR) else None
        ktail = kept.pop(0) if s.count(SPARSE) else None
        ck = kept.pop(0) if s.count(SPARSE) else None
        meta = rows_of(t, enc, dec, this, cu, slots, s.chunk_size)
        start = meta["start"]
        meta.update(pos=start[meta["t2b"]] + meta["off"], bt=bt)
        res = s.scale_depth / math.sqrt(s.published_layers)
        eps = s.rms_norm_eps
        with scope("embed"):
            x = p["embed"][tokens] * s.scale_emb
        counts = jnp.zeros((len(SPARSE_COUNTERS),), jnp.int32)
        n = {SPARSE: 0, LINEAR: 0}
        for i, kind in enumerate(s.mixer_types):
            w = {k.split(".", 2)[2]: v for k, v in p.items()
                 if k.startswith(f"layers.{i}.")}
            u = _norm(x, w["norm"], eps)
            if kind == LINEAR:
                with scope("linear_attention"):
                    y, lin = _lightning(s, w, u, meta, lin, n[kind])
            else:
                y, kc, vc, ktail, ck, c = _sparse_attention(
                    s, w, u, meta, enc, dec, this, cu, kc, vc, ktail, ck,
                    n[kind], mode == "fresh_prefill")
                counts = counts + c
            n[kind] += 1
            x = x + y * res
            with scope("mlp"):
                gu = _norm(x, w["mlp_norm"], eps) @ w["gate_up"]
                f = s.intermediate_size
                x = x + ((jax.nn.silu(gu[:, :f]) * gu[:, f:])
                         @ w["down"]) * res
        if s.count(SPARSE):
            live = meta["live"]
            dense = live & (start + this <= s.dense_len)
            counts = counts.at[3].set(jnp.sum(dense.astype(jnp.int32)))
        with scope("head"):
            last = x[jnp.maximum(cu[1:] - 1, 0)]                 # [B+1, H]
            logits = (_norm(last, p["final_norm"], eps) @ p["head"]) \
                / (s.hidden_size / s.dim_model_base)
        out = [v for v in (lin, ktail, ck) if v is not None]
        return (logits, kc, vc, *out, counts)


# -- lightning attention ---------------------------------------------------------

def _lightning(s, w, u, meta, lin, li):
    """The linear-attention mixer through the shared recurrence: `x` = v,
    `B` = k, `C` = q / sqrt(d), `dt` = 1, `A` = -slope, `D` = 0, groups =
    heads."""
    f32 = jnp.float32
    t = u.shape[0]
    h, d = s.lightning_nh, s.lightning_head_dim
    qkv = (u @ w["qkv"]).reshape(t, 3, h, d)
    q = _rope(_head_norm(qkv[:, 0], w["q_norm"], s.rms_norm_eps),
              meta["pos"], s.rope_theta)
    k = _rope(_head_norm(qkv[:, 1], w["k_norm"], s.rms_norm_eps),
              meta["pos"], s.rope_theta)
    v = qkv[:, 2]
    c = q * (d ** -0.5)
    dt = jnp.ones((t, h), f32)
    a = -jnp.asarray(s.slopes, f32)
    zero = jnp.zeros((h,), f32)
    ft = meta["first_tok"]
    lin, y_one = ssm_state_update(
        lin, v[ft], dt[ft], a, k[ft], c[ft], zero, meta["slots"],
        meta["one"], meta["reset"], layer_idx=li)
    lin, y = chunk_scan(s.chunk_size, lin, li, v, dt, a, k, c, zero,
                        meta["blocks"], meta["n_blocks"])
    y = y.at[jnp.where(meta["one"], ft, t)].set(y_one, mode="drop")
    y = y.reshape(t, h * d)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + s.rms_norm_eps) * w["out_norm"]
    o = y.astype(u.dtype) * jax.nn.sigmoid(u @ w["gate"])
    return o @ w["o_proj"], lin


# -- block-sparse attention ------------------------------------------------------

def _sparse_attention(s, w, u, meta, enc, dec, this, cu, kc, vc, ktail, ck,
                      li, fresh):
    from ..core.tensor import Tensor
    from ..incubate.nn import functional as IF

    t = u.shape[0]
    hq, hkv, d = s.num_attention_heads, s.num_key_value_heads, s.head_dim
    with scope("attention"):
        qkv = u @ w["qkv"]
        q = _head_norm(qkv[:, :hq * d].reshape(t, hq, d), w["q_norm"],
                       s.rms_norm_eps)
        k = _head_norm(qkv[:, hq * d:(hq + hkv) * d].reshape(t, hkv, d),
                       w["k_norm"], s.rms_norm_eps)
        qkv = jnp.concatenate([q.reshape(t, -1), k.reshape(t, -1),
                               qkv[:, (hq + hkv) * d:]], axis=1)
    with scope("kv_compress"):
        ck, ktail = _compress(s, k, meta, ktail, ck, li)
    if fresh:
        # every row starts at position 0 and a step is shorter than
        # dense_len: all of the pack, nothing to choose
        selection, counts = None, jnp.zeros((len(SPARSE_COUNTERS),),
                                            jnp.int32)
    else:
        with scope("sparse_select"):
            selection, counts = _select(s, q, meta, ck, li)
    with scope("attention"):
        out, _, kc, vc = IF.block_multihead_attention(
            Tensor(qkv), Tensor(kc), Tensor(vc), enc, dec, this, None,
            None, cu, None, meta["bt"], rope_emb=None, layer_idx=li,
            max_seq_len=meta["bt"].shape[1] * kc.shape[3],
            block_size=kc.shape[3], fresh_prefill=fresh,
            last_row_is_padding=True, selection=selection)
        o = out._value * jax.nn.sigmoid(u @ w["gate"])
        return o @ w["o_proj"], kc._value, vc._value, ktail, ck, counts


def _compress(s, k, meta, ktail, ck, li):
    """The selector's cache: the mean of each window of `kernel_size` keys
    whose LAST key is a token of this step, written at the page and entry
    of the window's first position; a window's earlier keys are this
    step's or the row's tail (its last `kernel_size - 1` keys, kept by
    slot). Returns (ck, ktail with each scheduled row's new tail)."""
    f32 = jnp.float32
    t, hkv, d = k.shape
    ks, st, bs = s.kernel_size, s.kernel_stride, s.block_size
    cu, this, slots, start = (meta["cu"], meta["this"], meta["slots"],
                              meta["start"])
    b1 = this.shape[0]
    n_slots = ktail.shape[1]
    tails = ktail[li, slots]                               # [B1, K-1, ..]
    pos, t2b = meta["pos"], meta["t2b"]
    closes = meta["real"] & (pos >= ks - 1) & ((pos - (ks - 1)) % st == 0)
    room = t // st + b1
    tc = jnp.nonzero(closes, size=room, fill_value=t)[0]
    ok = tc < t
    tc = jnp.minimum(tc, t - 1)
    rc, pc = t2b[tc], pos[tc]
    i = jnp.arange(ks)
    at = tc[:, None] - (ks - 1) + i[None]                  # packed index
    in_pack = at >= cu[rc][:, None]
    from_tail = tails[rc[:, None], jnp.clip(
        pc[:, None] - (ks - 1) + i[None] - start[rc][:, None] + ks - 1,
        0, ks - 2)]
    keys = jnp.where(in_pack[..., None, None], k[jnp.clip(at, 0, t - 1)],
                     from_tail)                            # [room, K, ..]
    mean = jnp.mean(keys.astype(f32), axis=1).astype(ck.dtype)
    first = pc - (ks - 1)                                  # window's first
    # a page's entries lie flat in one row: entry e at e * HKV * D. One
    # windowed scatter of [HKV * D] runs (a window past the pool is dropped)
    page = jnp.where(ok, meta["bt"][rc, first // bs], ck.shape[1])
    at = jnp.stack([jnp.full_like(page, li), page,
                    (first % bs) // st * (hkv * d)], axis=1)
    ck = jax.lax.scatter(
        ck, at, mean.reshape(room, hkv * d),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 2)),
        indices_are_sorted=False, unique_indices=True,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
    # the new tail: the last K - 1 of (old tail, this step's keys)
    j = jnp.arange(ks - 1)
    src = this[:, None] - (ks - 1) + j[None]               # [B1, K-1]
    new = jnp.where((src >= 0)[..., None, None],
                    k[jnp.clip(cu[:-1, None] + src, 0, t - 1)],
                    jnp.take_along_axis(
                        tails, jnp.clip(ks - 1 + src, 0, ks - 2)[
                            ..., None, None], axis=1))
    ktail = ktail.at[li, jnp.where(meta["live"], slots, n_slots)].set(
        new.astype(ktail.dtype), mode="drop")
    return ck, ktail


def _choose(s, scores, n, mb):
    """From `scores [N, HKV, G, NC]` of queries against their row's
    compressed keys and each query's context `n [N]` (itself included):
    the chosen blocks `idx [N, HKV, topk]` with `ok`, and the local
    window's first and last block `[N]`."""
    st, bs = s.kernel_stride, s.block_size
    per = bs // st
    nc = scores.shape[-1]
    t = n - 1
    closed = jnp.clip((n - s.kernel_size) // st + 1, 0, nc)
    valid = (jnp.arange(nc)[None] < closed[:, None])[:, None, None, :]
    sc = jnp.where(valid, scores * (s.head_dim ** -0.5), -jnp.inf)
    top = jnp.max(sc, axis=-1, keepdims=True)
    e = jnp.where(valid, jnp.exp(sc - jnp.where(top > -jnp.inf, top, 0.0)),
                  0.0)
    prob = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    pw = jnp.sum(prob, axis=2)                             # [N, HKV, NC]
    # a block's score: the max over the windows that overlap it, windows
    # block * per - back .. block * per + per - 1
    back = (s.kernel_size - 1) // st
    pw = jnp.pad(pw, ((0, 0), (0, 0), (back, 0)))
    cols = jnp.arange(mb)[:, None] * per + jnp.arange(per + back)[None]
    score = jnp.max(pw[..., cols], axis=-1)                # [N, HKV, MB]
    w_lo = jnp.maximum(t - s.window_size + 1, 0) // bs
    w_hi = t // bs
    m = jnp.arange(mb)[None]
    cand = (m >= s.init_blocks) & (m < w_lo[:, None])      # [N, MB]
    score = jnp.where(cand[:, None, :], score, -jnp.inf)
    if mb < s.topk:
        score = jnp.pad(score, ((0, 0), (0, 0), (0, s.topk - mb)),
                        constant_values=-jnp.inf)
    val, idx = jax.lax.top_k(score, s.topk)
    return idx, val > -jnp.inf, w_lo, w_hi


def _select(s, q, meta, ck, li):
    """What each query of the step attends among its row's pages. Returns
    (the selection `block_multihead_attention` takes, counts)."""
    i32 = jnp.int32
    t, hq, d = q.shape
    hkv = s.num_key_value_heads
    g = hq // hkv
    bt = meta["bt"]
    b1, mb = bt.shape
    bs, per = s.block_size, s.block_size // s.kernel_stride
    nc = mb * per
    qs = s.chunk_size
    start, this, pos = meta["start"], meta["this"], meta["pos"]
    m = jnp.arange(mb)
    qg = q.reshape(t, hkv, g, d)

    # -- rows of one token: a list a (row, KV head)
    n_row = start + 1
    listed = meta["one"] & (n_row > s.dense_len)
    ckr = ck[li, bt].reshape(b1, nc, hkv, d)
    sc = jnp.einsum("bhgd,bchd->bhgc", qg[meta["first_tok"]], ckr,
                    preferred_element_type=jnp.float32)
    idx, ok, w_lo, w_hi = _choose(s, sc, n_row, mb)
    n_win = w_hi - w_lo + 1
    n_top = jnp.sum(ok.astype(i32), axis=-1)               # [B1, HKV]
    max_sel = s.init_blocks + s.topk + s.window_size // bs + 1
    slot = jnp.arange(max_sel)[None, None, :]
    after = slot - s.init_blocks - n_win[:, None, None]
    sel = jnp.where(
        slot < s.init_blocks, slot,
        jnp.where(after < 0, w_lo[:, None, None] + slot - s.init_blocks,
                  jnp.take_along_axis(
                      idx, jnp.clip(after, 0, s.topk - 1), axis=-1)))
    n_sel = jnp.where(listed[:, None],
                      s.init_blocks + n_win[:, None] + n_top, 0)
    sel = jnp.clip(sel, 0, mb - 1).astype(i32)

    # -- tokens of a chunk: a mask a (token, KV head), block by block
    q_pad = jnp.pad(qg, ((0, qs), (0, 0), (0, 0), (0, 0)))
    pos_pad = jnp.pad(pos, (0, qs))
    blocks = meta["blocks"]

    def block(j, carry):
        mask, picked = carry
        off, length = blocks["off"][j], blocks["len"][j]
        keep = jnp.arange(qs) < length
        nq = jax.lax.dynamic_slice_in_dim(pos_pad, off, qs) + 1
        sparse = keep & (nq > s.dense_len)

        def choose(_):
            r = meta["t2b"][jnp.minimum(off, t - 1)]
            sc = jnp.einsum(
                "qhgd,chd->qhgc",
                jax.lax.dynamic_slice_in_dim(q_pad, off, qs),
                ck[li, bt[r]].reshape(nc, hkv, d),
                preferred_element_type=jnp.float32)
            idx, ok, lo, hi = _choose(s, sc, nq, mb)
            fixed = (m[None] < s.init_blocks) \
                | ((m[None] >= lo[:, None]) & (m[None] <= hi[:, None]))
            top = jnp.zeros((qs, hkv, max(mb, s.topk) + 1), bool).at[
                jnp.arange(qs)[:, None, None],
                jnp.arange(hkv)[None, :, None],
                jnp.where(ok, idx, max(mb, s.topk))].set(True)[..., :mb]
            return jnp.where(sparse[:, None, None],
                             fixed[:, None, :] | top, True)

        mk = jax.lax.cond(jnp.any(sparse), choose,
                          lambda _: jnp.ones((qs, hkv, mb), bool), None)
        cur = jax.lax.dynamic_slice_in_dim(mask, off, qs)
        mask = jax.lax.dynamic_update_slice_in_dim(
            mask, jnp.where(keep[:, None, None], mk, cur), off, 0)
        picked = picked + jnp.sum(jnp.where(
            sparse[:, None], jnp.sum(mk.astype(i32), axis=-1), 0))
        return mask, picked

    mask, picked_chunk = jax.lax.fori_loop(
        0, meta["n_blocks"], block,
        (jnp.ones((t + qs, hkv, mb), bool), jnp.zeros((), i32)))
    mask = page_mask_of_lists(mask[:t], sel, n_sel, listed,
                              meta["first_tok"])

    # -- counts: (query, KV head) page visits of the queries past dense_len
    ctx_tok = (pos + bs) // bs                             # pages of n keys
    in_chunk = meta["real"] & (this[meta["t2b"]] > 1) \
        & (pos + 1 > s.dense_len)
    ctx_chunk = jnp.sum(jnp.where(in_chunk, ctx_tok, 0)) * hkv
    sel_rows = jnp.sum(n_sel)
    ctx_rows = jnp.sum(jnp.where(listed, (n_row + bs - 1) // bs, 0)) * hkv
    # K slabs: a listed row's list; any other row's cached pages, once a
    # wide query tile (fetched) or once (the least)
    cached = jnp.where(meta["live"] & ~listed, (start + bs - 1) // bs, 0) \
        * hkv
    counts = jnp.stack([sel_rows + picked_chunk, sel_rows + ctx_chunk,
                        ctx_rows + ctx_chunk, jnp.zeros((), i32),
                        sel_rows + jnp.sum(cached * wide_tiles(meta["cu"],
                                                               this)),
                        sel_rows + jnp.sum(cached)]).astype(i32)
    return {"listed": listed.astype(i32), "sel": sel, "n_sel": n_sel,
            "page_mask": mask}, counts
