"""Plain reference of the served MiniCPM-SALA cut: one teacher-forced
forward pass in float32, matmuls at `highest`.

The published `minicpm_sala` block (`mixer_types`: one mixer a layer, then a
SwiGLU MLP), `L` = `published.num_hidden_layers` where the depth is reduced:
`h = E[id] scale_emb`; `h += (scale_depth / sqrt(L)) Mixer(RMSNorm(h))`;
`h += (scale_depth / sqrt(L)) W_down(silu(W_gate u) * W_up u)`; logits
`W_head RMSNorm(h) / (hidden_size / dim_model_base)`.

- `lightning-attn`: RMSNorm a head on q and k, rope on half-split pairs over
  the whole head, then the recurrence `S_t = lambda_h S_{t-1} + k_t^T v_t`,
  `o_t = (q_t / sqrt(d)) S_t` as a `lax.scan` OVER TOKENS (not the chunked
  form, not the kernel), `lambda_h = exp(-2^(-8 (h + 1) / H))`; RMSNorm over
  the concatenated heads, times `sigmoid(u W_z)`; `W_o`.
- `minicpm4`: RMSNorm a head on q and k, no rope. The compressed keys are
  RECOMPUTED FROM ALL KEYS (means of `kernel_stride` keys, then of
  `kernel_size / kernel_stride` of those); for a block of queries the
  selection is a boolean `[queries, KV heads, blocks]` mask: a query with
  `n > dense_len` keys takes the softmax over the windows that end at or
  before it, summed over its KV head's query heads; a block's score by a
  scatter-max of each window into the blocks of its first and of its last
  key; block 0 .. `init_blocks - 1`, the blocks of the last `window_size`
  positions, and among the others the `topk` that fewest outrank (a higher
  score, or the same score at a lower index). Then ONE masked softmax over
  all keys (causal, and the block mask where `n > dense_len`), a query head
  at a time; times `sigmoid(u W_g)`; `W_o`.

No cache, no paging, no batching, no kernel, no list of pages. Queries go
in blocks of `_Q_BLOCK` and the MLP in blocks of `_TOKEN_BLOCK` tokens, a
layer's weights are upcast a layer at a time, so that `assumed.max_context`
positions fit one chip beside the bfloat16 weights. With `precision="fp8"`
(the control) every matmul, the selection's scores among them, rounds both
operands to fp8; the recurrence and the norms stay float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .precision import matmul

F32 = jnp.float32
_Q_BLOCK = 256
_TOKEN_BLOCK = 2048


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(F32)


def _in_blocks(fn, n, size, *arrays):
    """fn over blocks of `size` rows of `arrays` (n a multiple of size)."""
    out = jax.lax.map(lambda a: fn(*a), tuple(
        a.reshape((n // size, size) + a.shape[1:]) for a in arrays))
    return out.reshape((n,) + out.shape[2:])


@functools.partial(jax.jit, static_argnames=("heads", "eps", "theta",
                                             "res", "precision"))
def _lightning(x, norm, qkv, q_norm, k_norm, out_norm, gate, o_proj, *,
               heads, eps, theta, res, precision):
    s = x.shape[0]
    u = _rms_norm(x, norm, eps)
    a = matmul(u, qkv.astype(F32), precision).reshape(s, 3, heads, -1)
    d = a.shape[-1]
    half = d // 2
    ang = jnp.arange(s, dtype=F32)[:, None] \
        * (1.0 / theta ** (jnp.arange(half, dtype=F32) / half))
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(t):
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                               axis=-1)

    q = rope(_rms_norm(a[:, 0], q_norm, eps)) * d ** -0.5
    k = rope(_rms_norm(a[:, 1], k_norm, eps))
    v = a[:, 2]
    lam = jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(heads, dtype=F32) + 1)
                            / heads)))

    def token(state, t):
        q_t, k_t, v_t = t                                  # [H, D]
        state = lam[:, None, None] * state \
            + k_t[:, :, None] * v_t[:, None, :]            # [H, Dk, Dv]
        return state, jnp.sum(q_t[:, :, None] * state, axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), F32), (q, k, v))
    o = _rms_norm(o.reshape(s, heads * d), out_norm, eps) \
        * jax.nn.sigmoid(matmul(u, gate.astype(F32), precision))
    return x + res * matmul(o, o_proj.astype(F32), precision)


@functools.partial(jax.jit, static_argnames=(
    "n_q", "n_kv", "eps", "res", "precision", "kernel", "stride", "block",
    "topk", "init", "window", "dense_len"))
def _sparse(x, norm, qkv, q_norm, k_norm, gate, o_proj, *, n_q, n_kv, eps,
            res, precision, kernel, stride, block, topk, init, window,
            dense_len):
    s = x.shape[0]                          # a multiple of block, _Q_BLOCK
    g = n_q // n_kv
    u = _rms_norm(x, norm, eps)
    a = matmul(u, qkv.astype(F32), precision)
    d = a.shape[1] // (n_q + 2 * n_kv)
    q = _rms_norm(a[:, :n_q * d].reshape(s, n_q, d), q_norm, eps)
    k = _rms_norm(a[:, n_q * d:(n_q + n_kv) * d].reshape(s, n_kv, d),
                  k_norm, eps)
    v = a[:, (n_q + n_kv) * d:].reshape(s, n_kv, d)
    # compressed keys from all keys: window j = keys stride j .. + kernel
    per = kernel // stride
    parts = jnp.mean(k.reshape(s // stride, stride, n_kv, d), axis=1)
    n_win = s // stride - per + 1
    ck = sum(parts[i:i + n_win] for i in range(per)) / per  # [NW, HKV, D]
    win_first = jnp.arange(n_win) * stride
    win_last = win_first + kernel - 1
    n_blocks = s // block
    blk = jnp.arange(n_blocks)
    scale = d ** -0.5

    def queries(q_b, pos):
        """q_b [Q, HQ, D] at positions pos [Q] -> attention out [Q, HQ, D]"""
        n = pos + 1
        qg = q_b.reshape(-1, n_kv, g, d)
        # the selection's scores, a KV head at a time
        def head_scores(h):
            lg = matmul(qg[:, h].reshape(-1, d), ck[:, h].T, precision) \
                .reshape(-1, g, n_win) * scale
            ok = (win_last[None, :] <= pos[:, None])[:, None, :]
            lg = jnp.where(ok, lg, -jnp.inf)
            top = jnp.max(lg, axis=-1, keepdims=True)
            e = jnp.where(ok, jnp.exp(lg - jnp.where(top > -jnp.inf, top,
                                                     0.0)), 0.0)
            p = jnp.sum(e / jnp.maximum(jnp.sum(e, -1, keepdims=True),
                                        1e-30), axis=1)    # [Q, NW]
            sc = jnp.full((p.shape[0], n_blocks), -jnp.inf, F32)
            sc = sc.at[:, win_first // block].max(p)
            return sc.at[:, win_last // block].max(p)

        score = jax.lax.map(head_scores, jnp.arange(n_kv))  # [HKV, Q, NB]
        w_lo = jnp.maximum(pos - window + 1, 0) // block
        local = (blk[None] >= w_lo[:, None]) \
            & (blk[None] <= (pos // block)[:, None])
        first = jnp.broadcast_to(blk[None] < init, local.shape)
        cand = ~first & (blk[None] < w_lo[:, None])
        score = jnp.where(cand[None], score, -jnp.inf)
        # a block's rank among the others: how many score higher, or the
        # same at a lower index (adjacent blocks share a window, so equal
        # scores are common: the lower block goes first)
        def rank(sc):                                       # [Q, NB]
            ahead = (sc[:, None, :] > sc[:, :, None]) | (
                (sc[:, None, :] == sc[:, :, None])
                & (blk[None, None, :] < blk[None, :, None]))
            return jnp.sum(ahead, axis=-1)

        chosen = cand[None] & (jax.lax.map(rank, score) < topk)
        take = jnp.where((n > dense_len)[None, :, None],
                         first[None] | local[None] | chosen, True)
        keys = jnp.repeat(take, block, axis=-1) \
            & (jnp.arange(s)[None, None, :] <= pos[None, :, None])

        def head(i):
            kv = i // g
            lg = matmul(q_b[:, i], k[:, kv].T, precision) * scale
            pr = jax.nn.softmax(jnp.where(keys[kv], lg, -jnp.inf), -1)
            return matmul(pr, v[:, kv], precision)

        return jax.lax.map(head, jnp.arange(n_q)).transpose(1, 0, 2)

    out = _in_blocks(queries, s, _Q_BLOCK, q, jnp.arange(s))
    o = out.reshape(s, n_q * d) \
        * jax.nn.sigmoid(matmul(u, gate.astype(F32), precision))
    return x + res * matmul(o, o_proj.astype(F32), precision)


@functools.partial(jax.jit, static_argnames=("eps", "res", "precision"))
def _mlp(x, norm, gate_up, down, *, eps, res, precision):
    f = down.shape[0]
    wgu, wd = gate_up.astype(F32), down.astype(F32)

    def tokens(xb):
        gu = matmul(_rms_norm(xb, norm, eps), wgu, precision)
        return xb + res * matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], wd,
                                 precision)

    return _in_blocks(tokens, x.shape[0], min(_TOKEN_BLOCK, x.shape[0]), x)


@functools.partial(jax.jit, static_argnames=("eps", "div", "precision"))
def _head(x, norm, head, *, eps, div, precision):
    return matmul(_rms_norm(x, norm, eps), head.astype(F32), precision) / div


def logits_at(weights, tokens, first, model, precision="f32", pad_to=None):
    """Logits [len(tokens) - first, V] that predict tokens[first + 1:] and
    one more: row i is the distribution after tokens[:first + i + 1].
    `pad_to` right-pads to one length, so that one shape compiles once
    (every layer is causal: the real positions are untouched); the length
    is then rounded up to whole blocks of queries and of tokens."""
    n = len(tokens)
    sp = model["assumed"]["sparse_config"]
    unit = _Q_BLOCK * sp["block_size"] // math.gcd(_Q_BLOCK,
                                                   sp["block_size"])
    length = -(-(pad_to or n) // unit) * unit
    if length > _TOKEN_BLOCK:
        length = -(-length // _TOKEN_BLOCK) * _TOKEN_BLOCK
    ids = jnp.zeros((length,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32) \
        * model["scale_emb"]
    eps = model["rms_norm_eps"]
    depth = model.get("published", {}).get("num_hidden_layers",
                                           model["num_hidden_layers"])
    res = model["scale_depth"] / math.sqrt(depth)
    for i, kind in enumerate(model["mixer_types"]):
        w = lambda name: weights[f"layers.{i}.{name}"]       # noqa: E731
        if kind == "lightning-attn":
            x = _lightning(x, *(w(k) for k in (
                "norm", "qkv", "q_norm", "k_norm", "out_norm", "gate",
                "o_proj")), heads=model["lightning_nh"], eps=eps,
                theta=float(model["rope_theta"]), res=res,
                precision=precision)
        else:
            x = _sparse(x, *(w(k) for k in (
                "norm", "qkv", "q_norm", "k_norm", "gate", "o_proj")),
                n_q=model["num_attention_heads"],
                n_kv=model["num_key_value_heads"], eps=eps, res=res,
                precision=precision, kernel=sp["kernel_size"],
                stride=sp["kernel_stride"], block=sp["block_size"],
                topk=sp["topk"], init=sp["init_blocks"],
                window=sp["window_size"], dense_len=sp["dense_len"])
        x = _mlp(x, w("mlp_norm"), w("gate_up"), w("down"), eps=eps,
                 res=res, precision=precision)
    return _head(x[first:n], weights["final_norm"], weights["head"],
                 eps=eps, div=model["hidden_size"] / model["dim_model_base"],
                 precision=precision)
