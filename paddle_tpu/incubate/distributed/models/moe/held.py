"""An expert layer that is told which experts it holds.

Expert parallelism gives each chip `count` of a layer's `n_routed` experts.
The router is whole on every chip: it scores all `n_routed` experts, picks
each token's `top_k` and normalises their weights over ALL the chosen. This
chip then computes the part of the result that ITS experts give: the
(token, expert) pairs whose expert lies in `held = (first, count)`, sorted
by expert and multiplied group by group (`jax.lax.ragged_dot`). There is no
capacity and no dropped token: a pair is left out only because its expert
lives elsewhere. On one chip there is no exchange; what the other chips'
experts would have added is simply not in the result, and the shares of all
the chips add up to the uncut layer (tests/test_nemotron_h.py,
`test_the_shares_add_up_to_the_uncut_layer`).

Functional, over plain arrays: the serving model in `models/nemotron_h.py`
calls these inside its compiled step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["sigmoid_topk_route", "held_pairs", "held_expert_product",
           "relu2"]


def relu2(x):
    """relu(x)^2, the experts' activation (not gated)."""
    r = jnp.maximum(x, 0)
    return r * r


def sigmoid_topk_route(u, w_gate, bias, top_k, scale=1.0, norm=True):
    """The router, in float32: `s = sigmoid(u W_g)` over all experts; the
    `top_k` largest of `s + bias` are chosen (the bias only selects); their
    weights are `s` itself, normalised over the chosen where `norm`, times
    `scale`. Returns (expert ids `[T, top_k]` int32, weights `[T, top_k]`
    float32)."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(u.astype(f32), w_gate.astype(f32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(f32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * scale


def held_pairs(idx, held, valid=None):
    """The pairs this chip computes, sorted by expert.

    idx `[T, K]` expert ids; `held = (first, count)`; `valid [T]` bool
    leaves a token's pairs out (padding). Returns (order `[T*K]`: the flat
    pair indices, held pairs first in expert order; group_sizes `[count]`:
    pairs of each held expert; n_held: their sum)."""
    first, count = held
    local = idx.reshape(-1) - first
    mine = (local >= 0) & (local < count)
    if valid is not None:
        mine = mine & jnp.repeat(valid, idx.shape[1])
    key = jnp.where(mine, local, count)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
    return order, sizes, jnp.sum(sizes)


def held_expert_product(x, idx, w, w1, w2, held, valid=None, act=relu2,
                        window=128):
    """`sum_i w_i act(x W1_i) W2_i` over the chosen experts `i` that this
    chip holds. x `[T, D]`; idx, w `[T, K]` from the router; w1
    `[count, D, F]`, w2 `[count, F, D]`: the held experts' weights.
    Returns (out `[T, D]` in x's dtype, group_sizes `[count]`).

    The held pairs, sorted by expert, go through `jax.lax.ragged_dot`
    `window` rows at a time, as many windows as hold a pair (a while loop:
    `T * K` rows are the bound, an eighth of them the rule). XLA's grouped
    product works in row tiles of min(512, rows) and spends a whole tile
    on every expert that has a row in it, so over all `T * K` rows at once
    the two products cost several times what reading the weights once
    does, and in windows of 128 rows less (the times are in PERF.md
    section 6, PR 29)."""
    t, k = idx.shape
    order, sizes, n_held = held_pairs(idx, held, valid)
    token = order // k
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    xs = jnp.pad(x[token], ((0, window), (0, 0)))          # [T*K + win, D]

    def one_window(carry):
        i, y = carry
        lo = i * window
        # the rows of each expert that lie in [lo, lo + window)
        here = jnp.clip(jnp.minimum(ends, lo + window)
                        - jnp.maximum(starts, lo), 0, window)
        rows = jax.lax.dynamic_slice_in_dim(xs, lo, window, 0)
        h = act(jax.lax.ragged_dot(rows, w1, here))
        out = jax.lax.ragged_dot(h.astype(x.dtype), w2, here)
        keep = (lo + jnp.arange(window) < n_held)[:, None]
        return i + 1, jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where(keep, out.astype(jnp.float32), 0.0), lo, 0)

    _, y = jax.lax.while_loop(
        lambda c: c[0] * window < n_held, one_window,
        (jnp.int32(0), jnp.zeros((t * k + window, x.shape[1]),
                                 jnp.float32)))
    # back to (token, choice) order by a gather (the inverse permutation),
    # then the weighted sum over a token's choices: no scatter
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    out = jnp.einsum("tkd,tk->td", y[back].reshape(t, k, -1), w)
    return out.astype(x.dtype), sizes
