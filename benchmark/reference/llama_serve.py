"""Plain reference of the served decoder: one teacher-forced forward pass.

The same published block as `llama_train`, with the two departures the
engine has and the configuration file lists: RMSNorm epsilon 1e-6 (the
engine's `nn.RMSNorm` default, where the model publishes 1e-5) and rope on
interleaved pairs (the engine's convention; a fixed permutation of each
head's columns away from the published rotate-half, which random weights
cannot tell apart). No cache, no paging, no batching: prompt and served
tokens go through once, and the logits at the served positions come back.

Weights are {name: array} under the names of the engine model's
parameters ("qkv.3.weight" is [H, H + 2*KV] with q, k, v side by side,
"gate_up.3.weight" is [H, 2*F] with gate then up). A layer's weights are
upcast one layer at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .precision import matmul


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope_pairs(t, theta):
    """t [S, heads, D]; rotates the pairs (0,1), (2,3), ..."""
    s, _, d = t.shape
    inv = 1.0 / (theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    t1, t2 = t[..., 0::2], t[..., 1::2]
    return jnp.stack([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                     axis=-1).reshape(t.shape)


@functools.partial(jax.jit, static_argnames=("n_q", "n_kv", "ffn", "eps",
                                             "theta", "precision"))
def _layer(x, ln1, qkv, proj, ln2, gate_up, down, *, n_q, n_kv, ffn, eps,
           theta, precision):
    f32 = jnp.float32
    s, h = x.shape
    d = h // n_q
    a = matmul(_rms_norm(x, ln1.astype(f32), eps), qkv.astype(f32),
               precision)
    q = _rope_pairs(a[:, :h].reshape(s, n_q, d), theta)
    k = _rope_pairs(a[:, h:h + n_kv * d].reshape(s, n_kv, d), theta)
    v = a[:, h + n_kv * d:].reshape(s, n_kv, d)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def attend(qkv_g):
        qg, kg, vg = qkv_g                       # [S, G, D], [S, D], [S, D]
        logits = matmul(qg.transpose(1, 0, 2), kg.T, precision) \
            / jnp.sqrt(f32(d))
        probs = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), -1)
        return matmul(probs, vg, precision).transpose(1, 0, 2)

    out = jax.lax.map(attend, (
        q.reshape(s, n_kv, n_q // n_kv, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    x = x + matmul(out.transpose(1, 0, 2, 3).reshape(s, h),
                   proj.astype(f32), precision)
    gu = matmul(_rms_norm(x, ln2.astype(f32), eps), gate_up.astype(f32),
                precision)
    return x + matmul(jax.nn.silu(gu[:, :ffn]) * gu[:, ffn:],
                      down.astype(f32), precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, ln_f, head, *, eps, precision):
    return matmul(_rms_norm(x, ln_f.astype(jnp.float32), eps),
                  head.astype(jnp.float32), precision)


def logits_at(weights, tokens, first, model, precision="f32", pad_to=None):
    """Logits [len(tokens) - first, V] that predict tokens[first + 1:] and
    one more: row i is the distribution after tokens[:first + i + 1].
    `pad_to` right-pads to one length, so that one shape compiles once
    (causal attention leaves the real positions untouched)."""
    n = len(tokens)
    ids = jnp.zeros((pad_to or n,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    x = jnp.take(weights["embed.weight"], ids, axis=0).astype(jnp.float32)
    kw = dict(n_q=model["num_attention_heads"],
              n_kv=model["num_key_value_heads"],
              ffn=model["intermediate_size"], eps=model["rms_norm_eps"],
              theta=model["rope_theta"], precision=precision)
    for i in range(model["num_hidden_layers"]):
        x = _layer(x, *(weights[f"{k}.{i}.weight"] for k in
                        ("ln1", "qkv", "proj", "ln2", "gate_up", "down")),
                   **kw)
    return _head(x[first:n], weights["ln_f.weight"], weights["head.weight"],
                 eps=model["rms_norm_eps"], precision=precision)
