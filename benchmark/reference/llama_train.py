"""Plain reference of the dense decoder's training step.

The published block (Mistral-7B-v0.1: RMSNorm -> grouped-query attention
with rotate-half rope -> residual -> RMSNorm -> SwiGLU -> residual), the
untied head, mean next-token cross-entropy, and AdamW with global-norm
clipping, in float32. Sliding-window attention is full causal attention
here because no sequence passes the 4096-token window.

It follows the first TWO updates and the first THREE losses: holding Adam's
two float32 moments beside the float32 gradient of a 0.9B-parameter model
does not fit one 16 GB chip next to the activations, while the first two
updates need only the two gradients:
    m1 = (1-b1) g1                 v1 = (1-b2) g1^2
    m2 = b1 m1 + (1-b1) g2         v2 = b2 v1 + (1-b2) g2^2
Parameters are stored in the configuration's dtype (bfloat16) after each
update, as the configuration states, so a gradient arrives in bfloat16 (it
is taken with respect to the stored parameters, as in the program);
clipping, the moments and the update arithmetic are float32.

Memory: layers run under `lax.scan` with `jax.checkpoint`, and inside a
layer each sequence of the batch runs alone (`lax.map`), attention one
group of query heads at a time, so the gradient of one layer's weights is
the only large temporary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .precision import matmul


_MLP_ROWS = 1024        # rows of one sequence the MLP takes at a time


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(t, theta):
    """t [S, heads, D]; rotate-half convention of the published model."""
    s, _, d = t.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], axis=-1)
    return t * cos + rot * sin


def _attention(q, k, v, precision):
    """q [S, G, D] (the G query heads that share one kv head), k, v [S, D];
    causal softmax attention -> [S, G, D]."""
    s, _, d = q.shape
    logits = matmul(q.transpose(1, 0, 2), k.T, precision) / jnp.sqrt(
        jnp.float32(d))                                     # [G, S, S]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), -1)
    return matmul(probs, v, precision).transpose(1, 0, 2)


def _block_one(x, w, model, precision):
    """One decoder block on one sequence x [S, H]."""
    n_q, n_kv = model["num_attention_heads"], model["num_key_value_heads"]
    s, h = x.shape
    d = h // n_q
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    hx = _rms_norm(x, w["ln_attn"], eps)
    q = _rope(matmul(hx, w["wq"], precision).reshape(s, n_q, d), theta)
    k = _rope(matmul(hx, w["wk"], precision).reshape(s, n_kv, d), theta)
    v = matmul(hx, w["wv"], precision).reshape(s, n_kv, d)
    qg = q.reshape(s, n_kv, n_q // n_kv, d).transpose(1, 0, 2, 3)
    attend = jax.checkpoint(
        functools.partial(_attention, precision=precision))
    out = jax.lax.map(lambda a: attend(*a),
                      (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    attn = out.transpose(1, 0, 2, 3).reshape(s, h)          # [S, H]
    x = x + matmul(attn, w["wo"], precision)
    hx = _rms_norm(x, w["ln_mlp"], eps)

    @jax.checkpoint
    def mlp(rows):
        gated = jax.nn.silu(matmul(rows, w["w_gate"], precision)) \
            * matmul(rows, w["w_up"], precision)
        return matmul(gated, w["w_down"], precision)

    return x + jax.lax.map(
        mlp, hx.reshape(-1, min(_MLP_ROWS, s), h)).reshape(s, h)


def _nll_one(x, labels, final_norm, lm_head, eps, precision):
    """Summed next-token NLL of one sequence x [S, H]."""
    logits = matmul(_rms_norm(x, final_norm, eps), lm_head, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


def loss_fn(params, ids, labels, model, precision="f32", rows=None):
    """Mean next-token NLL over the batch (or over its first `rows`
    sequences: the planted fault "half of the batch left out")."""
    if rows is not None:
        ids, labels = ids[:rows], labels[:rows]
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)

    block = jax.checkpoint(functools.partial(
        _block_one, model=model, precision=precision))

    @jax.checkpoint
    def layer(x, w):
        w = f32(w)
        return jax.lax.map(lambda xs: block(xs, w), x), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    head = jax.checkpoint(functools.partial(
        _nll_one, eps=model["rms_norm_eps"], precision=precision))
    final_norm = params["final_norm"].astype(jnp.float32)
    lm_head = params["lm_head"].astype(jnp.float32)
    nll = jax.lax.map(lambda a: head(a[0], a[1], final_norm, lm_head),
                      (x, labels))
    return jnp.sum(nll) / labels.size


def _clip(grads, clip):
    """The gradients in float32 (they arrive in the parameters' dtype),
    scaled so that their global norm is at most `clip`."""
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def _adamw(p, m, v, t, opt):
    b1, b2 = opt["beta1"], opt["beta2"]
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"]) \
        + opt["weight_decay"] * p.astype(jnp.float32)
    return (p.astype(jnp.float32) - opt["learning_rate"] * step) \
        .astype(p.dtype)


def leaf_norms(tree):
    """{leaf path: Frobenius norm} as one small device array per leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(k.key) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for path, a in flat}


def first_steps(params, batches, model, opt, precision="f32", rows=None):
    """The readings of the first steps: losses of steps 1-3, the norm of
    each leaf's first (clipped) gradient, and the norm of each leaf's change
    over the first two updates. `params` is donated leaf by leaf."""
    b1, b2 = opt["beta1"], opt["beta2"]
    grad = jax.jit(jax.value_and_grad(functools.partial(
        loss_fn, model=model, precision=precision, rows=rows)))
    clip = jax.jit(functools.partial(_clip, clip=opt["grad_clip_norm"]))

    @functools.partial(jax.jit, donate_argnums=0)
    def update1(p, g1):
        return jax.tree.map(lambda p_, g: _adamw(
            p_, (1 - b1) * g, (1 - b2) * g * g, 1, opt), p, g1)

    @functools.partial(jax.jit, donate_argnums=0)
    def update2(p, g1, g2):
        return jax.tree.map(lambda p_, a, b: _adamw(
            p_, b1 * (1 - b1) * a + (1 - b1) * b,
            b2 * (1 - b2) * a * a + (1 - b2) * b * b, 2, opt), p, g1, g2)

    p0_norm_of_change = jax.jit(lambda p2, p0: leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p2, p0)))
    p0 = jax.tree.map(jnp.copy, params)
    loss1, raw = grad(params, *batches[0])
    g1 = clip(raw)
    del raw
    grad_norms = jax.jit(leaf_norms)(g1)
    p1 = update1(params, g1)
    loss2, raw = grad(p1, *batches[1])
    g2 = clip(raw)
    del raw
    p2 = update2(p1, g1, g2)
    del g1, g2
    loss3 = jax.jit(functools.partial(
        loss_fn, model=model, precision=precision, rows=rows))(
            p2, *batches[2])
    change = p0_norm_of_change(p2, p0)
    return {"loss": [float(loss1), float(loss2), float(loss3)],
            "grad_norm": {k: float(v) for k, v in grad_norms.items()},
            "change_norm": {k: float(v) for k, v in change.items()}}
