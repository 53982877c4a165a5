"""Ring attention — context parallelism over the sequence axis.

Reference analog: SEP/context parallel (SURVEY §2.5 —
fleet/meta_parallel/segment_parallel.py + sep groups; the reference
delegates the attention math to fused kernels over p2p-exchanged segments;
no standalone ring-attention module exists there). TPU-native design: the
sequence is sharded over the 'sep' mesh axis; inside shard_map each device
holds [B, S/n, H, D] and the KV shards rotate around the ring with
lax.ppermute while each hop's partial attention is merged online in
log-sum-exp space. Per-hop compute uses the same blockwise flash math as
ops/pallas/flash_attention; ICI transfer overlaps with compute under XLA's
latency-hiding scheduler. Backward is rematerialized (jax.checkpoint over
the scanned ring), so memory stays O(S/n) per device.

Causality uses ABSOLUTE positions: device i's queries attend to a rotating
KV shard whose global offset is derived from the hop index, so masks are
exact for any n.

Backward is a hand-written ring VJP (jax.custom_vjp) using the flash
recurrences per hop: residuals are only (q, k, v, o, lse) locals — O(S/n)
per device — and dk/dv accumulators travel around the ring with their KV
shards, so the backward makes the same n ppermute hops as the forward
instead of retracing the scan (reference capability: flash-attention
backward kernels + p2p segment exchange; see also
pipeline_zero_bubble-style decoupled grads).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["ring_attention_bshd", "ring_attention_bhsd"]


def _block_attend(q, k, v, qpos, kpos, causal, scale):
    """Partial attention of local q against one KV shard.
    q: [B,H,Sq,D], k/v: [B,H,Sk,D]; returns (o [B,H,Sq,D], lse [B,H,Sq])."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    # rows with no visible keys: exp(-inf - -inf) guards via where
    safe_lse = jnp.where(jnp.isfinite(lse), lse, 0.0)
    probs = jnp.exp(logits - safe_lse[..., None])
    probs = jnp.where(jnp.isfinite(lse)[..., None], probs, 0.0)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return o, lse


def _merge(o, lse, o_new, lse_new):
    """Merge two NORMALIZED partial attentions in log-sum-exp space."""
    m = jnp.maximum(lse, lse_new)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w_old = jnp.where(jnp.isfinite(lse), jnp.exp(lse - m_safe), 0.0)
    w_new = jnp.where(jnp.isfinite(lse_new), jnp.exp(lse_new - m_safe), 0.0)
    denom = jnp.maximum(w_old + w_new, 1e-37)
    o_merged = (o * w_old[..., None] + o_new * w_new[..., None]) \
        / denom[..., None]
    lse_merged = m_safe + jnp.log(denom)
    lse_merged = jnp.where(jnp.isfinite(m), lse_merged, -jnp.inf)
    return o_merged, lse_merged


def _ring_fwd_impl(q, k, v, axis_name: str, causal: bool):
    """q,k,v: [B,H,Sl,D] local shards inside shard_map over axis_name.
    Returns (o normalized in q.dtype, lse [B,H,Sl] f32)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, sl, d = q.shape
    scale = 1.0 / math.sqrt(d)
    qpos = idx * sl + jnp.arange(sl)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, hop):
        o, lse, kk, vv = carry
        # the KV shard currently held came from device (idx - hop) mod n
        src = (idx - hop) % n
        kpos = src * sl + jnp.arange(sl)
        o_new, lse_new = _block_attend(q, kk, vv, qpos, kpos, causal, scale)
        o, lse = _merge(o, lse, o_new, lse_new)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return (o, lse, kk, vv), None

    o0 = jnp.zeros((b, h, sl, d), jnp.float32)
    lse0 = jnp.full((b, h, sl), -jnp.inf, jnp.float32)
    (o, lse, _, _), _ = jax.lax.scan(
        body, (o0, lse0, k.astype(jnp.float32), v.astype(jnp.float32)),
        jnp.arange(n))
    # denominator already folded into the merge weights; o is normalized
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_core(q, k, v, axis_name: str, causal: bool):
    o, _ = _ring_fwd_impl(q, k, v, axis_name, causal)
    return o


def _ring_core_fwd(q, k, v, axis_name, causal):
    o, lse = _ring_fwd_impl(q, k, v, axis_name, causal)
    return o, (q, k, v, o, lse)


def _ring_core_bwd(axis_name, causal, res, do):
    """Flash backward per hop; dk/dv accumulators ride the ring with their
    KV shards and arrive home after n hops."""
    q, k, v, o, lse = res
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, sl, d = q.shape
    scale = 1.0 / math.sqrt(d)
    qpos = idx * sl + jnp.arange(sl)
    perm = [(i, (i + 1) % n) for i in range(n)]

    q32 = q.astype(jnp.float32)
    do32 = do.astype(jnp.float32)
    # flash 'delta': rowwise sum(do * o) — the softmax normalization term
    delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)
    safe_lse = jnp.where(jnp.isfinite(lse), lse, 0.0)
    visible = jnp.isfinite(lse)

    def body(carry, hop):
        dq, kk, vv, dk, dv = carry
        src = (idx - hop) % n
        kpos = src * sl + jnp.arange(sl)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q32, kk) * scale
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
            logits = jnp.where(mask[None, None], logits, -jnp.inf)
        # p normalized by the FINAL lse -> exact softmax probabilities
        p = jnp.exp(logits - safe_lse[..., None])
        p = jnp.where(jnp.isfinite(logits) & visible[..., None], p, 0.0)
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, do32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do32, vv)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kk) * scale
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, q32) * scale
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        dk = jax.lax.ppermute(dk, axis_name, perm)
        dv = jax.lax.ppermute(dv, axis_name, perm)
        return (dq, kk, vv, dk, dv), None

    zeros_kv = jnp.zeros((b, h, sl, d), jnp.float32)
    (dq, _, _, dk, dv), _ = jax.lax.scan(
        body,
        (jnp.zeros((b, h, sl, d), jnp.float32),
         k.astype(jnp.float32), v.astype(jnp.float32), zeros_kv, zeros_kv),
        jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention_bhsd(q, k, v, axis_name: str = "sep",
                        is_causal: bool = True):
    """[B, H, S_local, D] layout, call inside shard_map over axis_name."""
    return _ring_core(q, k, v, axis_name, bool(is_causal))


def ring_attention_bshd(q, k, v, axis_name: str = "sep",
                        is_causal: bool = True):
    """Reference layout [B, S_local, H, D]."""
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    out = _ring_core(qt, kt, vt, axis_name, bool(is_causal))
    return jnp.swapaxes(out, 1, 2)
