"""Flash attention (Pallas TPU kernel + XLA blockwise fallback).

Reference analog: the flash-attention CUDA kernels the reference vendors
(third_party flashattn, surfaced at
python/paddle/nn/functional/flash_attention.py:147). TPU-native design:
online-softmax blockwise attention. Forward is a Pallas kernel — one q-block
per grid step, KV streamed through VMEM in blocks with the (m, l, acc)
running-softmax carry, logits never materialized in HBM. Backward uses the
standard flash recomputation formulas, as Pallas kernels (dkv gridded over KV
blocks, dq over Q blocks) or a lax.scan fallback (O(S) memory).

Dropout runs INSIDE the kernels: the keep mask is a counter-based hash of the
global (q_idx, k_idx, batch*head, seed) coordinates (lowbias32-style integer
mixer), so forward and both backward kernels regenerate bit-identical masks
with no PRNG state, no stored mask, and no in-kernel transposes — and the
XLA fallback generates the exact same mask, so the paths agree numerically.

Key-padding masks (the [B, 1, 1, Sk]-broadcastable case, which covers the
reference's padding-mask idiom) stream through the kernels as an additive
[B, Sk] bias — O(B*S) HBM instead of the O(B*H*S^2) a materialized-attention
fallback would spend. Arbitrary [B, H, Sq, Sk] masks still fall back.

Public entry points take the reference's [batch, seq, heads, head_dim]
("BSHD") layout.

Degenerate rows where every key is masked produce an (arbitrary) uniform
average of V rather than the reference's NaN.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import kernels_enabled, note_reference_dispatch

# 512 blocks: bigger MXU tiles amortize the VPU online-softmax work (the
# kernel's roofline share at this size is `train.flash_attention_roofline`
# in PERF.md); the bh grid axis is parallel, q/kv arbitrary.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512

# finite stand-in for -inf in additive masks: exp(x - m) underflows to exactly
# 0 while keeping the online-softmax max/alpha arithmetic NaN-free when a
# leading KV block is fully masked.
_MASK_MIN = -1e30


def _dim_semantics(*sems):
    return pltpu.CompilerParams(dimension_semantics=sems)


# ---------------------------------------------------------------------------
# dropout keep-mask: stateless counter-based hash over global coordinates.
# lowbias32-style mixer (Ellis' low-bias 32-bit permutation seeded per
# (bh, seed)); orientation-independent, so every kernel and the XLA fallback
# derive the identical mask.
# ---------------------------------------------------------------------------

def _dropout_threshold(dropout_p):
    """uint32 threshold: keep iff hash >= threshold, P(keep) = 1 - p."""
    return np.uint32(min(int(round(dropout_p * 4294967296.0)), 4294967295))


def _hash_keep(seed_u32, bh_u32, q_idx, k_idx, thresh_u32):
    """Elementwise keep mask. q_idx/k_idx: int32 arrays (any broadcastable
    orientation) of GLOBAL positions; seed_u32/bh_u32: uint32 scalars or
    arrays. Returns bool of the broadcast shape."""
    h = (q_idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + k_idx.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    h = h + seed_u32 + bh_u32 * jnp.uint32(0xC2B2AE3D)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h >= thresh_u32


def _key_to_seed(key):
    """Fold a jax PRNG key into a (1,) int32 seed for the hash mask."""
    data = jnp.ravel(jax.random.key_data(key)).astype(jnp.uint32)
    seed = data[0]
    for i in range(1, data.shape[0]):
        seed = seed ^ data[i]
    return seed.astype(jnp.int32).reshape(1)


# ---------------------------------------------------------------------------
# reference (generic-mask / ungridded cases + numerical ground truth in tests)
# ---------------------------------------------------------------------------

def _attention_ref(q, k, v, mask, is_causal, dropout_p, dropout_key=None):
    # q,k,v: [B, H, S, D]
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    sq, sk = q.shape[2], k.shape[2]
    if is_causal:
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(causal, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)
                      ).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas forward
# ---------------------------------------------------------------------------

def _fa_kernel(*refs, scale, causal, block_k, seq_k, dropout_p, has_kmask):
    if has_kmask:
        seed_ref, q_ref, k_ref, v_ref, kmask_ref, o_ref, lse_ref = refs
    else:
        seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        kmask_ref = None
    # dots run on native MXU dtype (bf16 in, f32 accumulate); softmax math
    # stays f32. scale folds into the f32 logits, not the bf16 operands.
    q = q_ref[0]                                      # [bq, d]
    block_q = q.shape[0]
    q_start = pl.program_id(1) * block_q
    num_kv = seq_k // block_k
    if dropout_p > 0.0:
        thresh = _dropout_threshold(dropout_p)
        seed_u32 = seed_ref[0].astype(jnp.uint32)
        bh_u32 = pl.program_id(0).astype(jnp.uint32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if has_kmask:
            km = kmask_ref[0, 0:1, pl.ds(j * block_k, block_k)]  # [1, bk]
            s = s + km
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            qi = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            ki = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p = jnp.where(_hash_keep(seed_u32, bh_u32, qi, ki, thresh),
                          p, 0.0)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    d = q.shape[-1]
    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    if causal:
        upper = jnp.minimum(
            (q_start + block_q + block_k - 1) // block_k, num_kv)
    else:
        upper = num_kv
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
    out = acc / l
    if dropout_p > 0.0:
        out = out * (1.0 / (1.0 - dropout_p))
    o_ref[0] = out.astype(o_ref.dtype)
    # lse block is (8, block_q): 8 replicated sublanes to satisfy TPU tiling
    lse_ref[0, 0] = jnp.broadcast_to((m + jnp.log(l))[:, 0][None, :],
                                     (8, block_q))


def _pallas_forward(q, k, v, kmask, seed, causal, dropout_p,
                    block_q, block_k):
    # q,k,v: [B, H, S, D] -> flatten heads into the grid's leading axis
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    scale = 1.0 / math.sqrt(d)
    grid = (bh, sq // block_q)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),            # seed (1,)
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
    ]
    operands = [seed, q3, k3, v3]
    if kmask is not None:
        # [B, 8, Sk]: 8 replicated sublanes so (8, seq) tiles load cleanly
        km8 = jnp.broadcast_to(kmask[:, None, :].astype(jnp.float32),
                               (b, 8, sk))
        in_specs.append(pl.BlockSpec((1, 8, sk), lambda i, j: (i // h, 0, 0)))
        operands.append(km8)
    o, lse = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_k=sk, dropout_p=dropout_p,
                          has_kmask=kmask is not None),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            # lse laid out [bh, n_q_blocks, 8, block_q] (8 replicated
            # sublanes) so the block's trailing dims satisfy (8,128) tiling
            jax.ShapeDtypeStruct((bh, sq // block_q, 8, block_q),
                                 jnp.float32),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda i, j: (i, j, 0, 0)),
        ),
        compiler_params=_dim_semantics("parallel", "arbitrary"),
        name="flash_attention_fwd", metadata={"kernel": "flash_attention_fwd"},
        interpret=_interpret(),
    )(*operands)
    lse = lse[:, :, 0, :].reshape(bh, sq)
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _pallas_ok(q, k, causal, block_q, block_k):
    """Shapes the Pallas kernels handle: lane-aligned seq lengths (the
    min(DEFAULT, seq) block clamp makes the divisibility check vacuous for
    short seqs, so alignment must be required explicitly), head dim a
    multiple of 64 (d=64 runs the MXU at half the contraction width but
    still beat the XLA fallback by ~1.1x end-to-end on BERT-base train
    steps; the earlier 25x regression came from PADDING d 64->128, not from
    native-64 operands), and (for causal) aligned q/k windows (sq == sk).
    Pure predicate; `_use_kernel` is the counted dispatch decision."""
    return (kernels_enabled() and q.shape[2] % block_q == 0
            and k.shape[2] % block_k == 0
            and q.shape[2] % 128 == 0 and k.shape[2] % 128 == 0
            and q.shape[-1] % 64 == 0
            and (not causal or q.shape[2] == k.shape[2]))


def _use_kernel(q, k, causal, block_q, block_k, site):
    """The dispatch decision at one call site: True runs the Pallas
    kernel; False runs the jnp path and — when kernels are enabled, i.e.
    the shape is what refused — counts a reference dispatch."""
    ok = _pallas_ok(q, k, causal, block_q, block_k)
    if not ok and kernels_enabled():
        note_reference_dispatch(site)
    return ok


def _forward_with_lse(q, k, v, kmask, seed, causal, dropout_p):
    """Blockwise forward; returns (o, lse). XLA path used off-TPU and for
    shapes that don't tile; it derives the identical hash-based dropout
    mask, so Pallas and XLA paths agree bit-for-bit on which probs drop."""
    block_q = min(DEFAULT_BLOCK_Q, q.shape[2])
    block_k = min(DEFAULT_BLOCK_K, k.shape[2])
    if _use_kernel(q, k, causal, block_q, block_k, "flash_attention_fwd"):
        return _pallas_forward(q, k, v, kmask, seed, causal, dropout_p,
                               block_q, block_k)
    # XLA fallback (still O(S^2) HBM for logits, fine for small S / CPU tests)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if kmask is not None:
        logits = logits + kmask[:, None, None, :].astype(jnp.float32)
    if causal:
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -jnp.inf)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[..., None])
    if dropout_p > 0.0:
        keep = _full_keep_mask(seed, b, h, sq, sk, dropout_p)
        probs = jnp.where(keep, probs, 0.0) * (1.0 / (1.0 - dropout_p))
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)
                   ).astype(q.dtype)
    return o, lse


def _full_keep_mask(seed, b, h, sq, sk, dropout_p, q_offset=0, k_offset=0):
    """[b,h,sq,sk] hash keep mask identical to the in-kernel blocks."""
    thresh = _dropout_threshold(dropout_p)
    seed_u32 = seed.reshape(()).astype(jnp.uint32)
    bh_u32 = jnp.arange(b * h, dtype=jnp.int32).reshape(b, h, 1, 1) \
        .astype(jnp.uint32)
    qi = (q_offset + jnp.arange(sq, dtype=jnp.int32)).reshape(1, 1, sq, 1)
    ki = (k_offset + jnp.arange(sk, dtype=jnp.int32)).reshape(1, 1, 1, sk)
    return _hash_keep(seed_u32, bh_u32, qi, ki, thresh)


# ---------------------------------------------------------------------------
# Pallas backward: two kernels (dk/dv gridded over KV blocks, dq gridded over
# Q blocks), both using the flash recomputation formulas. Logits are formed
# TRANSPOSED ([bk, bq]) so lse/delta enter as [1, bq] row vectors and
# broadcast without any in-kernel relayout/transpose; the dropout hash mask
# is regenerated directly in the transposed orientation.
# ---------------------------------------------------------------------------

def _fa_bwd_dkv_kernel(*refs, scale, causal, block_q, seq_q, dropout_p,
                       has_kmask):
    if has_kmask:
        (seed_ref, q_ref, do_ref, k_ref, v_ref, kmask_ref, lse_ref,
         delta_ref, dk_ref, dv_ref) = refs
    else:
        (seed_ref, q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
         dk_ref, dv_ref) = refs
        kmask_ref = None
    k = k_ref[0]                                       # [bk, d]
    v = v_ref[0]
    block_k, d = k.shape
    k_start = pl.program_id(1) * block_k
    num_q = seq_q // block_q
    if has_kmask:
        # [1, bk] -> [bk, 1] column bias (single relayout per kernel call)
        km_col = kmask_ref[0, 0:1, pl.ds(k_start, block_k)] \
            .reshape(block_k, 1)
    if dropout_p > 0.0:
        thresh = _dropout_threshold(dropout_p)
        seed_u32 = seed_ref[0].astype(jnp.uint32)
        bh_u32 = pl.program_id(0).astype(jnp.uint32)
        inv = 1.0 / (1.0 - dropout_p)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse_row = lse_ref[0, 0:1, pl.ds(i * block_q, block_q)]   # [1, bq]
        delta_row = delta_ref[0, 0:1, pl.ds(i * block_q, block_q)]
        # sT[k_idx, q_idx] = scale * (q . k)
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # [bk, bq]
        if has_kmask:
            s_t = s_t + km_col
        p_t = jnp.exp(s_t - lse_row)
        if causal:
            q_rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            k_cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            p_t = jnp.where(q_rows >= k_cols, p_t, 0.0)
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [bk, bq]
        if dropout_p > 0.0:
            qi = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            ki = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            keep_t = _hash_keep(seed_u32, bh_u32, qi, ki, thresh)
            p_used_t = jnp.where(keep_t, p_t, 0.0) * inv
            dp_eff_t = jnp.where(keep_t, dp_t, 0.0) * inv
        else:
            p_used_t = p_t
            dp_eff_t = dp_t
        dv = dv + jax.lax.dot_general(
            p_used_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [bk, d]
        ds_t = p_t * (dp_eff_t - delta_row) * scale
        dk = dk + jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [bk, d]
        return dk, dv

    lower = k_start // block_q if causal else 0
    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lower, num_q, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(*refs, scale, causal, block_k, seq_k, dropout_p,
                      has_kmask):
    if has_kmask:
        (seed_ref, q_ref, do_ref, k_ref, v_ref, kmask_ref, lse_ref,
         delta_ref, dq_ref) = refs
    else:
        (seed_ref, q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
         dq_ref) = refs
        kmask_ref = None
    q = q_ref[0]                                       # [bq, d]
    do = do_ref[0]
    block_q, d = q.shape
    q_start = pl.program_id(1) * block_q
    lse_row = lse_ref[0, 0:1, :]                       # [1, bq]
    delta_row = delta_ref[0, 0:1, :]
    num_kv = seq_k // block_k
    if dropout_p > 0.0:
        thresh = _dropout_threshold(dropout_p)
        seed_u32 = seed_ref[0].astype(jnp.uint32)
        bh_u32 = pl.program_id(0).astype(jnp.uint32)
        inv = 1.0 / (1.0 - dropout_p)

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # [bk, bq]
        if has_kmask:
            km_col = kmask_ref[0, 0:1, pl.ds(j * block_k, block_k)] \
                .reshape(block_k, 1)
            s_t = s_t + km_col
        p_t = jnp.exp(s_t - lse_row)
        if causal:
            q_rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            k_cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            p_t = jnp.where(q_rows >= k_cols, p_t, 0.0)
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [bk, bq]
        if dropout_p > 0.0:
            qi = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            ki = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            keep_t = _hash_keep(seed_u32, bh_u32, qi, ki, thresh)
            dp_eff_t = jnp.where(keep_t, dp_t, 0.0) * inv
        else:
            dp_eff_t = dp_t
        ds_t = p_t * (dp_eff_t - delta_row) * scale
        # dq[q_idx, d] = sum_k ds_t[k_idx, q_idx] * k[k_idx, d]
        return dq + jax.lax.dot_general(
            ds_t.astype(k.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        upper = jnp.minimum(
            (q_start + block_q + block_k - 1) // block_k, num_kv)
    else:
        upper = num_kv
    dq = jax.lax.fori_loop(
        0, upper, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _pallas_backward(q, k, v, kmask, seed, o, lse, do, causal, dropout_p,
                     block_q, block_k):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    do3 = do.reshape(bh, sq, d)
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(do3.astype(jnp.float32)
                    * o.reshape(bh, sq, d).astype(jnp.float32), axis=-1)
    # [bh, 8, sq]: 8 replicated sublanes so the (8, seq) tiles load cleanly
    lse8 = jnp.broadcast_to(lse.reshape(bh, 1, sq), (bh, 8, sq))
    delta8 = jnp.broadcast_to(delta[:, None, :], (bh, 8, sq))
    has_kmask = kmask is not None
    if has_kmask:
        km8 = jnp.broadcast_to(kmask[:, None, :].astype(jnp.float32),
                               (b, 8, sk))

    dkv_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
    ]
    dkv_operands = [seed, q3, do3, k3, v3]
    if has_kmask:
        dkv_specs.append(pl.BlockSpec((1, 8, sk), lambda i, j: (i // h, 0, 0)))
        dkv_operands.append(km8)
    dkv_specs += [
        pl.BlockSpec((1, 8, sq), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, 8, sq), lambda i, j: (i, 0, 0)),
    ]
    dkv_operands += [lse8, delta8]

    dk3, dv3 = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, seq_q=sq, dropout_p=dropout_p,
                          has_kmask=has_kmask),
        out_shape=(jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype)),
        grid=(bh, sk // block_k),
        in_specs=dkv_specs,
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ),
        compiler_params=_dim_semantics("parallel", "arbitrary"),
        name="flash_attention_dkv", metadata={"kernel": "flash_attention_dkv"},
        interpret=_interpret(),
    )(*dkv_operands)

    dq_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
    ]
    dq_operands = [seed, q3, do3, k3, v3]
    if has_kmask:
        dq_specs.append(pl.BlockSpec((1, 8, sk), lambda i, j: (i // h, 0, 0)))
        dq_operands.append(km8)
    dq_specs += [
        pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j)),
        pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j)),
    ]
    dq_operands += [lse8, delta8]

    dq3 = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_k=sk, dropout_p=dropout_p,
                          has_kmask=has_kmask),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        grid=(bh, sq // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        compiler_params=_dim_semantics("parallel", "arbitrary"),
        name="flash_attention_dq", metadata={"kernel": "flash_attention_dq"},
        interpret=_interpret(),
    )(*dq_operands)

    return (dq3.reshape(b, h, sq, d), dk3.reshape(b, h, sk, d),
            dv3.reshape(b, h, sk, d))


# ---------------------------------------------------------------------------
# custom VJP: flash backward as Pallas kernels or a scan over KV blocks
# (O(S) memory)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_attention(q, k, v, kmask, seed, causal, dropout_p):
    o, _ = _forward_with_lse(q, k, v, kmask, seed, causal, dropout_p)
    return o


def _flash_fwd(q, k, v, kmask, seed, causal, dropout_p):
    o, lse = _forward_with_lse(q, k, v, kmask, seed, causal, dropout_p)
    return o, (q, k, v, kmask, seed, o, lse)


def _flash_bwd(causal, dropout_p, res, do):
    q, k, v, kmask, seed, o, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    pbq = min(DEFAULT_BLOCK_Q, sq)
    pbk = min(DEFAULT_BLOCK_K, sk)
    km_zero = None if kmask is None else jnp.zeros_like(kmask)
    seed_zero = np.zeros(seed.shape, jax.dtypes.float0)
    if _use_kernel(q, k, causal, pbq, pbk, "flash_attention_bwd"):
        dq, dk, dv = _pallas_backward(q, k, v, kmask, seed, o, lse, do,
                                      causal, dropout_p, pbq, pbk)
        return dq, dk, dv, km_zero, seed_zero
    scale = 1.0 / math.sqrt(d)
    block_k = min(DEFAULT_BLOCK_K, sk)
    if sk % block_k != 0:
        block_k = sk  # single block
    num_kv = sk // block_k
    inv = 1.0 / (1.0 - dropout_p) if dropout_p > 0.0 else 1.0

    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)  # [b,h,sq]

    kb = k.reshape(b, h, num_kv, block_k, d)
    vb = v.reshape(b, h, num_kv, block_k, d)

    def body(dq_acc, blk):
        kj, vj, j = blk
        # s: [b,h,sq,bk]
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj.astype(jnp.float32)) * scale
        if kmask is not None:
            km_blk = jax.lax.dynamic_slice_in_dim(
                kmask.astype(jnp.float32), j * block_k, block_k, axis=1)
            s = s + km_blk[:, None, None, :]
        if causal:
            # bottom-right aligned window (offset sk-sq), matching the
            # forward fallback's tril(k=sk-sq) when sq != sk
            rows = jnp.arange(sq)[:, None] + (sk - sq)
            cols = j * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(rows >= cols, s, -jnp.inf)
        p = jnp.exp(s - lse[..., None])
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vj.astype(jnp.float32))
        if dropout_p > 0.0:
            keep = _full_keep_mask(seed, b, h, sq, block_k, dropout_p,
                                   k_offset=j * block_k)
            p_used = jnp.where(keep, p, 0.0) * inv
            dp_eff = jnp.where(keep, dp, 0.0) * inv
        else:
            p_used = p
            dp_eff = dp
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p_used, dof)
        ds = p * (dp_eff - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds,
                                     kj.astype(jnp.float32))
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((b, h, sq, d), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        body, dq0,
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0),
         jnp.arange(num_kv)))
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, h, sk, d)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, h, sk, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            km_zero, seed_zero)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _as_key_padding_mask(mask, b, sk):
    """Convert masks of the unambiguous [B|1, 1, 1, Sk] form into an
    additive [B, Sk] float32 bias (the streamable kernel form); None if the
    mask needs the generic fallback. 2D masks are NOT accepted: a [Sq, Sk]
    mask broadcasts per-query in the reference semantics and would be
    misread as per-batch whenever Sq == B."""
    m = mask
    if m.ndim == 4 and m.shape[1] == 1 and m.shape[2] == 1 \
            and m.shape[3] == sk and m.shape[0] in (1, b):
        m = m.reshape(m.shape[0], sk)
    else:
        return None
    if m.shape[0] == 1 and b != 1:
        m = jnp.broadcast_to(m, (b, sk))
    if m.dtype == jnp.bool_:
        return jnp.where(m, 0.0, _MASK_MIN).astype(jnp.float32)
    # clamp -inf style biases to a finite min so the online softmax's
    # max/alpha arithmetic stays NaN-free on fully-masked leading blocks
    return jnp.maximum(m.astype(jnp.float32), _MASK_MIN)


def flash_attention_bhsd(q, k, v, mask=None, is_causal=False,
                         dropout_p=0.0, dropout_key=None):
    """[B, H, S, D] layout."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    kmask = _as_key_padding_mask(mask, b, sk) if mask is not None else None
    block_q = min(DEFAULT_BLOCK_Q, sq)
    block_k = min(DEFAULT_BLOCK_K, sk)
    pallas = _pallas_ok(q, k, bool(is_causal), block_q, block_k)
    if dropout_p > 0.0 and dropout_key is None:
        from ...framework.random import next_key

        dropout_key = next_key()
    if mask is not None and kmask is None:
        # generic [B, H, Sq, Sk] masks: materialized-attention fallback
        if kernels_enabled():
            note_reference_dispatch("flash_attention_mask")
        return _attention_ref(q, k, v, mask, is_causal, dropout_p,
                              dropout_key)
    if dropout_p > 0.0 and not pallas:
        # off-TPU / unaligned: plain autodiff through the reference is
        # cheaper than the blockwise bwd at these (small) shapes
        if kernels_enabled():
            note_reference_dispatch("flash_attention_dropout")
        return _attention_ref(q, k, v, mask, is_causal, dropout_p,
                              dropout_key)
    if dropout_p > 0.0:
        seed = _key_to_seed(dropout_key)
    else:
        seed = jnp.zeros((1,), jnp.int32)
    return _flash_attention(q, k, v, kmask, seed, bool(is_causal),
                            float(dropout_p))


def flash_attention_bshd(q, k, v, mask=None, is_causal=False,
                         dropout_p=0.0, dropout_key=None):
    """Reference layout [B, S, H, D] (flash_attention.py:147)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(qt, kt, vt, mask, is_causal, dropout_p,
                               dropout_key)
    return jnp.swapaxes(out, 1, 2)
