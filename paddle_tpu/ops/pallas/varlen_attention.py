"""Varlen (packed / segment-ids) flash attention — Pallas TPU kernels.

Reference analog: the varlen/unpadded flash-attention entry points
(python/paddle/nn/functional/flash_attention.py:147 flash_attn_unpadded,
backed by the vendored flashattn varlen CUDA kernels taking cu_seqlens).
TPU-native design: raggedness is carried by SEGMENT IDS over one packed
token axis — one static-shape kernel for every cu_seqlens pattern (the
per-segment unrolled fallback compiles one program per pattern), with
block-diagonal masking fused into the online softmax. Forward and both
backward kernels mirror ops/pallas/flash_attention.py's layout choices:
bf16 operands on the MXU with f32 accumulation, transposed-logit backward,
(8, T) replicated-sublane tiles for per-token vectors.

Causality uses GLOBAL packed positions: within a segment the packed order
is the sequence order, and cross-segment pairs are already masked, so
`row >= col` on packed indices implements per-sequence causal exactly.

Padding tokens carry segment id -1 and match nothing (their outputs are
a uniform V average, finite, and sliced off / zero-grad by the wrapper's
pad-and-slice).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import interpret as _interpret
from . import kernels_enabled, note_reference_dispatch
from .flash_attention import _MASK_MIN, _dim_semantics

__all__ = ["varlen_flash_attention_packed", "segment_ids_from_cu_seqlens"]


def segment_ids_from_cu_seqlens(cu, total):
    """[total] int32 segment ids from cumulative offsets (host-side;
    positions >= cu[-1] get -1 = padding)."""
    cu = np.asarray(cu).astype(np.int64)
    seg = np.full((total,), -1, np.int32)
    for i in range(len(cu) - 1):
        seg[int(cu[i]):int(cu[i + 1])] = i
    return seg


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _vfa_kernel(segq_ref, segk_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *, scale, causal, block_k, seq_k):
    q = q_ref[0]                                        # [bq, d]
    block_q, d = q.shape
    q_start = pl.program_id(1) * block_q
    num_kv = seq_k // block_k
    segq = segq_ref[0, 0:1, pl.ds(q_start, block_q)]    # [1, bq]
    segq_col = segq.reshape(block_q, 1)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        segk = segk_ref[0, 0:1, pl.ds(j * block_k, block_k)]  # [1, bk]
        valid = (segq_col == segk) & (segq_col >= 0)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = valid & (rows >= cols)
        s = jnp.where(valid, s, _MASK_MIN)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), _MASK_MIN, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    if causal:
        upper = jnp.minimum(
            (q_start + block_q + block_k - 1) // block_k, num_kv)
    else:
        upper = num_kv
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.broadcast_to((m + jnp.log(l_safe))[:, 0][None, :],
                                     (8, block_q))


def _seg8(seg, b, t):
    """[B, T] int32 -> [B, 8, T] replicated-sublane tiles."""
    return jnp.broadcast_to(seg.astype(jnp.int32)[:, None, :], (b, 8, t))


def _vfa_forward(q, k, v, segq, segk, causal, block_q, block_k):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    scale = 1.0 / math.sqrt(d)
    segq8 = _seg8(segq, b, sq)
    segk8 = _seg8(segk, b, sk)
    o, lse = pl.pallas_call(
        functools.partial(_vfa_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_k=sk),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq // block_q, 8, block_q),
                                 jnp.float32),
        ),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 8, sq), lambda i, j: (i // h, 0, 0)),
            pl.BlockSpec((1, 8, sk), lambda i, j: (i // h, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda i, j: (i, j, 0, 0)),
        ),
        compiler_params=_dim_semantics("parallel", "arbitrary"),
        name="varlen_attention_fwd", metadata={"kernel": "varlen_attention_fwd"},
        interpret=_interpret(),
    )(segq8, segk8, q3, k3, v3)
    lse = lse[:, :, 0, :].reshape(bh, sq)
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)


# ---------------------------------------------------------------------------
# backward (flash recomputation, transposed logits)
# ---------------------------------------------------------------------------

def _vfa_bwd_dkv_kernel(segq_ref, segk_ref, q_ref, do_ref, k_ref, v_ref,
                        lse_ref, delta_ref, dk_ref, dv_ref,
                        *, scale, causal, block_q, seq_q):
    k = k_ref[0]                                        # [bk, d]
    v = v_ref[0]
    block_k, d = k.shape
    k_start = pl.program_id(1) * block_k
    num_q = seq_q // block_q
    segk_col = segk_ref[0, 0:1, pl.ds(k_start, block_k)] \
        .reshape(block_k, 1)                            # [bk, 1]

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse_row = lse_ref[0, 0:1, pl.ds(i * block_q, block_q)]  # [1, bq]
        delta_row = delta_ref[0, 0:1, pl.ds(i * block_q, block_q)]
        segq_row = segq_ref[0, 0:1, pl.ds(i * block_q, block_q)]
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bk, bq]
        valid = (segk_col == segq_row) & (segk_col >= 0)
        if causal:
            q_rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            k_cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            valid = valid & (q_rows >= k_cols)
        p_t = jnp.where(valid, jnp.exp(s_t - lse_row), 0.0)
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, bq]
        dv = dv + jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta_row) * scale
        dk = dk + jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    lower = k_start // block_q if causal else 0
    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lower, num_q, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _vfa_bwd_dq_kernel(segq_ref, segk_ref, q_ref, do_ref, k_ref, v_ref,
                       lse_ref, delta_ref, dq_ref,
                       *, scale, causal, block_k, seq_k):
    q = q_ref[0]
    do = do_ref[0]
    block_q, d = q.shape
    q_start = pl.program_id(1) * block_q
    lse_row = lse_ref[0, 0:1, :]
    delta_row = delta_ref[0, 0:1, :]
    num_kv = seq_k // block_k
    segq_row = segq_ref[0, 0:1, pl.ds(q_start, block_q)]  # [1, bq]

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bk, bq]
        segk_col = segk_ref[0, 0:1, pl.ds(j * block_k, block_k)] \
            .reshape(block_k, 1)
        valid = (segk_col == segq_row) & (segk_col >= 0)
        if causal:
            q_rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            k_cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            valid = valid & (q_rows >= k_cols)
        p_t = jnp.where(valid, jnp.exp(s_t - lse_row), 0.0)
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta_row) * scale
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


def _vfa_backward(q, k, v, segq, segk, o, lse, do, causal,
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
    lse8 = jnp.broadcast_to(lse.reshape(bh, 1, sq), (bh, 8, sq))
    delta8 = jnp.broadcast_to(delta[:, None, :], (bh, 8, sq))
    segq8 = _seg8(segq, b, sq)
    segk8 = _seg8(segk, b, sk)

    dk3, dv3 = pl.pallas_call(
        functools.partial(_vfa_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, seq_q=sq),
        out_shape=(jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype)),
        grid=(bh, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, 8, sq), lambda i, j: (i // h, 0, 0)),
            pl.BlockSpec((1, 8, sk), lambda i, j: (i // h, 0, 0)),
            pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 8, sq), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 8, sq), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ),
        compiler_params=_dim_semantics("parallel", "arbitrary"),
        name="varlen_attention_dkv", metadata={"kernel": "varlen_attention_dkv"},
        interpret=_interpret(),
    )(segq8, segk8, q3, do3, k3, v3, lse8, delta8)

    dq3 = pl.pallas_call(
        functools.partial(_vfa_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_k=sk),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 8, sq), lambda i, j: (i // h, 0, 0)),
            pl.BlockSpec((1, 8, sk), lambda i, j: (i // h, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        compiler_params=_dim_semantics("parallel", "arbitrary"),
        name="varlen_attention_dq", metadata={"kernel": "varlen_attention_dq"},
        interpret=_interpret(),
    )(segq8, segk8, q3, do3, k3, v3, lse8, delta8)

    return (dq3.reshape(b, h, sq, d), dk3.reshape(b, h, sk, d),
            dv3.reshape(b, h, sk, d))


# ---------------------------------------------------------------------------
# custom VJP + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _varlen_attention(q, k, v, segq, segk, causal):
    o, _ = _vfa_forward(q, k, v, segq, segk, causal,
                        _vfa_block(q.shape[2]), _vfa_block(k.shape[2]))
    return o


def _vfa_block(s):
    """Largest kernel block in (512, 256, 128) that DIVIDES the packed
    length, or 0 when none does. The grid is `s // block` whole tiles, so
    a block that merely fits (`min(512, s)`) silently dropped the
    trailing `s % block` tokens for lengths like 640/768/896 — the block
    must divide s exactly, and `_vfa_ok` gates on that."""
    for b in (512, 256, 128):
        if s % b == 0:
            return b
    return 0


def _vfa_fwd(q, k, v, segq, segk, causal):
    o, lse = _vfa_forward(q, k, v, segq, segk, causal,
                          _vfa_block(q.shape[2]), _vfa_block(k.shape[2]))
    return o, (q, k, v, segq, segk, o, lse)


def _vfa_bwd(causal, res, do):
    q, k, v, segq, segk, o, lse = res
    dq, dk, dv = _vfa_backward(q, k, v, segq, segk, o, lse, do, causal,
                               _vfa_block(q.shape[2]),
                               _vfa_block(k.shape[2]))
    zq = jnp.zeros_like(segq)
    zk = jnp.zeros_like(segk)
    return dq, dk, dv, zq, zk


_varlen_attention.defvjp(_vfa_fwd, _vfa_bwd)


def _varlen_ref(q, k, v, segq, segk, causal):
    """Dense segment-masked reference ([B, H, T, D]); ground truth in
    tests and the off-TPU / unaligned fallback (plain autodiff)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    valid = (segq[:, None, :, None] == segk[:, None, None, :]) \
        & (segq[:, None, :, None] >= 0)
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        valid = valid & (jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :])
    logits = jnp.where(valid, logits, _MASK_MIN)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)
                      ).astype(q.dtype)


def _vfa_ok(q, k):
    # a valid block must divide each packed length exactly (sq % block_q
    # == 0 and sk % block_k == 0 by construction of _vfa_block); packed
    # lengths with no such block (e.g. 600) fall back to _varlen_ref
    return (kernels_enabled()
            and _vfa_block(q.shape[2]) > 0 and _vfa_block(k.shape[2]) > 0
            and q.shape[-1] % 64 == 0)


def varlen_flash_attention_packed(q, k, v, seg_q, seg_k, is_causal=False):
    """Packed-sequence attention. q [B, H, Tq, D]; k/v [B, H, Tk, D];
    seg_q [B, Tq] / seg_k [B, Tk] int32 segment ids (-1 = padding).
    Tokens attend only keys of their own segment (block-diagonal);
    is_causal applies per-sequence causality via packed positions."""
    if _vfa_ok(q, k):
        return _varlen_attention(q, k, v, seg_q, seg_k, bool(is_causal))
    if kernels_enabled():
        note_reference_dispatch("varlen_attention")
    return _varlen_ref(q, k, v, seg_q, seg_k, bool(is_causal))
