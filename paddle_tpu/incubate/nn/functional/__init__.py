"""Fused ops (reference: python/paddle/incubate/nn/functional/ —
fused_rms_norm, fused_rotary_position_embedding, swiglu,
fused_matmul_bias, block_multihead_attention...).

On TPU these are either Pallas kernels (rms_norm, attention) or single jnp
expressions XLA fuses on its own (rope, swiglu, bias_act) — the win is the
same as the reference's hand-fused CUDA: one HBM round-trip."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....core.dispatch import apply
from ....core.tensor import Tensor
from ....profiler.scopes import scope

__all__ = ["fused_rms_norm", "fused_layer_norm",
           "fused_rotary_position_embedding", "swiglu", "fused_matmul_bias",
           "fused_linear", "fused_linear_activation", "fused_bias_act",
           "fused_dropout_add", "fused_multi_head_attention",
           "flash_attention", "flash_attn_unpadded",
           "variable_length_memory_efficient_attention"]


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, **kwargs):
    from ....ops.pallas import rms_norm as _rn

    def fn(a, *w):
        out = _rn.rms_norm(a, w[0] if w else None, epsilon)
        if norm_bias is not None:
            out = out + w[-1]
        return out
    args = [x] + [t for t in (norm_weight, norm_bias) if t is not None]
    out = apply(fn, *args, op_name="fused_rms_norm")
    return out


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     begin_norm_axis=-1, **kwargs):
    from ....nn import functional as F

    return F.layer_norm(x, x.shape[-1], norm_weight, norm_bias, epsilon)


def _apply_rope(t, cos, sin, use_neox):
    # t: [B, S, H, D]
    if use_neox:
        d2 = t.shape[-1] // 2
        t1, t2 = t[..., :d2], t[..., d2:]
        rotated = jnp.concatenate([-t2, t1], axis=-1)
    else:
        t1 = t[..., 0::2]
        t2 = t[..., 1::2]
        rotated = jnp.stack([-t2, t1], axis=-1).reshape(t.shape)
    return t * cos + rotated * sin


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0):
    """reference: incubate/nn/functional/fused_rotary_position_embedding.py.
    Layout [B, S, H, D]."""
    def fn(qa, *rest):
        i = 0
        ka = va = None
        if k is not None:
            ka = rest[i]; i += 1
        if v is not None:
            va = rest[i]; i += 1
        if sin is not None:
            sa, ca = rest[i], rest[i + 1]
            i += 2
            if position_ids is not None:
                # reference contract: provided sin/cos TABLES are
                # indexed by position_ids (kv-cached decode offsets)
                pid = rest[i].astype(jnp.int32)
                i += 1
                d_last = sa.shape[-1]
                sa = sa.reshape(-1, d_last)[pid][:, :, None, :]
                ca = ca.reshape(-1, d_last)[pid][:, :, None, :]
        else:
            s = qa.shape[1]
            d = qa.shape[-1]
            inv = 1.0 / (rotary_emb_base ** (
                jnp.arange(0, d, 2, dtype=jnp.float32) / d))
            if position_ids is not None:
                # absolute positions [B, S] (or [1, S] broadcast): the
                # kv-cached decode path rotates appended chunks at
                # their true offsets (reference position_ids contract)
                pid = rest[i].astype(jnp.float32)
                i += 1
                freqs = pid[..., None] * inv          # [B, S, d/2]
            else:
                pos = jnp.arange(s, dtype=jnp.float32)
                freqs = jnp.outer(pos, inv)[None]     # [1, S, d/2]
            if use_neox_rotary_style:
                emb = jnp.concatenate([freqs, freqs], axis=-1)
            else:
                emb = jnp.repeat(freqs, 2, axis=-1)
            ca = jnp.cos(emb)[:, :, None, :]
            sa = jnp.sin(emb)[:, :, None, :]
        ca = ca.astype(jnp.float32)
        sa = sa.astype(jnp.float32)
        outs = []
        for t in (qa, ka, va):
            if t is None:
                outs.append(None)
            else:
                o = _apply_rope(t.astype(jnp.float32), ca, sa,
                                use_neox_rotary_style)
                outs.append(o.astype(t.dtype))
        return tuple(o for o in outs if o is not None)

    args = [q] + [t for t in (k, v) if t is not None]
    if sin is not None:
        args += [sin, cos]
    if position_ids is not None:
        args += [position_ids]
    outs = apply(fn, *args, op_name="fused_rope")
    result = []
    it = iter(outs if isinstance(outs, tuple) else (outs,))
    for t in (q, k, v):
        result.append(next(it) if t is not None else None)
    return tuple(result)


def swiglu(x, y=None, name=None):
    """silu(x) * y; single fused elementwise region for XLA (reference
    fused swiglu kernel)."""
    if y is None:
        def fn(a):
            a1, a2 = jnp.split(a, 2, axis=-1)
            return jax.nn.silu(a1) * a2
        return apply(fn, x, op_name="swiglu")
    return apply(lambda a, b: jax.nn.silu(a) * b, x, y, op_name="swiglu")


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    def fn(a, b, *bs):
        if transpose_x:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_y:
            b = jnp.swapaxes(b, -1, -2)
        out = jnp.matmul(a, b)
        if bs:
            out = out + bs[0]
        return out
    args = [x, y] + ([bias] if bias is not None else [])
    return apply(fn, *args, op_name="fused_matmul_bias")


fused_linear = fused_matmul_bias


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu"):
    out = fused_matmul_bias(x, y, bias, trans_x, trans_y)
    from ....nn import functional as F

    return getattr(F, activation)(out)


def fused_bias_act(x, bias=None, dequant_scales=None, shift=None, smooth=None,
                   act_method="gelu", **kwargs):
    from ....nn import functional as F

    out = x if bias is None else x + bias
    return getattr(F, act_method)(out)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    from ....nn import functional as F

    return F.dropout(x, p, training=training, mode=mode) + y


def fused_multi_head_attention(x, qkv_weight, linear_weight, *args, **kwargs):
    raise NotImplementedError(
        "use nn.MultiHeadAttention (flash-attention backed) instead")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """reference: python/paddle/nn/functional/flash_attention.py — BSHD."""
    from ....nn import functional as F

    out = F.scaled_dot_product_attention(
        query, key, value, attn_mask=None, dropout_p=dropout,
        is_causal=causal, training=training)
    return (out, None) if return_softmax is not None else out


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q=None, max_seqlen_k=None, scale=None,
                        dropout=0.0, causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="",
                        training=True, name=None):
    """Varlen (packed/ragged) flash attention (reference
    flash_attn_unpadded): q/k/v are [total_tokens, H, D] with cumulative
    sequence offsets. TPU-native: segment-id block-diagonal masking over
    one fused attention — XLA keeps static shapes, the mask carries the
    raggedness."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ....core.dispatch import apply
    from ....core.tensor import Tensor

    cq = np.asarray(cu_seqlens_q.numpy()
                    if isinstance(cu_seqlens_q, Tensor) else cu_seqlens_q)
    ck = np.asarray(cu_seqlens_k.numpy()
                    if isinstance(cu_seqlens_k, Tensor) else cu_seqlens_k)

    # Pallas segment-ids kernel path: ONE static-shape program for every
    # cu_seqlens pattern (the per-segment fallback below compiles one
    # program per pattern). Identical q/k layouts make the kernel's
    # packed-position causal exactly FA2's per-segment causal.
    from ....ops import pallas as _pallas
    from ....ops.pallas import varlen_attention as VA

    d_head = int(query.shape[-1])
    kernel_ok = ((dropout == 0.0 or not training)
                 and scale is None
                 and _pallas.kernels_enabled()
                 and d_head % 64 == 0
                 and np.array_equal(cq, ck))
    if not kernel_ok and _pallas.kernels_enabled():
        _pallas.note_reference_dispatch("flash_attn_unpadded")
    if kernel_ok:
        total = int(query.shape[0])
        padded = 128 * ((total + 127) // 128)
        seg_np = VA.segment_ids_from_cu_seqlens(cq, padded)

        def fnk(q, k, v, seg):
            pad = padded - q.shape[0]
            qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
            kp = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
            vp = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
            qt = qp.transpose(1, 0, 2)[None]        # [1, H, Tp, D]
            kt = kp.transpose(1, 0, 2)[None]
            vt = vp.transpose(1, 0, 2)[None]
            o = VA.varlen_flash_attention_packed(
                qt, kt, vt, seg[None], seg[None], is_causal=causal)
            return o[0].transpose(1, 0, 2)[:q.shape[0]]

        out = apply(fnk, query, key, value,
                    jnp.asarray(seg_np),
                    op_name="flash_attn_unpadded_pallas")
        return out, None

    def fn(q, k, v):
        # per-segment dense attention (the reference kernel's memory
        # profile: logits bounded by the LARGEST segment, not total²;
        # cu_seqlens are concrete in eager so the loop unrolls statically)
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / (d ** 0.5)
        outs = []
        from ....framework.random import next_key

        key_d = next_key() if (dropout > 0.0 and training) else None
        for i in range(len(cq) - 1):
            qs = q[int(cq[i]):int(cq[i + 1])].astype(jnp.float32)
            ks = k[int(ck[i]):int(ck[i + 1])].astype(jnp.float32)
            vs = v[int(ck[i]):int(ck[i + 1])].astype(jnp.float32)
            logits = jnp.einsum("qhd,khd->hqk", qs, ks) * s
            if causal:
                # bottom-right aligned (FA2 varlen semantics): with
                # q_len < k_len the queries sit at the END of the keys
                off = ks.shape[0] - qs.shape[0]
                qi = jnp.arange(qs.shape[0])[:, None] + off
                ki = jnp.arange(ks.shape[0])[None, :]
                logits = jnp.where((qi >= ki)[None], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            if key_d is not None:
                keep = jax.random.bernoulli(
                    jax.random.fold_in(key_d, i), 1.0 - dropout,
                    probs.shape)
                probs = probs * keep / (1.0 - dropout)
            outs.append(jnp.einsum("hqk,khd->qhd", probs, vs))
        return jnp.concatenate(outs, axis=0).astype(q.dtype)

    out = apply(fn, query, key, value, op_name="flash_attn_unpadded")
    return out, None


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k,
                                max_seqlen_q=None, max_seqlen_k=None,
                                scale=None, dropout=0.0, causal=False,
                                return_softmax=False, training=True,
                                **kw):
    """Packed [total, 3, H, D] varlen attention (reference
    flash_attn_varlen_qkvpacked): unpack and delegate."""
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    return flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k,
                               max_seqlen_q, max_seqlen_k, scale,
                               dropout, causal, return_softmax,
                               training=training)


def variable_length_memory_efficient_attention(
        query, key, value, seq_lens=None, kv_seq_lens=None, mask=None,
        scale=None, causal=False, pre_cache_length=0, name=None):
    """Batched variable-length attention (reference
    variable_length_memory_efficient_attention): [B, H, S, D] with
    per-example valid lengths masking the key axis; `mask` is an
    additive attention bias."""
    import jax
    import jax.numpy as jnp

    from ....core.dispatch import apply
    from ....core.tensor import Tensor

    if pre_cache_length:
        raise NotImplementedError(
            "variable_length_memory_efficient_attention: "
            "pre_cache_length > 0 (cached-prefix offsets) is not "
            "implemented — silently ignoring it would misalign the "
            "causal mask")

    def fn(q, k, v, *rest):
        kl = rest[0] if len(rest) >= 1 else None
        bias = rest[1] if len(rest) >= 2 else None
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / (d ** 0.5)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * s
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        if kl is not None:
            valid = jnp.arange(k.shape[2])[None, :] < \
                kl.reshape(-1, 1)
            logits = jnp.where(valid[:, None, None, :], logits, -1e30)
        if causal:
            qi = jnp.arange(q.shape[2])[:, None]
            ki = jnp.arange(k.shape[2])[None, :]
            logits = jnp.where((qi >= ki)[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs,
                          v.astype(jnp.float32)).astype(q.dtype)

    lens = kv_seq_lens if kv_seq_lens is not None else seq_lens
    if mask is not None and lens is None:
        def fn_bias(q, k, v, b):
            return fn(q, k, v, None, b)
        return apply(fn_bias, query, key, value, mask,
                     op_name="varlen_attention")
    if lens is not None and mask is not None:
        return apply(fn, query, key, value, lens, mask,
                     op_name="varlen_attention")
    if lens is not None:
        return apply(fn, query, key, value, lens,
                     op_name="varlen_attention")
    return apply(fn, query, key, value, op_name="varlen_attention")


# ---------------------------------------------------------------------------
# transformer-block fusions (reference: incubate/nn/functional/
# fused_transformer.py) — on TPU each is one jnp composition XLA fuses
# ---------------------------------------------------------------------------

def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5,
                                           ln_epsilon=1e-5, training=True,
                                           mode="upscale_in_train",
                                           name=None):
    """layernorm(residual + dropout(x + bias)) — reference
    incubate/nn/functional/fused_transformer.py:fused_bias_dropout_residual_layer_norm."""
    from ....ops.registry import get as _get

    kern = _get("fused_bias_dropout_residual_layer_norm").fn

    def fn(xa, ra, *rest):
        it = iter(rest)
        b = next(it) if bias is not None else None
        s = next(it) if ln_scale is not None else None
        bb = next(it) if ln_bias is not None else None
        out, _, _, _, _ = kern(xa, ra, bias=b, ln_scale=s, ln_bias=bb,
                               dropout_rate=dropout_rate,
                               is_test=not training,
                               dropout_fix_seed=False,
                               dropout_implementation=mode,
                               ln_epsilon=ln_epsilon)
        return out

    args = [x, residual] + [t for t in (bias, ln_scale, ln_bias)
                            if t is not None]
    return apply(fn, *args,
                 op_name="fused_bias_dropout_residual_layer_norm")


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True,
                      mode="upscale_in_train", ring_id=-1,
                      add_residual=True, name=None):
    """linear2(dropout1(act(linear1(maybe_ln(x))))) (+ residual, post-LN) —
    reference fused_transformer.py:fused_feedforward pseudocode."""
    from ....nn import functional as F

    residual = x
    out = x
    if pre_layer_norm:
        out = F.layer_norm(out, out.shape[-1], ln1_scale, ln1_bias,
                           ln1_epsilon)
    out = F.linear(out, linear1_weight, linear1_bias)
    out = getattr(F, activation)(out)
    out = F.dropout(out, dropout1_rate, training=training, mode=mode)
    out = F.linear(out, linear2_weight, linear2_bias)
    out = F.dropout(out, dropout2_rate, training=training, mode=mode)
    if add_residual:
        out = residual + out
    if not pre_layer_norm:
        out = F.layer_norm(out, out.shape[-1], ln2_scale, ln2_bias,
                           ln2_epsilon)
    return out


def fused_ec_moe(x, gate, bmm0_weight, bmm0_bias, bmm1_weight, bmm1_bias,
                 act_type):
    """Expert-computation MoE: out = sum_e softmax(gate)_e * ffn_e(x)
    (reference incubate/nn/functional/fused_ec_moe.py; the CUDA kernel's
    grouped-GEMM becomes one batched einsum the MXU executes directly).
    bmm0_weight [E, H, I], bmm1_weight [E, I, H]."""
    assert act_type in ("gelu", "relu")

    def fn(xa, ga, w0, b0, w1, b1):
        probs = jax.nn.softmax(ga.astype(jnp.float32), axis=-1) \
            .astype(xa.dtype)                              # [B, S, E]
        h = jnp.einsum("bsh,ehi->bsei", xa, w0) + b0.reshape(
            1, 1, w0.shape[0], -1)                         # [B, S, E, I]
        h = jax.nn.gelu(h) if act_type == "gelu" else jax.nn.relu(h)
        o = jnp.einsum("bsei,eih->bseh", h, w1) + b1.reshape(
            1, 1, w1.shape[0], -1)                         # [B, S, E, H]
        return jnp.einsum("bseh,bse->bsh", o, probs)

    return apply(fn, x, gate, bmm0_weight, bmm0_bias, bmm1_weight,
                 bmm1_bias, op_name="fused_ec_moe")


# ---------------------------------------------------------------------------
# decode-time attention (reference: masked_multihead_attention.py,
# block_multihead_attention.py, blha_get_max_len.py). TPU-native stance:
# static-shape dense/paged caches updated by scatter; the CUDA kernels'
# int8-cache quant knobs are not applicable and must be left None.
# ---------------------------------------------------------------------------

def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size):
    """(max encoder len, max decoder len) — reference blha_get_max_len."""
    def fn(e, d):
        return jnp.max(e).reshape(1), jnp.max(d).reshape(1)

    return apply(fn, seq_lens_encoder, seq_lens_decoder,
                 op_name="blha_get_max_len")


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               cum_offsets=None, sequence_lengths=None,
                               rotary_tensor=None, beam_cache_offset=None,
                               qkv_out_scale=None, out_shift=None,
                               out_smooth=None, seq_len=1,
                               rotary_emb_dims=0,
                               use_neox_rotary_style=False,
                               compute_dtype="default", out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0):
    """One decode step of cached self-attention: x is the packed qkv of the
    new token [B, 3*H*D]; cache_kv [2, B, H, max_seq, D] holds past keys/
    values; the new k/v are written at each batch row's current length and
    q attends the filled prefix. Returns (out [B, H*D], cache_kv_out).
    Quant args (qkv_out_scale/out_shift/out_smooth/out_scale) are the CUDA
    int8 path and must be None/-1 here."""
    if qkv_out_scale is not None or out_shift is not None \
            or out_smooth is not None or (out_scale or -1) > 0:
        raise NotImplementedError(
            "masked_multihead_attention: static activation-scale int8 "
            "(qkv_out_scale/out_shift/out_smooth) is CUDA-calibration-"
            "specific; the TPU int8 KV-cache path is "
            "block_multihead_attention(use_dynamic_cachekv_quant=True) "
            "with per-slot dynamic scales")
    if cache_kv is None:
        raise ValueError("cache_kv is required")

    def fn(xa, cache, *rest):
        it = iter(rest)
        b = next(it) if bias is not None else None
        m = next(it) if src_mask is not None else None
        lens = next(it) if sequence_lengths is not None else None
        rot = next(it) if rotary_tensor is not None else None
        B = xa.shape[0]
        _, _, H, S, D = cache.shape
        qkv = xa.reshape(B, 3, H, D)
        if b is not None:
            qkv = qkv + b.reshape(1, 3, H, D)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]      # [B, H, D]
        pos = (lens.reshape(B).astype(jnp.int32) if lens is not None
               else jnp.full((B,), seq_len - 1, jnp.int32))
        if rot is not None:
            # rotary_tensor [B, 1, 1, max_seq, D] holds per-position
            # angles; index each row at ITS write position
            rr_all = rot.reshape(B, -1, rot.shape[-1])      # [B, max, D]
            rr = jnp.take_along_axis(
                rr_all, pos[:, None, None].astype(jnp.int32), axis=1
            )[:, 0, :]                                       # [B, D]
            cos, sin = jnp.cos(rr), jnp.sin(rr)
            def rope(t):
                t1, t2 = t[..., 0::2], t[..., 1::2]
                rotv = jnp.stack([-t2, t1], axis=-1).reshape(t.shape)
                return t * cos[:, None, :] + rotv * sin[:, None, :]
            q, k = rope(q), rope(k)
        bi = jnp.arange(B)
        cache = cache.at[0, bi, :, pos, :].set(k)
        cache = cache.at[1, bi, :, pos, :].set(v)
        keys, vals = cache[0], cache[1]                # [B, H, S, D]
        logits = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                            keys.astype(jnp.float32)) \
            / jnp.sqrt(jnp.float32(D))
        valid = jnp.arange(S)[None, :] <= pos[:, None]      # [B, S]
        logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
        if m is not None:
            if m.dtype == jnp.bool_:       # True = keep → additive float
                m = jnp.where(m, 0.0, -1e30)
            mm = m.reshape(B, 1, -1)[:, :, :S].astype(jnp.float32)
            if mm.shape[-1] < S:
                # reference masks cover only the filled prefix; padding
                # with 0 is safe (tail slots are already -inf-masked)
                mm = jnp.pad(mm, ((0, 0), (0, 0), (0, S - mm.shape[-1])))
            logits = logits + mm
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhs,bhsd->bhd", probs,
                         vals.astype(jnp.float32)).astype(xa.dtype)
        return out.reshape(B, H * D), cache

    args = [x, cache_kv] + [t for t in (bias, src_mask, sequence_lengths,
                                        rotary_tensor) if t is not None]
    return apply(fn, *args, op_name="masked_multihead_attention")


def block_multihead_attention(qkv, key_cache, value_cache,
                              seq_lens_encoder, seq_lens_decoder,
                              seq_lens_this_time, padding_offsets,
                              cum_offsets, cu_seqlens_q, cu_seqlens_k,
                              block_tables, pre_key_cache=None,
                              pre_value_cache=None,
                              cache_k_quant_scales=None,
                              cache_v_quant_scales=None,
                              cache_k_dequant_scales=None,
                              cache_v_dequant_scales=None,
                              qkv_out_scale=None, qkv_bias=None,
                              out_shift=None, out_smooth=None,
                              max_enc_len_this_time=None,
                              max_dec_len_this_time=None, rope_emb=None,
                              mask=None, tgt_mask=None, max_seq_len=-1,
                              block_size=64, use_neox_style=False,
                              use_dynamic_cachekv_quant=False,
                              quant_round_type=1, quant_max_bound=127.0,
                              quant_min_bound=-127.0, out_scale=-1,
                              compute_dtype="default", layer_idx=None,
                              fresh_prefill=False,
                              last_row_is_padding=False, selection=None):
    """Paged-KV-cache attention (reference block_multihead_attention):
    qkv [token_num, (HQ+2*HKV)*D] packs each batch row's tokens this step
    (prefill rows contribute seq_lens_encoder[b] tokens at positions
    0..n-1; decode/chunk rows seq_lens_this_time[b] tokens starting at
    position seq_lens_decoder[b]); key_cache/value_cache
    [num_blocks, HKV, block_size, D] are page pools indexed by
    block_tables [B, max_blocks]. HKV may divide HQ (GQA — the reference
    kernel's kv_num_heads path, block_multi_head_attention.cu). New k/v
    are scattered into their pages, then each token attends its row's
    filled prefix (causal). Returns
    (out [token_num, HQ*D], qkv, key_cache, value_cache).

    The page write and the attention are Pallas kernels on a TPU (or under
    PT_PALLAS_INTERPRET=1), for a bf16/float32 cache whose head_dim is a
    multiple of 128 and whose block_size tiles the dtype's sublanes:
    ops/pallas/paged_attention.py walks each row's own pages in place, as
    they were BEFORE this call's write, and takes this step's keys from
    qkv; then ops/pallas/kv_page_write.py puts the step's keys and values
    into their pages where the stacks lie (the returned caches alias the
    given ones: a caller that donates its stacks to the jitted step copies
    nothing). Both take the stacks in the plain layout, so a stack threaded
    through a model's layers is never re-laid. Elsewhere — kernels off, an
    int8 cache, shapes that do not tile — the write is XLA's scatter and
    the attention the gathered jnp formulation over the written caches,
    counted as pallas/reference_dispatch/paged_attention when kernels are
    on (one question decides both kernels, so one count). A program traced
    off the chip (save_paged_model's export) holds the scatter and the
    gathered formulation.

    Int8 KV cache (use_dynamic_cachekv_quant=True): caches are int8 page
    pools and cache_k_quant_scales / cache_v_quant_scales are PER-SLOT
    scale pools ([num_blocks, HKV, bs], or [L, ...] stacked) updated on
    write — the TPU mapping of the reference's dynamic cachekv quant
    (block_multi_head_attention.cu cache_k_quant_scales...): each
    written token stores round(x / s) with s = max|x|/127 per head, and
    the gather dequantizes s * int8 into the compute dtype. Cache HBM
    traffic and footprint halve vs bf16. Returns
    (out, qkv, key_cache, value_cache, k_scales, v_scales) in this mode.
    Static per-tensor scale args (the non-dynamic CUDA path) and
    pre_caches stay unsupported.

    last_row_is_padding=True is the engine's packing: the LAST batch row
    (index B-1, where B = block_tables.shape[0]) is the trash row, whose
    tokens pad the step to its token budget and whose page is trash by
    definition. The page-write kernel then skips that row (it holds most
    of a lightly loaded step's tokens); the scatter writes them into the
    trash page as before. Padding cannot be derived from the packed
    offsets alone: cu_seqlens_q[-1] equals the full token budget because
    the trash row's count is included (tokens in [cu_seqlens_q[B-1],
    cu_seqlens_q[B]) are the padding), so the identification goes through
    the row INDEX, not through a cu_q[-1]-vs-T comparison.

    fresh_prefill=True asserts every scheduled row starts at cache
    position 0 (seq_lens_decoder[b] == 0 for live rows), so this step's
    packed tokens ARE each row's full key set: attention runs as
    block-diagonal varlen flash over the pack, skipping the page-pool
    gather. It implies the padding-row contract above: the last row's
    tokens get segment id -1 and attend nothing. Callers scheduling real
    work into row B-1 must set neither.

    selection (block-sparse attention, ops/pallas/sparse_paged_attention.py):
    a dict of what each query attends among its row's pages, chosen on the
    device in the same step: `page_mask` [T, HKV, max_blocks] bool for
    every token, and for the rows of one token `listed` [B], `sel`
    [B, HKV, S] logical pages and `n_sel` [B, HKV]. The kernel walks a
    listed row's list and no other page, and every other row's pages under
    the mask; the gathered formulation takes the mask alone. A fresh
    prefill attends the whole pack and takes no selection."""
    if cache_k_quant_scales is not None and not use_dynamic_cachekv_quant:
        raise NotImplementedError("block_multihead_attention: static "
                                  "per-tensor cache scales are CUDA-"
                                  "specific; use dynamic cachekv quant")
    if use_dynamic_cachekv_quant and (cache_k_quant_scales is None
                                      or cache_v_quant_scales is None):
        raise ValueError("dynamic cachekv quant needs k/v scale pools")
    if pre_key_cache is not None:
        raise NotImplementedError("pre_caches not supported")
    if mask is not None or tgt_mask is not None:
        raise NotImplementedError("block_multihead_attention: explicit "
                                  "masks beyond the built-in causal/"
                                  "length masking are not supported")

    quant = bool(use_dynamic_cachekv_quant)

    def fn(qkva, kc_in, vc_in, enc, dec, this, cu_q, bt, *rest):
        it = iter(rest)
        ks_in = next(it) if quant else None
        vs_in = next(it) if quant else None
        b = next(it) if qkv_bias is not None else None
        rope = next(it) if rope_emb is not None else None
        T = qkva.shape[0]
        # stacked-cache mode (layer_idx given): caches are
        # [L, num_blocks, H, bs, D] and every access goes by a composite
        # (layer, page, ...) index straight into the stacked buffer — a
        # layer sliced out and put back was a copy of its whole cache
        kc, vc, ks, vs = kc_in, vc_in, ks_in, vs_in
        HKV, bs, D = kc.shape[-3:]
        B = bt.shape[0]
        if b is not None:
            qkva = qkva + b.reshape(1, -1)
        HQ = qkva.shape[1] // D - 2 * HKV                    # GQA: HQ >= HKV
        q = qkva[:, :HQ * D].reshape(T, HQ, D)
        k = qkva[:, HQ * D:(HQ + HKV) * D].reshape(T, HKV, D)
        v = qkva[:, (HQ + HKV) * D:].reshape(T, HKV, D)
        # token -> (batch, position)
        tok = jnp.arange(T)
        t2b = jnp.searchsorted(cu_q[1:], tok, side="right")  # [T]
        tok_in_seq = tok - cu_q[t2b]
        start = jnp.where(enc.reshape(-1) > 0, 0, dec.reshape(-1))  # [B]
        pos = start[t2b] + tok_in_seq                        # [T]
        if rope is not None:
            # rope_emb [2, B, 1, max_seq, D] (cos, sin): rotate q/k at
            # each token's absolute position
            re = rope.reshape(2, B, -1, rope.shape[-1])
            cos = re[0][t2b, pos]                            # [T, D]
            sin = re[1][t2b, pos]
            half = D // 2
            cos_h = (cos[..., :half] if cos.shape[-1] == D else cos) \
                [:, None, :]
            sin_h = (sin[..., :half] if sin.shape[-1] == D else sin) \
                [:, None, :]

            def rope_t(t):
                if use_neox_style:
                    t1, t2 = t[..., :half], t[..., half:]
                    return jnp.concatenate(
                        [t1 * cos_h - t2 * sin_h,
                         t2 * cos_h + t1 * sin_h], axis=-1)
                t1, t2 = t[..., 0::2], t[..., 1::2]
                return jnp.stack([t1 * cos_h - t2 * sin_h,
                                  t2 * cos_h + t1 * sin_h],
                                 axis=-1).reshape(t.shape)

            # rope promotes to the f32 angle dtype; restore the compute
            # dtype so the page scatter below matches the cache dtype
            q = rope_t(q).astype(qkva.dtype)
            k = rope_t(k).astype(qkva.dtype)
        # the step's k/v go to their pages, and each row attends over its
        # own pages: kernels where the cache tiles (one question for both),
        # XLA's scatter and the gathered reference elsewhere
        from ....ops.pallas import kv_page_write as _kw
        from ....ops.pallas import paged_attention as _pa

        kernels = _pa.use_kernel(q, kc, quant)
        if quant:
            # dynamic int8: one scale per written (token, head) —
            # s = max|x|/127, store round(x/s) int8 + s in the scale pool
            def q8(x):
                s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) \
                    / 127.0                                  # [T, HKV]
                s = jnp.maximum(s, 1e-8)
                xi = jnp.clip(jnp.round(x.astype(jnp.float32)
                                        / s[..., None]), -127, 127) \
                    .astype(jnp.int8)
                return xi, s.astype(jnp.float32)

            # straight into the stacked buffer via the composite
            # (layer, page, :, slot) index in stacked mode
            page = bt[t2b, pos // bs]                        # [T]
            slot = pos % bs
            at = (() if layer_idx is None else (layer_idx,)) \
                + (page, slice(None), slot)
            with scope("kv_write"):
                k8, k_s = q8(k)
                v8, v_s = q8(v)
                kc = kc.at[at].set(k8)
                vc = vc.at[at].set(v8)
                ks = ks.at[at].set(k_s)
                vs = vs.at[at].set(v_s)
        elif not kernels:
            with scope("kv_write"):
                kc, vc = _kw.kv_page_write_ref(kc, vc, k, v, bt, start, cu_q,
                                               layer_idx=layer_idx)
        if fresh_prefill:
            # every scheduled row starts at cache position 0, so keys ==
            # this step's packed tokens: block-diagonal varlen flash over
            # the pack (segment id = batch row; trash row = -1), no page
            # is read
            from ....ops.pallas.varlen_attention import \
                varlen_flash_attention_packed

            seg = jnp.where(t2b == B - 1, -1, t2b).astype(jnp.int32)
            G = HQ // HKV
            kr = jnp.repeat(k, G, axis=1) if G > 1 else k    # [T, HQ, D]
            vr = jnp.repeat(v, G, axis=1) if G > 1 else v
            o = varlen_flash_attention_packed(
                q.transpose(1, 0, 2)[None], kr.transpose(1, 0, 2)[None],
                vr.transpose(1, 0, 2)[None], seg[None], seg[None],
                is_causal=True)
            out = o[0].transpose(1, 0, 2)                    # [T, HQ, D]
        elif kernels and selection is not None:
            from ....ops.pallas.sparse_paged_attention import \
                sparse_paged_attention

            out = sparse_paged_attention(
                q, k, v, kc, vc, bt, start, cu_q, selection["listed"],
                selection["sel"], selection["n_sel"],
                selection["page_mask"], layer_idx=layer_idx)
        elif kernels:
            # over the caches as they are before this call's write, plus
            # this step's own k/v
            out = _pa.paged_attention(q, k, v, kc, vc, bt, start, cu_q,
                                      layer_idx=layer_idx)
        else:
            out = _pa.paged_attention_ref(
                q, kc, vc, bt, start, cu_q, layer_idx=layer_idx,
                k_scales=ks, v_scales=vs,
                page_mask=None if selection is None
                else selection["page_mask"])
        if kernels:
            # in place, once the attention has read the pages
            with scope("kv_write"):
                kc, vc = _kw.kv_page_write(
                    kc, vc, k, v, bt, start, cu_q, layer_idx=layer_idx,
                    last_row_is_padding=last_row_is_padding or fresh_prefill)
        out = out.reshape(T, HQ * D)
        if quant:
            return out, qkva, kc, vc, ks, vs
        return out, qkva, kc, vc

    args = [qkv, key_cache, value_cache, seq_lens_encoder,
            seq_lens_decoder, seq_lens_this_time, cu_seqlens_q,
            block_tables] \
        + ([cache_k_quant_scales, cache_v_quant_scales] if quant else []) \
        + [t for t in (qkv_bias, rope_emb) if t is not None]
    return apply(fn, *args, op_name="block_multihead_attention")


def _rope_cos_sin(positions, head_dim):
    """Default rope angles at absolute `positions` ([S] or [B, S]) ->
    (cos, sin) of shape [..., head_dim//2]."""
    half = head_dim // 2
    inv = 1.0 / (10000.0 ** (
        jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def _rope_apply(t, cos, sin, neox):
    """t [B, S, H, D]; cos/sin [S, D/2] or [B, S, D/2]."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :].astype(t.dtype)
    sin = sin[:, :, None, :].astype(t.dtype)
    d2 = t.shape[-1] // 2
    if neox:
        t1, t2 = t[..., :d2], t[..., d2:]
        return jnp.concatenate([t1 * cos - t2 * sin,
                                t2 * cos + t1 * sin], axis=-1)
    t1, t2 = t[..., 0::2], t[..., 1::2]
    return jnp.stack([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                     axis=-1).reshape(t.shape)


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights,
                            qkv_biases, linear_weights, linear_biases,
                            ffn_ln_scales, ffn_ln_biases, ffn1_weights,
                            ffn1_biases, ffn2_weights, ffn2_biases,
                            pre_layer_norm=True, epsilon=1e-5,
                            residual_alpha=1.0, cache_kvs=None,
                            beam_offset=None, pre_caches=None,
                            seq_lens=None, rotary_embs=None, time_step=None,
                            attn_mask=None, dropout_rate=0.0,
                            rotary_emb_dims=0, activation="gelu",
                            training=False, mode="upscale_in_train",
                            trans_qkvw=True, ring_id=-1,
                            norm_type="layernorm",
                            use_neox_rotary_style=False, gqa_group_size=-1,
                            name=None):
    """The whole decoder stack as one call (reference
    fused_transformer.py:fused_multi_transformer pseudocode): per layer
    pre/post-LN self-attention (+dense KV cache for decode when
    `time_step` is given) and the FFN. x: [B, S, H*D]; qkv_weights[i]
    [3, n_head, D, embed] when trans_qkvw else [embed, 3, n_head, D];
    cache_kvs[i] [2, B, n_head, max_seq, D]. rotary_embs (optional)
    [2, B, 1, max_seq, D] (cos, sin) indexed at each token's absolute
    position; when absent and rotary_emb_dims > 0 the default 10000-base
    angles are computed at the true positions (time_step offset in
    decode). seq_lens [B(,1)] gives per-row positions: prefill rows mask
    keys >= seq_lens[b]; decode rows write/attend at seq_lens[b] instead
    of the global time_step. Returns out or (out, cache_kvs_out).
    GQA (gqa_group_size>0), pre_caches and beam_offset are not
    supported."""
    from ....nn import functional as F

    if gqa_group_size not in (-1, None):
        raise NotImplementedError("gqa_group_size: use the model-zoo GQA "
                                  "attention path")
    if pre_caches is not None or beam_offset is not None:
        raise NotImplementedError("pre_caches / beam_offset are not "
                                  "supported")
    num_layers = len(qkv_weights)

    def norm(t, scale, bias_):
        if norm_type == "rmsnorm":
            return fused_rms_norm(t, scale, bias_, epsilon)
        return F.layer_norm(t, t.shape[-1], scale, bias_, epsilon)

    B, S, E = x.shape
    decode = time_step is not None
    lens = None
    if seq_lens is not None:
        lens = (seq_lens._value if isinstance(seq_lens, Tensor)
                else jnp.asarray(seq_lens)).reshape(-1).astype(jnp.int32)

    # absolute positions of this call's tokens, per row: [B, S]
    if decode:
        if lens is not None:
            base = lens
        else:
            ts = (time_step._value.reshape(()).astype(jnp.int32)
                  if hasattr(time_step, "_value")
                  else jnp.int32(int(time_step)))
            base = jnp.full((B,), 1, jnp.int32) * ts
        positions = base[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    else:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

    # prefill padding mask from seq_lens: additive [B, 1, 1, S]
    pad_mask = None
    if not decode and lens is not None:
        valid = jnp.arange(S, dtype=jnp.int32)[None, :] < lens[:, None]
        pad_mask = jnp.where(valid, 0.0, -1e30).astype(
            jnp.float32)[:, None, None, :]

    # a boolean attn_mask (True = keep) must become an additive float mask
    # before it is summed with pad_mask below — summing 0/1 logit offsets
    # would silently be a no-op mask
    if attn_mask is not None:
        _mv = attn_mask._value if isinstance(attn_mask, Tensor) \
            else jnp.asarray(attn_mask)
        if _mv.dtype == jnp.bool_:
            attn_mask = Tensor(
                jnp.where(_mv, 0.0, -1e30).astype(jnp.float32))

    out = x
    new_caches = [] if cache_kvs is not None else None
    for i in range(num_layers):
        residual = out
        h = norm(out, ln_scales[i],
                 ln_biases[i] if ln_biases else None) \
            if pre_layer_norm else out
        qw = qkv_weights[i]
        if len(qw.shape) == 4:
            n_head = qw.shape[1] if trans_qkvw else qw.shape[2]
            D = qw.shape[2] if trans_qkvw else qw.shape[3]
        elif cache_kvs is not None:
            n_head = cache_kvs[i].shape[2]
            D = cache_kvs[i].shape[4]
        else:
            raise ValueError("pass 4-D qkv weights ([3, n_head, D, E] when "
                             "trans_qkvw) or cache_kvs to carry the head "
                             "count")
        nhd = n_head * D
        qw3 = qw.reshape([3 * nhd, E]) if trans_qkvw \
            else qw.reshape([E, 3 * nhd]).transpose([1, 0])
        qkv = F.linear(h.reshape([B * S, E]), qw3.transpose([1, 0]))
        qkv = qkv.reshape([B, S, 3, nhd])
        if qkv_biases:
            qkv = qkv + qkv_biases[i].reshape([1, 1, 3, nhd])
        q = qkv[:, :, 0].reshape([B, S, n_head, D])
        k = qkv[:, :, 1].reshape([B, S, n_head, D])
        v = qkv[:, :, 2].reshape([B, S, n_head, D])
        if rotary_embs is not None or rotary_emb_dims > 0:
            qa, ka = q._value, k._value
            if rotary_embs is not None:
                re = (rotary_embs._value
                      if isinstance(rotary_embs, Tensor) else rotary_embs)
                re = re.reshape(2, B, -1, re.shape[-1])      # [2,B,max,D]
                cos = jnp.take_along_axis(
                    re[0], positions[:, :, None], axis=1)    # [B,S,D]
                sin = jnp.take_along_axis(
                    re[1], positions[:, :, None], axis=1)
                # caller supplies full-D cos/sin; halve for _rope_apply
                cos = cos[..., : D // 2] if cos.shape[-1] == D else cos
                sin = sin[..., : D // 2] if sin.shape[-1] == D else sin
            else:
                cos, sin = _rope_cos_sin(positions, D)       # [B,S,D/2]
            qa = _rope_apply(qa, cos, sin, use_neox_rotary_style)
            ka = _rope_apply(ka, cos, sin, use_neox_rotary_style)
            q, k = Tensor(qa), Tensor(ka)
        if decode and cache_kvs is not None:
            # masked attention over the dense cache, one new token per row
            cache = cache_kvs[i]
            ca = cache._value if isinstance(cache, Tensor) else cache
            pos_rows = positions[:, 0]                       # [B]
            bi = jnp.arange(B)
            ca = ca.at[0, bi, :, pos_rows, :].set(
                jnp.swapaxes(k._value, 1, 2)[:, :, 0])
            ca = ca.at[1, bi, :, pos_rows, :].set(
                jnp.swapaxes(v._value, 1, 2)[:, :, 0])
            keys, vals = ca[0], ca[1]              # [B, H, max_seq, D]
            qv = jnp.swapaxes(q._value, 1, 2)[:, :, 0]   # [B, H, D]
            logits = jnp.einsum("bhd,bhsd->bhs", qv.astype(jnp.float32),
                                keys.astype(jnp.float32)) \
                / jnp.sqrt(jnp.float32(D))
            maxs = keys.shape[2]
            valid = jnp.arange(maxs)[None, :] <= pos_rows[:, None]
            logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
            if attn_mask is not None:
                m = (attn_mask._value if isinstance(attn_mask, Tensor)
                     else jnp.asarray(attn_mask)).astype(jnp.float32)
                m = m.reshape(m.shape[0], -1)[:, :maxs]
                if m.shape[-1] < maxs:        # pad: tail already invalid
                    m = jnp.pad(m, ((0, 0), (0, maxs - m.shape[-1])))
                logits = logits + m[:, None, :]
            probs = jax.nn.softmax(logits, axis=-1)
            att = jnp.einsum("bhs,bhsd->bhd", probs,
                             vals.astype(jnp.float32))
            attn_out = Tensor(att.astype(qv.dtype).reshape(B, 1, nhd))
            new_caches.append(Tensor(ca))
        else:
            mask_arg = attn_mask
            if pad_mask is not None:
                mask_arg = (Tensor(pad_mask) if mask_arg is None
                            else mask_arg + Tensor(pad_mask))
            # the seq_lens-derived pad_mask only masks padding keys; it
            # must not switch prefill off the causal regime — only an
            # explicit user attn_mask overrides causality
            attn = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask_arg, is_causal=attn_mask is None,
                dropout_p=dropout_rate, training=training)
            attn_out = attn.reshape([B, S, nhd])
            if cache_kvs is not None:
                ca = cache_kvs[i]._value if isinstance(cache_kvs[i], Tensor) \
                    else cache_kvs[i]
                kk = jnp.swapaxes(k._value, 1, 2)    # [B, H, S, D]
                vv = jnp.swapaxes(v._value, 1, 2)
                ca = ca.at[0, :, :, :S, :].set(kk)
                ca = ca.at[1, :, :, :S, :].set(vv)
                new_caches.append(Tensor(ca))
        out_w = linear_weights[i]
        proj = F.linear(attn_out, out_w,
                        linear_biases[i] if linear_biases else None)
        proj = F.dropout(proj, dropout_rate, training=training, mode=mode)
        out = residual * residual_alpha + proj
        if not pre_layer_norm:
            out = norm(out, ln_scales[i],
                       ln_biases[i] if ln_biases else None)
        residual = out
        h = norm(out, ffn_ln_scales[i],
                 ffn_ln_biases[i] if ffn_ln_biases else None) \
            if pre_layer_norm else out
        h = F.linear(h, ffn1_weights[i],
                     ffn1_biases[i] if ffn1_biases else None)
        h = getattr(F, activation)(h)
        h = F.dropout(h, dropout_rate, training=training, mode=mode)
        h = F.linear(h, ffn2_weights[i],
                     ffn2_biases[i] if ffn2_biases else None)
        out = residual + h
        if not pre_layer_norm:
            out = norm(out, ffn_ln_scales[i],
                       ffn_ln_biases[i] if ffn_ln_biases else None)
    if cache_kvs is not None:
        return out, new_caches
    return out


__all__ += ["fused_bias_dropout_residual_layer_norm", "fused_feedforward",
            "fused_ec_moe", "blha_get_max_len",
            "masked_multihead_attention", "block_multihead_attention",
            "fused_multi_transformer"]
