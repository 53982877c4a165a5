"""Fused RMSNorm Pallas kernel.

Reference analog: fused_rms_norm (paddle/phi/kernels/fusion/gpu/, python
surface incubate/nn/functional/fused_rms_norm). RMSNorm is HBM-bound: one
read + one write of the activation. The kernel tiles rows into VMEM blocks,
does the reduction in fp32 on the VPU, and writes back in the input dtype —
one pass over HBM. Backward is the analytic jnp formula (XLA fuses it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret, kernels_enabled, note_reference_dispatch

_BLOCK_ROWS = 256


def _rms_norm_ref(x, weight, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    if weight is not None:
        out = out * weight.astype(jnp.float32)
    return out.astype(x.dtype)


def _rms_norm_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    out = out * w_ref[:].astype(jnp.float32)
    o_ref[:] = out.astype(o_ref.dtype)


def _rms_norm_nw_kernel(x_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps)).astype(o_ref.dtype)


def _pallas_forward(x, weight, eps):
    orig_shape = x.shape
    h = orig_shape[-1]
    x2 = x.reshape(-1, h)
    n = x2.shape[0]
    block = min(_BLOCK_ROWS, n)
    if n % block != 0:
        # row-count not tileable; XLA path handles the remainder fine
        note_reference_dispatch("rms_norm")
        return _rms_norm_ref(x, weight, eps)
    grid = (n // block,)
    if weight is not None:
        out = pl.pallas_call(
            functools.partial(_rms_norm_kernel, eps=eps),
            out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block, h), lambda i: (i, 0)),
                pl.BlockSpec((h,), lambda i: (0,)),
            ],
            out_specs=pl.BlockSpec((block, h), lambda i: (i, 0)),
            name="rms_norm", metadata={"kernel": "rms_norm"},
            interpret=interpret(),
        )(x2, weight)
    else:
        out = pl.pallas_call(
            functools.partial(_rms_norm_nw_kernel, eps=eps),
            out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
            grid=grid,
            in_specs=[pl.BlockSpec((block, h), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((block, h), lambda i: (i, 0)),
            name="rms_norm_noweight", metadata={"kernel": "rms_norm_noweight"},
            interpret=interpret(),
        )(x2)
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_norm(x, weight, eps, has_weight):
    if kernels_enabled():
        return _pallas_forward(x, weight if has_weight else None, eps)
    return _rms_norm_ref(x, weight if has_weight else None, eps)


def _fwd(x, weight, eps, has_weight):
    return _rms_norm(x, weight, eps, has_weight), (x, weight)


def _bwd(eps, has_weight, res, g):
    x, weight = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xf * inv
    if has_weight:
        wf = weight.astype(jnp.float32)
        gw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
        gxhat = gf * wf
    else:
        gw = jnp.zeros_like(weight, dtype=jnp.float32)
        gxhat = gf
    h = x.shape[-1]
    gx = inv * (gxhat - xhat * jnp.mean(gxhat * xhat, axis=-1, keepdims=True))
    return gx.astype(x.dtype), gw.astype(weight.dtype)


_rms_norm.defvjp(_fwd, _bwd)


def rms_norm(x, weight=None, eps: float = 1e-6):
    """rms_norm over the last axis. weight=None -> pure normalization."""
    if weight is None:
        w = jnp.ones((x.shape[-1],), x.dtype)
        return _rms_norm(x, w, eps, False)
    return _rms_norm(x, weight, eps, True)
