"""The precisions a reference can be computed in.

`f32` is the reference itself. `fp8` is the control of "How correct is
decided": the nearest precision below the bfloat16 the configurations
state, with every matmul's two operands rounded to float8_e4m3fn (scaled
into its range a tensor at a time, as fp8 matmuls are used) and the product
accumulated in float32. A later PR tempted to drop a matmul to fp8 would
compute this.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "fp8")
_FP8_MAX = 448.0


def _to_fp8(x):
    """x rounded to fp8 values; the gradient passes straight through (the
    backward matmuls then run in float32 on the rounded operands)."""
    x0 = jax.lax.stop_gradient(x)
    scale = jnp.maximum(jnp.max(jnp.abs(x0)), 1e-30) / _FP8_MAX
    rounded = (x0 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
        * scale
    return x + (rounded - x0)


def matmul(a, b, precision: str):
    """a @ b in float32, operands first rounded as `precision` says."""
    if precision == "fp8":
        a, b = _to_fp8(a), _to_fp8(b)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
