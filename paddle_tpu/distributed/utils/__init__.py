"""distributed.utils (reference: python/paddle/distributed/utils/) —
MoE global scatter/gather collectives (moe_utils.py:20,153)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import apply
from ...core.tensor import Tensor
from .. import collective

__all__ = ["global_scatter", "global_gather"]


def global_scatter(x, local_count, global_count, group=None):
    """Dispatch rows to expert owners (all_to_all on the ep axis).
    reference: python/paddle/distributed/utils/moe_utils.py:20."""
    ax = collective._axis(group)

    def fn(v, lc, gc):
        if collective._in_shard_map(v, group):
            n = jax.lax.axis_size(ax)
            per = v.shape[0] // n
            return jax.lax.all_to_all(
                v.reshape(n, per, *v.shape[1:]), ax, 0, 0, tiled=False
            ).reshape(v.shape)
        return v

    return apply(fn, x, local_count, global_count, op_name="global_scatter")


def global_gather(x, local_count, global_count, group=None):
    return global_scatter(x, global_count, local_count, group)
