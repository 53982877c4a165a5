"""Megatron-style sequence parallelism utilities.

Reference analog: fleet/utils/sequence_parallel_utils.py:85-137 (ScatterOp /
GatherOp / AllGatherOp / ReduceScatterOp PyLayers) + ColumnSequenceParallel
Linear (:427) — scatter activations along seq around TP blocks, allgather
before attention, reduce-scatter after.

TPU-native: in the compiled path SP is a sharding choice — activations carry
PartitionSpec('sp' on the seq dim) between TP blocks and XLA converts the
allgather/reduce-scatter pairs automatically (and removes redundant ones,
which the reference needs a dedicated pass for). These PyLayers provide the
explicit eager/shard_map forms for scripts that call them directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...autograd import PyLayer
from ...core.dispatch import apply
from ...core.tensor import Tensor
from .. import collective
from ..topology import get_hybrid_communicate_group

__all__ = ["ScatterOp", "GatherOp", "AllGatherOp", "ReduceScatterOp",
           "ColumnSequenceParallelLinear", "RowSequenceParallelLinear",
           "mark_as_sequence_parallel_parameter",
           "register_sequence_parallel_allreduce_hooks"]


def _mp_axis():
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        return None, 1
    g = hcg.get_model_parallel_group()
    return g.axis_name, g.nranks


def _in_shard_map(arr, axis):
    if not isinstance(arr, jax.core.Tracer) or axis is None:
        return False
    try:
        jax.lax.axis_index(axis)
        return True
    except Exception:
        return False


class ScatterOp(PyLayer):
    """split seq dim across mp; backward = allgather."""

    @staticmethod
    def forward(ctx, input, axis=0):
        ax_name, n = _mp_axis()
        ctx.axis = axis
        ctx.ax_name = ax_name

        def fn(x):
            if _in_shard_map(x, ax_name):
                idx = jax.lax.axis_index(ax_name)
                size = x.shape[axis] // jax.lax.axis_size(ax_name)
                return jax.lax.dynamic_slice_in_dim(x, idx * size, size,
                                                    axis)
            return x
        return apply(fn, input, op_name="sp_scatter", differentiable=False)

    @staticmethod
    def backward(ctx, grad):
        def fn(g):
            if _in_shard_map(g, ctx.ax_name):
                return jax.lax.all_gather(g, ctx.ax_name, axis=ctx.axis,
                                          tiled=True)
            return g
        return apply(fn, grad, op_name="sp_scatter_bwd",
                     differentiable=False)


class GatherOp(PyLayer):
    """allgather seq dim; backward = scatter."""

    @staticmethod
    def forward(ctx, input, axis=0):
        ax_name, n = _mp_axis()
        ctx.axis = axis
        ctx.ax_name = ax_name

        def fn(x):
            if _in_shard_map(x, ax_name):
                return jax.lax.all_gather(x, ax_name, axis=axis, tiled=True)
            return x
        return apply(fn, input, op_name="sp_gather", differentiable=False)

    @staticmethod
    def backward(ctx, grad):
        def fn(g):
            if _in_shard_map(g, ctx.ax_name):
                idx = jax.lax.axis_index(ctx.ax_name)
                size = g.shape[ctx.axis] // jax.lax.axis_size(ctx.ax_name)
                return jax.lax.dynamic_slice_in_dim(
                    g, idx * size, size, ctx.axis)
            return g
        return apply(fn, grad, op_name="sp_gather_bwd", differentiable=False)


class AllGatherOp(PyLayer):
    """allgather fwd; reduce-scatter bwd (reference AllGatherOp)."""

    @staticmethod
    def forward(ctx, input):
        ax_name, _ = _mp_axis()
        ctx.ax_name = ax_name

        def fn(x):
            if _in_shard_map(x, ax_name):
                return jax.lax.all_gather(x, ax_name, axis=0, tiled=True)
            return x
        return apply(fn, input, op_name="sp_allgather",
                     differentiable=False)

    @staticmethod
    def backward(ctx, grad):
        def fn(g):
            if _in_shard_map(g, ctx.ax_name):
                return jax.lax.psum_scatter(g, ctx.ax_name,
                                            scatter_dimension=0, tiled=True)
            return g
        return apply(fn, grad, op_name="sp_allgather_bwd",
                     differentiable=False)


class ReduceScatterOp(PyLayer):
    """reduce-scatter fwd; allgather bwd."""

    @staticmethod
    def forward(ctx, input):
        ax_name, _ = _mp_axis()
        ctx.ax_name = ax_name

        def fn(x):
            if _in_shard_map(x, ax_name):
                return jax.lax.psum_scatter(x, ax_name,
                                            scatter_dimension=0, tiled=True)
            return x
        return apply(fn, input, op_name="sp_reduce_scatter",
                     differentiable=False)

    @staticmethod
    def backward(ctx, grad):
        def fn(g):
            if _in_shard_map(g, ctx.ax_name):
                return jax.lax.all_gather(g, ctx.ax_name, axis=0, tiled=True)
            return g
        return apply(fn, grad, op_name="sp_reduce_scatter_bwd",
                     differentiable=False)


from ..meta_parallel.mp_layers import (ColumnParallelLinear,
                                       RowParallelLinear)


class ColumnSequenceParallelLinear(ColumnParallelLinear):  # reference :427
    def forward(self, x):
        x = AllGatherOp.apply(x)
        return super().forward(x)


class RowSequenceParallelLinear(RowParallelLinear):
    def forward(self, x):
        out = super().forward(x)
        return ReduceScatterOp.apply(out)


def mark_as_sequence_parallel_parameter(parameter):
    parameter.sequence_parallel = True


def register_sequence_parallel_allreduce_hooks(model, accumulation_steps=1,
                                               fuse_sequence_parallel_allreduce=False):
    """reference :192 — allreduce SP-marked params' grads over mp group."""
    hcg = get_hybrid_communicate_group()
    if hcg is None or hcg.get_model_parallel_world_size() <= 1:
        return
    group = hcg.get_model_parallel_group()
    for p in model.parameters():
        if getattr(p, "sequence_parallel", False):
            def hook(grad, _g=group):
                collective.all_reduce(grad, group=_g)
                return grad
            p.register_hook(hook)
