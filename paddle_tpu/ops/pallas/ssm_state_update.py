"""State-space decode step — one Pallas TPU kernel over the engine's per-row
state stack.

Reference analog: the selective-state-update kernel of Mamba-2 serving
(mamba_ssm `selective_state_update`): for each row that holds ONE token this
step, the row's state `h [H, P, N]` (float32) takes

    h <- exp(dt A) h + (dt x) B^T        y = h C + D x

with `A`, `D`, `dt` a head, `x [H, P]`, and `B`, `C [G, N]` shared by the
`H // G` heads of a group. It is bound by memory: a row reads and writes
its whole state (8.4e6 B at H 128, P 64, N 128) for 5e7 operations.

Layout, as the engine keeps it: `state [L, S, H, P, N]`, one slot a row
(the last slot is the padding row's). The kernel takes the WHOLE stack and
writes it back in place (`input_output_aliases`): the layer and each row's
slot are run-time scalars (scalar prefetch), so one compiled program serves
every layer and every assignment of slots. The grid is (group, row), rows
innermost: a row that holds no single token this step points at the padding
slot, whose block then stays where it is from one grid step to the next and
moves nothing. `N` lies on the lanes and `P` on the sublanes, so what varies
with `P` (`dt x`, `y`) reaches the kernel as columns: `[R, G, P, H // G]`.

Groups = heads (lightning attention, `models/minicpm_sala.py`: the same
recurrence with `dt` = 1, `A` = -slope, `B` = k, `C` = q / sqrt(d), `D` = 0,
every head its own `B` and `C`; H 32, P = N = 128). The layout above would
then put `H // G` = 1 on the lanes: a `[P, 1]` column is padded to 128 lanes
in HBM and in VMEM, and the grid takes one 64 KiB state a step. So the
grid's first axis is a BLOCK OF HEADS, not a group: where heads share their
group's `B`, `C` a block is the group, as before (Nemotron's shapes give the
program they gave); where each head has its own, a block is
`_HEADS_PER_BLOCK` heads, `dt x` and `y` are `[R, H / 16, P, 16]`, and `B`,
`C` reach the kernel as the block's `[16, N]` rows, one a head.

`ssm_state_update` is the dispatch; `ssm_state_update_ref` the jnp
formulation of the same signature, which the CPU runs and which the kernel
gives way to where the state does not tile (counted as
`pallas/reference_dispatch/ssm_state_update`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import kernels_enabled, note_reference_dispatch

__all__ = ["ssm_state_update", "ssm_state_update_ref", "use_kernel"]

_LANES = 128
_SUBLANES = 8
_HEADS_PER_BLOCK = 16        # where every head has its own B and C


def use_kernel(state) -> bool:
    """Whether the kernel runs: kernels on, a float32 state whose `[P, N]`
    tiles the (8, 128) registers. With kernels on, a no is counted."""
    if not kernels_enabled():
        return False
    p, n = state.shape[-2:]
    if state.dtype == jnp.float32 and n % _LANES == 0 and p % _SUBLANES == 0:
        return True
    note_reference_dispatch("ssm_state_update")
    return False


def _per_row(slots, active, n_slots):
    """Rows that hold no single token point at the padding slot."""
    return jnp.where(active.astype(bool), slots, n_slots - 1) \
        .astype(jnp.int32)


def ssm_state_update_ref(state, x, dt, a, b, c, d, slots, active, reset, *,
                         layer_idx):
    """state `[L, S, H, P, N]` float32; x `[R, H, P]`; dt `[R, H]` float32
    (after softplus); a, d `[H]` float32 (`a` negative); b, c `[R, G, N]`;
    slots, active, reset `[R]` int32: the row's slot, whether it holds one
    token this step, whether it starts from a zero state. Returns
    (state, y `[R, H, P]` float32); rows that are not active leave the
    state alone and read y = 0."""
    n_slots, heads = state.shape[1], state.shape[2]
    f32 = jnp.float32
    on = active.astype(bool)
    rep = heads // b.shape[1]
    h = jnp.where(reset.astype(bool)[:, None, None, None], 0.0,
                  state[layer_idx, _per_row(slots, active, n_slots)])
    xf = x.astype(f32)
    bh = jnp.repeat(b.astype(f32), rep, axis=1)              # [R, H, N]
    ch = jnp.repeat(c.astype(f32), rep, axis=1)
    hn = h * jnp.exp(dt * a)[:, :, None, None] \
        + (dt[:, :, None] * xf)[..., None] * bh[:, :, None, :]
    y = jnp.sum(hn * ch[:, :, None, :], axis=-1) + d[None, :, None] * xf
    # an index past the last slot is dropped: inactive rows write nothing
    where = jnp.where(on, slots, n_slots)
    state = state.at[layer_idx, where].set(hn, mode="drop")
    return state, jnp.where(on[:, None, None], y, 0.0)


def _kernel(layer_ref, slot_ref, active_ref, reset_ref,        # prefetch
            h_ref, da_ref, dtx_ref, b_ref, c_ref, ho_ref, y_ref, *, hb):
    r = pl.program_id(1)

    @pl.when(active_ref[r] == 1)
    def _():
        fresh = reset_ref[r] == 1
        # [1, N] shared by the block's heads, or [hb, N], one row a head
        b = b_ref[0, 0].astype(jnp.float32)
        c = c_ref[0, 0].astype(jnp.float32)
        own = b.shape[0] > 1
        da = da_ref[0, 0]                                     # [1, hb]
        dtx = dtx_ref[0, 0]                                   # [P, hb]
        for i in range(hb):
            h = jnp.where(fresh, 0.0, h_ref[0, 0, i])         # [P, N]
            hn = h * da[:, i:i + 1] \
                + dtx[:, i:i + 1] * (b[i:i + 1] if own else b)
            ho_ref[0, 0, i] = hn
            y_ref[0, 0, :, i:i + 1] = jnp.sum(
                hn * (c[i:i + 1] if own else c), axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(state, da, dtx, b, c, slot, active, reset, layer, *, interpret):
    heads, p, n = state.shape[2:]
    # b, c `[R, blocks, 1 or hb, N]`: the grid's first axis is a block of
    # `hb` heads (a group, or `_HEADS_PER_BLOCK` heads of their own B, C)
    rows, groups, bg = b.shape[0], b.shape[1], b.shape[2]
    hb = heads // groups

    def by_state(g, r, layer_ref, slot_ref, *_):
        return (layer_ref[0], slot_ref[r], g, 0, 0)

    def by_row(g, r, *_):
        return (r, g, 0, 0)

    new_state, y = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(groups, rows),
            in_specs=[
                pl.BlockSpec((1, 1, hb, p, n), by_state),
                pl.BlockSpec((1, 1, 1, hb), by_row),
                pl.BlockSpec((1, 1, p, hb), by_row),
                pl.BlockSpec((1, 1, bg, n), by_row),
                pl.BlockSpec((1, 1, bg, n), by_row),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb, p, n), by_state),
                pl.BlockSpec((1, 1, p, hb), by_row),
            ]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((rows, groups, p, hb), jnp.float32)],
        # operand 4 (after the four prefetched scalars) is the state stack:
        # written back where it lies
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="ssm_state_update", metadata={"kernel": "ssm_state_update"},
        interpret=interpret,
    )(layer, slot, active, reset, state, da, dtx, b, c)
    return new_state, y


def ssm_state_update(state, x, dt, a, b, c, d, slots, active, reset, *,
                     layer_idx):
    """The decode step of a state-space layer for the rows that hold one
    token (see `ssm_state_update_ref` for the operands): the kernel where
    `use_kernel(state)` says so, else the reference."""
    if not use_kernel(state):
        return ssm_state_update_ref(state, x, dt, a, b, c, d, slots, active,
                                    reset, layer_idx=layer_idx)
    rows, heads, p = x.shape
    groups, n = b.shape[1], b.shape[2]
    hb, bg = heads // groups, 1
    if hb == 1 and heads > 1:
        # groups = heads: blocks of heads, each head its own B and C
        hb = bg = _HEADS_PER_BLOCK if heads % _HEADS_PER_BLOCK == 0 \
            else heads
        groups = heads // hb
    f32 = jnp.float32
    xf = x.astype(f32)
    # what varies with P goes in as columns [R, G, P, hb]; what is a number
    # a head as a row [R, G, 1, hb]
    da = jnp.exp(dt * a).reshape(rows, groups, 1, hb)
    dtx = (dt[:, :, None] * xf).reshape(rows, groups, hb, p) \
        .transpose(0, 1, 3, 2)
    act = active.astype(jnp.int32)
    state, y = _call(
        state, da, dtx, b.reshape(rows, groups, bg, n),
        c.reshape(rows, groups, bg, n),
        _per_row(slots, active, state.shape[1]), act,
        reset.astype(jnp.int32), jnp.full((1,), layer_idx, jnp.int32),
        interpret=_interpret())
    y = y.transpose(0, 1, 3, 2).reshape(rows, heads, p) \
        + d[None, :, None] * xf
    return state, jnp.where(act.astype(bool)[:, None, None], y, 0.0)
