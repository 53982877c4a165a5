"""The rows of a packed serving step and the chunked scan of a linear
recurrence over them: what `models/nemotron_h.py` (Mamba-2) and
`models/minicpm_sala.py` (lightning attention) share.

The recurrence, a head: `h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T`,
`y_t = h_t C_t + D x_t`, the state `h [P, N]` in float32. Mamba-2 draws
`dt` from the token and shares `B`, `C` among the `H / G` heads of a group;
lightning attention is the same recurrence with `dt` = 1, `A` = -slope,
`B` = k, `C` = q / sqrt(d), `D` = 0, `x` = v and GROUPS = HEADS (each head
its own key and query). A row of one token goes through
`ops/pallas/ssm_state_update.py`; a row with a chunk through `chunk_scan`
below, block by block. `rows_of` makes, once a step, what both need to know
of the step's rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["rows_of", "chunk_scan"]


def rows_of(t, enc, dec, this, cu, slots, q):
    """What the layers need to know of the step's rows, once a step: `t`
    packed tokens, rows `[B + 1]` (the last the padding row), `slots` each
    row's slot (None: no row state), `q` the scan's block length."""
    b1 = this.shape[0]
    row = jnp.arange(b1)
    tok = jnp.arange(t)
    t2b = jnp.minimum(jnp.searchsorted(cu[1:], tok, side="right"), b1 - 1)
    live = (row < b1 - 1) & (this > 0)
    start = jnp.where(enc > 0, 0, dec)
    meta = {"t2b": t2b, "start": start,
            "off": tok - cu[t2b], "real": t2b < b1 - 1,
            "live": live, "reset": start == 0, "cu": cu, "this": this,
            "slots": slots, "one": live & (this == 1),
            "first_tok": jnp.minimum(cu[:-1], t - 1)}
    if slots is None:
        return meta
    # the chunk rows' blocks of `q` tokens, in packed order: each row with
    # a chunk adds at most one partial block to the `t / q` whole ones
    room = -(-t // q) + b1 - 1
    chunk = live & (this > 1)
    nblk = jnp.where(chunk, (this + q - 1) // q, 0)
    ends = jnp.cumsum(nblk)
    j = jnp.arange(room)
    r = jnp.minimum(jnp.searchsorted(ends, j, side="right"), b1 - 1)
    k = j - (ends[r] - nblk[r])                     # block within the row
    valid = j < ends[-1]
    n_slots_pad = slots[b1 - 1]                     # the padding row's slot
    meta["n_blocks"] = ends[-1]
    meta["blocks"] = {
        "off": jnp.where(valid, cu[r] + k * q, t),
        "len": jnp.where(valid, jnp.clip(this[r] - k * q, 0, q), 0),
        "first": k == 0,
        "fresh": meta["reset"][r],
        "read": jnp.where(valid, slots[r], n_slots_pad),
        "write": jnp.where(valid & (k == nblk[r] - 1), slots[r],
                           n_slots_pad)}
    return meta


def chunk_scan(q, ssm, li, xs, dt, a, b, c, d, blocks, n_blocks):
    """The rows that hold a chunk, block by block in packed order (the
    first `n_blocks` of `blocks`: a step with no chunk walks none): each
    block of up to `q` tokens of ONE row takes the within-block
    quadratic form plus what the state it entered with gives; the state
    passes to the row's next block, starts from the row's slot (or zero)
    at the row's first and is written to the slot at its last.
    `ssm [L, S, H, P, N]` float32, `xs [T, H, P]`, `dt [T, H]`, `a`, `d`
    `[H]`, `b`, `c` `[T, G, N]`. Returns (ssm, y `[T, H, P]` float32, zero
    where no chunk row has a token)."""
    f32 = jnp.float32
    t, hm, pd = xs.shape
    g, n = b.shape[1], b.shape[2]
    hb = hm // g
    pad = ((0, q), (0, 0), (0, 0))
    xs_p, b_p, c_p = (jnp.pad(v, pad) for v in (xs, b, c))
    dt_p = jnp.pad(dt, ((0, q), (0, 0)))
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]

    def block(j, carry):
        ssm, h, y = carry
        blk = {k: v[j] for k, v in blocks.items()}
        off = blk["off"]
        take = lambda v: jax.lax.dynamic_slice_in_dim(v, off, q, 0)
        keep = jnp.arange(q) < blk["len"]
        xq = take(xs_p).astype(f32)                            # [Q, H, P]
        bq, cq = take(b_p), take(c_p)                          # [Q, G, N]
        # a token past the block's length has dt 0: it neither decays the
        # state nor adds to it
        dtq = jnp.where(keep[:, None], take(dt_p), 0.0)        # [Q, H]
        h0 = jax.lax.dynamic_slice(
            ssm, (li, blk["read"], 0, 0, 0), (1, 1, hm, pd, n))[0, 0]
        h = jnp.where(blk["first"],
                      jnp.where(blk["fresh"], 0.0, h0), h)
        cs = jnp.cumsum(dtq * a, axis=0)                       # [Q, H]
        # within the block: (C_q . B_s) exp(cs_q - cs_s) dt_s x_s, s <= q
        cb = jnp.einsum("qgn,sgn->gqs", cq, bq,
                        preferred_element_type=f32)
        decay = jnp.where(causal[None],
                          jnp.exp(cs.T[:, :, None] - cs.T[:, None, :]), 0.0)
        m = jnp.repeat(cb, hb, axis=0) * decay * dtq.T[:, None, :]
        yq = jnp.einsum("hqs,shp->qhp", m, xq)
        # from the state the block entered with
        yq = yq + jnp.exp(cs)[:, :, None] * jnp.einsum(
            "qgn,gipn->qgip", cq.astype(f32),
            h.reshape(g, hb, pd, n)).reshape(q, hm, pd)
        yq = yq + d[None, :, None] * xq
        # the state the block leaves
        to_end = jnp.exp(cs[-1][None] - cs) * dtq              # [Q, H]
        h = jnp.exp(cs[-1])[:, None, None] * h + jnp.einsum(
            "sgip,sgn->gipn", (to_end[:, :, None] * xq).reshape(
                q, g, hb, pd), bq.astype(f32)).reshape(hm, pd, n)
        ssm = jax.lax.dynamic_update_slice(
            ssm, h[None, None], (li, blk["write"], 0, 0, 0))
        cur = jax.lax.dynamic_slice_in_dim(y, off, q, 0)
        y = jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where(keep[:, None, None], yq, cur), off, 0)
        return ssm, h, y

    ssm, _, y = jax.lax.fori_loop(
        0, n_blocks, block, (ssm, jnp.zeros((hm, pd, n), f32),
                             jnp.zeros((t + q, hm, pd), f32)))
    return ssm, y[:t]
