"""Pipeline-parallel engines.

Reference analog: PipelineParallel.train_batch / forward_backward_pipeline
(fleet/meta_parallel/pipeline_parallel.py:149,459,697 — 1F1B), interleaved
VPP (:1010), p2p helpers (pp_utils/p2p_communication.py:559), zero-bubble
static schedule (passes/pipeline_scheduler_pass/pipeline_zero_bubble.py).

TPU-native split of responsibilities:
- **Eager engine (this file, PipelineParallel)**: keeps the reference's
  micro-batch train_batch API and 1F1B accounting. Single-controller JAX
  owns every stage's devices, so "send/recv" are device-to-device array
  moves XLA schedules; the engine loops micro-batches and accumulates
  gradients on the tape.
- **Compiled engine (spmd_pipeline)**: the performance path. The 'pp' mesh
  axis runs a collective-permute pipeline inside ONE jitted program: stage
  weights are sharded over pp, micro-batch activations rotate along the axis
  each step (GPipe schedule; bubble 2*(P-1)/(M+P-1)), and XLA overlaps the
  ppermute with stage compute over ICI.
"""
from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...nn.layer.layers import Layer
from ...profiler import metrics as _metrics
from .pp_layers import PipelineLayer

__all__ = ["PipelineParallel", "PipelineParallelWithInterleave",
           "PipelineParallelZeroBubble", "spmd_pipeline",
           "spmd_pipeline_interleaved"]


class PipelineParallel(Layer):
    def __init__(self, layers, hcg, strategy=None):
        super().__init__()
        self._layers = layers
        self._hcg = hcg
        self._strategy = strategy
        pp_cfg = {}
        if strategy is not None:
            pp_cfg = strategy.hybrid_configs.get("pp_configs", {}) or {}
            if hasattr(pp_cfg, "keys"):
                pp_cfg = dict(pp_cfg)
        self.micro_batch_size = pp_cfg.get("micro_batch_size", 1)
        self.accumulate_steps = pp_cfg.get("accumulate_steps", 1)
        self.num_stages = hcg.get_pipe_parallel_world_size()
        self.stage_id = hcg.get_stage_id()
        self.total_loss = None

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    def _split_micro(self, data):
        if isinstance(data, (tuple, list)):
            xs, ys = data[0], data[1]
        else:
            xs, ys = data, None
        n = self.accumulate_steps
        from ...ops.manipulation import split as tsplit

        x_chunks = tsplit(xs, n, axis=0)
        y_chunks = tsplit(ys, n, axis=0) if ys is not None else [None] * n
        return list(zip(x_chunks, y_chunks))

    def forward_backward_pipeline(self, data, scaler=None):
        """1F1B accounting (reference :459). Stage compute runs in-order on
        the single controller; gradient accumulation matches the reference's
        micro-batch semantics exactly."""
        micros = self._split_micro(data)
        total_loss = None
        loss_fn = getattr(self._layers, "_loss_fn", None)
        for x, y in micros:
            out = self._layers(x)
            if loss_fn is not None and y is not None:
                loss = loss_fn(out, y)
            else:
                loss = out
            if scaler is not None:
                scaled = scaler.scale(loss / len(micros))
                scaled.backward()
            else:
                (loss / len(micros)).backward()
            det = loss.detach()
            total_loss = det if total_loss is None else total_loss + det
        self.total_loss = total_loss / len(micros)
        return self.total_loss

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        """reference :697."""
        self._layers.train()
        loss = self.forward_backward_pipeline(data, scaler)
        if scaler is not None:
            scaler.step(optimizer)
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        return loss

    def eval_batch(self, data, compute_loss=False):
        self._layers.eval()
        micros = self._split_micro(data)
        loss_fn = getattr(self._layers, "_loss_fn", None)
        total = None
        from ...core.autograd import no_grad

        with no_grad():
            for x, y in micros:
                out = self._layers(x)
                if compute_loss and loss_fn is not None:
                    out = loss_fn(out, y)
                det = out.detach()
                total = det if total is None else total + det
        return total / len(micros)

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, s, *a, **k):
        return self._layers.set_state_dict(s, *a, **k)

    def parameters(self, *a, **k):
        return self._layers.parameters(*a, **k)


class _ChunkExecutor:
    """Schedule-driven executor over virtual model chunks.

    Executes per-stage instruction streams from pipeline_schedules
    ((kind, micro, chunk) with kind F/B/W) on the single controller,
    honoring the cross-stage dataflow the schedule encodes: F passes
    activations to the next virtual stage, B returns cotangents to the
    previous one, W (zero-bubble only) computes weight grads decoupled
    from B. This is the eager analog of the reference's static scheduler
    passes feeding its interpreter (pipeline_scheduler_pass/)."""

    def __init__(self, pipeline_layer, num_stages: int, num_chunks: int,
                 loss_fn=None):
        import numpy as np

        self._layers = pipeline_layer
        self.p = num_stages
        self.v = num_chunks
        self.q = self.p * self.v
        self._loss_fn = loss_fn or getattr(pipeline_layer, "_loss_fn", None)
        funcs = getattr(pipeline_layer, "run_function", None)
        if funcs is None:
            funcs = [pipeline_layer]
        self._funcs = list(funcs)
        self._bounds = np.linspace(0, len(self._funcs), self.q + 1,
                                   dtype=int).tolist()
        self._chunk_params = []
        for gv in range(self.q):
            params, seen = [], set()
            for f in self._funcs[self._bounds[gv]:self._bounds[gv + 1]]:
                if isinstance(f, Layer):
                    for prm in f.parameters():
                        if id(prm) not in seen:
                            seen.add(id(prm))
                            params.append(prm)
            self._chunk_params.append(params)

    def _run_chunk(self, gv, x):
        for f in self._funcs[self._bounds[gv]:self._bounds[gv + 1]]:
            x = f(x)
        return x

    def run(self, scheds, micros, split_bw: bool, scaler=None):
        """Execute per-stage schedules; returns mean loss (detached).
        split_bw=False fuses W into B (1F1B/VPP). split_bw=True is the
        genuine zero-bubble split: B runs ONLY the input-grad pullback
        (critical path, graph retained), and each W instruction runs the
        weight-grad pullback itself — real deferred compute in the bubble
        slot, matching pipeline_zero_bubble.py's B/W decomposition.

        Cross-stage activation hand-offs are dispatched asynchronously
        by the single controller; the wall-clock between a chunk output's
        dispatch and its consumption by the next virtual stage is the
        window the schedule hides the transfer in — recorded per hand-off
        as the ``comm/overlap_ms`` histogram."""
        import time as _time

        from ...core import autograd

        n_micro = len(micros)
        acts = {}     # (micro, gv) -> (x_in, out_or_loss)
        cots = {}     # (micro, gv) -> upstream cotangent for chunk output
        dws = {}      # (micro, gv) -> param grads awaiting W (split_bw)
        hand = {}     # (micro, gv) -> dispatch ts of the F hand-off
        total_loss = None

        ptr = [0] * self.p
        pending = sum(len(s) for s in scheds)
        while pending:
            progressed = False
            for s in range(self.p):
                if ptr[s] >= len(scheds[s]):
                    continue
                kind, mi, c = scheds[s][ptr[s]]
                gv = c * self.p + s
                if kind == "F":
                    if gv == 0:
                        x_in = micros[mi][0]
                    else:
                        prev = acts.get((mi, gv - 1))
                        if prev is None:
                            continue
                        t_sent = hand.pop((mi, gv - 1), None)
                        if t_sent is not None:
                            _metrics.observe(
                                "comm/overlap_ms",
                                (_time.perf_counter() - t_sent) * 1e3)
                        x_in = prev[1].detach()
                        x_in.stop_gradient = False
                    out = self._run_chunk(gv, x_in)
                    if gv < self.q - 1:
                        hand[(mi, gv)] = _time.perf_counter()
                    if gv == self.q - 1:
                        y = micros[mi][1]
                        if self._loss_fn is not None and y is not None:
                            out = self._loss_fn(out, y)
                        det = out.detach()
                        total_loss = det if total_loss is None \
                            else total_loss + det
                        if scaler is not None:
                            out = scaler.scale(out)
                        out = out / n_micro
                    acts[(mi, gv)] = (x_in, out)
                elif kind == "B":
                    if (mi, gv) not in acts:
                        continue
                    if gv != self.q - 1 and (mi, gv) not in cots:
                        continue
                    x_in, out = acts[(mi, gv)]
                    dy = cots.pop((mi, gv), None)
                    params = self._chunk_params[gv]
                    if split_bw:
                        # input-grad pullback only; graph retained for W
                        gx = autograd.grad(
                            out, [x_in], grad_outputs=dy,
                            retain_graph=True, allow_unused=True)
                        if gv > 0 and gx[0] is not None:
                            cots[(mi, gv - 1)] = gx[0]
                        dws[(mi, gv)] = (out, dy)
                    else:
                        grads = autograd.grad(
                            out, [x_in] + params, grad_outputs=dy,
                            retain_graph=False, allow_unused=True)
                        if gv > 0 and grads[0] is not None:
                            cots[(mi, gv - 1)] = grads[0]
                        self._accum(params, grads[1:])
                    del acts[(mi, gv)]
                else:  # W
                    if (mi, gv) not in dws:
                        continue
                    out, dy = dws.pop((mi, gv))
                    params = self._chunk_params[gv]
                    gw = autograd.grad(
                        out, params, grad_outputs=dy,
                        retain_graph=False, allow_unused=True)
                    self._accum(params, gw)
                ptr[s] += 1
                pending -= 1
                progressed = True
            if not progressed:
                raise RuntimeError(
                    f"pipeline executor wedged at ptr={ptr} "
                    f"(schedule/dataflow mismatch)")
        return total_loss / n_micro if total_loss is not None else None

    @staticmethod
    def _accum(params, grads):
        for prm, g in zip(params, grads):
            if g is None:
                continue
            prm.grad = g if prm.grad is None else prm.grad + g


class PipelineParallelWithInterleave(PipelineParallel):
    """Interleaved/VPP engine (reference :1010): each stage owns
    `num_virtual_pipeline_stages` model chunks executed in Megatron
    interleaved-1F1B order via the schedule generators; numerics match
    plain 1F1B exactly (same per-micro grad accumulation)."""

    def __init__(self, layers, hcg, strategy=None,
                 num_virtual_pipeline_stages=None):
        super().__init__(layers, hcg, strategy)
        v = num_virtual_pipeline_stages or getattr(
            layers, "_num_virtual_pipeline_stages", None) or 2
        self.num_virtual = max(int(v), 1)

    def _schedules(self):
        from . import pipeline_schedules as psched

        return [psched.gen_interleave_1f1b(
                    s, self.num_stages, self.accumulate_steps,
                    self.num_virtual)
                for s in range(self.num_stages)]

    _split_bw = False

    def forward_backward_pipeline(self, data, scaler=None):
        micros = self._split_micro(data)
        key = (self.num_stages, self.num_virtual, len(micros))
        if getattr(self, "_sched_cache_key", None) != key:
            self._sched_cache_key = key
            self._sched_cache = self._schedules()
            self._executor = _ChunkExecutor(
                self._layers, self.num_stages, self.num_virtual)
        self.total_loss = self._executor.run(
            self._sched_cache, micros, split_bw=self._split_bw,
            scaler=scaler)
        return self.total_loss


class PipelineParallelZeroBubble(PipelineParallelWithInterleave):
    """Zero-bubble (ZB-H1) engine (reference
    passes/pipeline_scheduler_pass/pipeline_zero_bubble.py): backward is
    genuinely split — B computes input grads only (critical path), W
    computes weight grads and is scheduled into bubble slots."""

    def __init__(self, layers, hcg, strategy=None):
        super().__init__(layers, hcg, strategy,
                         num_virtual_pipeline_stages=1)

    _split_bw = True

    def _schedules(self):
        from . import pipeline_schedules as psched

        return psched._zb_h1_all_stages(self.num_stages,
                                        self.accumulate_steps)


def spmd_pipeline(stage_fn: Callable, stacked_params, x, n_micro: int,
                  axis_name: str = "pp", overlap_sends: bool = False):
    """Collective-permute GPipe pipeline, to be called INSIDE shard_map over
    the 'pp' axis.

    stage_fn(params, x) -> y   : one pipeline stage's computation
    stacked_params             : this stage's params (already sharded by the
                                 caller via shard_map over 'pp')
    x                          : [n_micro, mb, ...] micro-batched input
                                 (only stage 0's value is consumed)

    Returns [n_micro, mb, ...] outputs valid on the LAST stage.
    Total steps = n_micro + P - 1; each step: compute on current buffer,
    then ppermute the activation ring one hop toward the next stage.

    ``overlap_sends=True`` is the latency-hidden variant: each tick's
    micro-batch is split into two halves along the batch dim, and the
    first half's ppermute is issued BEFORE the second half's compute —
    giving XLA's scheduler a real window to run the ICI hop behind the
    MXU instead of serializing compute -> send.  Requires a per-sample
    stage_fn (true for transformer blocks) and an even micro-batch;
    otherwise the call falls back to the unsplit schedule.  Both
    schedules send the same rows through the same products (the halves
    are independent rows), so the results agree bit for bit wherever
    the backend's product does not change with the row count; a half
    of a single row may meet another product (matrix-vector for
    matrix-matrix on the CPU) and then differs in the last place
    (tests/test_overlap.py).
    """
    p = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    n_steps = n_micro + p - 1
    mb_shape = x.shape[1:]
    perm = [(i, (i + 1) % p) for i in range(p)]
    split = overlap_sends and len(mb_shape) >= 1 \
        and mb_shape[0] % 2 == 0 and mb_shape[0] >= 2

    def body(carry, t):
        state, outputs = carry
        # stage 0 feeds a fresh micro-batch; others consume the ring
        feed = jnp.where(t < n_micro, jnp.minimum(t, n_micro - 1), 0)
        inject = jax.lax.dynamic_index_in_dim(x, feed, 0, keepdims=False)
        cur = jnp.where(stage == 0, inject, state)
        if split:
            half = mb_shape[0] // 2
            y0 = stage_fn(stacked_params, cur[:half])
            # issued before y1's compute: the hop for half 0 is in
            # flight while half 1 occupies the MXU
            s0 = jax.lax.ppermute(y0, axis_name, perm)
            y1 = stage_fn(stacked_params, cur[half:])
            s1 = jax.lax.ppermute(y1, axis_name, perm)
            y = jnp.concatenate([y0, y1], axis=0)
            nxt = jnp.concatenate([s0, s1], axis=0)
        else:
            y = stage_fn(stacked_params, cur)
            # rotate activations one hop forward along the ring
            nxt = jax.lax.ppermute(y, axis_name, perm)
        # last stage records its finished micro-batch (t - (p-1))
        out_idx = jnp.clip(t - (p - 1), 0, n_micro - 1)
        record = jnp.logical_and(stage == p - 1, t >= p - 1)
        outputs = jax.lax.cond(
            record,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, out_idx, 0),
            lambda o: o,
            outputs)
        return (nxt, outputs), None

    outputs0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
    state0 = jnp.zeros(mb_shape, x.dtype)
    (state, outputs), _ = jax.lax.scan(
        body, (state0, outputs0), jnp.arange(n_steps))
    return outputs


def spmd_pipeline_interleaved(stage_fn: Callable, chunked_params, x,
                              n_micro: int, n_chunks: int,
                              axis_name: str = "pp"):
    """Interleaved (virtual-stage) collective-permute pipeline, called
    INSIDE shard_map over the 'pp' axis — the compiled analog of the
    reference's VPP runtime (:1010) on the TPU ring.

    Each device owns `n_chunks` model chunks; virtual stage
    gv = c*P + stage. Per tick every device computes ALL its resident
    chunks (vmapped — in steady state all V are live, so this is exactly
    the useful work), then the stacked activations rotate one hop: chunk c
    on stage P-1 feeds chunk c+1 on stage 0, shrinking the bubble from
    (P-1)/(M+P-1) to (P-1)/(V*M+P-1) per wavefront hop.

    chunked_params : pytree with leading dim [n_chunks] on every leaf
                     (this stage's V chunks)
    x              : [n_micro, mb, ...] (consumed on stage 0)
    Returns [n_micro, mb, ...] outputs valid on the LAST stage.
    """
    p = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    v = n_chunks
    q = p * v
    n_steps = n_micro + q - 1
    mb_shape = x.shape[1:]

    vmapped = jax.vmap(stage_fn, in_axes=(0, 0))

    def body(carry, t):
        buf, outputs = carry                     # buf: [V, mb...]
        # stage 0 / chunk 0 injects micro t (clamped; inactive lanes are
        # discarded by the wavefront bookkeeping)
        feed = jnp.clip(t, 0, n_micro - 1)
        inject = jax.lax.dynamic_index_in_dim(x, feed, 0, keepdims=False)
        buf = jnp.where(stage == 0,
                        buf.at[0].set(inject), buf)
        ys = vmapped(chunked_params, buf)        # compute all V chunks
        # last vstage (stage P-1, chunk V-1) finishes micro t-(Q-1)
        out_idx = jnp.clip(t - (q - 1), 0, n_micro - 1)
        record = jnp.logical_and(stage == p - 1, t >= q - 1)
        outputs = jax.lax.cond(
            record,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, ys[v - 1], out_idx, 0),
            lambda o: o,
            outputs)
        # rotate: every chunk's output hops to the next device; on wrap
        # (P-1 -> 0) it also advances to the next chunk slot
        nxt = jax.lax.ppermute(
            ys, axis_name, [(i, (i + 1) % p) for i in range(p)])
        rolled = jnp.roll(nxt, 1, axis=0)        # chunk c -> slot c+1
        buf = jnp.where(stage == 0, rolled, nxt)
        return (buf, outputs), None

    outputs0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
    buf0 = jnp.zeros((v,) + mb_shape, x.dtype)
    (_, outputs), _ = jax.lax.scan(
        body, (buf0, outputs0), jnp.arange(n_steps))
    return outputs
