"""Collective–compute overlap: sharding-stage-3 param prefetch with
reduce-scatter backward, latency-hidden pipeline sends, and the
``comm/overlap_ms`` accounting.

The load-bearing contract is PARITY: the overlapped paths must match
the non-overlapped paths bitwise (same per-layer ops, only issuance
order changes), so enabling overlap can never change training
numerics — the win is wall-clock only and is priced into metrics.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from jax import shard_map


def _mesh(shape, names):
    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), names)


def _stage3_fns(mesh, L, d):
    from paddle_tpu.distributed.meta_parallel.sharding_optimizer import (
        stage3_forward)

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    def build(overlap):
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(tuple(P("sharding", None) for _ in range(L)), P()),
            out_specs=P(), check_vma=False)
        def f(shards, xs):
            return stage3_forward(stage_fn, shards, xs,
                                  axis_name="sharding", overlap=overlap)

        return jax.jit(f)

    return build(True), build(False)


def test_stage3_prefetch_matches_sequential_bitwise():
    mesh = _mesh((4,), ("sharding",))
    rng = np.random.RandomState(0)
    L, d = 4, 16
    ws = tuple(rng.randn(d, d).astype(np.float32) * 0.3
               for _ in range(L))
    x = rng.randn(8, d).astype(np.float32)
    f_ovl, f_seq = _stage3_fns(mesh, L, d)

    out_o = np.asarray(f_ovl(ws, x))
    out_s = np.asarray(f_seq(ws, x))
    assert (out_o == out_s).all()          # bitwise: same ops per layer
    ref = x
    for w in ws:
        ref = np.tanh(ref @ w)
    np.testing.assert_allclose(out_o, ref, atol=1e-5)


def test_stage3_backward_reduce_scatter_grad_parity():
    """Grads THROUGH the prefetch path (all-gather fwd, reduce-scatter
    bwd via the custom VJP) match the sequential path bitwise — the
    grad-reduce-scatter-overlapped-with-backward contract."""
    mesh = _mesh((4,), ("sharding",))
    rng = np.random.RandomState(1)
    L, d = 3, 16
    ws = tuple(rng.randn(d, d).astype(np.float32) * 0.3
               for _ in range(L))
    x = rng.randn(8, d).astype(np.float32)
    f_ovl, f_seq = _stage3_fns(mesh, L, d)

    g_o = jax.grad(lambda sh, xs: jnp.sum(f_ovl(sh, xs) ** 2))(ws, x)
    g_s = jax.grad(lambda sh, xs: jnp.sum(f_seq(sh, xs) ** 2))(ws, x)
    for a, b in zip(jax.tree.leaves(g_o), jax.tree.leaves(g_s)):
        assert (np.asarray(a) == np.asarray(b)).all()

    # and the gather backward really is a reduce-scatter: the sum of
    # the sharded grads equals the dense reference grad
    def dense(ws_, xs):
        h = xs
        for w in ws_:
            h = jnp.tanh(h @ w)
        return jnp.sum(h ** 2)

    g_ref = jax.grad(dense)(ws, x)
    for a, b in zip(jax.tree.leaves(g_o), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_measure_overlap_win_records_comm_overlap_ms():
    from paddle_tpu.distributed.meta_parallel.sharding_optimizer import (
        measure_overlap_win)
    from paddle_tpu.profiler import metrics

    mesh = _mesh((2,), ("sharding",))
    rng = np.random.RandomState(2)
    ws = tuple(rng.randn(8, 8).astype(np.float32) for _ in range(2))
    x = rng.randn(4, 8).astype(np.float32)
    f_ovl, f_seq = _stage3_fns(mesh, 2, 8)

    before = metrics.registry().histogram("comm/overlap_ms").count
    saved_ms, t_ovl, t_seq = measure_overlap_win(f_ovl, f_seq, ws, x)
    assert saved_ms >= 0.0 and t_ovl > 0 and t_seq > 0
    assert metrics.registry().histogram("comm/overlap_ms").count \
        == before + 1


@pytest.mark.parametrize("mb", [4, 2])
def test_spmd_pipeline_overlap_sends_bitwise_parity(mb):
    """The split schedule sends the same rows through the same products.
    Halves of two rows (mb=4) agree bit for bit; a half of ONE row (mb=2)
    may meet another product in the backend (matrix-vector for
    matrix-matrix on the CPU) and then agrees to the last place only."""
    from paddle_tpu.distributed.meta_parallel import spmd_pipeline

    mesh = _mesh((4,), ("pp",))
    n_micro, d = 8, 16
    rng = np.random.RandomState(0)
    ws = rng.rand(4, d, d).astype(np.float32) * 0.5
    x = rng.rand(n_micro, mb, d).astype(np.float32)

    def run(overlap):
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("pp", None, None), P(None)),
            out_specs=P(None), check_vma=False)
        def f(w_stage, xs):
            def stage_fn(w, h):
                return h @ w[0]

            out = spmd_pipeline(stage_fn, w_stage, xs, n_micro,
                                axis_name="pp", overlap_sends=overlap)
            stage = jax.lax.axis_index("pp")
            return jax.lax.psum(jnp.where(stage == 3, out, 0.0), "pp")

        return np.asarray(f(ws, x))

    out_o, out_s = run(True), run(False)
    if mb >= 4:
        assert (out_o == out_s).all()
    else:
        np.testing.assert_allclose(out_o, out_s, rtol=1e-6, atol=0)
    ref = x
    for i in range(4):
        ref = ref @ ws[i]
    np.testing.assert_allclose(out_o, ref, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_llama_pipelined_loss_and_grads_with_overlap_sends():
    """The flagship wiring: loss_fn_pipelined(overlap_sends=True) must
    reproduce the non-overlapped pipeline's loss AND grads.  (slow: two
    pipelined value_and_grad compiles over the 8-device sim mesh; the
    in-budget parity evidence is the bitwise spmd_pipeline +
    stage3_forward tests above.)"""
    from paddle_tpu.models import llama

    mesh = _mesh((2, 2, 1, 1, 2), ("dp", "pp", "sharding", "sep", "mp"))
    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=2,
        num_key_value_heads=2, max_position_embeddings=64,
        dtype="float32")
    params = llama.init_stacked_params(cfg, jax.random.key(0))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    idm = ids.reshape(4, -1, ids.shape[1])
    labm = labels.reshape(4, -1, labels.shape[1])

    l0, g0 = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn_pipelined(p, (idm, labm), cfg, mesh,
                                          remat=False)))(params)
    l1, g1 = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn_pipelined(
            p, (idm, labm), cfg, mesh, remat=False,
            overlap_sends=True)))(params)
    assert abs(float(l0) - float(l1)) < 1e-6
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_spmd_pipeline_odd_microbatch_falls_back():
    """mb=1 cannot half-split: overlap_sends must silently use the
    unsplit schedule, not mis-shape."""
    from paddle_tpu.distributed.meta_parallel import spmd_pipeline

    mesh = _mesh((2,), ("pp",))
    n_micro, mb, d = 4, 1, 8
    rng = np.random.RandomState(3)
    ws = rng.rand(2, d, d).astype(np.float32) * 0.5
    x = rng.rand(n_micro, mb, d).astype(np.float32)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P("pp", None, None), P(None)),
        out_specs=P(None), check_vma=False)
    def f(w_stage, xs):
        def stage_fn(w, h):
            return h @ w[0]

        out = spmd_pipeline(stage_fn, w_stage, xs, n_micro,
                            axis_name="pp", overlap_sends=True)
        stage = jax.lax.axis_index("pp")
        return jax.lax.psum(jnp.where(stage == 1, out, 0.0), "pp")

    out = np.asarray(f(ws, x))
    ref = x @ ws[0] @ ws[1]
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_1f1b_executor_records_handoff_overlap_windows():
    """The eager 1F1B executor accounts each cross-stage activation
    hand-off's latency-hidden window into comm/overlap_ms."""
    from paddle_tpu import nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.meta_parallel.pipeline_parallel import (
        PipelineParallelWithInterleave)
    from paddle_tpu.distributed.meta_parallel.pp_layers import (
        PipelineLayer)
    from paddle_tpu.profiler import metrics

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
        "pp_configs": {"accumulate_steps": 4}}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()

    paddle.seed(0)
    layers = []
    for _ in range(8):
        layers.append(nn.Linear(12, 12))
        layers.append(nn.Tanh())
    model = PipelineLayer(layers, num_stages=2, loss_fn=nn.MSELoss())
    eng = PipelineParallelWithInterleave(
        model, hcg, strategy=strategy, num_virtual_pipeline_stages=2)

    before = metrics.registry().histogram("comm/overlap_ms").count
    rng = np.random.RandomState(7)
    x = paddle.to_tensor(rng.randn(8, 12).astype(np.float32))
    y = paddle.to_tensor(rng.randn(8, 12).astype(np.float32))
    eng.forward_backward_pipeline((x, y))
    after = metrics.registry().histogram("comm/overlap_ms").count
    # 4 micros x (q-1 = 3) hand-offs between virtual stages
    assert after - before == 12
