"""Pipeline schedules: generator properties (bubble formulas via clock
simulation), interleaved/zero-bubble eager engines matching plain 1F1B
numerics, compiled interleaved ring pipeline vs sequential reference.

Reference analogs: fleet/meta_parallel/pipeline_parallel.py:459,1010 and
distributed/passes/pipeline_scheduler_pass/pipeline_zero_bubble.py."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.meta_parallel import pipeline_schedules as psched


def _counts(sched):
    out = {}
    for k, _, _ in sched:
        out[k] = out.get(k, 0) + 1
    return out


def test_1f1b_matches_textbook_makespan():
    p, m = 4, 8
    scheds = [psched.gen_1f1b(s, p, m) for s in range(p)]
    for s in range(p):
        assert _counts(scheds[s]) == {"F": m, "B": m}
    mk = psched.simulate(scheds, p, m)
    assert mk == 2 * (m + p - 1)          # (m + p-1) wavefront, F=B=1
    assert abs(psched.bubble_ratio(mk, p, m)
               - (2 * (p - 1)) / mk) < 1e-9


def test_fthenb_validates_and_is_worse():
    p, m = 4, 8
    f = [psched.gen_fthenb(s, p, m) for s in range(p)]
    o = [psched.gen_1f1b(s, p, m) for s in range(p)]
    assert psched.simulate(f, p, m) >= psched.simulate(o, p, m)


def test_interleaved_cuts_bubble():
    p, m, v = 4, 8, 2
    sv = [psched.gen_interleave_1f1b(s, p, m, v) for s in range(p)]
    for s in range(p):
        assert _counts(sv[s]) == {"F": m * v, "B": m * v}
    mkv = psched.simulate(sv, p, m, v)
    mk1 = psched.simulate([psched.gen_1f1b(s, p, m) for s in range(p)], p, m)
    # per-chunk work doubles but bubble per unit work shrinks
    assert psched.bubble_ratio(mkv, p, m, v) \
        < psched.bubble_ratio(mk1, p, m, 1)
    with pytest.raises(ValueError):
        psched.gen_interleave_1f1b(0, 4, 6, 2)     # m % p != 0


def test_zero_bubble_h1_properties():
    p, m = 4, 8
    sz = [psched.gen_zero_bubble_h1(s, p, m) for s in range(p)]
    for s in range(p):
        assert _counts(sz[s]) == {"F": m, "B": m, "W": m}
        # every W follows its own B
        b_seen = set()
        for k, mi, _ in sz[s]:
            if k == "B":
                b_seen.add(mi)
            if k == "W":
                assert mi in b_seen
    mkz = psched.simulate(sz, p, m)
    # 1F1B with W fused costs one extra tick per micro per stage
    mk1 = psched.simulate(
        [psched.gen_1f1b(s, p, m) for s in range(p)], p, m) + m
    assert mkz < mk1                       # W fills the drain bubble


def _seq_model(n_layers=8, width=12, seed=0):
    paddle.seed(seed)
    layers = []
    for i in range(n_layers):
        layers.append(nn.Linear(width, width))
        layers.append(nn.Tanh())
    return layers


def _run_engine(engine_cls, strategy_extras=None, **engine_kw):
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.meta_parallel.pp_layers import PipelineLayer

    strategy = fleet.DistributedStrategy()
    cfg = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
           "pp_configs": {"accumulate_steps": 4}}
    cfg["pp_configs"].update(strategy_extras or {})
    strategy.hybrid_configs = cfg
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()

    model = PipelineLayer(_seq_model(), num_stages=2,
                          loss_fn=nn.MSELoss())
    eng = engine_cls(model, hcg, strategy=strategy, **engine_kw)
    rng = np.random.RandomState(7)
    x = paddle.to_tensor(rng.randn(8, 12).astype(np.float32))
    y = paddle.to_tensor(rng.randn(8, 12).astype(np.float32))
    loss = eng.forward_backward_pipeline((x, y))
    grads = {n: np.asarray(p.grad._value)
             for n, p in model.named_parameters() if p.grad is not None}
    for p in model.parameters():
        p.clear_grad()
    return float(np.asarray(loss._value)), grads


def test_interleave_and_zero_bubble_match_1f1b_numerics():
    from paddle_tpu.distributed.meta_parallel.pipeline_parallel import (
        PipelineParallel, PipelineParallelWithInterleave,
        PipelineParallelZeroBubble)

    base_loss, base_g = _run_engine(PipelineParallel)
    il_loss, il_g = _run_engine(PipelineParallelWithInterleave,
                                num_virtual_pipeline_stages=2)
    zb_loss, zb_g = _run_engine(PipelineParallelZeroBubble)
    assert abs(il_loss - base_loss) < 1e-5
    assert abs(zb_loss - base_loss) < 1e-5
    assert set(base_g) == set(il_g) == set(zb_g)
    for k in base_g:
        np.testing.assert_allclose(il_g[k], base_g[k], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(zb_g[k], base_g[k], rtol=1e-5, atol=1e-6)


def test_fleet_dispatches_schedule_mode():
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.meta_parallel.pipeline_parallel import (
        PipelineParallelWithInterleave, PipelineParallelZeroBubble)
    from paddle_tpu.distributed.meta_parallel.pp_layers import PipelineLayer

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
        "pp_configs": {"accumulate_steps": 4, "schedule_mode": "ZBH1"}}
    fleet.init(is_collective=True, strategy=strategy)
    model = PipelineLayer(_seq_model(), num_stages=2, loss_fn=nn.MSELoss())
    assert isinstance(fleet.distributed_model(model),
                      PipelineParallelZeroBubble)

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
        "pp_configs": {"accumulate_steps": 4}}
    fleet.init(is_collective=True, strategy=strategy)
    model = PipelineLayer(_seq_model(), num_stages=2, loss_fn=nn.MSELoss(),
                          num_virtual_pipeline_stages=2)
    assert isinstance(fleet.distributed_model(model),
                      PipelineParallelWithInterleave)


def test_spmd_pipeline_interleaved_matches_sequential():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed.meta_parallel.pipeline_parallel import (
        spmd_pipeline_interleaved)

    pp, v, n_micro, mb, d = 4, 2, 8, 2, 16
    q = pp * v
    rng = np.random.RandomState(0)
    # per-vstage weights, laid out [pp, v, d, d]: w[s, c] is vstage c*pp+s
    w = rng.randn(pp, v, d, d).astype(np.float32) / np.sqrt(d)
    x = rng.randn(n_micro, mb, d).astype(np.float32)

    def stage_fn(wc, h):
        return jnp.tanh(h @ wc)

    # sequential reference through all Q vstages in order
    ref = x.copy()
    out_ref = []
    for m in range(n_micro):
        h = x[m]
        for gv in range(q):
            s, c = gv % pp, gv // pp
            h = np.tanh(h @ w[s, c])
        out_ref.append(h)
    out_ref = np.stack(out_ref)

    mesh = Mesh(np.array(jax.devices()[:pp]), ("pp",))

    def run(wv, xv):
        out = spmd_pipeline_interleaved(
            stage_fn, wv[0], xv, n_micro, v, axis_name="pp")
        # outputs are valid on the last stage only; broadcast to all
        mask = (jax.lax.axis_index("pp") == pp - 1).astype(out.dtype)
        return jax.lax.psum(out * mask, "pp")

    fn = jax.shard_map(
        run, mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
        check_vma=False)
    out = jax.jit(fn)(jnp.asarray(w), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), out_ref,
                               rtol=2e-5, atol=2e-5)


def test_zero_bubble_with_grad_scaler_matches_unscaled():
    """Regression: engines must scale the loss when a GradScaler is passed
    (scaler.step unscales), so the update trajectory matches no-scaler."""
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.meta_parallel.pipeline_parallel import (
        PipelineParallelZeroBubble)
    from paddle_tpu.distributed.meta_parallel.pp_layers import PipelineLayer

    def train(use_scaler):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 1, "mp_degree": 1, "pp_degree": 2,
            "pp_configs": {"accumulate_steps": 4}}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        model = PipelineLayer(_seq_model(), num_stages=2,
                              loss_fn=nn.MSELoss())
        eng = PipelineParallelZeroBubble(model, hcg, strategy=strategy)
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=model.parameters())
        scaler = amp.GradScaler(init_loss_scaling=1024.0) \
            if use_scaler else None
        rng = np.random.RandomState(5)
        x = paddle.to_tensor(rng.randn(8, 12).astype(np.float32))
        y = paddle.to_tensor(rng.randn(8, 12).astype(np.float32))
        losses = [float(np.asarray(
            eng.train_batch((x, y), opt, scaler=scaler)._value))
            for _ in range(3)]
        return losses

    np.testing.assert_allclose(train(True), train(False),
                               rtol=1e-4, atol=1e-5)


def test_zero_bubble_w_instructions_do_real_pullbacks(monkeypatch):
    """The ZB split must run the input-grad pullback at B (graph retained)
    and the weight-grad pullback at W — not one fused grad call at B with
    deferred application."""
    from paddle_tpu.core import autograd as ag
    from paddle_tpu.distributed.meta_parallel.pipeline_parallel import (
        PipelineParallelZeroBubble)

    calls = []
    real_grad = ag.grad

    def spy(outputs, inputs, *a, **kw):
        ins = inputs if isinstance(inputs, list) else [inputs]
        calls.append(len(ins))
        return real_grad(outputs, ins, *a, **kw)

    monkeypatch.setattr(ag, "grad", spy)
    loss, grads = _run_engine(PipelineParallelZeroBubble)
    assert grads, "no grads produced"
    # B pullbacks see exactly 1 input (x_in); W pullbacks see the chunk's
    # params (>1). Both kinds must be present, in equal numbers.
    b_calls = [c for c in calls if c == 1]
    w_calls = [c for c in calls if c > 1]
    assert b_calls and w_calls and len(b_calls) == len(w_calls), \
        (len(b_calls), len(w_calls))
