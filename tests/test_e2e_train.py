"""End-to-end slices (SURVEY.md §7 step 3: the MNIST smoke) — eager loop,
compiled TrainStep, and eager/compiled parity."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.vision.models import LeNet


def _batch():
    rng = np.random.RandomState(0)
    x = rng.rand(8, 1, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, (8,)).astype(np.int64)
    return paddle.to_tensor(x), paddle.to_tensor(y)


def test_lenet_overfits_eager():
    paddle.seed(42)
    model = LeNet()
    opt = optimizer.Adam(parameters=model.parameters(), learning_rate=1e-3)
    loss_fn = nn.CrossEntropyLoss()
    x, y = _batch()
    first = None
    for _ in range(60):
        loss = loss_fn(model(x), y)
        if first is None:
            first = float(loss.numpy())
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert float(loss.numpy()) < 0.2 < first


def test_trainstep_matches_eager():
    x, y = _batch()
    loss_fn = nn.CrossEntropyLoss()

    paddle.seed(42)
    m1 = LeNet()
    o1 = optimizer.Adam(parameters=m1.parameters(), learning_rate=1e-3)
    eager_losses = []
    for _ in range(5):
        loss = loss_fn(m1(x), y)
        eager_losses.append(float(loss.numpy()))
        loss.backward()
        o1.step()
        o1.clear_grad()

    paddle.seed(42)
    m2 = LeNet()
    o2 = optimizer.Adam(parameters=m2.parameters(), learning_rate=1e-3)
    step = TrainStep(m2, loss_fn, o2)
    jit_losses = [float(step(x, y).numpy()) for _ in range(5)]

    assert np.allclose(eager_losses, jit_losses, rtol=1e-4), \
        (eager_losses, jit_losses)


def test_trainstep_mlp_with_dropout_runs():
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 64), nn.ReLU(), nn.Dropout(0.5),
                          nn.Linear(64, 4))
    opt = optimizer.AdamW(parameters=model.parameters(), learning_rate=1e-3)
    loss_fn = nn.CrossEntropyLoss()
    step = TrainStep(model, loss_fn, opt)
    x = paddle.to_tensor(np.random.rand(8, 16).astype(np.float32))
    y = paddle.to_tensor(np.random.randint(0, 4, (8,)).astype(np.int64))
    l1 = float(step(x, y).numpy())
    l2 = float(step(x, y).numpy())
    assert np.isfinite(l1) and np.isfinite(l2)
    # dropout key must differ between steps: losses differ even with the
    # same batch (and both finite)
    assert l1 != l2


def test_batchnorm_buffers_update_under_jit():
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.BatchNorm1D(8),
                          nn.Linear(8, 2))
    opt = optimizer.SGD(learning_rate=0.01, parameters=model.parameters())
    loss_fn = nn.MSELoss()
    step = TrainStep(model, loss_fn, opt)
    bn = model[1]
    before = bn._mean.numpy().copy()
    x = paddle.to_tensor(np.random.rand(16, 4).astype(np.float32) + 3)
    y = paddle.to_tensor(np.random.rand(16, 2).astype(np.float32))
    step(x, y)
    after = bn._mean.numpy()
    assert not np.allclose(before, after)


def test_recompute_matches_plain():
    from paddle_tpu.distributed.fleet import recompute

    paddle.seed(1)
    lin1 = nn.Linear(8, 8)
    lin2 = nn.Linear(8, 8)

    def block(x):
        return lin2(paddle.tanh(lin1(x)))

    x1 = paddle.to_tensor(
        np.random.RandomState(1).rand(4, 8).astype(np.float32),
        stop_gradient=False)
    out = recompute(block, x1)
    out.sum().backward()
    g_re = x1.grad.numpy().copy()
    w_re = lin1.weight.grad.numpy().copy()

    x2 = paddle.to_tensor(x1.numpy(), stop_gradient=False)
    lin1.clear_gradients()
    block(x2).sum().backward()
    assert np.allclose(g_re, x2.grad.numpy(), rtol=1e-5)
    assert np.allclose(w_re, lin1.weight.grad.numpy(), rtol=1e-5)
