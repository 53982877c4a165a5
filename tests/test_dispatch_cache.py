"""Per-signature jit cache in the dispatch funnel (VERDICT r2 #1).

Reference analog: the reference keeps eager fast with an all-C++ hot path
(eager/auto_code_generator/generator/python_c_gen.py:111); here the eager
hot path is a cached jax.jit executable per (op fingerprint, treedef,
static args, avals) signature, with jax.vjp run inside the jitted function
on the autograd path.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.core import dispatch
from paddle_tpu.core.dispatch import apply


def _t(a, sg=True):
    t = paddle.to_tensor(a)
    t.stop_gradient = sg
    return t


@pytest.fixture(autouse=True)
def _fresh_cache():
    dispatch.clear_op_cache()
    yield
    dispatch.clear_op_cache()


def test_cached_matches_legacy_values():
    rng = np.random.RandomState(0)
    a, b = rng.randn(32, 32).astype(np.float32), \
        rng.randn(32, 32).astype(np.float32)
    outs = []
    with paddle.no_grad():
        for _ in range(4):      # warmup -> trace -> steady -> steady
            outs.append(paddle.matmul(_t(a), _t(b)).numpy())
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-6)
    stats = dispatch.op_cache_stats()
    assert stats["entries"] >= 1 and stats["ready"] >= 1


def test_closure_config_discriminates_entries():
    """Two inline closures with the same code but different closed-over
    config must not collide (the take(mode=...) class of bug)."""
    x = _t(np.ones((4, 4), np.float32))

    def call(k):
        def fn(a):
            return a * k

        return apply(fn, x, op_name="closure_scale")

    with paddle.no_grad():
        for _ in range(3):
            r2 = call(2.0).numpy()
            r3 = call(3.0).numpy()
    np.testing.assert_allclose(r2, 2.0)
    np.testing.assert_allclose(r3, 3.0)


def test_static_scalar_args_discriminate():
    x = _t(np.ones((4,), np.float32))
    with paddle.no_grad():
        for _ in range(3):
            np.testing.assert_allclose((x * 2).numpy(), 2.0)
            np.testing.assert_allclose((x * 2.5).numpy(), 2.5)
            np.testing.assert_allclose((x * 2.0).numpy(), 2.0)


def test_rng_threaded_not_frozen():
    """Cached RNG-consuming ops must draw fresh randomness per call."""
    x = _t(np.ones((64, 64), np.float32))
    with paddle.no_grad():
        outs = [F.dropout(x, 0.5, training=True).numpy()
                for _ in range(5)]
    for i in range(4):
        assert np.abs(outs[i] - outs[i + 1]).max() > 0, \
            "dropout mask frozen by the jit cache"


def test_rng_reproducible_after_seed():
    x = _t(np.ones((32, 32), np.float32))
    with paddle.no_grad():
        paddle.seed(7)
        first = [F.dropout(x, 0.5, training=True).numpy()
                 for _ in range(3)]
        paddle.seed(7)
        second = [F.dropout(x, 0.5, training=True).numpy()
                  for _ in range(3)]
    # calls at the same post-seed position with the same cache state
    # (>=2nd call is cached in both sequences) must agree exactly
    np.testing.assert_array_equal(first[1], second[1])
    np.testing.assert_array_equal(first[2], second[2])


def test_grad_through_cache_matches_uncached():
    rng = np.random.RandomState(1)
    a = rng.randn(16, 16).astype(np.float32)
    b = rng.randn(16, 16).astype(np.float32)

    def grads():
        x, y = _t(a, sg=False), _t(b, sg=False)
        z = (paddle.matmul(x, y) + x).sum()
        z.backward()
        return x.grad.numpy(), y.grad.numpy()

    dispatch.set_op_cache_enabled(False)
    try:
        gx_ref, gy_ref = grads()
    finally:
        dispatch.set_op_cache_enabled(True)
    for _ in range(3):      # warmup, trace, steady
        gx, gy = grads()
        np.testing.assert_allclose(gx, gx_ref, atol=1e-5)
        np.testing.assert_allclose(gy, gy_ref, atol=1e-5)


def test_stop_gradient_pattern_switches_entry():
    rng = np.random.RandomState(2)
    a = rng.randn(8, 8).astype(np.float32)
    b = rng.randn(8, 8).astype(np.float32)
    for _ in range(3):
        x, y = _t(a, sg=False), _t(b, sg=True)
        z = paddle.matmul(x, y).sum()
        z.backward()
        np.testing.assert_allclose(x.grad.numpy(), b.sum(1)[None, :]
                                   + np.zeros_like(a), atol=1e-5)
        assert y.grad is None
    for _ in range(3):
        x, y = _t(a, sg=True), _t(b, sg=False)
        z = paddle.matmul(x, y).sum()
        z.backward()
        assert y.grad is not None and x.grad is None


def test_host_validation_op_bails_to_legacy():
    """An op that inspects concrete values raises at trace time; the cache
    must disable itself and keep returning correct eager results."""
    def fn(a):
        if float(a.sum()) > 1e9:      # host-side check: traces would fail
            raise ValueError("too big")
        return a + 1

    x = _t(np.ones((4,), np.float32))
    with paddle.no_grad():
        for _ in range(4):
            np.testing.assert_allclose(apply(fn, x, op_name="hosty").numpy(),
                                       2.0)
    st = dispatch.op_cache_stats()
    assert st["disabled"] >= 1


def test_cacheable_false_skips_cache():
    x = _t(np.arange(6.0, dtype=np.float32))
    with paddle.no_grad():
        # warm with valid indices first: if take were cached, the OOB
        # host check below would be silently skipped by the trace
        for _ in range(3):
            paddle.take(x, _t(np.array([0, 5, -1])))
        for _ in range(3):
            with pytest.raises(IndexError):
                paddle.take(x, _t(np.array([0, 6])))
        with pytest.raises(ValueError):
            paddle.masked_scatter(
                _t(np.zeros((4,), np.float32)),
                _t(np.array([True, True, True, False])),
                _t(np.array([1.0], np.float32)))


def test_double_backward_through_cached_ops():
    a = np.array([2.0, 3.0], np.float32)
    entries = []
    for _ in range(4):
        x = _t(a, sg=False)
        y = (x * x * x).sum()
        (g,) = paddle.grad(y, x, create_graph=True)
        (gg,) = paddle.grad(g.sum(), x)
        np.testing.assert_allclose(g.numpy(), 3 * a ** 2, atol=1e-5)
        np.testing.assert_allclose(gg.numpy(), 6 * a, atol=1e-5)
        entries.append(dispatch.op_cache_stats()["entries"])
    # the second graph rides the per-signature cache: once the signature
    # is steady a further step adds no entry (no retrace)
    assert entries[-1] == entries[-2]


def test_amp_autocast_composes_with_cache():
    rng = np.random.RandomState(3)
    a = rng.randn(16, 16).astype(np.float32)
    with paddle.no_grad():
        for _ in range(3):
            with paddle.amp.auto_cast(True, level="O1", dtype="bfloat16"):
                out = paddle.matmul(_t(a), _t(a))
            assert out.numpy().dtype == np.dtype("float32") or \
                str(out.dtype) in ("paddle.bfloat16", "bfloat16")


def test_tensor_list_args_cached():
    """Ops taking lists of tensors (concat/stack) flow through the cache."""
    xs = [_t(np.full((2, 2), float(i), np.float32)) for i in range(3)]
    with paddle.no_grad():
        for _ in range(3):
            out = paddle.concat(xs, axis=0).numpy()
    assert out.shape == (6, 2)
    np.testing.assert_allclose(out[4], 2.0)


def test_rng_guard_respected_by_cache():
    """rng_guard determinism contract: with a warm cache entry, draws
    must still derive from the guard key, not the global state."""
    from paddle_tpu.framework.random import rng_guard, get_rng_state

    x = _t(np.ones((32, 32), np.float32))
    with paddle.no_grad():
        for _ in range(3):                      # warm the entry
            F.dropout(x, 0.5, training=True)
        st0 = get_rng_state()
        with rng_guard(123):
            a = F.dropout(x, 0.5, training=True).numpy()
        with rng_guard(123):
            b = F.dropout(x, 0.5, training=True).numpy()
        st1 = get_rng_state()
    np.testing.assert_array_equal(a, b)          # same guard -> same mask
    assert st0[1] == st1[1], "guard draws advanced the global counter"
    with paddle.no_grad():
        with rng_guard(124):
            c = F.dropout(x, 0.5, training=True).numpy()
    assert np.abs(a - c).max() > 0               # different guard differs


def test_callable_static_arg_cached_correctly():
    """A plain-function argument is static key material but must be
    passed through to the traced call as itself, not its fingerprint."""
    import jax.numpy as jnp

    def op(a, act):
        return act(a) + 1.0

    x = _t(np.full((4,), 4.0, np.float32))
    with paddle.no_grad():
        for _ in range(4):
            r = apply(op, x, jnp.sqrt, op_name="apply_act").numpy()
            np.testing.assert_allclose(r, 3.0)
            r2 = apply(op, x, jnp.square, op_name="apply_act").numpy()
            np.testing.assert_allclose(r2, 17.0)
    st = dispatch.op_cache_stats()
    assert st["disabled"] == 0, "callable arg disabled the entry"
    # a numpy ufunc can't trace: the entry must bail to legacy but stay
    # CORRECT (this is the fingerprint-substitution regression shape)
    with paddle.no_grad():
        for _ in range(4):
            r = apply(op, x, np.sqrt, op_name="apply_act_np").numpy()
            np.testing.assert_allclose(r, 3.0)


def test_seed_reproducible_across_cache_states():
    """The i-th post-seed RNG draw must be identical whether the op's
    cache entry is cold (probe run) or warm (cached executable)."""
    x = _t(np.ones((32, 32), np.float32))
    with paddle.no_grad():
        paddle.seed(7)
        cold = [F.dropout(x, 0.5, training=True).numpy()
                for _ in range(3)]          # call 0 = probe, 1 = trace, 2+
        paddle.seed(7)
        warm = [F.dropout(x, 0.5, training=True).numpy()
                for _ in range(3)]          # all warm
    for i in range(3):
        np.testing.assert_array_equal(cold[i], warm[i])
    # and non-RNG probe calls must not perturb the stream
    dispatch.clear_op_cache()
    with paddle.no_grad():
        paddle.seed(9)
        _ = paddle.matmul(x, x)             # cold probe, draws nothing
        a = F.dropout(x, 0.5, training=True).numpy()
        paddle.seed(9)
        _ = paddle.matmul(x, x)             # warm, draws nothing
        b = F.dropout(x, 0.5, training=True).numpy()
    np.testing.assert_array_equal(a, b)


def test_rng_op_during_other_ops_probe_keeps_fast_path():
    """ADVICE r3: a cached RNG op invoked while another op's deferred
    probe guard is active must materialize the guard (as next_key does)
    instead of feeding the sentinel to fold_in and burning its cache
    entry."""
    dispatch.clear_op_cache()
    x = _t(np.ones((16, 16), np.float32))
    with paddle.no_grad():
        paddle.seed(3)
        # warm dropout to the cached state (probe, trace, steady)
        for _ in range(3):
            F.dropout(x, 0.5, training=True)

        def outer(a):
            # runs under the OUTER op's deferred probe guard; the inner
            # dropout dispatch is a nested eager call only on the probe
            # run (host-side), exercising _next_rng_inputs under guard
            return a * 2.0

        from paddle_tpu.core.dispatch import apply

        # probe an op while issuing a cached RNG op between dispatches
        from paddle_tpu.framework import random as rnd
        with rnd.deferred_rng_guard():
            out = F.dropout(x, 0.5, training=True)  # cached RNG op
        assert out.shape == x.shape
    # the dropout entry must not be disabled
    stats = dispatch.op_cache_stats()
    assert stats["disabled"] == 0, stats


def test_transient_cache_failure_retries_before_disable():
    """ADVICE r3: a transient cached-executable failure falls back to
    legacy for that call but re-enables the fast path; only repeated
    failures pin the signature to the slow path."""
    dispatch.clear_op_cache()
    x = _t(np.ones((4, 4), np.float32))
    with paddle.no_grad():
        r = None
        for _ in range(3):
            r = paddle.matmul(x, x)
    key, entry = next(iter(dispatch._op_cache.items()))
    assert entry.fwd is not None

    class Boom:
        def __call__(self, *a, **k):
            raise RuntimeError("transient device flake")

    entry.fwd = Boom()                      # simulate a transient failure
    with paddle.no_grad():
        out = paddle.matmul(x, x)           # legacy fallback, no raise
    np.testing.assert_allclose(out.numpy(), r.numpy())
    assert not entry.disabled and entry.fails == 1
    with paddle.no_grad():
        paddle.matmul(x, x)                 # rebuilds fwd, succeeds
    assert entry.fwd is not None and not isinstance(entry.fwd, Boom)
    # three failures pin it
    import warnings as _w

    entry.fails = 2
    entry.fwd = Boom()
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        with paddle.no_grad():
            paddle.matmul(x, x)
    assert entry.disabled and entry.fails == 3
    assert any("legacy eager path" in str(w.message) for w in rec)
