"""Per-op microbenchmark (reference analog: tools/ci_op_benchmark.sh —
a relative regression gate over op kernels).

Times a representative set of registered ops under jit on the attached
device and writes JSON: {"device": ..., "ops": {name: sec_per_call}}.
Compare two runs with tools/check_op_bench.py.

Usage: python tools/op_bench.py [out.json]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _bench(fn, *args, chain=50, repeats=5):
    """Time `fn` with the op CHAINED inside one compiled scan — a single
    dispatch per measurement, so device compute dominates instead of the
    host's per-dispatch latency (which would swamp ~µs ops and make the
    regression gate pure noise). Returns min over repeats."""
    def chained(*a):
        def body(carry, _):
            # thread the carry into the first float operand so the op is
            # loop-VARIANT — otherwise XLA CSE-hoists it and the scan
            # times an empty loop
            a2 = list(a)
            for i, arr in enumerate(a2):
                if jnp.issubdtype(arr.dtype, jnp.floating):
                    a2[i] = arr + carry.astype(arr.dtype)
                    break
            out = fn(*a2)
            leaf = jax.tree_util.tree_leaves(out)[0]
            return (carry + jnp.sum(leaf).astype(jnp.float32) * 1e-30,
                    None)

        total, _ = jax.lax.scan(body, jnp.float32(0), None, length=chain)
        return total

    jitted = jax.jit(chained)
    # the timing ends when the result is on the host
    jax.device_get(jitted(*args))           # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.device_get(jitted(*args))
        best = min(best, (time.perf_counter() - t0) / chain)
    return best


def main():
    import paddle_tpu  # noqa: F401  (registers ops)
    from paddle_tpu.ops import registry

    rng = np.random.RandomState(0)
    m = jnp.asarray(rng.randn(1024, 1024).astype(np.float32))
    v = jnp.asarray(rng.randn(1024, 4096).astype(np.float32))
    x4 = jnp.asarray(rng.randn(8, 64, 56, 56).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, 1000, (64, 512)))

    cases = {
        "matmul": (lambda a, b: a @ b, m, v),
        "softmax": (lambda a: jax.nn.softmax(a, -1), v),
        "layer_norm": (lambda a: (a - a.mean(-1, keepdims=True))
                       / (a.std(-1, keepdims=True) + 1e-5), v),
        "gelu": (jax.nn.gelu, v),
        "reduce_sum": (lambda a: a.sum(), v),
        "transpose": (lambda a: a.T, m),
        "embedding_gather": (lambda t, i: t[i], m, ids),
        "conv_relu": (lambda a: jax.nn.relu(
            jax.lax.conv_general_dilated(
                a, jnp.ones((64, 64, 3, 3), jnp.float32) * 0.01,
                (1, 1), "SAME",
                dimension_numbers=("NCHW", "OIHW", "NCHW"))), x4),
    }
    # a sample of registry kernels exercised through the yaml surface
    reg_cases = {
        "p_norm": ((2.0, -1), v),
        "clip_by_norm": ((1.0,), v),
        "frobenius_norm": ((), m),
    }
    results = {}
    for name, (fn, *args) in cases.items():
        results[name] = _bench(fn, *args)
    for name, (extra, arr) in reg_cases.items():
        info = registry.get(name)
        if info is not None:
            results[f"op:{name}"] = _bench(
                lambda a, _f=info.fn, _e=extra: _f(a, *_e), arr)
    results.update(_bench_eager_dispatch())

    out = {"device": str(jax.devices()[0]),
           "backend": jax.default_backend(),
           "ops": {k: round(v, 6) for k, v in results.items()}}
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    if any(a in ("-h", "--help") for a in sys.argv[1:]):
        print(__doc__)
        sys.exit(0)
    path = args[0] if args else "op_bench.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


def _bench_eager_dispatch():
    """Steady-state eager dispatch through the per-signature jit cache:
    grad-recorded matmul(1024²)+add, forward and forward+backward."""
    import paddle_tpu as paddle

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(1024, 1024).astype(np.float32))
    y = paddle.to_tensor(rng.randn(1024, 1024).astype(np.float32))
    x.stop_gradient = False

    def fwd():
        return (paddle.matmul(x, y) + x)._value

    def fwdbwd():
        z = (paddle.matmul(x, y) + x).sum()
        z.backward()
        g = x.grad._value
        x.clear_grad()
        return g

    out = {}
    for name, f in (("eager:matmul_add_fwd", fwd),
                    ("eager:matmul_add_fwd_bwd", fwdbwd)):
        for _ in range(6):
            jax.device_get(f())          # legacy + trace + steady warmup
        n = 50
        best = float("inf")
        for _ in range(3):
            jax.device_get(f())          # drain: sync outside the window
            t0 = time.perf_counter()
            for _ in range(n):
                f()
            best = min(best, (time.perf_counter() - t0) / n)
        out[name] = best
    return out


if __name__ == "__main__":
    main()
