"""Observability layer: metrics registry (threads, exporters, atomic
flush), and the live instrumentation in dispatch, jit, collectives and
serving. All single-device / CPU (tier-1)."""
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import metrics as M
from paddle_tpu.profiler.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# registry core
# ---------------------------------------------------------------------------

def test_counters_and_histograms_thread_exact():
    r = MetricsRegistry()
    c = r.counter("t/c")
    h = r.histogram("t/h")
    g = r.gauge("t/g")
    n_threads, n_iter = 8, 2000

    def work(i):
        for j in range(n_iter):
            c.inc()
            h.observe(float(j % 7))
            g.set(i)

    ts = [threading.Thread(target=work, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    snap = r.snapshot()
    assert snap["counters"]["t/c"] == n_threads * n_iter
    hs = snap["histograms"]["t/h"]
    assert hs["count"] == n_threads * n_iter
    assert hs["min"] == 0.0 and hs["max"] == 6.0
    assert 0 <= snap["gauges"]["t/g"] < n_threads


def test_metric_kind_collision_raises():
    r = MetricsRegistry()
    r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")


def test_snapshot_to_file_atomic(tmp_path):
    r = MetricsRegistry()
    r.counter("a/b").inc(3)
    r.histogram("a/h").observe(1.5)
    path = str(tmp_path / "metrics.json")
    r.snapshot_to_file(path)
    got = json.loads(open(path).read())
    assert got["counters"]["a/b"] == 3
    assert got["histograms"]["a/h"]["count"] == 1
    # no tmp litter left behind (atomic rename completed)
    assert [f for f in os.listdir(tmp_path) if f.endswith(".tmp")] == []


def test_periodic_flush_leaves_snapshot_behind(tmp_path):
    """The crash-safety contract: a registry with the flusher armed
    writes complete snapshots on its own, without any explicit export
    call from the (possibly-killed) workload."""
    r = MetricsRegistry()
    path = str(tmp_path / "flush.json")
    r.enable_periodic_flush(path, interval_s=0.05)
    try:
        r.counter("live/updates").inc(7)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if os.path.exists(path):
                try:
                    if json.loads(open(path).read())["counters"].get(
                            "live/updates") == 7:
                        break
                except (json.JSONDecodeError, KeyError):
                    pass  # caught a snapshot from before the inc
            time.sleep(0.02)
        got = json.loads(open(path).read())
        assert got["counters"]["live/updates"] == 7
    finally:
        r.disable_periodic_flush()
    # final flush on disable keeps the last state
    assert json.loads(open(path).read())["counters"]["live/updates"] == 7


def test_prometheus_text_exporter():
    r = MetricsRegistry()
    r.counter("jit/compile_count").inc(2)
    r.gauge("serving/batch_occupancy").set(0.5)
    h = r.histogram("comm/latency_ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    text = r.to_prometheus_text()
    assert "# TYPE jit_compile_count counter" in text
    assert "jit_compile_count 2" in text
    assert "serving_batch_occupancy 0.5" in text
    assert 'comm_latency_ms_bucket{le="1.0"} 1' in text
    assert 'comm_latency_ms_bucket{le="10.0"} 2' in text
    assert 'comm_latency_ms_bucket{le="+Inf"} 3' in text
    assert "comm_latency_ms_count 3" in text


def test_timed_context_manager():
    r = MetricsRegistry()
    h = r.histogram("t/timed_ms")
    with M.timed(h):
        time.sleep(0.01)
    assert h.count == 1
    assert h.sum >= 5.0          # at least ~10ms observed, in ms units


# ---------------------------------------------------------------------------
# dispatch instrumentation
# ---------------------------------------------------------------------------

def test_dispatch_cache_counters_and_op_tallies():
    from paddle_tpu.core import dispatch
    from paddle_tpu.ops import registry

    calls0 = M.counter("dispatch/calls").value
    hits0 = M.counter("dispatch/cache_hit").value
    mm0 = registry.op_call_counts().get("matmul", 0)
    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    for _ in range(3):
        y = paddle.matmul(x, x)
    assert M.counter("dispatch/calls").value >= calls0 + 3
    # call 1 probes (miss), calls 2..3 ride the cached executable
    assert M.counter("dispatch/cache_hit").value >= hits0 + 2
    assert registry.op_call_counts()["matmul"] >= mm0 + 3
    st = dispatch.op_cache_stats()
    assert st["hits"] >= 2 and st["misses"] >= 1


# ---------------------------------------------------------------------------
# jit instrumentation
# ---------------------------------------------------------------------------

def test_to_static_compile_counters():
    from paddle_tpu.jit import to_static

    def f(a):
        return a * 2.0 + 1.0

    sf = to_static(f)
    n0 = M.counter("jit/compile_count").value
    h0 = M.histogram("jit/compile_ms").count
    x = paddle.to_tensor(np.ones((4,), np.float32))
    y1 = sf(x)
    y2 = sf(x)
    np.testing.assert_allclose(y1.numpy(), np.full((4,), 3.0))
    np.testing.assert_allclose(y2.numpy(), y1.numpy())
    # one fresh entry compiled (second call reuses it), wall time recorded
    assert M.counter("jit/compile_count").value == n0 + 1
    assert M.histogram("jit/compile_ms").count == h0 + 1


def test_graph_break_and_retrace_counters():
    from paddle_tpu.jit import to_static

    def breaker(a):
        v = float(np.asarray(a.numpy()).sum())   # host read -> trace break
        return a + v

    sf = to_static(breaker)
    r0 = M.counter("jit/retrace_count").value
    g0 = M.counter("jit/graph_break_count").value
    x = paddle.to_tensor(np.ones((3,), np.float32))
    with pytest.warns(RuntimeWarning):
        out = sf(x)
    np.testing.assert_allclose(out.numpy(), np.full((3,), 4.0))
    assert M.counter("jit/retrace_count").value >= r0 + 1
    assert M.counter("jit/graph_break_count").value == g0 + 1
    # per-cause tally named after the exception class
    causes = [n for n in M.registry().names()
              if n.startswith("jit/retrace_cause/")]
    assert causes, "retrace cause counter missing"


# ---------------------------------------------------------------------------
# collective instrumentation (single-device path)
# ---------------------------------------------------------------------------

def test_collective_byte_and_latency_stats():
    from paddle_tpu.distributed import collective as C
    from paddle_tpu.distributed.watchdog import comm_task_manager

    c0 = M.counter("comm/all_reduce_count").value
    b0 = M.counter("comm/all_reduce_bytes").value
    l0 = M.histogram("comm/latency_ms").count
    gs0 = comm_task_manager.group_stats().get(0, {}).get(
        "all_reduce", {"count": 0, "bytes": 0})

    t = paddle.to_tensor(np.ones((16,), np.float32))
    task = C.all_reduce(t)
    task.wait()
    np.testing.assert_allclose(t.numpy(), np.ones((16,)))  # world of 1

    assert M.counter("comm/all_reduce_count").value == c0 + 1
    assert M.counter("comm/all_reduce_bytes").value == b0 + 64
    assert M.histogram("comm/latency_ms").count >= l0 + 1
    # cumulative per-group stats shared with the watchdog dump path
    st = comm_task_manager.group_stats()[0]["all_reduce"]
    assert st["count"] == gs0["count"] + 1
    assert st["bytes"] == gs0["bytes"] + 64
    assert st["total_ms"] >= 0.0


def test_watchdog_dump_includes_cumulative_stats(capsys):
    from paddle_tpu.distributed import collective as C
    from paddle_tpu.distributed.watchdog import CommTask, comm_task_manager

    t = paddle.to_tensor(np.ones((4,), np.float32))
    C.broadcast(t, src=0).wait()
    task = CommTask("all_reduce", 0, [0], 1, 0)
    comm_task_manager._dump(task)
    err = capsys.readouterr().err
    report = json.loads(err.split("[comm_watchdog] ", 1)[1])
    assert "group_cumulative_stats" in report
    assert "broadcast" in report["group_cumulative_stats"]["0"] \
        or "broadcast" in report["group_cumulative_stats"].get(0, {})


# ---------------------------------------------------------------------------
# serving instrumentation
# ---------------------------------------------------------------------------

def test_serving_ttft_tpot_and_gauges():
    from paddle_tpu.inference.serving import (PagedCausalLM,
                                              PagedServingConfig,
                                              ServingEngine)

    cfg = PagedServingConfig(vocab_size=64, hidden_size=16, num_layers=1,
                             num_heads=2, num_kv_heads=2, ffn_size=32,
                             block_size=8, num_blocks=16, max_batch=2,
                             max_blocks_per_seq=3, token_budget=16)
    paddle.seed(0)
    model = PagedCausalLM(cfg)
    model.eval()
    engine = ServingEngine.from_model(model, cfg, seed=0)

    ttft0 = M.histogram("serving/ttft_ms").count
    tpot0 = M.histogram("serving/tpot_ms").count
    tok0 = M.counter("serving/tokens_generated").value

    rng = np.random.RandomState(0)
    for _ in range(2):
        engine.add_request(list(rng.randint(1, cfg.vocab_size, 6)),
                           max_new_tokens=4)
    # prefill tip -> first tokens: dispatched by this call, known (and
    # their first-token time observed) once the step is settled
    produced = engine.step()
    assert produced == [] and M.histogram("serving/ttft_ms").count == ttft0
    produced = engine.settle()
    assert produced, "tip rows must sample on the first step"
    assert M.histogram("serving/ttft_ms").count == ttft0 + 2
    assert 0.0 < M.gauge("serving/batch_occupancy").value <= 1.0
    assert 0.0 < M.gauge("serving/kv_cache_utilization").value <= 1.0

    out = engine.decode_run(2)             # device-fed decode window
    assert out
    # one meaning on every path: a request's own time per output token,
    # observed when it finishes, and none has yet
    assert M.histogram("serving/tpot_ms").count == tpot0
    out += engine.decode_run(1)            # the last token of both
    assert M.histogram("serving/tpot_ms").count == tpot0 + 2
    assert M.counter("serving/tokens_generated").value \
        == tok0 + len(produced) + len(out)


# ---------------------------------------------------------------------------
# profiler span integration + trace report tool
# ---------------------------------------------------------------------------

def _load_trace_report():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dispatch_spans_recorded_under_profiler(tmp_path):
    from paddle_tpu import profiler

    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    paddle.matmul(x, x)
    prof.stop()
    trace_path = str(tmp_path / "trace.json")
    prof.export(trace_path)
    trace = json.loads(open(trace_path).read())
    names = {ev["name"] for ev in trace["traceEvents"]}
    assert "op::matmul" in names


def test_trace_report_merges_trace_and_metrics(tmp_path):
    tr = _load_trace_report()
    trace = {"traceEvents": [
        {"name": "op::matmul", "ph": "X", "ts": 0.0, "dur": 1500.0},
        {"name": "op::matmul", "ph": "X", "ts": 2000.0, "dur": 500.0},
        {"name": "jit::compile", "ph": "X", "ts": 0.0, "dur": 9000.0},
    ]}
    r = MetricsRegistry()
    r.counter("dispatch/cache_hit").inc(5)
    r.gauge("serving/batch_occupancy").set(0.75)
    h = r.histogram("serving/ttft_ms")
    for v in (10.0, 20.0, 400.0):
        h.observe(v)
    report = tr.build_report(trace, r.snapshot())
    assert "op::matmul" in report and "jit::compile" in report
    assert "dispatch/cache_hit" in report and "5" in report
    assert "serving/ttft_ms" in report
    # CLI path: files in, report file out
    tp, mp, op = (str(tmp_path / n) for n in
                  ("t.json", "m.json", "report.txt"))
    open(tp, "w").write(json.dumps(trace))
    r.snapshot_to_file(mp)
    assert tr.main(["--trace", tp, "--metrics", mp, "-o", op]) == 0
    assert "op::matmul" in open(op).read()


def test_reset_zeroes_in_place():
    r = MetricsRegistry()
    c = r.counter("z/c")
    c.inc(5)
    h = r.histogram("z/h")
    h.observe(1.0)
    r.reset()
    assert c.value == 0 and h.count == 0
    assert r.counter("z/c") is c     # same object, still registered
